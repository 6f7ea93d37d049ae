"""ERNIE 1.0 — knowledge-enhanced BERT pretraining.

Ref: BASELINE.json capability target "ERNIE 1.0". ERNIE 1.0 (Baidu, 2019 —
contemporary with the reference's Fluid BERT recipes) keeps the BERT
transformer backbone and changes the *pretraining masking strategy*:
instead of masking only independent word pieces, whole PHRASES and named
ENTITIES are masked as units (basic-level / phrase-level / entity-level
masking), forcing the model to recover knowledge spans from context. It
also trains on dialogue data with a sentence-pair (DLM/NSP-style) head.

TPU-first: the backbone reuses BertForPretraining unchanged (same MXU
path); the ERNIE-ness lives in `knowledge_mask`, a host-side batch
transform that masks whole spans, and in the config (Chinese vocab,
ERNIE-base dimensions). This mirrors how the original implementation
shipped: same net, different data pipeline.
"""

import dataclasses

import numpy as np

from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    pretrain_loss)


@dataclasses.dataclass
class ErnieConfig(BertConfig):
    """ERNIE 1.0 base: BERT-base dims over an 18k Chinese vocab."""
    vocab_size: int = 18000

    @staticmethod
    def base():
        return ErnieConfig()

    @staticmethod
    def tiny():
        return ErnieConfig(vocab_size=512, hidden_size=64, num_layers=2,
                           num_heads=4, intermediate_size=128,
                           max_position=64)


class ErnieForPretraining(BertForPretraining):
    """Same heads as BERT (MLM over spans + sentence-pair); the knowledge
    masking happens in the data pipeline (knowledge_mask). The step-fusion
    perf surface rides along through the shared backbone: cfg.scan_layers /
    cfg.remat (scan-over-layers encoder) and the fused .loss() entry point
    (chunked vocab cross-entropy, PT_FUSED_XENT) — including the
    vocab-sharded GSPMD path (.loss(vocab_axis="tp", batch_axis="dp")
    with the tied table P(tp, None), inherited from
    BertForPretraining.loss)."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__(cfg)


ernie_pretrain_loss = pretrain_loss


def knowledge_mask(ids, spans, mask_id, vocab_size, mask_prob=0.15,
                   seed=0, pad_id=0):
    """Span-level knowledge masking (host-side batch transform).

    ids:   [B, T] int token ids
    spans: per example, a list of (start, end) half-open intervals marking
           phrase/entity units (from a host tokenizer/NER); positions not
           covered by any span are treated as single-token (basic) units.
    Units are selected with probability ~mask_prob; a selected unit is
    masked AS A WHOLE — 80% [MASK], 10% random id, 10% unchanged (BERT's
    replacement distribution applied per unit, ERNIE's unit granularity).

    Returns (masked_ids, mlm_labels, mlm_weights) ready for
    pretrain_loss: labels hold the original ids, weights are 1.0 on masked
    positions.
    """
    ids = np.asarray(ids)
    B, T = ids.shape
    rng = np.random.RandomState(seed)
    masked = ids.copy()
    weights = np.zeros((B, T), np.float32)
    for b in range(B):
        covered = np.zeros(T, bool)
        units = []
        for s, e in spans[b] if b < len(spans) else []:
            s, e = max(0, int(s)), min(T, int(e))
            if e > s:
                units.append((s, e))
                covered[s:e] = True
        for t in range(T):
            if not covered[t] and ids[b, t] != pad_id:
                units.append((t, t + 1))
        for s, e in units:
            if rng.random_sample() >= mask_prob:
                continue
            weights[b, s:e] = 1.0
            r = rng.random_sample()
            if r < 0.8:
                masked[b, s:e] = mask_id
            elif r < 0.9:
                masked[b, s:e] = rng.randint(0, vocab_size, e - s)
            # else: keep original (10%)
    return masked, ids, weights
