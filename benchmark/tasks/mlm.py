"""The MLM + NSP pretraining task: how a batch is drawn, how the
program's loss is called, and which plain reference follows it.

A traffic file of kind ``train`` names a task module; a later PR adds a
task (a causal LM, say) as a new file here.
"""

import numpy as np

REFERENCE = "benchmark.reference.bert"


def make_batch(rng, model, traffic):
    """One batch drawn from ``rng``: token ids, the masked positions'
    labels, NSP labels, MLM weights (all 1), the masked positions (drawn
    inside each row's real length), a ragged key-padding mask and
    segment ids (segment B starts somewhere inside the row). Every row
    differs."""
    b, t, m = traffic["batch"], traffic["seq"], traffic["masked_per_row"]
    lo, hi = traffic["length_min"], traffic["length_max"]
    vocab = model["vocab_size"]
    lens = rng.integers(lo, hi + 1, (b,))
    split = (lens * rng.uniform(0.3, 0.7, (b,))).astype(np.int64)
    pos = np.arange(t)[None, :]
    return (
        rng.integers(0, vocab, (b, t)).astype(np.int32),
        rng.integers(0, vocab, (b, m)).astype(np.int32),
        rng.integers(0, 2, (b,)).astype(np.int32),
        np.ones((b, m), np.float32),
        np.stack([np.sort(rng.choice(n, m, replace=False))
                  for n in lens]).astype(np.int32),
        (pos < lens[:, None]).astype(np.float32),
        (pos >= split[:, None]).astype(np.int32),
    )


def bind_loss(model):
    """The program's entry: ``BertForPretraining.loss`` through
    ``apply``, in the Trainer's ``loss_fn(params, *batch)`` form."""
    def loss_fn(p, ids, mlm_labels, nsp_labels, mlm_mask, mask_pos,
                attn_mask, token_type):
        return model.apply(
            {"params": p, "state": {}}, ids, mlm_labels, nsp_labels,
            mlm_mask, token_type_ids=token_type, attention_mask=attn_mask,
            mask_positions=mask_pos, method="loss"), 0.0
    return loss_fn


def tokens_per_step(traffic):
    return traffic["batch"] * traffic["seq"]
