"""Transformer (encoder-decoder) for NMT — WMT en-de "big"/"base" configs.

Ref: BASELINE.json "Transformer big WMT en-de (Fluid
neural_machine_translation)" and the reference's transformer test fixture
(/root/reference/python/paddle/fluid/tests/unittests/dist_transformer.py —
the Fluid-era layers implementation). Rebuilt with first-class attention ops
and lax.scan beam-search decoding (ops/rnn.py beam_search_decode).
"""

import dataclasses

import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.ops import activations as A
from paddle_tpu.ops import loss as L


@dataclasses.dataclass
class TransformerConfig:
    src_vocab: int = 32000
    tgt_vocab: int = 32000
    d_model: int = 512
    num_heads: int = 8
    ffn_dim: int = 2048
    enc_layers: int = 6
    dec_layers: int = 6
    dropout: float = 0.1
    max_len: int = 256

    @staticmethod
    def base():
        return TransformerConfig()

    @staticmethod
    def big():
        return TransformerConfig(d_model=1024, num_heads=16, ffn_dim=4096)

    @staticmethod
    def tiny():
        return TransformerConfig(src_vocab=1000, tgt_vocab=1000, d_model=64,
                                 num_heads=4, ffn_dim=128, enc_layers=2,
                                 dec_layers=2, max_len=32)


def positional_encoding(max_len, d_model):
    pos = jnp.arange(max_len)[:, None].astype(jnp.float32)
    i = jnp.arange(d_model // 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, 2 * i / d_model)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return pe


class EncoderLayer(nn.Module):
    def __init__(self, cfg):
        super().__init__()
        self.attn = nn.MultiHeadAttention(cfg.d_model, cfg.num_heads,
                                          dropout=cfg.dropout)
        self.ln1 = nn.LayerNorm(cfg.d_model)
        self.fc1 = nn.Linear(cfg.d_model, cfg.ffn_dim)
        self.fc2 = nn.Linear(cfg.ffn_dim, cfg.d_model)
        self.ln2 = nn.LayerNorm(cfg.d_model)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, mask=None):
        # residual=: fused add+LN (one HBM pass, Pallas kernel on TPU)
        x = self.ln1(self.drop(self.attn(x, mask=mask)), residual=x)
        x = self.ln2(self.drop(self.fc2(A.relu(self.fc1(x)))), residual=x)
        return x


class DecoderLayer(nn.Module):
    def __init__(self, cfg):
        super().__init__()
        self.self_attn = nn.MultiHeadAttention(cfg.d_model, cfg.num_heads,
                                               dropout=cfg.dropout)
        self.cross_attn = nn.MultiHeadAttention(cfg.d_model, cfg.num_heads,
                                                dropout=cfg.dropout)
        self.ln1 = nn.LayerNorm(cfg.d_model)
        self.ln2 = nn.LayerNorm(cfg.d_model)
        self.ln3 = nn.LayerNorm(cfg.d_model)
        self.fc1 = nn.Linear(cfg.d_model, cfg.ffn_dim)
        self.fc2 = nn.Linear(cfg.ffn_dim, cfg.d_model)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, memory, self_mask=None, cross_mask=None):
        x = self.ln1(self.drop(self.self_attn(x, causal=True,
                                              mask=self_mask)), residual=x)
        x = self.ln2(self.drop(self.cross_attn(x, kv=memory,
                                               mask=cross_mask)),
                     residual=x)
        x = self.ln3(self.drop(self.fc2(A.relu(self.fc1(x)))), residual=x)
        return x


class Transformer(nn.Module):
    def __init__(self, cfg: TransformerConfig):
        super().__init__()
        self.cfg = cfg
        self.src_emb = nn.Embedding(cfg.src_vocab, cfg.d_model)
        self.tgt_emb = nn.Embedding(cfg.tgt_vocab, cfg.d_model)
        self.enc_layers = [EncoderLayer(cfg) for _ in range(cfg.enc_layers)]
        self.dec_layers = [DecoderLayer(cfg) for _ in range(cfg.dec_layers)]
        self.out_proj = nn.Linear(cfg.d_model, cfg.tgt_vocab, bias=False)
        self.drop = nn.Dropout(cfg.dropout)

    def encode(self, src, src_mask=None):
        pe = positional_encoding(src.shape[1], self.cfg.d_model)
        x = self.src_emb(src) * (self.cfg.d_model ** 0.5) + pe[None]
        x = self.drop(x)
        mask = src_mask[:, None, None, :] if src_mask is not None else None
        for layer in self.enc_layers:
            x = layer(x, mask=mask)
        return x

    def decode_hidden(self, tgt, memory, src_mask=None):
        """Decoder stack output [B, T, D] before the vocab projection (the
        fused loss consumes this directly)."""
        pe = positional_encoding(tgt.shape[1], self.cfg.d_model)
        x = self.tgt_emb(tgt) * (self.cfg.d_model ** 0.5) + pe[None]
        x = self.drop(x)
        cross = src_mask[:, None, None, :] if src_mask is not None else None
        for layer in self.dec_layers:
            x = layer(x, memory, cross_mask=cross)
        return x

    def decode(self, tgt, memory, src_mask=None):
        return self.out_proj(self.decode_hidden(tgt, memory, src_mask))

    def forward(self, src, tgt, src_mask=None):
        memory = self.encode(src, src_mask)
        return self.decode(tgt, memory, src_mask)

    def loss(self, src, tgt_in, tgt_out, src_mask=None, pad_id=0,
             label_smoothing=0.1, vocab_axis=None, batch_axis=None,
             mesh=None, mesh_plan=None):
        """Label-smoothed NMT loss as an apply() entry point. Default path
        fuses the vocab projection into the chunked cross-entropy — no
        [B, T, V] logits and no same-shape one_hot soft labels (the two
        HBM sinks of the reference recipe). PT_FUSED_XENT=0 restores
        forward() + nmt_loss.

        vocab_axis/batch_axis: mesh axis names when out_proj is
        vocab-partitioned (P(None, tp), the hv layout) and the batch
        dp-sharded under GSPMD — the fused CE then runs per vocab shard
        with pmax/psum combines instead of gathering the projection.
        mesh_plan: an autoplan MeshPlan — fills the three kwargs above
        from the planned mesh (explicit values win)."""
        from paddle_tpu.ops.fused import fused_xent, fused_xent_enabled
        if mesh_plan is not None:
            vocab_axis, batch_axis, mesh = mesh_plan.resolve_loss_axes(
                vocab_axis, batch_axis, mesh)
        memory = self.encode(src, src_mask)
        h = self.decode_hidden(tgt_in, memory, src_mask)
        if not fused_xent_enabled() or self.out_proj.has_p("weight_q"):
            return nmt_loss(self.out_proj(h), tgt_out, pad_id,
                            label_smoothing)
        ce = fused_xent(h, self.out_proj.p("weight"), tgt_out,
                        weight_layout="hv", label_smoothing=label_smoothing,
                        vocab_axis=vocab_axis, batch_axis=batch_axis,
                        mesh=mesh)
        valid = (tgt_out != pad_id).astype(jnp.float32)
        return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def nmt_loss(logits, labels, pad_id=0, label_smoothing=0.1):
    """Label-smoothed CE ignoring pads (ref: the reference transformer recipe
    uses label_smooth + softmax_with_cross_entropy soft labels). Parity
    reference for Transformer.loss's fused path (PT_FUSED_XENT gates)."""
    vocab = logits.shape[-1]
    valid = (labels != pad_id).astype(jnp.float32)
    import jax
    smooth_pos = 1.0 - label_smoothing
    smooth_neg = label_smoothing / (vocab - 1)
    onehot = jax.nn.one_hot(labels, vocab) * (smooth_pos - smooth_neg) \
        + smooth_neg
    loss = L.softmax_with_cross_entropy(logits, onehot, soft_label=True)[..., 0]
    return jnp.sum(loss * valid) / jnp.maximum(jnp.sum(valid), 1.0)
