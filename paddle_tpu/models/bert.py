"""BERT — transformer encoder for masked-LM pretraining.

Ref: BASELINE.json flagship "BERT-base pretraining (PaddleNLP Fluid bert/
recipe)". The reference frames it over fluid.layers (multi_head_attention in
layers/nn.py + ERNIE-style recipes); here it's a first-class model with
flash-attention, bf16 policy support, and mesh-shardable params.

Sharding plan (parallel/api.py + models/sharding.py): embeddings and FFN
weights shard over "tp"; sequence dim over "sp" with ring attention for
long-context.
"""

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu import initializer as I
from paddle_tpu import nn
from paddle_tpu.ops import activations as A
from paddle_tpu.ops import loss as L


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    use_flash: bool = True
    scan_layers: bool = False  # stack layer params + lax.scan over layers
    remat: str = None          # nothing|dots_saveable|full (None -> flag)

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def tiny():
        return BertConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position=128)

    @staticmethod
    def large():
        return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                          intermediate_size=4096)


class TransformerLayer(nn.Module):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attn = nn.MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                          dropout=cfg.dropout,
                                          use_flash=cfg.use_flash)
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, mask=None):
        h = self.attn(x, mask=mask)
        x = self.ln1(self.drop(h), residual=x)   # fused add+LN
        h = nn.fused_ffn(self.fc1, self.fc2, x)
        x = self.ln2(self.drop(h), residual=x)
        return x


class BertEncoder(nn.Module):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.pos_emb = nn.Embedding(cfg.max_position, cfg.hidden_size)
        self.seg_emb = nn.Embedding(cfg.type_vocab_size, cfg.hidden_size)
        self.emb_ln = nn.LayerNorm(cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        if cfg.scan_layers:
            self.layers = nn.ScanLayers(TransformerLayer(cfg),
                                        cfg.num_layers, remat=cfg.remat,
                                        needs_rng=cfg.dropout > 0)
        else:
            self.layers = [TransformerLayer(cfg)
                           for _ in range(cfg.num_layers)]

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        b, t = input_ids.shape
        pos = jnp.arange(t)[None, :]
        x = self.tok_emb(input_ids) + self.pos_emb(pos)
        if token_type_ids is not None:
            x = x + self.seg_emb(token_type_ids)
        x = self.drop(self.emb_ln(x))
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :]  # [B,1,1,T]
        if self.cfg.scan_layers:
            x = self.layers(x, mask=mask)
        else:
            for layer in self.layers:
                x = layer(x, mask=mask)
        return x


class BertForPretraining(nn.Module):
    """MLM + NSP heads (ref: the Fluid BERT recipe's create_model)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.encoder = BertEncoder(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                       act="gelu")
        self.mlm_ln = nn.LayerNorm(cfg.hidden_size)
        self.param("mlm_bias", (cfg.vocab_size,), I.zeros())
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size, act="tanh")
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                mask_positions=None):
        """mask_positions [B, M] (int): gather only the masked positions
        before the MLM transform + vocab projection, the way the reference
        recipe gathers mask_pos before its fc — at 15% masking this skips
        ~85% of the head's [*, H]x[H, V] MXU work and its backward. Returned
        mlm_logits are then [B, M, V] (align labels/weights to the same
        positions). None keeps the full [B, T, V] head."""
        h = self.encoder(input_ids, token_type_ids, attention_mask)
        hm = h if mask_positions is None else jnp.take_along_axis(
            h, mask_positions[..., None], axis=1)
        mlm_h = self.mlm_ln(self.mlm_transform(hm))
        # weight tying with token embedding (standard BERT); int8-table
        # aware (nn.tied_vocab_head) for weight-only serving
        mlm_logits = (nn.tied_vocab_head(self.encoder.tok_emb, mlm_h)
                      + self.p("mlm_bias"))
        pooled = self.pooler(h[:, 0])
        nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits

    def loss(self, input_ids, mlm_labels, nsp_labels, mlm_mask,
             token_type_ids=None, attention_mask=None, mask_positions=None,
             vocab_axis=None, batch_axis=None, mesh=None, mesh_plan=None):
        """MLM + NSP pretraining loss as an apply() entry point. Default
        path fuses the MLM vocab projection into the chunked cross-entropy
        (no [B, M, V] logits, no tied-head matmul output in HBM);
        PT_FUSED_XENT=0 restores forward() + pretrain_loss.

        vocab_axis/batch_axis: mesh axis names when the tied embedding
        (and mlm_bias) are vocab-partitioned and the batch dp-sharded
        under GSPMD — the fused CE then runs per vocab shard with
        pmax/psum combines instead of gathering the table. mesh_plan: an
        autoplan MeshPlan — fills the three kwargs above from the
        planned mesh (explicit values win)."""
        from paddle_tpu.ops.fused import fused_xent, fused_xent_enabled
        if mesh_plan is not None:
            vocab_axis, batch_axis, mesh = mesh_plan.resolve_loss_axes(
                vocab_axis, batch_axis, mesh)
        if (not fused_xent_enabled()
                or self.encoder.tok_emb.has_p("weight_q")):
            mlm_logits, nsp_logits = self.forward(
                input_ids, token_type_ids, attention_mask, mask_positions)
            return pretrain_loss(mlm_logits, nsp_logits, mlm_labels,
                                 nsp_labels, mlm_mask)
        h = self.encoder(input_ids, token_type_ids, attention_mask)
        hm = h if mask_positions is None else jnp.take_along_axis(
            h, mask_positions[..., None], axis=1)
        mlm_h = self.mlm_ln(self.mlm_transform(hm))
        ce = fused_xent(mlm_h, self.encoder.tok_emb.p("weight"),
                        mlm_labels, bias=self.p("mlm_bias"),
                        vocab_axis=vocab_axis, batch_axis=batch_axis,
                        mesh=mesh)
        mlm = (jnp.sum(ce * mlm_mask)
               / jnp.maximum(jnp.sum(mlm_mask), 1))
        nsp_logits = self.nsp(self.pooler(h[:, 0]))
        nsp = jnp.mean(L.softmax_with_cross_entropy(nsp_logits,
                                                    nsp_labels[..., None]))
        return mlm + nsp


def pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                  mlm_mask):
    """Masked-LM + NSP loss. mlm_mask: 1.0 at masked positions. Parity
    reference for BertForPretraining.loss's fused path."""
    mlm = L.softmax_with_cross_entropy(mlm_logits, mlm_labels[..., None])
    mlm = jnp.sum(mlm[..., 0] * mlm_mask) / jnp.maximum(jnp.sum(mlm_mask), 1)
    nsp = jnp.mean(L.softmax_with_cross_entropy(nsp_logits,
                                                nsp_labels[..., None]))
    return mlm + nsp
