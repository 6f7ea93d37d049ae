"""CTR models: DeepFM and Wide&Deep — the sparse-embedding flagship path.

Ref: BASELINE.json "DeepFM / Wide&Deep CTR (sparse embedding + pserver
distributed path)" and the reference's CTR fixture
(/root/reference/python/paddle/fluid/tests/unittests/dist_ctr.py — embedding
+ fc over sparse slots trained against pservers).

TPU-first: embedding tables shard over the "ep" mesh axis via
parallel/embedding.py (the pserver-shard successor) or run dense on one
chip; the model code is identical either way.
"""

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu import initializer as I
from paddle_tpu import nn
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.ops import loss as L


@dataclasses.dataclass
class CTRConfig:
    num_sparse_fields: int = 26
    num_dense_fields: int = 13
    vocab_size: int = 10000       # per-field hash size
    embed_dim: int = 16
    hidden: tuple = (400, 400, 400)

    @staticmethod
    def tiny():
        return CTRConfig(num_sparse_fields=4, num_dense_fields=3,
                         vocab_size=100, embed_dim=8, hidden=(32, 16))


class DeepFM(nn.Module):
    """FM (1st+2nd order) + DNN over shared embeddings.

    With sparse_tables=True the embedding tables are NOT model params: they
    live in parallel/sparse.py SparseTable/HostTable objects and the model is
    driven through ``forward_from_emb`` with pre-pulled embeddings — the
    PSLib pull/push flow (ref fleet_wrapper.h:76) where only touched rows
    enter the autodiff graph. See make_sparse_deepfm_train_step.
    """

    def __init__(self, cfg: CTRConfig, sparse_tables=False):
        super().__init__()
        self.cfg = cfg
        self.sparse_tables = sparse_tables
        # one shared table across fields; ids offset per field by caller or
        # hashed into one space (reference dist_ctr uses per-slot tables;
        # single offset table shards better on TPU)
        if not sparse_tables:
            self.embed = nn.Embedding(cfg.vocab_size * cfg.num_sparse_fields,
                                      cfg.embed_dim,
                                      weight_init=I.normal(0, 0.01))
            self.fm_linear = nn.Embedding(
                cfg.vocab_size * cfg.num_sparse_fields,
                1, weight_init=I.zeros())
        self.dense_linear = nn.Linear(cfg.num_dense_fields, 1)
        dnn_in = cfg.num_sparse_fields * cfg.embed_dim + cfg.num_dense_fields
        layers = []
        for h in cfg.hidden:
            layers.append(nn.Linear(dnn_in, h, act="relu"))
            dnn_in = h
        self.dnn = nn.Sequential(layers)
        self.dnn_out = nn.Linear(dnn_in, 1)

    def _offset_ids(self, sparse_ids):
        offsets = jnp.arange(self.cfg.num_sparse_fields) * self.cfg.vocab_size
        return sparse_ids + offsets[None, :]

    def forward(self, dense, sparse_ids):
        """dense [B, D_dense]; sparse_ids [B, F] per-field ids."""
        if self.sparse_tables:
            raise EnforceError(
                "DeepFM(sparse_tables=True) has no in-model embedding "
                "tables; drive it via apply(..., method='forward_from_emb') "
                "with rows pulled from SparseTable/HostTable (see "
                "make_sparse_deepfm_train_step)")
        ids = self._offset_ids(sparse_ids)
        return self.forward_from_emb(dense, self.embed(ids),
                                     self.fm_linear(ids))

    def forward_from_emb(self, dense, emb, first_order):
        """Head over pre-pulled embeddings: emb [B, F, K], first_order
        [B, F, 1]. Sparse-table entry point (apply with
        method='forward_from_emb')."""
        # FM first order
        first = jnp.sum(first_order, axis=(1, 2), keepdims=False)
        first = first[:, None] + self.dense_linear(dense)
        # FM second order: 0.5 * ((sum v)^2 - sum v^2)
        sum_v = jnp.sum(emb, axis=1)
        sum_sq = jnp.sum(jnp.square(emb), axis=1)
        second = 0.5 * jnp.sum(jnp.square(sum_v) - sum_sq, axis=1,
                               keepdims=True)
        # DNN
        flat = jnp.concatenate(
            [emb.reshape(emb.shape[0], -1), dense], axis=1)
        deep = self.dnn_out(self.dnn(flat))
        return first + second + deep               # logits [B, 1]


class WideAndDeep(nn.Module):
    """ref: wide_deep CTR pattern (linear wide part + DNN deep part)."""

    def __init__(self, cfg: CTRConfig):
        super().__init__()
        self.cfg = cfg
        self.wide = nn.Embedding(cfg.vocab_size * cfg.num_sparse_fields, 1,
                                 weight_init=I.zeros())
        self.wide_dense = nn.Linear(cfg.num_dense_fields, 1)
        self.embed = nn.Embedding(cfg.vocab_size * cfg.num_sparse_fields,
                                  cfg.embed_dim,
                                  weight_init=I.normal(0, 0.01))
        dnn_in = cfg.num_sparse_fields * cfg.embed_dim + cfg.num_dense_fields
        layers = []
        for h in cfg.hidden:
            layers.append(nn.Linear(dnn_in, h, act="relu"))
            dnn_in = h
        self.dnn = nn.Sequential(layers)
        self.dnn_out = nn.Linear(dnn_in, 1)

    def forward(self, dense, sparse_ids):
        offsets = jnp.arange(self.cfg.num_sparse_fields) * self.cfg.vocab_size
        ids = sparse_ids + offsets[None, :]
        wide = jnp.sum(self.wide(ids), axis=(1, 2))[:, None] \
            + self.wide_dense(dense)
        emb = self.embed(ids).reshape(ids.shape[0], -1)
        deep = self.dnn_out(self.dnn(jnp.concatenate([emb, dense], 1)))
        return wide + deep


def ctr_loss(logits, labels):
    """Sigmoid CE (ref: dist_ctr.py uses cross_entropy over softmax; modern
    CTR uses logistic loss)."""
    return jnp.mean(L.sigmoid_cross_entropy_with_logits(logits, labels))


def make_sparse_deepfm_train_step(model, opt, embed_tbl, linear_tbl):
    """Sparse-row DeepFM training (ref: the reference CTR path — DownpourWorker
    pulls sparse rows, trains, pushes row grads; fleet_wrapper.h:76,:110,
    selected_rows.h sparse embedding gradients).

    model: DeepFM(cfg, sparse_tables=True); embed_tbl/linear_tbl:
    parallel.sparse.SparseTable for the [V*F, K] and [V*F, 1] tables. The
    returned step is fully jittable: only the batch's unique rows enter the
    autodiff graph, never a dense [V, D] gradient.

        step(params, opt_state, emb_st, lin_st, dense, sparse_ids, labels)
          -> (loss, params, opt_state, emb_st, lin_st)
    """
    cfg = model.cfg

    def step(params, opt_state, emb_st, lin_st, dense, sparse_ids, labels):
        offsets = jnp.arange(cfg.num_sparse_fields) * cfg.vocab_size
        ids = sparse_ids + offsets[None, :]
        erows, ectx = embed_tbl.pull(emb_st, ids)
        lrows, lctx = linear_tbl.pull(lin_st, ids)

        def loss_fn(p, erows, lrows):
            emb = embed_tbl.embed(erows, ectx)          # [B, F, K]
            first = linear_tbl.embed(lrows, lctx)       # [B, F, 1]
            logits = model.apply({"params": p, "state": {}}, dense, emb,
                                 first, method="forward_from_emb")
            return ctr_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            params, erows, lrows)
        params, opt_state = opt.apply_gradients(params, grads[0], opt_state)
        emb_st = embed_tbl.push(emb_st, grads[1], ectx)
        lin_st = linear_tbl.push(lin_st, grads[2], lctx)
        return loss, params, opt_state, emb_st, lin_st

    return step
