"""Fused vocab cross-entropy — Pallas TPU kernels (forward stats + backward).

The LM-head loss is the last untiled HBM sink on the flagship train steps:
``softmax_with_cross_entropy(x @ W.T, y)`` materializes [batch, seq, vocab]
f32 logits (~1.6 GB per GPT step at 16 x 512 x 50k) only to reduce them to
one scalar per row. The forward kernel computes the three per-row
reductions the loss needs — running max/sum-exp (online logsumexp,
flash-attention style), the logit at the label, and the plain logit sum
(label smoothing) — while tiling the vocab axis through VMEM, so no logits
tile ever round-trips HBM.

Layout: hidden [N, H] (rows = batch*seq flattened), weight [V, H] (the
tied-embedding layout), bias [V]. Grid (rows/bn, vocab/bv); the vocab axis
is innermost so the per-row accumulators stay resident in the revisited
output block across vocab tiles. fp32 statistics regardless of input dtype;
the padded tail vocab tile is masked by the static V.

Backward (flash-attention-2 discipline, mirroring _fa_bwd_dq/_fa_bwd_dkv in
flash_attention.py): TWO kernels, each recomputing the per-tile
probabilities from the saved per-row logsumexp instead of storing them —
  * dh: grid (rows, vocab), vocab innermost; the [bn, H] output block is
    revisited across vocab tiles and accumulates gch @ W_tile.
  * dw/db: grid (vocab, rows), rows innermost; the [bv, H] / [1, bv]
    output blocks accumulate gch^T @ h over row tiles.
The smoothed-CE dlogits is closed-form from the recomputed softmax:
(p - sn - (sp - sn) * onehot) * g. The chunked-XLA recompute in
ops/fused.py stays behind ``use_pallas_xent_bwd=False`` as the escape
hatch.

Vocab-sharded (GSPMD) note: both kernels tolerate out-of-range labels —
a row whose label lives on another vocab shard simply never matches any
local column, so `picked` stays 0 and the one-hot term of gch is 0 on
non-owning shards. ops/fused.py uses exactly this to run the kernels
per-shard inside shard_map (labels pre-offset by the shard's base).
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas.core import (INTERPRET, NEG_INF, kernel_call,
                                        kernel_mode, logsumexp_update,
                                        pick_rv_blocks, tile_spec)


def _xent_fwd_kernel(h_ref, w_ref, b_ref, lbl_ref, m_ref, s_ref, p_ref,
                     sl_ref, *, total_vocab, block_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        s_ref[:] = jnp.zeros(s_ref.shape, s_ref.dtype)
        p_ref[:] = jnp.zeros(p_ref.shape, p_ref.dtype)
        sl_ref[:] = jnp.zeros(sl_ref.shape, sl_ref.dtype)

    h = h_ref[:].astype(jnp.float32)                       # [BN, H]
    w = w_ref[:].astype(jnp.float32)                       # [BV, H]
    logits = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [BN, BV]
    logits = logits + b_ref[:].astype(jnp.float32)          # [1, BV] row
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = col < total_vocab                   # mask the padded tail tile
    masked = jnp.where(valid, logits, NEG_INF)
    logsumexp_update(masked, m_ref, s_ref)
    # the label's column. Out-of-range labels — another vocab shard's rows
    # in the GSPMD case — must pick 0: a label in [V, padded_V) would
    # otherwise match a PADDED column and pick up its undefined logit, so
    # the hit is intersected with the validity mask.
    hit = (col == lbl_ref[:]) & valid                      # [BN, BV]
    p_ref[:] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)
    sl_ref[:] += jnp.sum(jnp.where(valid, logits, 0.0), axis=1,
                         keepdims=True)


def _tuned_blocks(kernel, hidden, v, runner):
    """(bn, bv) from the shared VMEM heuristic, or — with the ``autotune``
    flag on — the cached/swept winner for this (shape, chip)."""
    n, h = hidden.shape
    # forward stats: the label block plus four [bn, 1] outputs, no
    # [*, h] accumulator
    bn, bv = pick_rv_blocks(n, v, h, hidden.dtype.itemsize,
                            resident="rows", row_blocks=5, out=None)
    from paddle_tpu.core.flags import get_flag
    if not get_flag("autotune"):
        return bn, bv
    from paddle_tpu.ops.pallas import autotune
    sig = autotune.signature(n=n, v=v, h=h, dt=hidden.dtype.name)
    cands = [{"bn": cn, "bv": cv}
             for cn in (64, 128, 256, 512) if cn <= max(n, 8)
             for cv in (256, 512, 1024) if cv <= max(v, 128)]
    blocks = autotune.tuned_blocks(
        kernel, sig, defaults={"bn": bn, "bv": bv}, candidates=cands,
        runner=runner, flops=2.0 * n * v * h, args=(hidden,))
    return blocks["bn"], blocks["bv"]


def xent_stats_pallas(hidden, weight, bias, labels, interpret=False,
                      return_parts=False, blocks=None):
    """Per-row loss stats. Default: (logz, picked, sum_logits), each [N]
    f32. return_parts=True: the raw online pair (m, s, picked, sum_logits)
    — the vocab-sharded caller combines (m, s) across shards with
    pmax/psum before taking logz = m + log(s).

    hidden [N, H]; weight [V, H]; bias [V]; labels [N] int32.
    """
    N, H = hidden.shape
    V = weight.shape[0]
    if blocks is None:
        bn, bv = _tuned_blocks(
            "xent_stats", hidden, V,
            lambda bn, bv: xent_stats_pallas(hidden, weight, bias, labels,
                                             interpret, blocks=(bn, bv)))
    else:
        bn, bv = blocks
    kern = functools.partial(_xent_fwd_kernel, total_vocab=V, block_v=bv)
    row_out = tile_spec((bn, 1), (0, None))
    m, s, picked, sl = kernel_call(
        kern,
        name="xent_stats",
        grid=(pl.cdiv(N, bn), pl.cdiv(V, bv)),
        in_specs=[
            tile_spec((bn, H), (0, None)),
            tile_spec((bv, H), (1, None)),
            tile_spec((1, bv), (None, 1)),
            tile_spec((bn, 1), (0, None)),
        ],
        out_specs=[row_out] * 4,
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 4,
        interpret=interpret,
    )(hidden, weight, bias[None, :], labels[:, None].astype(jnp.int32))
    if return_parts:
        return m[:, 0], s[:, 0], picked[:, 0], sl[:, 0]
    logz = m[:, 0] + jnp.log(s[:, 0])
    return logz, picked[:, 0], sl[:, 0]


def xent_stats(hidden, weight, bias, labels, return_parts=False, context=""):
    """Kernel when it applies (TPU, or interpreter when pallas_interpret is
    set), else None — the caller falls back to the chunked XLA stats."""
    mode = kernel_mode(
        "xent_stats", enable_flag="use_pallas_xent", log_unavailable=True,
        unavailable_reason="no TPU and pallas_interpret off" + context,
        level=logging.WARNING if context else logging.DEBUG)
    if mode is None:
        return None
    return xent_stats_pallas(hidden, weight, bias, labels,
                             interpret=mode == INTERPRET,
                             return_parts=return_parts)


# ---- backward ------------------------------------------------------------


def _bwd_gch(h, w_ref, b_ref, lbl_ref, logz_ref, g_ref, j, block_v,
             total_vocab, sn, sp, extra_valid=None):
    """Recompute this tile's smoothed-CE dlogits [BN, BV] from the saved
    per-row logsumexp: gch = (softmax - sn - (sp - sn) * onehot) * g.
    Padded tail entries (vocab tail here, plus the caller's row tail) come
    out as garbage from the undefined out-of-bounds block regions and are
    replaced by exact zeros via where() — a select, so NaNs are discarded,
    not propagated."""
    logits = jax.lax.dot_general(
        h, w_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [BN, BV]
    logits = logits + b_ref[:].astype(jnp.float32)          # [1, BV] row
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = col < total_vocab
    if extra_valid is not None:
        valid = valid & extra_valid
    p = jnp.exp(logits - logz_ref[:])                      # [BN, BV]
    hit = (col == lbl_ref[:]).astype(jnp.float32)
    gch = (p - sn - (sp - sn) * hit) * g_ref[:]
    return jnp.where(valid, gch, 0.0)


def _xent_bwd_dh_kernel(h_ref, w_ref, b_ref, lbl_ref, logz_ref, g_ref,
                        dh_ref, *, total_vocab, block_v, sn, sp):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[:] = jnp.zeros(dh_ref.shape, dh_ref.dtype)

    h = h_ref[:].astype(jnp.float32)                       # [BN, H]
    # zero the padded tail rows of the weight tile: gch's zeroed tail
    # columns would otherwise meet undefined rows in the matmul (0 * NaN)
    w = w_ref[:].astype(jnp.float32)                       # [BV, H]
    wrow = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (w.shape[0], 1), 0)
    w = jnp.where(wrow < total_vocab, w, 0.0)
    gch = _bwd_gch(h, w_ref, b_ref, lbl_ref, logz_ref, g_ref, j, block_v,
                   total_vocab, sn, sp)
    dh_ref[:] += jax.lax.dot_general(
        gch, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _xent_bwd_dwb_kernel(h_ref, w_ref, b_ref, lbl_ref, logz_ref, g_ref,
                         dw_ref, db_ref, *, total_vocab, total_rows,
                         block_n, block_v, sn, sp):
    vj = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        dw_ref[:] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
        db_ref[:] = jnp.zeros(db_ref.shape, db_ref.dtype)

    # zero the padded tail rows of the hidden tile before BOTH matmuls:
    # gch's zeroed tail rows would otherwise meet undefined h rows (0*NaN)
    h = h_ref[:].astype(jnp.float32)                       # [BN, H]
    hrow = ni * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (h.shape[0], 1), 0)
    h = jnp.where(hrow < total_rows, h, 0.0)
    row_valid = (ni * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (h.shape[0], block_v), 0)) < total_rows
    gch = _bwd_gch(h, w_ref, b_ref, lbl_ref, logz_ref, g_ref, vj, block_v,
                   total_vocab, sn, sp, extra_valid=row_valid)
    dw_ref[:] += jax.lax.dot_general(
        gch, h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [BV, H]
    db_ref[:] += jnp.sum(gch, axis=0, keepdims=True)       # [1, BV]


def xent_bwd_pallas(hidden, weight, bias, labels, logz, g, sn, sp,
                    interpret=False):
    """(dh [N, H], dw [V, H], db [V]) in f32, for per-row cotangent g.

    hidden [N, H]; weight [V, H] (the vh tied-embedding layout); bias [V];
    labels [N] int (out-of-range rows never hit — vocab-sharded callers
    pre-offset); logz [N] f32 saved by the forward; g [N] f32.
    """
    N, H = hidden.shape
    V = weight.shape[0]
    # each kernel sizes its own tiles from what it holds in VMEM: dh
    # keeps a [bn, H] f32 block across the vocab sweep, dW/db a [bv, H]
    # one across the row sweep — one shared pair sized for the stats
    # kernel overflowed the 16 MB scoped limit in dW/db
    ib = hidden.dtype.itemsize
    bn, bv = pick_rv_blocks(N, V, H, ib, resident="rows", row_blocks=3,
                            out="rows")
    # the bias rides as a [1, V] row: a 1-D block's Mosaic tiling must
    # equal the layout XLA gave the 1-D operand (f32[V] is T(1024), so
    # only bv == 1024 was ever legal on the chip)
    bias2 = bias[None, :]
    lbl2 = labels[:, None].astype(jnp.int32)
    logz2 = logz[:, None].astype(jnp.float32)
    g2 = g[:, None].astype(jnp.float32)
    row_specs = [tile_spec((bn, 1), (0, None))] * 3
    dh = kernel_call(
        functools.partial(_xent_bwd_dh_kernel, total_vocab=V, block_v=bv,
                          sn=sn, sp=sp),
        name="xent_bwd_dh",
        grid=(pl.cdiv(N, bn), pl.cdiv(V, bv)),
        in_specs=[
            tile_spec((bn, H), (0, None)),
            tile_spec((bv, H), (1, None)),
            tile_spec((1, bv), (None, 1)),
            *row_specs,
        ],
        out_specs=tile_spec((bn, H), (0, None)),
        out_shape=jax.ShapeDtypeStruct((N, H), jnp.float32),
        interpret=interpret,
    )(hidden, weight, bias2, lbl2, logz2, g2)
    # transposed grid — vocab outer, rows inner — so the [BV, H] dw block
    # (and [1, BV] db block) stays resident across the row sweep
    bn, bv = pick_rv_blocks(N, V, H, ib, resident="vocab", row_blocks=3,
                            out="vocab")
    tr_row_specs = [tile_spec((bn, 1), (1, None))] * 3
    dw, db = kernel_call(
        functools.partial(_xent_bwd_dwb_kernel, total_vocab=V, total_rows=N,
                          block_n=bn, block_v=bv, sn=sn, sp=sp),
        name="xent_bwd_dwb",
        grid=(pl.cdiv(V, bv), pl.cdiv(N, bn)),
        in_specs=[
            tile_spec((bn, H), (1, None)),
            tile_spec((bv, H), (0, None)),
            tile_spec((1, bv), (None, 0)),
            *tr_row_specs,
        ],
        out_specs=[
            tile_spec((bv, H), (0, None)),
            tile_spec((1, bv), (None, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, H), jnp.float32),
            jax.ShapeDtypeStruct((1, V), jnp.float32),
        ],
        interpret=interpret,
    )(hidden, weight, bias2, lbl2, logz2, g2)
    return dh, dw, db[0]


def xent_bwd(hidden, weight, bias, labels, logz, g, sn, sp, context=""):
    """Backward kernels when they apply (TPU, or interpreter when
    pallas_interpret is set), else None — the caller falls back to the
    chunked XLA recompute."""
    mode = kernel_mode(
        "xent_bwd", enable_flag="use_pallas_xent_bwd", log_unavailable=True,
        unavailable_reason="no TPU and pallas_interpret off" + context,
        level=logging.WARNING if context else logging.DEBUG)
    if mode is None:
        return None
    return xent_bwd_pallas(hidden, weight, bias, labels, logz, g,
                           sn, sp, interpret=mode == INTERPRET)
