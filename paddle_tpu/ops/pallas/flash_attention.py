"""Flash attention — Pallas TPU kernel + XLA fallback.

The counterpart of the reference's fused attention path
(/root/reference/paddle/fluid/framework/ir/multihead_matmul_fuse_pass.h,
operators/fused/), rebuilt as a memory-efficient online-softmax kernel:
O(T) memory instead of materializing the [Tq, Tk] score matrix, VMEM-tiled
so the MXU stays fed from on-chip memory.

Layout: q,k,v [B, H, T, D]. Grid (B*H, Tq/BQ, Tk/BK); the kv axis is the
innermost (sequential on TPU), carrying the online-softmax state (running
max m, running sum l, unnormalized accumulator acc) in VMEM scratch across
kv steps. fp32 accumulation regardless of input dtype. The tiling,
masking, and (m, l, acc) combiner all come from ops/pallas/core.py — this
module contributes only the attention math.

Masking: `kv_mask` [B, Tk] (True = attend) covers the padded-batch case —
the mask the reference's fused multihead path handles via the eltwise-add
bias input (multihead_matmul_fuse_pass). Tail blocks (T not divisible by
the block size) are masked by absolute position inside the kernels, and
probabilities (not just scores) are masked so a fully-masked row yields
exactly zero output and zero gradients in both the Pallas and chunked
paths.

Backward: Pallas dq / dkv kernels by default (flash-attention-2 style —
the forward saves the per-row logsumexp, the backward recomputes
probabilities block-wise from q,k and lse, never materializing the full
score matrix). A recompute-based fallback (jax.checkpoint over the chunked
XLA formulation) remains behind the `flash_pallas_bwd=False` flag as the
escape hatch.

lse/delta are carried as [B*H, 1, Tq] with block (1, 1, block_q) so the
lane dimension is block_q (a [block_q, 1] layout would pad the single lane
to 128 and waste VMEM/bandwidth). The singleton middle dim matters on real
silicon: Mosaic requires the last two dims of every block to be divisible
by (8, 128) or equal to the array dims — a 2-D [B*H, Tq] array with block
(1, block_q) fails that check (the leading 1 is neither a multiple of 8
nor equal to B*H), which interpret mode does not enforce. Same story for
the [B, Tk] kv mask, carried as [B, 1, Tk].
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import describe_sharding, log_fallback
from paddle_tpu.ops.pallas.core import (NEG_INF, block_valid, kernel_call,
                                        kernel_mode, legal_block,
                                        partitioned, softmax_finalize,
                                        softmax_init, softmax_update,
                                        tail_zero, tail_zero_row, tile_spec)

logger = logging.getLogger("paddle_tpu.flash")


def _log_fallback(reason):
    """One-time notice when the Pallas fast path is refused — so a user
    benchmarking "flash" knows they are measuring the chunked fallback."""
    log_fallback("flash_attention", reason)


def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
               causal_offset, tq, tk, has_mask):
    if has_mask:
        mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        mask_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    def _step():
        q = tail_zero(q_ref[0].astype(jnp.float32), qi, block_q, tq)
        k = tail_zero(k_ref[0].astype(jnp.float32), ki, block_k, tk)
        v = tail_zero(v_ref[0].astype(jnp.float32), ki, block_k, tk)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK]
        valid = block_valid(qi, ki, block_q=block_q, block_k=block_k,
                            tq=tq, tk=tk, causal=causal,
                            causal_offset=causal_offset,
                            mask_row=mask_ref[0] if has_mask else None)
        p, alpha = softmax_update(s, m_scr, l_scr, valid)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip kv blocks entirely above the diagonal — sound with or
        # without a kv mask (a skipped block contributes p == 0 exactly)
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + causal_offset)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = softmax_finalize(l, acc_scr[:], o_ref.dtype)
        lse_ref[0] = jnp.transpose(
            m_scr[:] + jnp.log(jnp.maximum(l, 1e-30)), (1, 0))


def _flash_attention_fwd_tpu(q, k, v, scale, causal, block_q, block_k,
                             kv_mask=None, interpret=None, return_lse=False):
    if interpret is None:
        from paddle_tpu.core.flags import get_flag
        interpret = get_flag("pallas_interpret")
    has_mask = kv_mask is not None

    def call(q, k, v, *mask):
        # batch and heads are read off the arguments: under a mesh this
        # runs per shard, on the shard's [b, h]
        b, h, tq, d = q.shape
        tk = k.shape[2]
        bh = b * h
        bq = legal_block(block_q, tq, interpret)
        bk = legal_block(block_k, tk, interpret)
        kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                                   block_q=bq, block_k=bk,
                                   causal_offset=tk - tq, tq=tq, tk=tk,
                                   has_mask=has_mask)
        in_specs = [
            tile_spec((1, bq, d), (0, 1, None)),
            tile_spec((1, bk, d), (0, 2, None)),
            tile_spec((1, bk, d), (0, 2, None)),
        ]
        operands = [q.reshape(bh, tq, d), k.reshape(bh, tk, d),
                    v.reshape(bh, tk, d)]
        if has_mask:
            in_specs.append(pl.BlockSpec(
                (1, 1, bk), lambda bhi, qi, ki: (bhi // h, 0, ki)))
            operands.append(mask[0].astype(jnp.int32).reshape(b, 1, tk))
        out, lse = kernel_call(
            kernel,
            name="flash_attention",
            grid=(bh, pl.cdiv(tq, bq), pl.cdiv(tk, bk)),
            in_specs=in_specs,
            out_specs=[
                tile_spec((1, bq, d), (0, 1, None)),
                tile_spec((1, 1, bq), (0, None, 1)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            interpret=interpret,
        )(*operands)
        return out.reshape(b, h, tq, d), lse.reshape(b, h, tq)

    mask = (kv_mask,) if has_mask else ()
    out, lse = partitioned(call, (0,) * (3 + len(mask)), (0, 0))(
        q, k, v, *mask)
    return (out, lse) if return_lse else out


def _bwd_p(s, lse_row, valid):
    """exp(s - lse) with masking. lse arrives as (1, BQ) — lane-major —
    and is transposed to a column for the row-broadcast. Masked entries are
    exact zeros; for fully-masked rows lse is the ~-1e30 sentinel and the
    where() discards the overflowed exp."""
    lse_col = jnp.transpose(lse_row, (1, 0))         # [BQ, 1]
    p = jnp.exp(s - lse_col)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    return p


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
                      scale, causal, block_q, block_k, causal_offset, tq, tk,
                      has_mask):
    if has_mask:
        mask_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        mask_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step():
        q = tail_zero(q_ref[0].astype(jnp.float32), qi, block_q, tq)
        k = tail_zero(k_ref[0].astype(jnp.float32), ki, block_k, tk)
        v = tail_zero(v_ref[0].astype(jnp.float32), ki, block_k, tk)
        do = tail_zero(do_ref[0].astype(jnp.float32), qi, block_q, tq)
        lse = tail_zero_row(lse_ref[0], qi, block_q, tq)
        dlt = tail_zero_row(dlt_ref[0], qi, block_q, tq)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid = block_valid(qi, ki, block_q=block_q, block_k=block_k,
                            tq=tq, tk=tk, causal=causal,
                            causal_offset=causal_offset,
                            mask_row=mask_ref[0] if has_mask else None)
        p = _bwd_p(s, lse, valid)                    # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BQ, BK]
        delta_col = jnp.transpose(dlt, (1, 0))
        ds = p * (dp - delta_col) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + causal_offset)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
                       scale, causal, block_q, block_k, causal_offset, tq, tk,
                       has_mask):
    if has_mask:
        mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        mask_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step():
        q = tail_zero(q_ref[0].astype(jnp.float32), qi, block_q, tq)
        k = tail_zero(k_ref[0].astype(jnp.float32), ki, block_k, tk)
        v = tail_zero(v_ref[0].astype(jnp.float32), ki, block_k, tk)
        do = tail_zero(do_ref[0].astype(jnp.float32), qi, block_q, tq)
        lse = tail_zero_row(lse_ref[0], qi, block_q, tq)
        dlt = tail_zero_row(dlt_ref[0], qi, block_q, tq)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid = block_valid(qi, ki, block_q=block_q, block_k=block_k,
                            tq=tq, tk=tk, causal=causal,
                            causal_offset=causal_offset,
                            mask_row=mask_ref[0] if has_mask else None)
        p = _bwd_p(s, lse, valid)                    # [BQ, BK]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BK, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BQ, BK]
        delta_col = jnp.transpose(dlt, (1, 0))
        ds = p * (dp - delta_col) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BK, D]

    if causal:
        @pl.when(qi * block_q + block_q - 1 + causal_offset >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_attention_bwd_tpu(q, k, v, out, lse, do, scale, causal,
                             block_q, block_k, kv_mask=None, interpret=None):
    if interpret is None:
        from paddle_tpu.core.flags import get_flag
        interpret = get_flag("pallas_interpret")
    has_mask = kv_mask is not None

    def call(q, k, v, out, lse, do, *mask):
        b, h, tq, d = q.shape
        tk = k.shape[2]
        bh = b * h
        # delta_i = rowsum(dO_i * O_i) — cheap elementwise, XLA fuses it
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                         # [B, H, Tq]
        q3 = q.reshape(bh, tq, d)
        k3 = k.reshape(bh, tk, d)
        v3 = v.reshape(bh, tk, d)
        do3 = do.reshape(bh, tq, d)
        lse2 = lse.reshape(bh, 1, tq)
        dlt2 = delta.reshape(bh, 1, tq)
        bq = legal_block(block_q, tq, interpret)
        bk = legal_block(block_k, tk, interpret)
        nq = pl.cdiv(tq, bq)
        nk = pl.cdiv(tk, bk)
        offset = tk - tq
        mask_i32 = (mask[0].astype(jnp.int32).reshape(b, 1, tk)
                    if has_mask else None)
        common = dict(scale=scale, causal=causal, block_q=bq,
                      block_k=bk, causal_offset=offset, tq=tq, tk=tk,
                      has_mask=has_mask)
        # dq grid (bh, nq, nk): axis 1 picks q blocks, axis 2 kv blocks
        q_specs = [
            tile_spec((1, bq, d), (0, 1, None)),
            tile_spec((1, bk, d), (0, 2, None)),
            tile_spec((1, bk, d), (0, 2, None)),
            tile_spec((1, bq, d), (0, 1, None)),
            tile_spec((1, 1, bq), (0, None, 1)),
            tile_spec((1, 1, bq), (0, None, 1)),
        ]
        q_ops = [q3, k3, v3, do3, lse2, dlt2]
        if has_mask:
            q_specs.append(pl.BlockSpec(
                (1, 1, bk), lambda bhi, qi, ki: (bhi // h, 0, ki)))
            q_ops.append(mask_i32)
        dq = kernel_call(
            functools.partial(_fa_bwd_dq_kernel, **common),
            name="flash_attention_bwd_dq",
            grid=(bh, nq, nk),
            in_specs=q_specs,
            out_specs=tile_spec((1, bq, d), (0, 1, None)),
            out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
        )(*q_ops)
        # dkv grid (bh, nk, nq): axis 1 picks kv blocks, axis 2 q blocks
        kv_specs = [
            tile_spec((1, bq, d), (0, 2, None)),
            tile_spec((1, bk, d), (0, 1, None)),
            tile_spec((1, bk, d), (0, 1, None)),
            tile_spec((1, bq, d), (0, 2, None)),
            tile_spec((1, 1, bq), (0, None, 2)),
            tile_spec((1, 1, bq), (0, None, 2)),
        ]
        kv_ops = [q3, k3, v3, do3, lse2, dlt2]
        if has_mask:
            kv_specs.append(pl.BlockSpec(
                (1, 1, bk), lambda bhi, ki, qi: (bhi // h, 0, ki)))
            kv_ops.append(mask_i32)
        dk, dv = kernel_call(
            functools.partial(_fa_bwd_dkv_kernel, **common),
            name="flash_attention_bwd_dkv",
            grid=(bh, nk, nq),
            in_specs=kv_specs,
            out_specs=[
                tile_spec((1, bk, d), (0, 1, None)),
                tile_spec((1, bk, d), (0, 1, None)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            interpret=interpret,
        )(*kv_ops)
        return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
                dv.reshape(b, h, tk, d))

    mask = (kv_mask,) if has_mask else ()
    return partitioned(call, (0,) * (6 + len(mask)), (0, 0, 0))(
        q, k, v, out, lse, do, *mask)


def chunked_attention(q, k, v, scale=None, causal=False, kv_mask=None,
                      chunk_size=512):
    """Flash-style attention in pure XLA: lax.scan over KV chunks with online
    softmax. O(T) memory, differentiable, runs anywhere. Used as the CPU/
    fallback path and as the recompute backward for the Pallas forward.
    Same semantics as the Pallas path: kv_mask [B, Tk] (True = attend);
    fully-masked rows yield exactly zero output."""
    scale = scale if scale is not None else 1.0 / jnp.sqrt(q.shape[-1])
    # accumulate in f32, except when fed f64 inputs (the precision-probe
    # ground-truth path under jax_enable_x64) — then keep full f64 so the
    # baseline really is higher-precision than the kernel under test
    acc_dtype = jnp.float64 if q.dtype == jnp.float64 else jnp.float32
    scale = jnp.asarray(scale, acc_dtype)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    chunk = min(chunk_size, tk)
    nchunks = (tk + chunk - 1) // chunk
    pad = nchunks * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, h, nchunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, nchunks, chunk, d).transpose(2, 0, 1, 3, 4)
    if kv_mask is not None:
        mc = jnp.pad(kv_mask.astype(bool), ((0, 0), (0, pad)),
                     constant_values=False)
        mc = mc.reshape(b, nchunks, chunk).transpose(1, 0, 2)  # [N, B, C]
    qf = q.astype(acc_dtype)
    # bottom-right aligned causal (matches scaled_dot_product_attention)
    q_pos = jnp.arange(tq) + (tk - tq)

    def step(carry, inp):
        m, l, acc = carry
        if kv_mask is not None:
            kb, vb, ci, mb = inp
        else:
            kb, vb, ci = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(acc_dtype)) * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        valid = jnp.broadcast_to((k_pos < tk)[None, None, None, :], s.shape)
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])[None, None]
        if kv_mask is not None:
            valid = valid & mb[:, None, None, :]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        # mask p, not just s: in a fully-masked row m_new stays NEG_INF and
        # exp(s - m_new) = 1 — identical semantics to the Pallas kernel
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                       vb.astype(acc_dtype))
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, tq, 1), NEG_INF, acc_dtype)
    l0 = jnp.zeros((b, h, tq, 1), acc_dtype)
    acc0 = jnp.zeros((b, h, tq, d), acc_dtype)
    xs = (kc, vc, jnp.arange(nchunks))
    if kv_mask is not None:
        xs = xs + (mc,)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, acc0), xs)
    out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, mask, scale, causal, block_q, block_k, has_mask):
    return _flash_attention_fwd_tpu(q, k, v, scale, causal, block_q, block_k,
                                    kv_mask=mask if has_mask else None)


def _flash_core_fwd(q, k, v, mask, scale, causal, block_q, block_k, has_mask):
    out, lse = _flash_attention_fwd_tpu(
        q, k, v, scale, causal, block_q, block_k,
        kv_mask=mask if has_mask else None, return_lse=True)
    return out, (q, k, v, mask, out, lse)


def _flash_core_bwd(scale, causal, block_q, block_k, has_mask, res, g):
    q, k, v, mask, out, lse = res
    kv_mask = mask if has_mask else None
    from paddle_tpu.core.flags import get_flag
    if get_flag("flash_pallas_bwd"):
        dq, dk, dv = _flash_attention_bwd_tpu(
            q, k, v, out, lse, g, scale, causal, block_q, block_k,
            kv_mask=kv_mask)
    else:
        _, vjp = jax.vjp(lambda q_, k_, v_: chunked_attention(
            q_, k_, v_, scale=scale, causal=causal, kv_mask=kv_mask,
            chunk_size=block_k), q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(mask)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _tuned_flash_blocks(q, k, v, scale, causal, kv_mask, block_q, block_k,
                        interpret):
    """Autotune hook: with the `autotune` flag on, resolve (block_q,
    block_k) through the tile cache — sweeping the forward eagerly on
    first contact with this (shape, chip), reusing the cached winner
    (or the static flag defaults, under tracing) afterwards."""
    from paddle_tpu.ops.pallas import autotune
    b, h, tq, d = q.shape
    tk = k.shape[2]
    sig = autotune.signature(b=b, h=h, tq=tq, tk=tk, d=d, c=int(causal),
                             m=int(kv_mask is not None), dt=q.dtype.name)

    def candidates():
        qs = sorted({legal_block(x, tq, interpret)
                     for x in (64, 128, 256, 512)})
        ks = sorted({legal_block(x, tk, interpret)
                     for x in (64, 128, 256, 512)})
        return [{"block_q": bq, "block_k": bk} for bq in qs for bk in ks]

    def runner(block_q, block_k):
        return _flash_attention_fwd_tpu(q, k, v, scale, causal, block_q,
                                        block_k, kv_mask=kv_mask,
                                        interpret=interpret)

    blocks = autotune.tuned_blocks(
        "flash_attention", sig,
        defaults={"block_q": block_q, "block_k": block_k},
        candidates=candidates, runner=runner,
        flops=4.0 * b * h * tq * tk * d,
        args=(q, k, v) + (() if kv_mask is None else (kv_mask,)))
    return blocks["block_q"], blocks["block_k"]


def flash_attention(q, k, v, scale=None, causal=False, kv_mask=None,
                    block_q=None, block_k=None):
    """Memory-efficient attention. q,k,v: [B, H, T, D]; kv_mask: [B, Tk]
    bool/0-1, True = attend (the key-padding mask of a padded batch).

    On TPU: Pallas online-softmax forward + Pallas dq/dkv backward
    (flash-attention-2 recomputation from the saved logsumexp; set the
    `flash_pallas_bwd=False` flag to fall back to a jax.checkpoint
    recompute over the chunked XLA formulation). Head dims that are
    multiples of 64 are supported (Mosaic pads the 64-lane case;
    BERT-base's D=64 still wins because the [BQ,BK] matmuls dominate).
    Elsewhere: chunked XLA formulation (same math, same semantics).
    """
    from paddle_tpu.core.flags import get_flag
    # default block sizes come from flags so a tools/autotune.py sweep
    # result applies fleet-wide via PT_FLAGS_flash_block_{q,k}
    block_q = block_q if block_q is not None else get_flag("flash_block_q")
    block_k = block_k if block_k is not None else get_flag("flash_block_k")
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    shape_ok = (q.shape[-1] % 64 == 0 and q.shape[2] % 8 == 0
                and k.shape[2] % 8 == 0)
    # include the requested shardings: under GSPMD/shard_map the
    # PER-SHARD T is what must divide by 8, so a globally-legal shape
    # can still land here once the sequence axis is partitioned — the
    # log must show what was asked for vs what the kernel supports
    mode = kernel_mode(
        "flash_attention",
        unsupported=None if shape_ok else (
            f"D={q.shape[-1]} not a multiple of 64 or "
            f"T={q.shape[2]}/{k.shape[2]} not a multiple of 8; "
            f"requested {describe_sharding(q=q, k=k)} "
            "(supported: per-shard D%64==0 and T%8==0)"))
    if mode is not None:
        if get_flag("autotune"):
            block_q, block_k = _tuned_flash_blocks(
                q, k, v, scale, causal, kv_mask, block_q, block_k,
                interpret=(mode == "interpret"))
        if kv_mask is None:
            # dummy float operand keeps the custom_vjp signature static;
            # has_mask=False drops it before the pallas_call
            mask = jnp.zeros((1, 1), jnp.float32)
            return _flash_core(q, k, v, mask, scale, causal, block_q,
                               block_k, False)
        return _flash_core(q, k, v, kv_mask.astype(jnp.float32), scale,
                           causal, block_q, block_k, True)
    return chunked_attention(q, k, v, scale=scale, causal=causal,
                             kv_mask=kv_mask, chunk_size=block_k)
