"""Paged decode attention — Pallas TPU kernel for the serving fast path.

Single-query attention over a paged KV cache: each decode slot reads ONLY
its live pages (gathered through the page table by the BlockSpec index
map — the scalar-prefetch idiom, so the DMA engine fetches exactly the
pages a slot owns) and masks by the slot's true token count. Flash-style
online softmax carries (m, l, acc) in VMEM scratch across page tiles, so
no [slots, Tmax] score row ever exists — the XLA escape hatch in
ops/attention.py gathers densely and does materialize one, which is what
tools/compile_smoke.py's serve probe greps for (with the fallback as the
positive control).

Layout: q [S, H, hd] (one query token per slot), k_pages/v_pages
[N, H, page_size, hd] (the pool the whole engine shares), page_table
[S, Pmax] int32, lengths [S] int32 (tokens valid in the cache INCLUDING
the one written this step). Grid (S, H/block_h, Pmax) with the page axis
innermost (sequential on TPU) carrying the softmax state; the head axis
is the autotuned tile knob (``block_h``, default all heads). fp32
statistics and accumulation regardless of the pool dtype (bf16 pools
re-read through f32 math — same contract as flash_attention). Inside
the kernel the query keeps a size-1 row dim ([BH, 1, hd]) so both
products are head-batched matmuls with a real non-contracting lhs dim.

Int8 pools ride the same (m, l, acc) pipeline: the per-row scales
([N, page_size] beside the pool) come in as two extra gathered blocks —
the aligned group of ``SCALE_ROWS`` pages that holds the live one — and
fold in after the contractions (K's scale onto the score column, V's
onto the probability column; a scale is per token row, shared over heads
and head_dim, so this is the same product as dequantizing the tiles).
Dequant is a tile-level extension of the existing pipeline, not a
separate kernel (the TPP argument). The quantized variant registers
under its own autotune shape-sig (``kv=int8``), so sweeps and measured
rates feed the cost model per dtype.

Every page_table entry must be an IN-RANGE page index (0 for unallocated
slots/pages is fine — the kernel skips blocks past `length`, but the
BlockSpec still issues the gather DMA for them). A slot with length 0
(inactive) skips every block and emits exactly zero output, matching the
fully-masked-row semantics of the flash/chunked paths.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.core import (kernel_call, softmax_finalize,
                                        softmax_init, softmax_update)

#: rows of the [N, page_size] scale arrays one gathered block carries. A
#: (1, page_size) block is illegal on the chip (second-to-last block dim
#: must be a multiple of 8 or the array's), so the kernel fetches the
#: aligned group of 8 pages holding the live one and picks its row.
SCALE_ROWS = 8


def _decode_kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, *refs,
                   scale, page_size, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    length = lens_ref[s]

    @pl.when(j * page_size < length)
    def _step():
        # the one query row keeps a size-1 row dim: [BH, 1, hd] against
        # [BH, ps, hd] is a head-batched matmul Mosaic lowers, where the
        # row-less [BH, hd] form has no non-contracting lhs dim
        q = q_ref[0].astype(jnp.float32)               # [BH, 1, hd]
        k = k_ref[0].astype(jnp.float32)               # [BH, ps, hd]
        v = v_ref[0].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [BH, 1, ps]
        if quantized:
            # per-row scales fold in AFTER the contraction (one scale
            # per token row, shared over heads and head_dim): the score
            # column and the probability column carry them, so the
            # [BH, ps, hd] tiles are never rescaled elementwise
            row = ptab_ref[s, j] % SCALE_ROWS
            sc = sc * ks_ref[pl.ds(row, 1), :][None]   # [1, 1, ps]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        valid = pos < length                 # broadcasts over heads
        p, alpha = softmax_update(sc, m_scr, l_scr,
                                  jnp.broadcast_to(valid, sc.shape))
        if quantized:
            p = p * vs_ref[pl.ds(row, 1), :][None]
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # [BH, 1, hd]

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = softmax_finalize(l_scr[:], acc_scr[:], o_ref.dtype)


def _tuned_block_h(q, k_pages, page_table, runner):
    """Head-tile size for the decode grid, autotuned per (shape, pool
    dtype, chip). The shape-sig carries ``kv=<dtype>`` so the int8 kernel
    is its own cache row — its sweeps/measured rates feed the cost model
    separately from the f32 kernel's."""
    s_slots, h, hd = q.shape
    from paddle_tpu.core.flags import get_flag
    if not get_flag("autotune"):
        return h
    from paddle_tpu.ops.pallas import autotune
    page_size = k_pages.shape[2]
    p_max = page_table.shape[1]
    sig = autotune.signature(s=s_slots, h=h, hd=hd, ps=page_size,
                             pmax=p_max, kv=k_pages.dtype.name)
    cands = [{"block_h": b} for b in (1, 2, 4, 8, 16)
             if b < h and h % b == 0]
    blocks = autotune.tuned_blocks(
        "decode_attention", sig, defaults={"block_h": h}, candidates=cands,
        runner=runner, flops=4.0 * s_slots * h * p_max * page_size * hd,
        args=(q, k_pages, page_table))
    return blocks["block_h"]


def paged_decode_attention_tpu(q, k_pages, v_pages, page_table, lengths,
                               scale, k_scale=None, v_scale=None,
                               interpret=None, block_h=None):
    """q [S, H, hd]; k_pages/v_pages [N, H, ps, hd]; page_table [S, Pmax]
    int32 (in-range everywhere); lengths [S] int32; k_scale/v_scale
    [N, ps] f32 per-row scales for int8 pools (None = unquantized pool).
    -> [S, H, hd]."""
    if interpret is None:
        from paddle_tpu.core.flags import get_flag
        interpret = get_flag("pallas_interpret")
    quantized = k_scale is not None
    if block_h is None:
        block_h = _tuned_block_h(
            q, k_pages, page_table,
            lambda block_h: paged_decode_attention_tpu(
                q, k_pages, v_pages, page_table, lengths, scale,
                k_scale=k_scale, v_scale=v_scale, interpret=interpret,
                block_h=block_h))
    s_slots, h, hd = q.shape
    page_size = k_pages.shape[2]
    p_max = page_table.shape[1]
    bh = block_h if h % block_h == 0 else h
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, quantized=quantized)
    # q/out carry an explicit size-1 row dim ([S, H, 1, hd]); see _step
    q_spec = pl.BlockSpec((1, bh, 1, hd),
                          lambda s, b, j, pt, ln: (s, b, 0, 0))
    page_spec = pl.BlockSpec((1, bh, page_size, hd),
                             lambda s, b, j, pt, ln: (pt[s, j], b, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q[:, :, None, :], k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (SCALE_ROWS, page_size),
            lambda s, b, j, pt, ln: (pt[s, j] // SCALE_ROWS, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, h // bh, p_max),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((bh, 1, 1), jnp.float32),
            pltpu.VMEM((bh, 1, 1), jnp.float32),
            pltpu.VMEM((bh, 1, hd), jnp.float32),
        ],
    )
    out = kernel_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, h, 1, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return out[:, :, 0, :]
