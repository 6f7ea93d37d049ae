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
[N, page_size, H*hd] — THE pool layout (ops/attention.py): token-major
and lane-dense, so a page is one contiguous (page_size, H*hd) block, the
Mosaic call takes the writer's scatter result as it lies, and the
compiled serve step holds no copy of a pool (a head-major [N, H, ps, hd]
pool cost three whole-pool relayouts per layer per step on the v5e and
fed the kernel sixteen half-lane [ps, 64] tiles a page). page_table
[S, Pmax] int32, lengths [S] int32 (tokens valid in the cache INCLUDING
the one written this step). Grid (S, Pmax) with the page axis innermost
(sequential on TPU) carrying the softmax state.

Per-head products on a lane-dense tile need no transposition: the query
row is placed block-diagonally (Q[H, H*hd] = q where the lane belongs to
the head, else 0), scores[H, ps] = Q . K^T is one NT matmul, P . V gives
[H, H*hd] of which head h's own hd lanes are the answer — the carry
keeps all of it and the finalize masks by the same one-hot and sums over
H. The MXU does H times the useful products; at 2-4 MFLOP a page that is
nothing beside the page's DMA. fp32 statistics and accumulation
regardless of the pool dtype (bf16 pools re-read through f32 math — same
contract as flash_attention).

Grouped K/V heads (``num_kv_heads < num_heads``: a token row holds KVH
heads of hd, each shared by H/KVH query heads; one K/V head is
multi-query attention) take the same tile and the same two products: the
queries arrive as [H, hd], are laid KVH times along the lanes and masked
to their OWN K/V head's lanes, and the finalize folds the KVH lane
groups into [H, hd]. No copy of K/V per query head exists anywhere; at
``num_kv_heads == num_heads`` the kernel is the one described above,
operation for operation.

Int8 pools ride the same (m, l, acc) pipeline: the per-row scales
([N, page_size] beside the pool) come in as two extra gathered blocks —
the aligned group of ``SCALE_ROWS`` pages that holds the live one — and
fold in after the contractions (K's scale onto the score column, V's
onto the probability column; a scale is per token row, shared over heads
and head_dim, so this is the same product as dequantizing the tiles).
Dequant is a tile-level extension of the existing pipeline, not a
separate kernel (the TPP argument). The kernel has no tile knob: a page
is one block.

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


def _head_lanes(num_heads, num_kv_heads, width):
    """[H, KVH*hd] bool: lane e of row h belongs to query head h's K/V
    head (head h itself where every head has its own)."""
    hd = width // num_kv_heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (num_heads, width), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (num_heads, width), 0)
    if num_kv_heads != num_heads:
        row = row // (num_heads // num_kv_heads)
    lo = row * hd
    return (lane >= lo) & (lane < lo + hd)


def _decode_kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, *refs,
                   scale, page_size, num_heads, num_kv_heads, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    width = acc_scr.shape[1]
    grouped = num_kv_heads != num_heads
    own = functools.partial(_head_lanes, num_heads, num_kv_heads, width)

    @pl.when(j == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    length = lens_ref[s]

    @pl.when(j * page_size < length)
    def _step():
        # the query row [1, H*hd] laid block-diagonally: row h keeps
        # head h's lanes, so one NT matmul scores every head. Grouped:
        # the queries come as [H, hd] and row h keeps its K/V head's
        q = q_ref[0].astype(jnp.float32)
        if grouped and num_kv_heads > 1:
            q = jnp.concatenate([q] * num_kv_heads, axis=1)
        if num_kv_heads > 1:
            q = jnp.where(own(), q, 0.0)                       # [H, E]
        k = k_ref[0].astype(jnp.float32)                       # [ps, E]
        v = v_ref[0].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H, ps]
        if quantized:
            # per-row scales fold in AFTER the contraction (one scale
            # per token row, shared over heads and head_dim): the score
            # column and the probability column carry them, so the
            # [ps, E] tiles are never rescaled elementwise
            row = ptab_ref[s, j] % SCALE_ROWS
            sc = sc * ks_ref[pl.ds(row, 1), :]                 # [1, ps]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = jnp.broadcast_to(pos < length, sc.shape)
        p, alpha = softmax_update(sc, m_scr, l_scr, valid)
        if quantized:
            p = p * vs_ref[pl.ds(row, 1), :]
        # [H, E]: row h's own lanes are head h's weighted sum; the
        # other lanes ride along unread
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        out = softmax_finalize(l_scr[:], acc_scr[:], jnp.float32)
        if not grouped:
            o_ref[0] = jnp.sum(jnp.where(own(), out, 0.0), axis=0,
                               keepdims=True).astype(o_ref.dtype)
            return
        # [H, KVH*hd] -> [H, hd]: each row's own lane group
        if num_kv_heads > 1:
            out = jnp.where(own(), out, 0.0)
        hd = width // num_kv_heads
        o_ref[0] = sum(out[:, g * hd:(g + 1) * hd]
                       for g in range(num_kv_heads)).astype(o_ref.dtype)


def paged_decode_attention_tpu(q, k_pages, v_pages, page_table, lengths,
                               scale, k_scale=None, v_scale=None,
                               interpret=None):
    """q [S, H, hd]; k_pages/v_pages [N, ps, KVH*hd] (KVH = the row's
    width over hd: the pool's own head count, a divisor of H);
    page_table [S, Pmax] int32 (in-range everywhere); lengths [S] int32;
    k_scale/v_scale [N, ps] f32 per-row scales for int8 pools (None =
    unquantized pool). -> [S, H, hd]."""
    if interpret is None:
        from paddle_tpu.core.flags import get_flag
        interpret = get_flag("pallas_interpret")
    quantized = k_scale is not None
    s_slots, h, hd = q.shape
    page_size, width = k_pages.shape[1:]
    p_max = page_table.shape[1]
    kvh = width // hd
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, num_heads=h,
                               num_kv_heads=kvh, quantized=quantized)
    # q/out ride as one lane-dense row per slot ([S, 1, H*hd]); with
    # grouped K/V heads as the slot's [H, hd] tile
    q_block = (1, 1, width) if kvh == h else (1, h, hd)
    q_spec = pl.BlockSpec(q_block, lambda s, j, pt, ln: (s, 0, 0))
    page_spec = pl.BlockSpec((1, page_size, width),
                             lambda s, j, pt, ln: (pt[s, j], 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q.reshape(s_slots, *q_block[1:]), k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (SCALE_ROWS, page_size),
            lambda s, j, pt, ln: (pt[s, j] // SCALE_ROWS, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, p_max),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, width), jnp.float32),
        ],
    )
    out = kernel_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, *q_block[1:]), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return out.reshape(s_slots, h, hd)
