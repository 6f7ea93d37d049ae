"""Paged decode attention — Pallas TPU kernel for the serving fast path.

Single-query attention over a paged KV cache: each decode slot reads ONLY
its live pages and masks by the slot's true token count. Flash-style
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
the one written this step).

The grid is one step a slot, whatever is live. Step s runs a loop over
its own ``cdiv(lengths[s], page_size)`` pages: the pools stay in HBM and
the kernel copies each live page by hand (K and V, ``make_async_copy``)
into one of two VMEM buffers, starting page i + 1 before it waits for
page i. After the last page it starts the first page of the next slot
that has one, so a slot never begins on a cold copy: the slot axis is
sequential on the single-core v5e, and the buffers, their semaphores and
the index of the buffer that holds the next slot's first page carry
from step to step. A slot with no page runs no iteration. So a call
costs a grid step a slot and a copy and the products a live page; a page
past a slot's length is never copied (with a grid over every page a
slot could hold, each dead step still cost its pipeline step).

Per-head products on a lane-dense tile need no transposition: the query
row is placed block-diagonally (Q[H, H*hd] = q where the lane belongs to
the head, else 0), scores[H, ps] = Q . K^T is one NT matmul, P . V gives
[H, H*hd] of which head h's own hd lanes are the answer — the carry
keeps all of it and the finalize masks by the same one-hot and sums over
H. The MXU does H times the useful products (ROADMAP S9: with the dead
steps gone they are most of what a live page costs). fp32 statistics
and accumulation regardless of the pool dtype (bf16 pools re-read
through f32 math — same contract as flash_attention).

Grouped K/V heads (``num_kv_heads < num_heads``: a token row holds KVH
heads of hd, each shared by H/KVH query heads; one K/V head is
multi-query attention) take the same tile and the same two products: the
queries arrive as [H, hd], are laid KVH times along the lanes and masked
to their OWN K/V head's lanes, and the finalize folds the KVH lane
groups into [H, hd]. No copy of K/V per query head exists anywhere; at
``num_kv_heads == num_heads`` the kernel is the one described above,
operation for operation.

Int8 pools ride the same (m, l, acc) pipeline: the per-row scales
([N, page_size] f32 beside the pool) of each slot's table are gathered
into [S, Pmax, page_size] before the call and come in one slot's block
a grid step, fetched while the slot before runs. They cannot be copied
page by page from the pool: an HBM array narrower than 128 lanes is
laid out 128 lanes wide, and Mosaic slices no such array, a row, an
aligned group of 8 or a whole tile alike. So a call moves the scales of
every table entry, S * Pmax * page_size * 4 bytes an array, and scoped
VMEM holds two slots' blocks, whatever the pool's size. The scales fold
in after the contractions (K's scale onto the score column, V's onto the
probability column; a scale is per token row, shared over heads and
head_dim, so this is the same product as dequantizing the tiles).
Dequant is a tile-level extension of the existing pipeline, not a
separate kernel (the TPP argument). The kernel has no tile knob: a page
is one block.

Every page_table entry below a slot's length must be an IN-RANGE page
index; entries past it never reach the output: no K/V page past a slot's
length is copied, and the int8 gather clamps such an entry into range
and its scale row is never read. A slot with length 0 (inactive) copies
nothing and emits exactly zero output, matching the fully-masked-row
semantics of the flash/chunked paths.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.core import (kernel_call, softmax_finalize,
                                        softmax_init, softmax_update)


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


def _decode_kernel(ptab_ref, lens_ref, q_ref, k_hbm, v_hbm, *refs,
                   scale, page_size, num_heads, num_kv_heads, quantized):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, k_buf, v_buf, sems, nxt, q_scr, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    p_max = ptab_ref.shape[1]
    width = acc_scr.shape[1]
    grouped = num_kv_heads != num_heads
    own = functools.partial(_head_lanes, num_heads, num_kv_heads, width)

    def live_pages(t):
        return jnp.minimum((lens_ref[t] + page_size - 1) // page_size,
                           p_max)

    def copies(t, i, buf):
        """The copies of page i of slot t, K and V, into buffer buf."""
        page = ptab_ref[t, i]
        return [pltpu.make_async_copy(src.at[page], dst.at[buf],
                                      sems.at[c, buf])
                for c, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def start_next_slot(t, buf):
        """Start the first page of the first slot from t on that has one
        into buffer buf, and leave buf where that slot will look."""
        t = jax.lax.while_loop(
            lambda t: (t < n_slots) & (
                live_pages(jnp.minimum(t, n_slots - 1)) == 0),
            lambda t: t + 1, t)
        nxt[0] = buf

        @pl.when(t < n_slots)
        def _start():
            for c in copies(t, 0, buf):
                c.start()

    @pl.when(s == 0)
    def _first():
        start_next_slot(0, 0)

    softmax_init(m_scr, l_scr, acc_scr)
    length = lens_ref[s]
    n = live_pages(s)

    @pl.when(n > 0)
    def _slot():
        # the query row [1, H*hd] laid block-diagonally: row h keeps
        # head h's lanes, so one NT matmul scores every head. Grouped:
        # the queries come as [H, hd] and row h keeps its K/V head's
        q = q_ref[0].astype(jnp.float32)
        if grouped and num_kv_heads > 1:
            q = jnp.concatenate([q] * num_kv_heads, axis=1)
        if num_kv_heads > 1:
            q = jnp.where(own(), q, 0.0)
        q_scr[:] = jnp.broadcast_to(q, q_scr.shape)            # [H, E]
        b0 = nxt[0]

        def page(i, carry):
            buf = (b0 + i) % 2

            # the other buffer's page was consumed at iteration i - 1:
            # fill it with the next page, this slot's or the next live
            # slot's first
            @pl.when(i + 1 < n)
            def _prefetch():
                for c in copies(s, i + 1, 1 - buf):
                    c.start()

            @pl.when(i + 1 == n)
            def _prefetch_next_slot():
                start_next_slot(s + 1, 1 - buf)

            for c in copies(s, i, buf):
                c.wait()
            k = k_buf[buf].astype(jnp.float32)                 # [ps, E]
            v = v_buf[buf].astype(jnp.float32)
            sc = jax.lax.dot_general(
                q_scr[:], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [H, ps]
            if quantized:
                # per-row scales fold in AFTER the contraction (one
                # scale per token row, shared over heads and head_dim):
                # the score column and the probability column carry
                # them, so the [ps, E] tiles are never rescaled
                row = pl.ds(i, 1)
                sc = sc * ks_ref[0, row, :]                    # [1, ps]
            pos = i * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1)
            valid = jnp.broadcast_to(pos < length, sc.shape)
            p, alpha = softmax_update(sc, m_scr, l_scr, valid)
            if quantized:
                p = p * vs_ref[0, row, :]
            # [H, E]: row h's own lanes are head h's weighted sum; the
            # other lanes ride along unread
            acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n, page, 0)

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
    page_table [S, Pmax] int32 (in range below each slot's length;
    entries past it never reach the output); lengths [S] int32;
    k_scale/v_scale [N, ps] f32 per-row scales for int8 pools (None =
    unquantized pool). -> [S, H, hd]."""
    if interpret is None:
        from paddle_tpu.core.flags import get_flag
        interpret = get_flag("pallas_interpret")
    quantized = k_scale is not None
    s_slots, h, hd = q.shape
    page_size, width = k_pages.shape[1:]
    kvh = width // hd
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, num_heads=h,
                               num_kv_heads=kvh, quantized=quantized)
    # q/out ride as one lane-dense row per slot ([S, 1, H*hd]); with
    # grouped K/V heads as the slot's [H, hd] tile
    q_block = (1, 1, width) if kvh == h else (1, h, hd)
    q_spec = pl.BlockSpec(q_block, lambda s, pt, ln: (s, 0, 0))
    # the pools stay where they lie; the kernel copies the live pages
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [q_spec, hbm, hbm]
    operands = [q.reshape(s_slots, *q_block[1:]), k_pages, v_pages]
    if quantized:
        # each slot's table of scale rows (the module's docstring)
        p_max = page_table.shape[1]
        in_specs += [pl.BlockSpec((1, p_max, page_size),
                                  lambda s, pt, ln: (s, 0, 0))] * 2
        operands += [jnp.take(x, page_table, axis=0, mode="clip")
                     for x in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, page_size, width), k_pages.dtype),
            pltpu.VMEM((2, page_size, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, width), jnp.float32),
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
