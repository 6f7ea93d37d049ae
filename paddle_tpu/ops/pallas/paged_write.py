"""Paged K/V write — Pallas TPU kernel for the serving fast path.

Writes the K and V rows of one layer's new tokens into that layer's two
page pools, in place: ``pool[page_ids[i], offsets[i]] = rows[i]`` for
every row whose target lands, the same bytes in the same places as
``pool.at[page_ids, offsets].set(rows, mode="drop")``. A row whose page
id or offset is out of range writes nothing (a negative one too, which
``.at[]`` would count from the end): the serving engine routes
its inactive slots and a chunk's pad positions to page id
``num_pages`` on purpose (a window layer's ring: to ``num_slots``).

XLA's scatter costs a fixed ~9 us a pool on the v5e, live rows or not:
a decode round of gpt2_medium paid 48 of them (PERF.md section 6).
Here one call a layer moves the tiles of the rows that land.

Layout: the pools as they lie, token-major ``[num_pages, page_size,
H*hd]`` (ops/attention.py), left in HBM and aliased input to output,
so the donated buffers are written where they are. A page's rows are
tiled eight at a time in HBM, and Mosaic copies no slice of a tiled
axis narrower than its tile: a one-row copy into a page does not lower
(not for f32 either). So the kernel copies the aligned eight-row tile
that holds a row: it reads the tile into VMEM, sets the row there and
writes the tile back.

One pass over the rows lists those that land, in order, and groups
them in runs: a run is consecutive listed rows that land in one tile,
and shares one read and one write. A decode round writes one row a
page (a run each), a prefill chunk's rows are consecutive positions (a
run for each tile they touch). A loop of its own then starts each
run's reads (started inside the pass, they cost a call ~1.6 us more at
13 live rows of 64 on the v5e: PERF.md section 6); every read is
waited for, each listed row is set in its run's tile, and a run's write
starts as soon as its last row is in. A dropped row costs the pass
alone. A block of rows waits for its writes before the next block
reads. The contract that makes this the scatter: rows that land in one
tile are ADJACENT among the rows that land in a block. Two runs of one
block on the same tile would each read it before either wrote it back,
and the later write would undo the earlier one's rows. The serving
callers keep it (a window layer's prefill hands its rows over in ring
order for that reason: nn/layers.py ``ring_prefill_chunk``), and the
interpreter checks it: a call that breaks it raises there
(``check_tile_order``).

The rows come in as float32 ``[T, H*hd]``, already rounded to the pool
dtype (ops/attention.py ``paged_write``): Mosaic loads no single
dynamic row of a packed bf16 block, and a value rounded before it is
widened narrows back exactly. Rows are split into blocks of at most
256 whose VMEM (two tiles a row for K and V, the double-buffered f32
rows) stays within a budget, so the call's footprint does not grow with
the number of rows.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.core import kernel_call

#: rows of a page tile in HBM (T(8,128) f32, T(8,128)(2,1) bf16): the
#: smallest slice of a page the kernel can copy
TILE_ROWS = 8
#: scoped VMEM a block of rows may plan for
VMEM_BUDGET = 8 * 2 ** 20


def block_rows(rows, width, pool_bytes, budget=VMEM_BUDGET):
    """Rows a grid step takes: all of them where they fit, else the
    largest power of two from 256 down whose tiles (K and V, one tile a
    row) and double-buffered f32 rows fit ``budget``."""
    per_row = 2 * TILE_ROWS * width * pool_bytes + 2 * 2 * width * 4
    for r in (256, 128, 64, 32, 16, 8):
        if r * per_row <= budget:
            return rows if rows <= r else r
    return min(rows, TILE_ROWS)


def check_tile_order(page_ids, offsets, *, n_pages, page_size, block):
    """Raise where two runs of one block's landing rows share a tile:
    the kernel's contract, checked on the host under the interpreter."""
    for first in range(0, len(page_ids), block):
        seen, last = set(), None
        for page, off in zip(page_ids[first:first + block],
                             offsets[first:first + block]):
            if not (0 <= page < n_pages and 0 <= off < page_size):
                continue
            tile = (int(page), int(off) // TILE_ROWS)
            if tile != last:
                if tile in seen:
                    raise ValueError(
                        f"paged_write: rows landing in tile {tile} (page, "
                        "tile of the page) are not adjacent among the rows "
                        "that land")
                seen.add(tile)
                last = tile


def _write_kernel(ids_ref, offs_ref, k_src, v_src, k_in, v_in, k_out,
                  v_out, k_buf, v_buf, sems, live_ref, run_ref, head_ref, *,
                  block):
    del k_in, v_in                    # aliased: read and written as out
    n_pages, page_size, width = k_out.shape
    first = pl.program_id(0) * block
    rows = jnp.minimum(block, ids_ref.shape[0] - first)
    pools = ((k_out, k_buf), (v_out, v_buf))

    def tile_copies(j, run, into_vmem):
        """Row j's tile of K and of V, into buffer ``run`` or out of it."""
        base = pl.multiple_of(offs_ref[first + j] & -TILE_ROWS, TILE_ROWS)
        out = []
        for c, (pool, buf) in enumerate(pools):
            hbm = pool.at[ids_ref[first + j], pl.ds(base, TILE_ROWS)]
            src, dst = (hbm, buf.at[run]) if into_vmem else (buf.at[run],
                                                            hbm)
            out.append(pltpu.make_async_copy(src, dst,
                                             sems.at[int(into_vmem), c]))
        return out

    def wait_all(n_runs, into_vmem):
        # every copy is one tile: any tile's descriptor waits for one
        def wait(_, carry):
            for c, (pool, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    pool.at[0, pl.ds(0, TILE_ROWS)], buf.at[0],
                    sems.at[int(into_vmem), c]).wait()
            return carry
        jax.lax.fori_loop(0, n_runs, wait, 0)

    # one pass over the block's rows: the rows that land are listed in
    # order with their run, and each run with the row that opens it. A
    # row that is dropped costs this pass alone
    def scan(j, carry):
        n, runs, last = carry
        page, off = ids_ref[first + j], offs_ref[first + j]
        lands = ((page >= 0) & (page < n_pages) & (off >= 0)
                 & (off < page_size))
        tile = page * page_size + (off & -TILE_ROWS)  # its first row
        opens = lands & (tile != last)
        live_ref[n] = j                 # kept only where the row lands
        run_ref[n] = runs - 1 + opens.astype(jnp.int32)
        head_ref[runs] = j              # kept only where a run opens
        return (n + lands.astype(jnp.int32),
                runs + opens.astype(jnp.int32), jnp.where(lands, tile, last))

    # ``last``: the tile of the last row that landed (-1: none yet)
    n, runs, _ = jax.lax.fori_loop(0, rows, scan, (0, 0, -1))

    def read(r, carry):
        for c in tile_copies(head_ref[r], r, True):
            c.start()
        return carry

    jax.lax.fori_loop(0, runs, read, 0)
    wait_all(runs, True)
    sub = jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, width), 0)

    # each listed row set in its run's tile; a run's tile goes back as
    # soon as its last row is in
    def merge(i, carry):
        j, run = live_ref[i], run_ref[i]
        at = offs_ref[first + j] & (TILE_ROWS - 1)
        for src, (_, buf) in zip((k_src, v_src), pools):
            row = src[pl.ds(j, 1), :].astype(buf.dtype)
            buf[run] = jnp.where(sub == at,
                                 jnp.broadcast_to(row, (TILE_ROWS, width)),
                                 buf[run])

        @pl.when((i + 1 == n) | (run_ref[jnp.minimum(i + 1, n - 1)] != run))
        def _write_back():
            for c in tile_copies(j, run, False):
                c.start()
        return carry

    jax.lax.fori_loop(0, n, merge, 0)
    wait_all(runs, False)


# jitted, so that a model's layers, which call it with the same shapes,
# share one trace and one lowering of the kernel (24 lowered apart cost
# ~1 s of a step program's set-up and ~10 KB of its text each)
@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_tpu(k_pool, v_pool, k_rows, v_rows, page_ids, offsets,
                    interpret=False):
    """k_pool/v_pool [N, ps, W] (ps a multiple of 8), written in place
    where the caller donates them; k_rows/v_rows [T, W] float32 holding
    values of the pool dtype; page_ids/offsets [T] int32.
    -> (k_pool, v_pool)."""
    n_rows, width = k_rows.shape
    block = block_rows(n_rows, width, k_pool.dtype.itemsize)
    if interpret:
        jax.debug.callback(functools.partial(
            check_tile_order, n_pages=k_pool.shape[0],
            page_size=k_pool.shape[1], block=block), page_ids, offsets)
    rows_spec = pl.BlockSpec((block, width), lambda b, ids, offs: (b, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(n_rows, block),),
        in_specs=[rows_spec, rows_spec, hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[
            pltpu.VMEM((block, TILE_ROWS, width), k_pool.dtype),
            pltpu.VMEM((block, TILE_ROWS, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((block,), jnp.int32),
            pltpu.SMEM((block,), jnp.int32),
            pltpu.SMEM((block,), jnp.int32),
        ],
    )
    return kernel_call(
        functools.partial(_write_kernel, block=block),
        name="paged_write",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands counted with the two scalar-prefetch arrays
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(page_ids.astype(jnp.int32), offsets.astype(jnp.int32), k_rows,
      v_rows, k_pool, v_pool)
