"""Grouped gated MLP — Pallas TPU kernel for the experts one chip holds.

A routed expert layer (nn/moe.py, ``HeldExperts``) sorts its (row,
choice) pairs by expert, so that the rows of expert ``g`` are the
contiguous run ``[offsets[g], offsets[g + 1])`` of one ``[M, D]`` array.
This kernel computes, for every such run,

    out[rows of g] = (silu(x W_gate[g]) * (x W_up[g])) W_down[g]

without a buffer per expert, without padding a run to a block of rows
and without a dropped row: a ragged batch of small matrix products. Rows
behind the last run (the pairs whose expert is not held here) are
nobody's: no grid step reads them and their output is UNDEFINED, so the
caller selects (``jnp.where``), never multiplies.

The grid walks WORK ITEMS, not experts: one item is one (expert, row
tile) pair that shares at least one row, found on the device from the
runs' bounds (``work_items``) and handed over as scalar prefetch, the
idiom of the paged decode kernel. A run that spans two row tiles is two
items, two runs inside one tile are two items on the same tile, an
expert with no row is no item and streams no weight. The item axis has
the static length ``M / tile_m + G - 1`` (its worst case). The items
past the live ones skip the arithmetic (``pl.when``) and move nothing,
BECAUSE their block indices are pinned to the ones the last live step
holds (``weight_cols_index``, ``weight_rows_index``): the pipeline
fetches a block whenever its index differs from the step before, and
``pl.when`` cuts the products, not the fetches: a dead item that kept
its expert but walked its column steps from 0 again streamed that
expert whole (PERF.md section 6, PR 36).

An item streams its expert's three matrices once, ``tile_f`` columns of
the hidden dim at a step (the inner grid axis): [D, tile_f] of W_gate
and W_up, [tile_f, D] of W_down, each step's product added, rows of this
run only, into the float32 output tile, which stays resident while
consecutive items share it (the first item on a tile zeroes it). In the
(8, 128)-tiled HBM layout a [D, tile_f] column block is whole 4 KB
tiles, so all three streams are dense DMAs. A run that crosses a row
tile reads its expert a second time, so the row tile is as tall as the
blocks allow: everything fits Mosaic's DEFAULT scoped VMEM (16 MiB),
because a kernel that asks for a raised limit has hung a whole step
program on the v5e (PERF.md section 6, PR 28).

Decode hands this kernel about as many rows as there are live slots
spread over the held experts, a handful each: it is bound by reading
the held experts once a call (16 x 37.7M x 2 bytes a layer of
K-EXAONE's share), not by the MXU, which is why a row tile is not
chosen to fill the MXU and runs are never padded to it. Operands are
the weights' dtype (bfloat16 served), products accumulate in float32,
the activation is float32 and rounded once before the down product.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.core import kernel_call

#: what the double-buffered blocks of one grid step may take of Mosaic's
#: default 16 MiB of scoped VMEM (ops/pallas/mlp.py keeps the same line)
_BLOCKS_BUDGET = 15 * 2 ** 20


def pick_tiles(m, d, f, dtype_bytes=2):
    """(tile_m, tile_f). ``tile_f``: 128 columns of the hidden dim, the
    narrowest lane-aligned block (the whole of ``f`` where 128 does not
    divide it: tiny shapes), so that the rows get what is left.
    ``tile_m``: the tallest of 128 .. 8 rows that divides ``m`` (which
    the caller pads to a multiple of 8) with every block inside the
    budget: W_gate, W_up and W_down blocks and the x rows double
    buffered in the operands' dtype, the float32 output tile double
    buffered."""
    tile_f = 128 if f % 128 == 0 else f
    weights = 2 * 3 * d * tile_f * dtype_bytes
    for tile_m in (128, 64, 32, 16, 8):
        if m % tile_m == 0 and (weights + 2 * tile_m * d * (dtype_bytes + 4)
                                <= _BLOCKS_BUDGET):
            return tile_m, tile_f
    return 8, tile_f


def work_items(offsets, m, tile_m):
    """The (expert, row tile) pairs that share a row, in order, from the
    runs' bounds ``offsets`` [G + 1] (ascending, ``offsets[0] == 0``).
    -> (expert of each item, row tile of each item, both [W] int32 with
    W = m / tile_m + G - 1; the live count [1] int32). Items past the
    live ones repeat the last live one (item 0 of expert 0 where no run
    holds a row)."""
    g = offsets.shape[0] - 1
    starts, ends = offsets[:-1], offsets[1:]
    first = starts // tile_m
    tiles = jnp.where(ends > starts, (ends - 1) // tile_m - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    n = item_end[-1]
    w = jnp.minimum(jnp.arange(m // tile_m + g - 1), jnp.maximum(n - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(item_end, w, side="right"), g - 1)
    tile = first[gid] + w - (item_end[gid] - tiles[gid])
    return (gid.astype(jnp.int32), tile.astype(jnp.int32),
            n.astype(jnp.int32)[None])


def live_items(sizes, tile_m):
    """``work_items``' live count on the host, from the rows routed to
    each expert ``sizes`` [..., G] (runs laid from row 0), summed over
    the leading axes (the engine's expert layers and programs)."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes, axis=-1)
    starts = ends - sizes
    return int(np.where(sizes > 0,
                        (ends - 1) // tile_m - starts // tile_m + 1, 0).sum())


def rows_index(w, j, gid, tile, n, off):
    """x rows and the output tile: the item's row tile (a dead item's is
    the last live one's already)."""
    return tile[w], 0


def _column_step(w, j, n, nf):
    """The column step a weight block is fetched at: ``j`` for a live
    item, the last one (``nf - 1``, where the last live step ended) for
    a dead one, so that no index changes past the last live step."""
    return jnp.where(w < n[0], j, nf - 1)


def weight_cols_index(w, j, gid, tile, n, off, *, nf):
    """W_gate and W_up: [1, D, tile_f] blocks (expert, 0, column step)."""
    return gid[w], 0, _column_step(w, j, n, nf)


def weight_rows_index(w, j, gid, tile, n, off, *, nf):
    """W_down: [1, tile_f, D] blocks (expert, row step, 0)."""
    return gid[w], _column_step(w, j, n, nf), 0


def _kernel(gid_ref, tile_ref, n_ref, off_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, *, tile_m):
    w, f = pl.program_id(0), pl.program_id(1)
    live = w < n_ref[0]
    new_tile = (w == 0) | (tile_ref[w] != tile_ref[jnp.maximum(w - 1, 0)])

    @pl.when(live & (f == 0) & new_tile)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _step():
        x = x_ref[:]                                          # [tm, D]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        act = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        g = gid_ref[w]
        rows = tile_ref[w] * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, 1), 0)
        mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
        o_ref[:] += jnp.where(mine, jnp.dot(
            act, wd_ref[0], preferred_element_type=jnp.float32), 0.0)


def expert_mlp_tpu(x, w_gate, w_up, w_down, offsets, interpret=False):
    """x [M, D] (M a multiple of 8; rows sorted by expert), w_gate, w_up
    [G, D, F], w_down [G, F, D], offsets [G + 1] int32. -> [M, D]
    float32; rows of a tile that no run reaches are undefined."""
    m, d = x.shape
    g, _, f = w_gate.shape
    tile_m, tile_f = pick_tiles(m, d, f, x.dtype.itemsize)
    gid, tile, n = work_items(offsets, m, tile_m)
    nf = f // tile_f
    rows = pl.BlockSpec((tile_m, d), rows_index)
    # the item axis is outermost: a live item's successor starts at
    # column 0 again; a dead item stays where the last live step ended
    cols = pl.BlockSpec((1, d, tile_f),
                        functools.partial(weight_cols_index, nf=nf))
    down = pl.BlockSpec((1, tile_f, d),
                        functools.partial(weight_rows_index, nf=nf))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(gid.shape[0], nf),
        in_specs=[rows, cols, cols, down],
        out_specs=rows,
    )
    return kernel_call(
        functools.partial(_kernel, tile_m=tile_m),
        name="moe_expert_mlp",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
    )(gid, tile, n, offsets.astype(jnp.int32), x, w_gate, w_up, w_down)


def expert_mlp_xla(x, w_gate, w_up, w_down, group_sizes):
    """The same products by ``jax.lax.ragged_dot`` (the XLA path: CPU
    tests without the interpreter, and the kernel's parity oracle). Rows
    behind the last run come out as zeros."""
    def rd(a, w):
        return jax.lax.ragged_dot(a.astype(w.dtype), w, group_sizes,
                                  preferred_element_type=jnp.float32)
    gate = rd(x, w_gate)
    act = gate * jax.nn.sigmoid(gate) * rd(x, w_up)
    return rd(act, w_down)


def expert_mlp(x, w_gate, w_up, w_down, group_sizes):
    """The gated MLP of expert ``g`` over its run of ``group_sizes[g]``
    rows of ``x`` [M, D], runs laid one after another from row 0; the
    rows behind the last run belong to no expert and their output is
    UNDEFINED (select, do not multiply). -> [M, D] float32. On a TPU or
    under ``pallas_interpret`` the kernel above; elsewhere
    ``ragged_dot``."""
    from paddle_tpu.ops.pallas.core import INTERPRET, kernel_mode
    mode = kernel_mode("moe_expert_mlp")
    if mode is None:
        return expert_mlp_xla(x, w_gate, w_up, w_down, group_sizes)
    m = x.shape[0]
    pad = -m % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    out = expert_mlp_tpu(x.astype(w_gate.dtype), w_gate, w_up, w_down,
                         offsets, interpret=mode == INTERPRET)
    return out[:m]
