"""Shared tiled-primitive layer for the Pallas TPU kernels.

Every kernel family in ops/pallas/ used to carry private copies of the
same four concerns: (1) deciding whether the Pallas path applies at all
(enable flag, TPU vs interpreter, fallback telemetry), (2) building
BlockSpecs/grids from tile sizes, (3) the online-softmax (m, l, acc)
combiner, and (4) masking — causal triangles, ragged sequence tails, and
the padded tail tiles Pallas fills with undefined values. In the spirit
of Tensor Processing Primitives (arxiv 2104.05755), this module is the
one place those live; a new kernel is ~50 lines of math on top of it
(see ops/pallas/mlp.py, the first kernel born on the layer, and the
README "Pallas primitive core & autotuning" section).

The contract enforced by graft-lint's ``raw-pallas-call`` rule: this
module holds the ONLY ``pl.pallas_call`` site in the tree. Kernels call
:func:`kernel_call`; dispatchers resolve their execution mode through
:func:`kernel_mode`, which owns the enable-flag check, on-TPU/interpret
detection, `log_fallback`, and the ``pallas.fallback{kernel}`` counter.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import log_fallback, on_tpu

NEG_INF = -1e30

#: execution modes returned by :func:`kernel_mode`
TPU, INTERPRET = "tpu", "interpret"


# --------------------------------------------------------------- dispatch

def kernel_mode(kernel, *, enable_flag=None, unsupported=None,
                log_unavailable=False, unavailable_reason="",
                level=None):
    """Resolve how a kernel should run: ``"tpu"``, ``"interpret"``, or
    None (the caller takes its XLA fallback).

    Owns the whole refusal protocol the five kernel families used to
    duplicate:

      * ``enable_flag`` False -> None, silently (the flag is the
        documented escape hatch; flipping it off is a request, not a
        refusal worth a warning).
      * off-TPU without ``pallas_interpret`` -> None. Silent by default
        (plain CPU runs are not an anomaly); ``log_unavailable=True``
        emits ``unavailable_reason`` the way the xent kernels always
        have.
      * ``unsupported`` (a reason string naming requested vs supported
        configuration, or None when the shapes qualify) -> None with a
        `log_fallback` — a silent drop under GSPMD is invisible, so
        this one always logs and counts ``pallas.fallback{kernel}``.
    """
    import logging
    from paddle_tpu.core.flags import get_flag
    if level is None:
        level = logging.WARNING
    if enable_flag is not None and not get_flag(enable_flag):
        return None
    interpret = get_flag("pallas_interpret")
    if not (on_tpu() or interpret):
        if log_unavailable and unavailable_reason:
            log_fallback(kernel, unavailable_reason, level)
        return None
    if unsupported:
        log_fallback(kernel, unsupported, level)
        return None
    return TPU if on_tpu() else INTERPRET


def kernel_call(kernel_fn, *, name, grid=None, grid_spec=None,
                in_specs=None, out_specs=None, out_shape=None,
                scratch_shapes=None, input_output_aliases=None,
                interpret=False):
    """The one ``pl.pallas_call`` site in the tree (graft-lint's
    ``raw-pallas-call`` rule rejects any other). Accepts either a plain
    ``grid`` + in/out specs or a prebuilt ``grid_spec`` (e.g. the
    scalar-prefetch spec of the paged decode kernel, which carries its
    own scratch shapes). ``name`` is the kernel's stable identity: it
    names the Mosaic kernel, so the compiled HLO's ``tpu_custom_call``
    and the device trace's events carry it (what chip_smoke.py's kernel
    evidence and a trace reduction key on). ``input_output_aliases``
    ({operand index, scalar-prefetch operands counted: output index})
    lets a kernel update a donated buffer in place: only the blocks its
    grid visits are rewritten."""
    kwargs = {"name": name}
    if input_output_aliases:
        kwargs["input_output_aliases"] = dict(input_output_aliases)
    if grid_spec is not None:
        kwargs["grid_spec"] = grid_spec
    else:
        kwargs["grid"] = grid
        kwargs["in_specs"] = in_specs
        kwargs["out_specs"] = out_specs
    if scratch_shapes is not None:
        kwargs["scratch_shapes"] = scratch_shapes
    return pl.pallas_call(kernel_fn, out_shape=out_shape,
                          interpret=interpret, **kwargs)


def partitioned(fn, in_dims, out_dims):
    """Run a kernel per shard when the step is partitioned over a mesh.

    A Mosaic kernel is opaque to GSPMD: the chip's compiler refuses a
    jitted step over a mesh that reaches a bare ``pallas_call``
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"). So under an enclosing ``with mesh:`` of
    more than one device, ``fn`` — arrays in, arrays out, every shape
    read off its arguments — is wrapped in that shard_map:
    ``in_dims``/``out_dims`` give, per argument and per result, the
    batch-like dim (row tiles, batch) or None. That dim is split over
    the mesh's data axis (``parallel.mesh.DP``, the axis ``shard_batch``
    and the Trainer stage batches over) where it divides; every other
    axis computes its block redundantly, since no kernel here splits
    its contraction dims. No kernel reduces across the dim it lets be
    split, so the shards need no collective.

    With no mesh, one device, or inside a shard_map that is already
    manual over every axis, ``fn`` is returned as it is."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.mesh import DP, current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    auto = frozenset(mesh.axis_names) - frozenset(
        jax.sharding.get_abstract_mesh().manual_axes)
    if not auto:
        return fn
    n = mesh.shape[DP] if DP in auto else 1

    def per_shard(*args):
        split = n > 1 and all(a.shape[d] % n == 0
                              for a, d in zip(args, in_dims)
                              if d is not None)

        def spec(d):
            return P(*([None] * d), DP) if split and d is not None else P()

        out_specs = (tuple(spec(d) for d in out_dims)
                     if isinstance(out_dims, tuple) else spec(out_dims))
        return jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(spec(d) for d in in_dims),
            out_specs=out_specs, axis_names=auto, check_vma=False)(*args)

    return per_shard


# --------------------------------------------------- BlockSpec/grid builders

def tile_spec(block_shape, dims):
    """BlockSpec whose index map routes grid axes to block dims:
    ``dims[k]`` is the grid-axis index feeding block dim ``k``, or None
    for a dim pinned at 0. ``tile_spec((1, bq, d), (0, 1, None))`` is
    the flash q tile — grid axis 0 picks the batch*head slab, axis 1 the
    query block, and the head dim is whole."""
    dims = tuple(dims)

    def imap(*gids):
        return tuple(0 if d is None else gids[d] for d in dims)

    return pl.BlockSpec(block_shape, imap)


def legal_block(block, t, interpret=False):
    """Largest Mosaic-tileable block ≤ the request. Lane-major operands
    (lse/delta/masks) ride with the block size in the lane dimension,
    which Mosaic accepts only when it is a multiple of 128 or covers the
    whole sequence — a perf knob, never semantics, so silently legalize
    rather than fall back. Interpret mode does NOT legalize: the
    interpreter has no tiling rule, and the CPU suite's small-block
    cases (block 8/16/32 at T ≤ 128) are what exercise the multi-block
    online-softmax, tail-masking, and causal block-skip paths."""
    b = min(block, t)
    if interpret or b == t or b % 128 == 0:
        return b
    return (b // 128) * 128 if b >= 128 else min(t, 128)


def pick_block_rows(rows, cols, dtype_bytes, vmem_budget=2 ** 21, copies=2,
                    cap=256, floor=1):
    """Rows per tile for a rows-major kernel: keep ``copies`` copies of a
    [rows, cols] tile within the VMEM budget. Need not divide rows — the
    grid rounds up and the tail tile is padded (callers mask it)."""
    per_row = max(cols * dtype_bytes * copies, 1)
    return max(min(vmem_budget // per_row, rows, cap), floor)


#: Mosaic's default scoped-VMEM limit on the v5e, and what a rows x vocab
#: kernel may plan to use of it. Compiled inside the whole train step the
#: same kernel was allocated up to 3 MB more than compiled alone (XLA's
#: memory-space assignment moves operands around the call: dW/db at
#: 512 x 1024 tiles took 13.22 MB alone and 16.21 MB in the GPT-small
#: step), so the plan stops 4 MB short of the limit.
VMEM_LIMIT_BYTES = 16 * 2 ** 20
RV_VMEM_BUDGET = VMEM_LIMIT_BYTES - 4 * 2 ** 20


def rv_vmem_bytes(bn, bv, h, dtype_bytes, row_blocks, out):
    """Scoped VMEM one grid step of a rows x vocab kernel plans for — an
    upper bound on what the v5e compiler allocated at every tile shape
    probed (tests/test_mosaic_compile.py compiles the real shapes):

      * the pipeline's double buffers: the [bn, h] hidden and [bv, h]
        weight tiles in the input dtype; ``row_blocks`` [bn, 1] row
        operands/outputs, each row padded to a full 128-lane f32 tile;
        and the f32 block the kernel accumulates — ``out`` "rows" is
        [bn, h] (dh), "vocab" is [bv, h] (dW, the big one), None is
        nothing beyond the row blocks (forward stats);
      * the working set: logits, probabilities and their masks, two and
        a half f32 [bn, bv] tiles (the bf16->f32 upcasts of the operand
        tiles stream through registers and were not seen allocated).
    """
    out_elems = {"rows": bn * h, "vocab": bv * h, None: 0}[out]
    pipeline = 2 * (dtype_bytes * h * (bn + bv)
                    + row_blocks * bn * 128 * 4
                    + 4 * out_elems)
    return pipeline + 10 * bn * bv


def pick_rv_blocks(n, v, h, dtype_bytes, resident="rows", row_blocks=5,
                   out=None, vmem_budget=RV_VMEM_BUDGET):
    """(row tile, vocab tile) for a rows x vocab kernel: the largest
    tiles whose :func:`rv_vmem_bytes` fits the budget. ``resident`` names
    the axis whose block stays put while the grid sweeps the other one —
    its tile is maximized first, because the streamed operand is re-read
    once per resident block ("rows": stats and dh re-read the weight per
    row tile; "vocab": dW/db re-reads the hidden rows per vocab tile)."""
    # a block dim is legal when it tiles (x8 rows, x128 lanes) or covers
    # the array; candidates past the array clamp to it
    bns = sorted({min(c, max(n, 8)) for c in (512, 256, 128, 64, 32, 16,
                                              8)}, reverse=True)
    bvs = sorted({max(min(c, v), 128) for c in (1024, 512, 256, 128)},
                 reverse=True)
    pairs = ([(bn, bv) for bn in bns for bv in bvs] if resident == "rows"
             else [(bn, bv) for bv in bvs for bn in bns])
    for bn, bv in pairs:
        if rv_vmem_bytes(bn, bv, h, dtype_bytes, row_blocks,
                         out) <= vmem_budget:
            return bn, bv
    return bns[-1], bvs[-1]


# ------------------------------------------------------- masking builders

def block_valid(qi, ki, *, block_q, block_k, tq, tk, causal, causal_offset,
                mask_row):
    """[BQ, BK] validity for one attention tile: tail rows/cols past the
    true sequence end, the causal triangle, and the kv padding mask.
    Returns None when every position is valid (no masking work)."""
    valid = None
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def _and(a, b):
        return b if a is None else a & b

    if tq % block_q:
        valid = _and(valid, q_pos < tq)
    if tk % block_k:
        valid = _and(valid, k_pos < tk)
    if causal:
        valid = _and(valid, q_pos + causal_offset >= k_pos)
    if mask_row is not None:
        valid = _and(valid, mask_row > 0)      # (1, BK) broadcasts over rows
    return valid


def tail_zero(x, idx, block, t):
    """Zero the rows of a loaded [block, D] tile that lie past the true
    sequence end t. Pallas pads out-of-bounds block regions with
    undefined values (NaN in interpret mode) and 0 * NaN = NaN, so
    masking the probabilities alone is not enough — the operands
    themselves must be clean before they enter a matmul. Static no-op
    when block divides t."""
    if t % block == 0:
        return x
    rows = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return jnp.where(rows < t, x, 0.0)


def tail_zero_row(x, idx, block, t):
    """Same for a (1, block) lane-major tile (lse/delta)."""
    if t % block == 0:
        return x
    cols = idx * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return jnp.where(cols < t, x, 0.0)


def tail_valid_cols(idx, block, total, shape, axis=1):
    """[shape] bool marking columns ``idx*block + i < total`` along
    ``axis`` — the padded-tail mask of a tiled reduction axis (vocab
    tiles, intermediate tiles)."""
    pos = idx * block + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return pos < total


# ------------------------------------------- online-softmax (m, l) combiner

def softmax_init(m_scr, l_scr, *acc_scrs):
    """Reset the online-softmax carry at the first sequential step:
    m <- -inf sentinel, l <- 0, each accumulator <- 0."""
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    for acc in acc_scrs:
        acc[:] = jnp.zeros_like(acc)


def softmax_update(s, m_scr, l_scr, valid=None):
    """One online-softmax step over a [R, C] score tile (or any
    [..., R, C] stack of them — the reduction is over the last axis, the
    carry keeps a size-1 last dim): rescale the
    running (m, l) carry and return ``(p, alpha)`` — the tile's masked
    probabilities and the accumulator rescale factor — so the caller
    applies ``acc <- acc * alpha + p @ v`` with whatever contraction its
    value layout needs (flash: [BQ,BK]x[BK,D]; decode: head-batched).

    Masks p, not just s: in a fully-masked row m stays at the NEG_INF
    sentinel and exp(s - m) = exp(0) = 1 — without the p mask, masked
    positions would each contribute weight 1."""
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:]                            # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                       # [R, C]
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)              # [R, 1]
    l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:] = m_new
    return p, alpha


def softmax_finalize(l, acc, out_dtype):
    """Normalize the accumulator by the softmax denominator. Fully-masked
    rows (l == 0) are defined as exactly zero output in every path — the
    chunked/XLA fallbacks match."""
    return jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0).astype(
        out_dtype)


def logsumexp_update(masked, m_ref, s_ref):
    """Online logsumexp over a [R, C] tile of NEG_INF-masked logits:
    fold the tile into the running (max, sum-exp) pair held in the
    revisited output refs (the xent-stats discipline — same carry as
    softmax_update without a value accumulator)."""
    m_old = m_ref[:]                                       # [R, 1]
    m_new = jnp.maximum(m_old, jnp.max(masked, axis=1, keepdims=True))
    s_ref[:] = (s_ref[:] * jnp.exp(m_old - m_new)
                + jnp.sum(jnp.exp(masked - m_new), axis=1, keepdims=True))
    m_ref[:] = m_new
