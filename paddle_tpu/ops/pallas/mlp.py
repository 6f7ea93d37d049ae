"""Fused transformer feed-forward (MLP / GLU) — the first kernel built
on the shared primitive core (ops/pallas/core.py).

The unfused composition ``fc2(act(fc1(x)))`` writes the [rows,
intermediate] activation — 4x the hidden width on GPT/BERT — to HBM and
immediately reads it back. This kernel tiles the intermediate axis
through VMEM instead: grid (rows/BN, I/BI) with the intermediate axis
innermost, a [BN, H_out] f32 accumulator resident in scratch across
intermediate tiles, so no [rows, I] array ever exists. With gate weights
(``wg``/``bg``) the block computes the GLU family
``(act(x@w1+b1) * (x@wg+bg)) @ w2 + b2`` in the same sweep.

Everything but the ~50 lines of math here comes from the core layer:
tile routing (tile_spec), tile-size choice (pick_block_rows +
the autotuner), tail masking (tail_valid_cols / tail_zero), dispatch and
fallback telemetry (kernel_mode / kernel_call). The padded row tail
computes garbage rows whose writes fall off the array (the layer_norm
discipline); the padded intermediate tail is masked on BOTH operands of
the second matmul — the activation tile by validity select, the w2 tile
by tail_zero — because 0 * NaN = NaN (Pallas pads out-of-bounds block
regions with undefined values).

Forward only: the backward recomputes through the unfused XLA
composition (jax.vjp over `_mlp_unfused`) — flash-attention-style
recompute-not-store, so training never materializes the activation in
the forward pass either. Numerics: the kernel accumulates in f32
regardless of input dtype; the unfused composition stays in the input
dtype (it IS the pre-existing model math, and the parity reference).
"""

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import log_fallback
from paddle_tpu.ops.pallas.core import (INTERPRET, kernel_call, kernel_mode,
                                        legal_block, partitioned,
                                        pick_block_rows, tail_valid_cols,
                                        tail_zero, tile_spec)

#: what a call's blocks may take of the compiler's scoped VMEM (16 MiB
#: on a v5e): every operand block double-buffered, and the accumulator.
#: Raising the limit instead is no way out: the 2560-wide prefill
#: program hung on the chip with this kernel at 79 MiB (PERF.md
#: section 6, PR 28)
_BLOCKS_BUDGET = 15 * 2 ** 20
#: rows x (x row + activation row + accumulator row) up to which all
#: rows go into one row tile (pick_block_rows alone stops at 2 MiB)
_ALL_ROWS_BUDGET = 3 * 2 ** 20

#: a row tile's pass over the weights is bound by READING them while the
#: tile has fewer rows than the chip multiplies in the time it reads a
#: weight (a v5e: 197e12 / 819e9 = 240 operations a byte, 240 rows of
#: bfloat16), so a call split into such tiles pays one read of every
#: weight a tile. What the code can compute is the tile (``bn``, from
#: the VMEM budget) and the rows; the bound on the ROWS is twice that
#: ridge and deliberately conservative: a call of 512 rows or more (the
#: training shapes, a long prefill) keeps the kernel it was measured
#: with, because there the [rows, I] activation that the kernel keeps
#: out of HBM weighs against the weights, and that crossover has one
#: reading only (256 x 6144: PERF.md section 6, PR 35)
_WEIGHT_BOUND_ROWS = 512

_ACTS = {
    # exact erf gelu — must match ops/activations.py A.gelu for parity
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": lambda x: jnp.maximum(x, 0.0),
    "silu": jax.nn.silu,
    "identity": lambda x: x,
}

# erf(x) ~= x * P(x^2) / Q(x^2) on |x| <= 4 (saturated beyond): the
# float32 rational fit XLA itself expands erf into, highest order first
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _gelu_erf_kernel(x):
    """Exact (erf-form) gelu for the kernel body. Mosaic lowers neither
    ``erf`` nor the ``erfc`` jax.nn.gelu expands to, so erf is the
    rational polynomial above — within float32 rounding of lax.erf, so
    the kernel still matches `_ACTS["gelu"]` at the parity tolerance
    (this is NOT the tanh approximation)."""
    z = jnp.clip(x * 0.7071067811865476, -4.0, 4.0)
    z2 = z * z
    erf = z * _horner(z2, _ERF_P) / _horner(z2, _ERF_Q)
    return 0.5 * x * (1.0 + erf)


# what the kernel body evaluates: the same functions, except where Mosaic
# has no lowering for the primitive the XLA form uses
_KERNEL_ACTS = {**_ACTS, "gelu": _gelu_erf_kernel}


def _mlp_kernel(x_ref, w1_ref, b1_ref, *rest, act, total_i, block_i,
                has_gate):
    if has_gate:
        wg_ref, bg_ref, w2_ref, b2_ref, o_ref, acc_scr = rest
    else:
        w2_ref, b2_ref, o_ref, acc_scr = rest
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    x = x_ref[:].astype(jnp.float32)                       # [BN, H]
    h = jax.lax.dot_general(
        x, w1_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [BN, BI]
    h = h + b1_ref[:].astype(jnp.float32)               # [1, BI] bias row
    a = _KERNEL_ACTS[act](h)
    if has_gate:
        g = jax.lax.dot_general(
            x, wg_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        a = a * (g + bg_ref[:].astype(jnp.float32))
    w2 = w2_ref[:].astype(jnp.float32)                     # [BI, Hout]
    if total_i % block_i:
        # padded intermediate tail: clean BOTH matmul operands (select
        # discards the garbage; 0 * NaN would not)
        a = jnp.where(tail_valid_cols(j, block_i, total_i, a.shape), a, 0.0)
        w2 = tail_zero(w2, j, block_i, total_i)
    acc_scr[:] += jax.lax.dot_general(
        a, w2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [BN, Hout]

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] + b2_ref[:].astype(jnp.float32)).astype(
            o_ref.dtype)


def _mlp_pallas(x2, w1, b1, w2, b2, wg, bg, act, interpret=False,
                blocks=None):
    if blocks is None:
        blocks = _tuned_mlp_blocks(x2, w1, b1, w2, b2, wg, bg, act,
                                   interpret)
    has_gate = wg is not None

    def call(x2, w1, b1, w2, b2, *gate):
        R, H = x2.shape                  # the shard's rows under a mesh
        I, Hout = w1.shape[1], w2.shape[1]
        bn, bi = min(blocks[0], R), blocks[1]
        kern = functools.partial(_mlp_kernel, act=act, total_i=I,
                                 block_i=bi, has_gate=has_gate)
        # biases ride as [1, n] rows: a 1-D block's Mosaic tiling need
        # not match the layout XLA gives the 1-D operand (bf16[3072] in
        # (512,) blocks was refused on the v5e); a (1, n) block is
        # always legal
        in_specs = [
            tile_spec((bn, H), (0, None)),
            tile_spec((H, bi), (None, 1)),
            tile_spec((1, bi), (None, 1)),
        ]
        operands = [x2, w1, b1[None, :]]
        if has_gate:
            in_specs += [tile_spec((H, bi), (None, 1)),
                         tile_spec((1, bi), (None, 1))]
            operands += [gate[0], gate[1][None, :]]
        in_specs += [tile_spec((bi, Hout), (1, None)),
                     tile_spec((1, Hout), (None, None))]
        operands += [w2, b2[None, :]]
        return kernel_call(
            kern,
            name="mlp",
            grid=(pl.cdiv(R, bn), pl.cdiv(I, bi)),
            in_specs=in_specs,
            out_specs=tile_spec((bn, Hout), (0, None)),
            out_shape=jax.ShapeDtypeStruct((R, Hout), x2.dtype),
            scratch_shapes=[pltpu.VMEM((bn, Hout), jnp.float32)],
            interpret=interpret,
        )(*operands)

    weights = (w1, b1, w2, b2) + ((wg, bg) if has_gate else ())
    return partitioned(call, (0,) + (None,) * len(weights), 0)(
        x2, *weights)


def _default_mlp_blocks(x2, w1, w2, interpret, has_gate=False):
    R, H = x2.shape
    I, Hout = w1.shape[1], w2.shape[1]
    # per row the kernel holds the x row, one activation row and the
    # accumulator row — budget the row tile for those three
    bn = pick_block_rows(R, H + Hout + 512, 4, copies=1)
    if bn < R and R * (H + Hout + 512) * 4 <= _ALL_ROWS_BUDGET:
        # a decode round's or a prefill chunk's rows in ONE row tile:
        # a second tile reads every weight a second time
        bn = R
    if not interpret and bn % 8:
        bn = max((bn // 8) * 8, min(R, 8))
    bi = legal_block(min(I, 512), I, interpret)
    # wide layers (H 2560: three bf16 matrices in 512-wide tiles are
    # 15.7 MB double-buffered): halve the intermediate tile until the
    # blocks fit; the shapes that fitted keep their 512
    bn_ = min(bn, R)

    def blocks_bytes(bi):
        return (2 * ((2 + has_gate) * H * bi * w1.dtype.itemsize
                     + bn_ * (H + Hout) * x2.dtype.itemsize)
                + bn_ * Hout * 4)
    while not interpret and bi % 256 == 0 and \
            blocks_bytes(bi) > _BLOCKS_BUDGET:
        bi //= 2
    return bn, bi


def _tuned_mlp_blocks(x2, w1, b1, w2, b2, wg, bg, act, interpret):
    bn, bi = _default_mlp_blocks(x2, w1, w2, interpret, wg is not None)
    from paddle_tpu.core.flags import get_flag
    if not get_flag("autotune"):
        return bn, bi
    from paddle_tpu.ops.pallas import autotune
    R, H = x2.shape
    I, Hout = w1.shape[1], w2.shape[1]
    sig = autotune.signature(r=R, h=H, i=I, ho=Hout,
                             g=int(wg is not None), dt=x2.dtype.name)
    cands = [{"bn": cn, "bi": ci}
             for cn in (32, 64, 128, 256) if cn <= max(R, 8)
             for ci in (128, 256, 512) if ci <= I]
    blocks = autotune.tuned_blocks(
        "mlp", sig, defaults={"bn": bn, "bi": bi}, candidates=cands,
        runner=lambda bn, bi: _mlp_pallas(x2, w1, b1, w2, b2, wg, bg, act,
                                          interpret, blocks=(bn, bi)),
        flops=2.0 * R * I * (H + Hout) * (1 + (wg is not None)),
        args=(x2, w1, w2))
    return blocks["bn"], blocks["bi"]


def _mlp_unfused(x2, w1, b1, w2, b2, wg, bg, act):
    """The plain composition — exactly the pre-existing model math
    (Linear matmul + bias in the input dtype, then the activation), kept
    as the fallback, the parity reference, and the backward recompute."""
    h = x2 @ w1 + b1
    a = _ACTS[act](h)
    if wg is not None:
        a = a * (x2 @ wg + bg)
    return a @ w2 + b2


def _mlp_weights_once(x2, w1, b1, w2, b2, wg, bg, act):
    """The composition with every product's operands in the WEIGHT's
    dtype and float32 accumulation (``nn.layers.matmul``'s rule; the
    kernel's own precision where the weights are bfloat16): XLA reads
    each matrix once whatever the rows. For a call that is bound by
    reading its weights and whose rows the kernel would have to split.

    Not ``_mlp_unfused``, and the two do not fold into one: that one
    multiplies in the INPUT's dtype (float32 rows promote bfloat16
    weights to float32), which is the model math from before the kernel,
    bit for bit: the path off the chip, the parity reference and the
    backward's recompute. On the chip it would read every weight at
    twice the bytes through the MXU's float32 passes and hand a served
    model other numbers at 256 rows than at 128; this one keeps the
    kernel's precision, so the rows a round happens to hold do not
    change what is served."""
    def dot(a, w):
        return jnp.dot(a.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)
    a = _ACTS[act](dot(x2, w1) + b1)
    if wg is not None:
        a = a * (dot(x2, wg) + bg)
    return (dot(a, w2) + b2).astype(x2.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _mlp_core(x2, w1, b1, w2, b2, wg, bg, act, has_gate, interpret):
    return _mlp_pallas(x2, w1, b1, w2, b2, wg if has_gate else None,
                       bg if has_gate else None, act, interpret)


def _mlp_core_fwd(x2, w1, b1, w2, b2, wg, bg, act, has_gate, interpret):
    out = _mlp_core(x2, w1, b1, w2, b2, wg, bg, act, has_gate, interpret)
    return out, (x2, w1, b1, w2, b2, wg, bg)


def _mlp_core_bwd(act, has_gate, interpret, res, g):
    x2, w1, b1, w2, b2, wg, bg = res
    if has_gate:
        _, vjp = jax.vjp(lambda *a: _mlp_unfused(*a, act=act),
                         x2, w1, b1, w2, b2, wg, bg)
        return vjp(g)
    _, vjp = jax.vjp(
        lambda x2_, w1_, b1_, w2_, b2_: _mlp_unfused(
            x2_, w1_, b1_, w2_, b2_, None, None, act=act),
        x2, w1, b1, w2, b2)
    dx2, dw1, db1, dw2, db2 = vjp(g)
    return dx2, dw1, db1, dw2, db2, jnp.zeros_like(wg), jnp.zeros_like(bg)


_mlp_core.defvjp(_mlp_core_fwd, _mlp_core_bwd)


def fused_mlp(x, w1, b1, w2, b2, wg=None, bg=None, act="gelu"):
    """Fused feed-forward ``act(x@w1+b1) @ w2 + b2`` (GLU with
    ``wg``/``bg``: the activation branch is gated by ``x@wg+bg``).

    x [..., H]; w1 [H, I]; w2 [I, Hout]; biases may be None (zeros).
    On TPU / under pallas_interpret (``use_pallas_mlp`` flag on): the
    Pallas kernel — the [rows, I] activation never reaches HBM.
    Elsewhere: the plain XLA composition, bit-identical to the
    pre-existing unfused model math."""
    if act not in _ACTS:
        raise ValueError(f"fused_mlp: unknown act {act!r} "
                         f"(have {sorted(_ACTS)})")
    H, I = w1.shape
    Hout = w2.shape[1]
    b1 = b1 if b1 is not None else jnp.zeros((I,), x.dtype)
    b2 = b2 if b2 is not None else jnp.zeros((Hout,), x.dtype)
    has_gate = wg is not None
    if has_gate and bg is None:
        bg = jnp.zeros((I,), x.dtype)
    # MLP refuses silently, like layer_norm: every shape is supported,
    # so the only refusal is "not on TPU" — not an anomaly worth logging
    mode = kernel_mode("mlp", enable_flag="use_pallas_mlp")
    if mode is None:
        # unfused fallback on the ORIGINAL leading shape — flattening to
        # [rows, H] hands XLA different fusion boundaries than the
        # pre-existing model math (and a collapsed row count that can
        # collide with the HLO-contract probe dims)
        return _mlp_unfused(x, w1, b1, w2, b2, wg, bg, act)
    lead = x.shape[:-1]
    R = 1
    for d in lead:
        R *= d
    x2 = x.reshape(R, H)
    if R < _WEIGHT_BOUND_ROWS:
        bn, _ = _default_mlp_blocks(x2, w1, w2, mode == INTERPRET, has_gate)
        if bn < R:
            # a decode round's rows at a hidden size whose row tile the
            # scoped VMEM cuts short (256 rows x 6144: tiles of 40, the
            # weights read seven times): refused like any shape the
            # kernel does not serve well, logged and counted
            log_fallback("mlp", f"{R} rows x H={H} need {-(-R // bn)} row "
                         f"tiles of {bn}, each reading every weight again "
                         f"(supported: the rows in one tile, or at least "
                         f"{_WEIGHT_BOUND_ROWS} rows)", logging.INFO)
            return _mlp_weights_once(x2, w1, b1, w2, b2, wg, bg,
                                     act).reshape(*lead, Hout)
    # dummy gate operands keep the custom_vjp signature static
    wg_ = wg if has_gate else jnp.zeros((1, 1), x.dtype)
    bg_ = bg if has_gate else jnp.zeros((1,), x.dtype)
    out = _mlp_core(x2, w1, b1, w2, b2, wg_, bg_, act, has_gate,
                    mode == INTERPRET)
    return out.reshape(*lead, Hout)
