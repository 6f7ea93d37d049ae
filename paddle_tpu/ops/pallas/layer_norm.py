"""Fused layer norm — Pallas TPU kernel + XLA fallback.

The counterpart of the reference's hand-written CUDA layer_norm
(/root/reference/paddle/fluid/operators/layer_norm_op.cu — block-reduce
mean/var then normalize in one pass) and the fused
fused_fc_elementwise_layernorm op family. One HBM read + one write per
element: mean/var/normalize/affine all happen on a VMEM-resident row tile;
the kernel also emits mean/rstd so the backward needs no second stats pass.

Layout: x [R, C] (rows = everything before begin_norm_axis, flattened).
Grid: (ceil(R / BR),); each program normalizes a [BR, C] tile (the padded
tail tile computes garbage rows whose writes fall off the array). fp32
statistics regardless of input dtype; backward consumes the saved stats.

This is the single implementation behind the registered "layer_norm" op
(ops/nn.py routes here), so module path, captured programs, and direct
callers all share it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas.core import (INTERPRET, kernel_call, kernel_mode,
                                        partitioned, pick_block_rows,
                                        tile_spec)


def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, m_ref, r_ref, *, epsilon):
    x = x_ref[:].astype(jnp.float32)                       # [BR, C]
    m = jnp.mean(x, axis=1, keepdims=True)
    xc = x - m
    v = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(v + epsilon)
    y = xc * r
    y = y * g_ref[:].astype(jnp.float32)[None, :]
    y = y + b_ref[:].astype(jnp.float32)[None, :]
    o_ref[:] = y.astype(o_ref.dtype)
    m_ref[:] = m
    r_ref[:] = r


def _tuned_block_rows(kernel, x2d, runner):
    """Row-tile size, autotuned when the flag is on (the default comes
    from the shared VMEM heuristic). ``runner(block_rows=...)`` executes
    the live kernel for the sweep."""
    R, C = x2d.shape
    br = pick_block_rows(R, C, x2d.dtype.itemsize)
    from paddle_tpu.core.flags import get_flag
    if not get_flag("autotune"):
        return br
    from paddle_tpu.ops.pallas import autotune
    sig = autotune.signature(r=R, c=C, dt=x2d.dtype.name)
    cands = [{"block_rows": b} for b in (32, 64, 128, 256) if b <= R]
    blocks = autotune.tuned_blocks(
        kernel, sig, defaults={"block_rows": br}, candidates=cands,
        runner=runner, flops=9.0 * R * C, args=(x2d,))
    return blocks["block_rows"]


def _stats_pallas(x2d, gamma, beta, epsilon, interpret=False,
                  block_rows=None):
    R, C = x2d.shape
    if block_rows is None:
        block_rows = _tuned_block_rows(
            "layer_norm", x2d,
            lambda block_rows: _stats_pallas(x2d, gamma, beta, epsilon,
                                             interpret, block_rows))
    kern = functools.partial(_ln_fwd_kernel, epsilon=epsilon)

    def call(x2d, gamma, beta):
        R = x2d.shape[0]                 # the shard's rows under a mesh
        br = min(block_rows, R)
        return kernel_call(
            kern,
            name="layer_norm",
            grid=(pl.cdiv(R, br),),
            in_specs=[
                tile_spec((br, C), (0, None)),
                tile_spec((C,), (None,)),
                tile_spec((C,), (None,)),
            ],
            out_specs=_row_out_specs(br, C),
            out_shape=_row_out_shapes(R, C, x2d.dtype),
            interpret=interpret,
        )(x2d, gamma, beta)

    return partitioned(call, (0, None, None), (0, 0, 0))(x2d, gamma, beta)


def _row_out_specs(br, C):
    return [tile_spec((br, C), (0, None)), tile_spec((br, 1), (0, None)),
            tile_spec((br, 1), (0, None))]


def _row_out_shapes(R, C, dtype):
    """(normalized rows, mean, rstd) — what both LN kernels emit."""
    return [jax.ShapeDtypeStruct((R, C), dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32)]


def _stats_xla(x2d, gamma, beta, epsilon):
    x = x2d.astype(jnp.float32)
    m = jnp.mean(x, axis=1, keepdims=True)
    xc = x - m
    v = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(v + epsilon)
    y = xc * r
    y = y * gamma.astype(jnp.float32)[None, :] + \
        beta.astype(jnp.float32)[None, :]
    return y.astype(x2d.dtype), m, r


def _stats(x2d, gamma, beta, epsilon):
    # escape hatch (ADVICE r1): PT_FLAGS_use_pallas_layer_norm=0 forces the
    # XLA twin if the Pallas kernel misbehaves on some shape/hardware;
    # pallas_interpret engages the kernel off-TPU via the interpreter.
    # LN refuses silently — every shape is supported, so the only refusal
    # is "not on TPU", which is not an anomaly worth a log line.
    mode = kernel_mode("layer_norm", enable_flag="use_pallas_layer_norm")
    if mode is not None:
        return _stats_pallas(x2d, gamma, beta, epsilon,
                             interpret=mode == INTERPRET)
    return _stats_xla(x2d, gamma, beta, epsilon)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_rows(x2d, gamma, beta, epsilon):
    return _stats(x2d, gamma, beta, epsilon)[0]


def _ln_fwd(x2d, gamma, beta, epsilon):
    out, m, r = _stats(x2d, gamma, beta, epsilon)
    return out, (x2d, gamma, beta, m, r)


def _ln_bwd(epsilon, res, dy):
    x2d, gamma, beta, m, r = res
    x = x2d.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    xhat = (x - m) * r
    dgamma = jnp.sum(dy * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(dy, axis=0).astype(beta.dtype)
    wdy = dy * gamma.astype(jnp.float32)[None, :]
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * r
    return dx.astype(x2d.dtype), dgamma, dbeta


_layer_norm_rows.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_fused(x, scale=None, bias=None, begin_norm_axis=1,
                     epsilon=1e-5):
    """Layer norm over dims [begin_norm_axis:]; scale/bias flat over those
    dims (the reference layer_norm_op.cc contract)."""
    lead = x.shape[:begin_norm_axis]
    tail = x.shape[begin_norm_axis:]
    R = 1
    for d in lead:
        R *= d
    C = 1
    for d in tail:
        C *= d
    gamma = (scale.reshape(C) if scale is not None
             else jnp.ones((C,), x.dtype))
    beta = (bias.reshape(C) if bias is not None
            else jnp.zeros((C,), x.dtype))
    out = _layer_norm_rows(x.reshape(R, C), gamma, beta, epsilon)
    return out.reshape(x.shape)


# ---- fused residual-add + layer norm ------------------------------------
# The transformer hot pattern ln(x + h): Pallas kernels are opaque to XLA
# fusion, so the residual add could not fuse into the LN kernel from
# outside — fold it in instead. Saves a full HBM round-trip of the
# activations per call (ref: the reference's fused_fc_elementwise_layernorm
# family, operators/fused/).

def _ln_add_fwd_kernel(x_ref, h_ref, g_ref, b_ref, o_ref, m_ref, r_ref, *,
                       epsilon):
    s = x_ref[:].astype(jnp.float32) + h_ref[:].astype(jnp.float32)
    m = jnp.mean(s, axis=1, keepdims=True)
    sc = s - m
    v = jnp.mean(sc * sc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(v + epsilon)
    y = sc * r
    y = y * g_ref[:].astype(jnp.float32)[None, :]
    y = y + b_ref[:].astype(jnp.float32)[None, :]
    o_ref[:] = y.astype(o_ref.dtype)
    m_ref[:] = m
    r_ref[:] = r


def _stats_add_pallas(x2d, h2d, gamma, beta, epsilon, interpret=False,
                      block_rows=None):
    R, C = x2d.shape
    if block_rows is None:
        block_rows = _tuned_block_rows(
            "add_layer_norm", x2d,
            lambda block_rows: _stats_add_pallas(x2d, h2d, gamma, beta,
                                                 epsilon, interpret,
                                                 block_rows))
    kern = functools.partial(_ln_add_fwd_kernel, epsilon=epsilon)

    def call(x2d, h2d, gamma, beta):
        R = x2d.shape[0]                 # the shard's rows under a mesh
        br = min(block_rows, R)
        return kernel_call(
            kern,
            name="add_layer_norm",
            grid=(pl.cdiv(R, br),),
            in_specs=[
                tile_spec((br, C), (0, None)),
                tile_spec((br, C), (0, None)),
                tile_spec((C,), (None,)),
                tile_spec((C,), (None,)),
            ],
            out_specs=_row_out_specs(br, C),
            out_shape=_row_out_shapes(R, C, x2d.dtype),
            interpret=interpret,
        )(x2d, h2d, gamma, beta)

    return partitioned(call, (0, 0, None, None), (0, 0, 0))(
        x2d, h2d, gamma, beta)


def _stats_add(x2d, h2d, gamma, beta, epsilon):
    mode = kernel_mode("layer_norm", enable_flag="use_pallas_layer_norm")
    if mode is not None:
        return _stats_add_pallas(x2d, h2d, gamma, beta, epsilon,
                                 interpret=mode == INTERPRET)
    return _stats_xla((x2d.astype(jnp.float32)
                       + h2d.astype(jnp.float32)).astype(x2d.dtype),
                      gamma, beta, epsilon)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _add_layer_norm_rows(x2d, h2d, gamma, beta, epsilon):
    return _stats_add(x2d, h2d, gamma, beta, epsilon)[0]


def _aln_fwd(x2d, h2d, gamma, beta, epsilon):
    out, m, r = _stats_add(x2d, h2d, gamma, beta, epsilon)
    return out, (x2d, h2d, gamma, beta, m, r)


def _aln_bwd(epsilon, res, dy):
    x2d, h2d, gamma, beta, m, r = res
    s = x2d.astype(jnp.float32) + h2d.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    shat = (s - m) * r
    dgamma = jnp.sum(dy * shat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(dy, axis=0).astype(beta.dtype)
    wdy = dy * gamma.astype(jnp.float32)[None, :]
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * shat, axis=1, keepdims=True)
    ds = (wdy - c1 - shat * c2) * r
    ds_x = ds.astype(x2d.dtype)
    return ds_x, ds.astype(h2d.dtype), dgamma, dbeta


_add_layer_norm_rows.defvjp(_aln_fwd, _aln_bwd)


def add_layer_norm_fused(x, h, scale=None, bias=None, begin_norm_axis=1,
                         epsilon=1e-5):
    """Fused ln(x + h) (residual + layer norm in one HBM pass)."""
    lead = x.shape[:begin_norm_axis]
    C = 1
    for d in x.shape[begin_norm_axis:]:
        C *= d
    R = 1
    for d in lead:
        R *= d
    gamma = (scale.reshape(C) if scale is not None
             else jnp.ones((C,), x.dtype))
    beta = (bias.reshape(C) if bias is not None
            else jnp.zeros((C,), x.dtype))
    out = _add_layer_norm_rows(x.reshape(R, C), h.reshape(R, C), gamma,
                               beta, epsilon)
    return out.reshape(x.shape)
