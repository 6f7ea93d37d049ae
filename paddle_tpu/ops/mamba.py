"""State-space (Mamba-1) ops: the causal depthwise convolution with a
carried window and the selective scan with a carried state.

Both take and return the state of MANY sequences in one buffer and touch
only the sequences they are given (``slots``; None: row b is slot b, a
decode round over every slot): the serving engine keeps one such buffer
per layer for all its slots and donates it to its two step programs
(serving/engine.py, "cache protocol").

  conv state  [K-1, S, D]   the last K-1 inputs of each slot's conv
                            (window-major: no padded minor dims)
  scan state  [S, N, D]     float32 ``h`` (ops/pallas/selective_scan.py)

Ref: no counterpart in the reference (2019-era); the equations are those
of Gu & Dao, "Mamba: Linear-Time Sequence Modeling with Selective State
Spaces" (arXiv:2312.00752), section 3.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op


@register_op("causal_conv1d")
def causal_conv1d(x, weight, bias, state, slots, lengths, fresh):
    """Depthwise causal convolution over time with a carried window.
    x [B, T, D]; weight [K, D] (tap k multiplies the input K-1-k
    positions back); bias [D]; state [K-1, S, D]; slots, lengths [B]
    int32; fresh [B] bool (the sequence starts here: its window is
    zeros whatever the slot held; None: none does). Position t of row b sees the slot's
    carried inputs before the chunk's own. Only the first ``lengths[b]``
    positions are real: the new window is the last K-1 REAL inputs, so
    padding never enters it (``lengths[b] == 0`` keeps it as it was).
    -> (y [B, T, D] in x's dtype, new state)."""
    k = weight.shape[0]
    t = x.shape[1]
    prev = state if slots is None else state[:, slots]          # [K-1,B,D]
    if fresh is not None:
        prev = jnp.where(fresh[None, :, None], 0, prev)
    window = jnp.concatenate(
        [prev.transpose(1, 0, 2).astype(x.dtype), x], axis=1)   # [B,K-1+T,D]
    y = bias.astype(x.dtype) + sum(
        window[:, i:i + t] * weight[i].astype(x.dtype) for i in range(k))
    # rows [len, len + K-1) of the window are inputs len-(K-1) .. len-1
    keep = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, k - 1, axis=0))(window, lengths)                  # [B,K-1,D]
    keep = keep.transpose(1, 0, 2).astype(state.dtype)
    return y, keep if slots is None else state.at[:, slots].set(keep)


def _selective_scan_xla(x, dt, b, c, z, a, d, state, slots, lengths, fresh):
    """The plain recurrence as a ``lax.scan`` over time: the kernel's
    parity oracle and what a CPU run without the interpreter takes."""
    h0 = state if slots is None else state[slots]              # [B, N, D]
    if fresh is not None:
        h0 = jnp.where(fresh[:, None, None], 0.0, h0)

    def step(h, inp):
        t, x_t, dt_t, b_t, c_t = inp                # [B, D] / [B, N]
        h_new = (jnp.exp(dt_t[:, None, :] * a) * h
                 + b_t[:, :, None] * (dt_t * x_t)[:, None, :])
        y = jnp.einsum("bn,bnd->bd", c_t, h_new) + d * x_t
        return jnp.where((t < lengths)[:, None, None], h_new, h), y

    t_len = x.shape[1]
    seq = [jnp.arange(t_len)] + [jnp.moveaxis(v, 1, 0)
                                 for v in (x, dt, b, c)]
    h, ys = jax.lax.scan(step, h0, seq)
    out = jnp.moveaxis(ys, 0, 1) * jax.nn.silu(z)
    return out, h if slots is None else state.at[slots].set(h)


@register_op("selective_scan")
def selective_scan(x, dt, b, c, z, a_log, d, state, slots, lengths, fresh,
                   name="selective_scan"):
    """The selective scan of ``B`` sequences over ``T`` positions, from
    and to their slots of ``state``. x (after the conv and its SiLU), dt
    (after its projection, bias and softplus), z (the gate) [B, T, D];
    b, c [B, T, N]; a_log [D, N] and d [D] (the layer's parameters);
    state [S, N, D] float32; slots, lengths [B] int32, fresh [B] bool as
    in ``causal_conv1d`` (slots None: every slot in order; fresh None:
    none). Everything is computed in float32.
    -> (y * silu(z) [B, T, D] float32, new state).

    On a TPU (or under pallas_interpret) the Pallas kernel, named
    ``name`` in the device trace (``selective_scan`` for a prefill chunk,
    ``ssm_state_update`` for a decode round); elsewhere the same
    recurrence as a ``lax.scan``."""
    from paddle_tpu.ops.pallas.core import INTERPRET, kernel_mode
    f32 = jnp.float32
    x, dt, b, c, z = (v.astype(f32) for v in (x, dt, b, c, z))
    a = -jnp.exp(a_log.astype(f32)).T                          # [N, D]
    d = d.astype(f32)
    mode = kernel_mode("selective_scan")
    if mode is None:
        return _selective_scan_xla(x, dt, b, c, z, a, d, state, slots,
                                   lengths, fresh)
    from paddle_tpu.ops.pallas.selective_scan import selective_scan_tpu
    bsz = x.shape[0]
    if slots is None:
        slots = jnp.arange(bsz, dtype=jnp.int32)
    if fresh is None:
        fresh = jnp.zeros((bsz,), jnp.int32)
    return selective_scan_tpu(x, dt, b, c, z, a, d, state, slots, lengths,
                              fresh, name=name,
                              interpret=mode == INTERPRET)
