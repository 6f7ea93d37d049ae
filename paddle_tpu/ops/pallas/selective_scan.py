"""Selective scan (Mamba-1) — Pallas TPU kernel for a recurrent layer's
state, at the two lengths the serving engine runs it.

The recurrence of one sequence, per channel d of ``D`` and state index n
of ``N`` (Gu & Dao 2023, eq. 2 with the zero-order-hold of A and the
Euler step of B that every implementation uses):

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d]
                + B_t[n] * dt_t[d] * x_t[d]
    y_t[d]    = sum_n C_t[n] * h_t[n, d] + D[d] * x_t[d]
    out_t[d]  = y_t[d] * silu(z_t[d])

The carried state lives in ONE buffer for all slots, ``[slots, N, D]``
float32 (N on sublanes, D on lanes: a lane-dense minor dim, so the
buffer is stored as declared and 16 states a channel cost no padding).
The kernel's state blocks are found through the scalar-prefetched slot
ids and the buffer is aliased input-to-output: a call reads the blocks of
the slots it is given once, keeps ``h`` in registers / VMEM over the
call's positions, writes them once, and leaves every other slot's state
where it lies — no copy of the whole state per call (the lesson of the
K/V pools, PERF.md section 6, PR 27).

One kernel, two names in the device trace:

  * ``selective_scan`` — a prefill chunk: batch 1, ``T`` positions.
    Positions at or beyond ``lengths[b]`` (the chunk's padding) leave
    ``h`` as it was; a sequence that starts here (``fresh[b]``) begins
    from zeros whatever the slot held before.
  * ``ssm_state_update`` — a decode round: every slot, one position; a
    slot with ``lengths[b] == 0`` (inactive or page-stalled) keeps its
    state.

Grid (batch, D / block_d); everything is float32 inside (the issue's
stated precision: the recurrence, ``exp(dt A)`` and ``h`` in float32).
``B_t`` and ``C_t`` arrive as rows of N lanes and are turned into
columns of N sublanes with an identity-mask and a lane reduction (a
[N, N] tile: no transposition, nothing laid out by XLA for the kernel).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.core import kernel_call

#: VMEM the four [T, block_d] float32 blocks (x, dt, z, out; each double
#: buffered) may take together
_BLOCK_BUDGET = 6 * 2 ** 20


def pick_block_d(t, d, interpret=False):
    """The widest lane-aligned divisor of ``d`` whose [t, block] blocks
    fit the budget (the whole of ``d`` under the interpreter, or where
    ``d`` has no multiple of 128 as a divisor)."""
    if interpret or d % 128:
        return d
    best = 128
    for k in range(1, d // 128 + 1):
        bd = 128 * k
        if d % bd == 0 and 8 * 4 * t * bd <= _BLOCK_BUDGET:
            best = bd
    return best


def _scan_kernel(slot_ref, len_ref, fresh_ref, x_ref, dt_ref, b_ref, c_ref,
                 z_ref, a_ref, d_ref, h_in_ref, o_ref, h_out_ref, *, t_len):
    del slot_ref                       # read by the index maps
    i = pl.program_id(0)
    length = len_ref[i]
    a = a_ref[:]                                           # [N, bd]
    skip = d_ref[:]                                        # [1, bd]
    n = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):
        """[1, N] (lanes) -> [N, 1] (sublanes)."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, h):
        x = x_ref[0, pl.ds(t, 1), :]                       # [1, bd]
        dt = dt_ref[0, pl.ds(t, 1), :]
        z = z_ref[0, pl.ds(t, 1), :]
        b_col = column(b_ref[0, pl.ds(t, 1), :])           # [N, 1]
        c_col = column(c_ref[0, pl.ds(t, 1), :])
        h_new = jnp.exp(dt * a) * h + b_col * (dt * x)     # [N, bd]
        y = jnp.sum(h_new * c_col, axis=0, keepdims=True) + skip * x
        o_ref[0, pl.ds(t, 1), :] = y * (z * jax.nn.sigmoid(z))
        return jnp.where(t < length, h_new, h)

    h0 = jnp.where(fresh_ref[i] > 0, 0.0, h_in_ref[0])
    if t_len == 1:
        h = step(0, h0)
    else:
        h = jax.lax.fori_loop(0, t_len, step, h0)
    h_out_ref[0] = h


def selective_scan_tpu(x, dt, b, c, z, a, d, state, slots, lengths, fresh,
                       *, name, interpret=False):
    """x, dt, z [B, T, D] f32; b, c [B, T, N] f32; a [N, D] f32 (the
    NEGATIVE decay rates, ``-exp(A_log)`` laid state-major); d [D] f32;
    state [S, N, D] f32 (donated by the caller's jit: updated in place);
    slots, lengths, fresh [B] int32. -> (out [B, T, D] f32, new state).
    ``name`` is the kernel's name in the device trace."""
    bsz, t_len, dim = x.shape
    n = a.shape[0]
    bd = pick_block_d(t_len, dim, interpret)
    seq = pl.BlockSpec((1, t_len, bd), lambda i, j, *_: (i, 0, j))
    bc = pl.BlockSpec((1, t_len, n), lambda i, j, *_: (i, 0, 0))
    h_spec = pl.BlockSpec((1, n, bd), lambda i, j, sl, *_: (sl[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, dim // bd),
        in_specs=[seq, seq, bc, bc, seq,
                  pl.BlockSpec((n, bd), lambda i, j, *_: (0, j)),
                  pl.BlockSpec((1, bd), lambda i, j, *_: (0, j)),
                  h_spec],
        out_specs=[seq, h_spec],
    )
    return kernel_call(
        functools.partial(_scan_kernel, t_len=t_len),
        name=name,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 10 (three prefetched scalars, then seven arrays) is the
        # state: it IS output 1
        input_output_aliases={10: 1},
        interpret=interpret,
    )(slots.astype(jnp.int32), lengths.astype(jnp.int32),
      fresh.astype(jnp.int32), x, dt, b, c, z, a, d[None, :], state)
