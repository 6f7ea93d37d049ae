"""The Mamba-1 mixer as a layer (Gu & Dao, arXiv:2312.00752), with the
three inner RMSNorms of Jamba (Lieber et al., arXiv:2403.19887: on dt, B
and C after the input-dependent projection).

    [x, z] = u W_in                                   (hidden -> 2 D)
    x      = silu(causal_conv1d(x; K taps) + b_conv)
    dt, B, C = split(x W_x, [R, N, N]);  each RMS-normed with its scale
    dt     = softplus(dt W_dt + b_dt)                 (R -> D)
    y      = selective_scan(x, dt, A = -exp(A_log), B, C, D) * silu(z)
    out    = y W_out                                  (D -> hidden)

One forward serves every use: whole sequences from a zero state (a
model's plain forward), a prefill chunk from and to one slot of the
serving engine's per-slot state, a decode round over every slot. The
state of a sequence is the conv's last K-1 inputs and the scan's ``h``
(ops/mamba.py gives their layouts).

Precision: matmuls take operands in the weights' dtype and accumulate in
float32 (``nn.layers.matmul``); the conv, the norms, softplus and the whole
recurrence run in float32 whatever the weights are stored in.
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu import initializer as I
from paddle_tpu.nn.layers import Linear, RMSNorm, matmul
from paddle_tpu.nn.module import Module
from paddle_tpu.ops import mamba as M
from paddle_tpu.ops import nn as F


def _a_log_init(key, shape, dtype=jnp.float32):
    """The published S4D-real initialisation: A[d, n] = -(n + 1)."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
        shape).astype(dtype)


def _dt_bias_init(dt_min=1e-3, dt_max=1e-1):
    """The published dt bias: softplus(bias) is log-uniform on
    [dt_min, dt_max], so channels remember over 10 to 1000 positions."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class MambaMixer(Module):
    def __init__(self, hidden_size, d_inner, d_state=16, d_conv=4,
                 dt_rank=None, epsilon=1e-6, dtype=jnp.float32):
        super().__init__()
        dt_rank = dt_rank or -(-hidden_size // 16)
        self.d_inner, self.d_state, self.d_conv = d_inner, d_state, d_conv
        self.dt_rank = dt_rank
        self.in_proj = Linear(hidden_size, 2 * d_inner, bias=False,
                              dtype=dtype)
        self.param("conv_weight", (d_conv, d_inner),
                   I.uniform(-d_conv ** -0.5, d_conv ** -0.5), dtype)
        self.param("conv_bias", (d_inner,), I.zeros(), dtype)
        self.x_proj = Linear(d_inner, dt_rank + 2 * d_state, bias=False,
                             dtype=dtype)
        self.dt_norm = RMSNorm(dt_rank, epsilon, dtype)
        self.b_norm = RMSNorm(d_state, epsilon, dtype)
        self.c_norm = RMSNorm(d_state, epsilon, dtype)
        self.dt_proj = Linear(dt_rank, d_inner, bias_init=_dt_bias_init(),
                              dtype=dtype)
        self.param("A_log", (d_inner, d_state), _a_log_init, dtype)
        self.param("D", (d_inner,), I.ones(), dtype)
        self.out_proj = Linear(d_inner, hidden_size, bias=False, dtype=dtype)

    def init_state(self, num_slots, dtype=jnp.float32):
        """One layer's state for ``num_slots`` sequences, zeros: the
        conv window in ``dtype``, the scan's ``h`` always float32."""
        return {"conv": jnp.zeros((self.d_conv - 1, num_slots,
                                   self.d_inner), dtype),
                "ssm": jnp.zeros((num_slots, self.d_state, self.d_inner),
                                 jnp.float32)}

    def forward(self, u, state, slots, lengths, fresh,
                name="selective_scan"):
        """u [B, T, hidden]; state as ``init_state`` gives it; slots,
        lengths [B] int32 and fresh [B] bool as in ops/mamba.py (row b
        continues slot ``slots[b]`` or, fresh, starts it; only its first
        ``lengths[b]`` positions are real). ``name``: the scan kernel's
        name in the device trace. -> (out [B, T, hidden] f32, new state)."""
        f32 = jnp.float32
        n, r = self.d_state, self.dt_rank
        xz = matmul(u, self.in_proj.p("weight"))               # [B, T, 2D]
        x, z = xz[..., :self.d_inner], xz[..., self.d_inner:]
        x, conv = M.causal_conv1d(
            x, self.p("conv_weight").astype(f32),
            self.p("conv_bias").astype(f32), state["conv"], slots, lengths,
            fresh)
        x = jax.nn.silu(x)
        dbc = matmul(x, self.x_proj.p("weight"))               # [B, T, R+2N]

        def normed(norm, v):
            return F.rms_norm(v, norm.p("scale").astype(f32), norm.epsilon)

        dt = normed(self.dt_norm, dbc[..., :r])
        b = normed(self.b_norm, dbc[..., r:r + n])
        c = normed(self.c_norm, dbc[..., r + n:])
        dt = jax.nn.softplus(matmul(dt, self.dt_proj.p("weight"))
                             + self.dt_proj.p("bias").astype(f32))
        y, ssm = M.selective_scan(x, dt, b, c, z, self.p("A_log"),
                                  self.p("D"), state["ssm"], slots, lengths,
                                  fresh, name=name)
        out = matmul(y, self.out_proj.p("weight"))
        return out, {"conv": conv, "ssm": ssm}
