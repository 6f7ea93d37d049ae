"""Plain reference: the Jamba forward pass (Lieber et al.,
arXiv:2403.19887; the Mamba-1 mixer of Gu & Dao, arXiv:2312.00752) in
straightforward ``jax.numpy`` and float32. No cache, no batching, no
kernels: the recurrence is a sequential ``lax.scan`` over time. It
imports nothing of the program and is handed nothing the program has
made: the weights come from ``benchmark.harness.weights`` and the seed,
in the dtype the configuration serves them in (bfloat16), and are
upcast here, one layer at a time (28 layers of float32 copies would not
stand beside the bfloat16 originals on one chip).

Every layer is pre-norm with RMSNorm (eps 1e-6):

    x   = u + mixer(norm1(u))
    out = x + W_down(silu(W_gate n) * (W_up n)),   n = norm2(x)

``mixer`` is attention where the layer's parameters hold ``wq``
(``num_heads`` query heads over the K/V heads that ``wk``'s width gives,
causal, NO positional encoding, no biases) and Mamba elsewhere:

    [x, z] = n W_in;  x = silu(causal depthwise conv(x; K taps) + b)
    dt, B, C = split(x W_x);  dt = rms(dt), B = rms(B), C = rms(C)
    dt = softplus(dt W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t
    y_t = h_t C_t + D * x_t;  out = (y * silu(z)) W_out

The final norm, then logits against the tied embedding.

Tree layout (the benchmark's): ``tok_emb``, ``norm_f``,
``blocks/<i>/{norm1, norm2, mixer, gate_proj, up_proj, down_proj}``;
a Mamba ``mixer`` holds ``in_proj, conv_weight [K, D] (tap K-1 is the
current input), conv_bias, x_proj, dt_norm, b_norm, c_norm, dt_proj
(weight, bias), A_log [D, N], D, out_proj``, an attention ``mixer``
``wq, wk, wv, wo``.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.bert import MATMULS

EPS = 1e-6


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def attention(y, p, num_heads, mm):
    t = y.shape[0]
    hd = p["wq"].shape[1] // num_heads
    kv_heads = p["wk"].shape[1] // hd

    def heads(z, n):
        return z.reshape(t, n, hd).transpose(1, 0, 2)          # [n, T, hd]

    q = heads(mm(y, p["wq"]), num_heads)
    k, v = (jnp.repeat(heads(mm(y, p[w]), kv_heads), num_heads // kv_heads,
                       axis=0) for w in ("wk", "wv"))
    s = mm(q, k.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    ctx = mm(jax.nn.softmax(s, -1), v).transpose(1, 0, 2)
    return mm(ctx.reshape(t, num_heads * hd), p["wo"])


def mamba(y, p, mm):
    t = y.shape[0]
    k_taps, d = p["conv_weight"].shape
    n = p["A_log"].shape[1]
    r = p["dt_proj"]["weight"].shape[0]
    xz = mm(y, p["in_proj"]["weight"])
    x, z = xz[:, :d], xz[:, d:]
    padded = jnp.concatenate([jnp.zeros((k_taps - 1, d), x.dtype), x])
    x = jax.nn.silu(p["conv_bias"] + sum(
        padded[i:i + t] * p["conv_weight"][i] for i in range(k_taps)))
    dbc = mm(x, p["x_proj"]["weight"])
    dt = rms_norm(dbc[:, :r], p["dt_norm"]["scale"])
    b = rms_norm(dbc[:, r:r + n], p["b_norm"]["scale"])
    c = rms_norm(dbc[:, r + n:], p["c_norm"]["scale"])
    dt = jax.nn.softplus(mm(dt, p["dt_proj"]["weight"])
                         + p["dt_proj"]["bias"])
    a = -jnp.exp(p["A_log"])                                   # [D, N]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t[:, None] * a) * h
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, jnp.sum(h * c_t[None, :], -1) + p["D"] * x_t

    _, ys = jax.lax.scan(step, jnp.zeros((d, n), jnp.float32),
                         (x, dt, b, c))
    return mm(ys * jax.nn.silu(z), p["out_proj"]["weight"])


def block(x, p, num_heads, mm):
    y = rms_norm(x, p["norm1"]["scale"])
    if "wq" in p["mixer"]:
        x = x + attention(y, p["mixer"], num_heads, mm)
    else:
        x = x + mamba(y, p["mixer"], mm)
    y = rms_norm(x, p["norm2"]["scale"])
    return x + mm(jax.nn.silu(mm(y, p["gate_proj"]["weight"]))
                  * mm(y, p["up_proj"]["weight"]), p["down_proj"]["weight"])


def upcast(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("num_heads", "precision"))
def one_block(x, p, *, num_heads, precision):
    return block(x, upcast(p), num_heads, MATMULS[precision])


@functools.partial(jax.jit, static_argnames=("n_out", "precision"))
def head(x, scale, emb, first, *, n_out, precision):
    x = rms_norm(x, upcast(scale))
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return MATMULS[precision](rows, upcast(emb).T)


def logits_at(params, ids, first, *, num_heads, n_out, precision="highest"):
    """Next-token logits [n_out, V] of one sequence ``ids`` [T] (padded
    on the right; causal, so padding changes nothing before it) at the
    ``n_out`` positions from ``first`` on: row j scores the token that
    follows position ``first + j``. One compiled program per KIND of
    layer, called layer by layer: only the layer at hand is upcast."""
    emb = params["tok_emb"]["weight"]
    x = upcast(emb[ids])
    for i in range(len(params["blocks"])):
        x = one_block(x, params["blocks"][str(i)], num_heads=num_heads,
                      precision=precision)
    return head(x, params["norm_f"]["scale"], emb, first, n_out=n_out,
                precision=precision)
