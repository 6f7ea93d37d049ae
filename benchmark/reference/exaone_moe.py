"""Plain reference: the K-EXAONE (``model_type`` ``exaone_moe``) forward
pass of ONE CHIP'S SHARE of an expert-parallel deployment, in
straightforward ``jax.numpy`` and float32 (``highest`` matmuls). No
cache, no batching, no kernel, no sorting of rows: every held expert is
applied to every position and its output weighted by the position's
gate (zero where the expert was not chosen). It imports nothing of the
program and is handed nothing the program has made: the weights come
from ``benchmark.harness.weights`` and the seed, in the dtype the
configuration serves them in (bfloat16), and are upcast here, one layer
at a time (a sparse layer's share is 3 GB in float32).

With ``n(.)`` an RMSNorm (eps ``rms_norm_eps``, learned scale) and ``u``
the residual stream:

    layer i:  x = u + n_a(Attn_i(u)),  out = x + n_f(FFN_i(x))
    logits  = n_out(out) W_head        (a head of its own, not tied)

    Attn:  q = n_q(u W_q), k = n_k(u W_k)   (the norm over each head's
           head_dim, one learned scale for q and one for k), v = u W_v;
           num_heads query heads share num_kv_heads K/V heads; scores
           q.k / sqrt(head_dim); no biases.
           layer_types[i] == "window": rotary positions on q and k after
           their norms (rotate-half over the whole head, theta
           rope_theta), and position p sees keys p - window + 1 .. p.
           "full": no rotation, every key <= p.
    FFN dense:  (silu(x W_gate) * (x W_up)) W_down
    FFN moe:    s = sigmoid(x W_r^T), float32;  S = top-k(s + b);
                g_e = scale * s_e / (sum_{j in S} s_j + 1e-20)
                E_shared(x) + sum_{e in S, e HELD} g_e E_e(x),
                every E a SiLU-gated MLP without biases

The normaliser runs over all k chosen experts, held or not; what an
absent expert would have added is left out and nothing else changes
(model-configs guide, section 4), and that partial result goes on to
the next layer. The vocabulary is the slice the chip holds.

Departures from the published description (each is an ``assumed`` key
of ``benchmark/configs/k_exaone_236b.json``, because the catalog's keys
do not state it): the norm placement (``shapes.norm_placement``, read in
ONE place, ``block``: "post" is EXAONE 4.0's, arXiv:2507.11407), the
norm on q and k, rotation in window layers only, the selection bias
``b``. The multi-token-prediction module is not held and not computed.
With ``precision`` below ``highest`` (the CONTROL) every matmul's
operands are rounded, the router's too.

Tree layout (the benchmark's): ``tok_emb/weight [V, H]``,
``lm_head/weight [H, V]``, ``norm_f/scale``, ``blocks/<i>/{norm1,
norm2}/scale``, ``blocks/<i>/mixer/{wq, wk, wv, wo, q_norm/scale,
k_norm/scale}``, and either ``blocks/<i>/{gate_proj, up_proj,
down_proj}/weight`` or ``blocks/<i>/moe/{router [E, H], bias [E],
w_gate, w_up [held, H, F], w_down [held, F, H], shared_gate, shared_up
[H, Fs], shared_down [Fs, H]}``.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.bert import MATMULS


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x [T, heads, d] at positions 0 .. T-1: rotate-half."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def attention(u, p, cfg, window, mm):
    t = u.shape[0]
    heads, hd, eps = cfg["num_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    kv_heads = p["wk"].shape[1] // hd
    q = rms_norm(mm(u, p["wq"]).reshape(t, heads, hd),
                 p["q_norm"]["scale"], eps)
    k = rms_norm(mm(u, p["wk"]).reshape(t, kv_heads, hd),
                 p["k_norm"]["scale"], eps)
    v = mm(u, p["wv"]).reshape(t, kv_heads, hd)
    pos = jnp.arange(t)
    keep = pos[None, :] <= pos[:, None]
    if window:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
        keep &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    k, v = (jnp.repeat(z, heads // kv_heads, axis=1).transpose(1, 0, 2)
            for z in (k, v))                                   # [H, T, hd]
    s = mm(q.transpose(1, 0, 2), k.transpose(0, 2, 1)) / jnp.sqrt(
        jnp.float32(hd))
    s = jnp.where(keep[None], s, -1e30)
    ctx = mm(jax.nn.softmax(s, -1), v).transpose(1, 0, 2)
    return mm(ctx.reshape(t, heads * hd), p["wo"])


def gated_mlp(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def gates(x, router, bias, k, scale, mm):
    """[T, E]: the gate of expert e at each position, 0 where e is not
    among the position's k chosen."""
    s = jax.nn.sigmoid(mm(x, router.T))
    _, chosen = jax.lax.top_k(s + bias, k)
    picked = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(1.0)
    return scale * s * picked / (jnp.sum(s * picked, -1, keepdims=True)
                                 + 1e-20)


def moe(x, p, cfg, mm):
    first, count = cfg["held_experts"]
    g = gates(x, p["router"], p["bias"], cfg["num_experts_per_tok"],
              cfg["routed_scaling_factor"], mm)
    out = gated_mlp(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                    mm)

    def one(out, e):
        y = gated_mlp(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], mm)
        return out + g[:, first + e][:, None] * y, None

    out, _ = jax.lax.scan(one, out, jnp.arange(count))
    return out


def block(u, p, cfg, window, mm):
    eps = cfg["rms_norm_eps"]
    pre = {"pre": True, "post": False}[cfg["norm_placement"]]

    def sub(x, norm, fn):
        """One sub-layer with its norm on the input or on the output:
        the ONE place that reads the placement."""
        if pre:
            return x + fn(rms_norm(x, norm["scale"], eps))
        return x + rms_norm(fn(x), norm["scale"], eps)

    x = sub(u, p["norm1"],
            lambda y: attention(y, p["mixer"], cfg, window, mm))
    if "moe" in p:
        return sub(x, p["norm2"], lambda y: moe(y, p["moe"], cfg, mm))
    return sub(x, p["norm2"], lambda y: gated_mlp(
        y, p["gate_proj"]["weight"], p["up_proj"]["weight"],
        p["down_proj"]["weight"], mm))


def upcast(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


class _Shapes(dict):
    """The configuration's ``shapes`` as a static (hashable) argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("cfg", "window", "precision"))
def one_block(x, p, *, cfg, window, precision):
    return block(x, upcast(p), cfg, window, MATMULS[precision])


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "precision"))
def head(x, scale, w, first, *, n_out, eps, precision):
    x = rms_norm(x, upcast(scale), eps)
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return MATMULS[precision](rows, upcast(w))


def logits_at(params, ids, first, *, shapes, n_out, precision="highest"):
    """Next-token logits [n_out, V] of one sequence ``ids`` [T] (padded
    on the right; causal, so padding changes nothing before it) at the
    ``n_out`` positions from ``first`` on: row j scores the token that
    follows position ``first + j``. One compiled program per KIND of
    layer (window or full, dense or sparse), called layer by layer: only
    the layer at hand is upcast. ``shapes`` is the configuration's
    ``shapes``, whole: the kinds of its layers, the held share, the
    router's rule."""
    cfg = _Shapes({k: tuple(v) if isinstance(v, list) else v
                   for k, v in shapes.items()})
    x = upcast(params["tok_emb"]["weight"][ids])
    for i in range(len(params["blocks"])):
        x = one_block(x, params["blocks"][str(i)], cfg=cfg,
                      window=cfg["layer_types"][i] == "window",
                      precision=precision)
    return head(x, params["norm_f"]["scale"], params["lm_head"]["weight"],
                first, n_out=n_out, eps=cfg["rms_norm_eps"],
                precision=precision)
