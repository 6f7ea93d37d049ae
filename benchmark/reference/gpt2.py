"""Plain reference: GPT-2's forward pass in straightforward
``jax.numpy`` and float32 (Radford et al. 2019; pre-LN blocks, learned
positions, tied output head). No cache, no batching, no kernels. It
imports nothing of the program and is handed nothing the program has
made: the weights come from ``benchmark.harness.weights`` and the seed.

Departure from the publication, because the system under test does the
same: the exact (erf) GELU where GPT-2 uses the tanh form.

Tree layout (the benchmark's): ``blocks/<i>/{ln1,attn,ln2,fc1,fc2}``,
``tok_emb``, ``pos_emb``, ``ln_f``.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.bert import MATMULS, gelu, layer_norm


def block(x, p, num_heads, mm):
    """x + attn(LN(x)); then x + ffn(LN(x)). Causal."""
    t, h = x.shape
    hd = h // num_heads
    y = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    a = p["attn"]

    def heads(z):
        return z.reshape(t, num_heads, hd).transpose(1, 0, 2)

    q = heads(mm(y, a["wq"]) + a["bq"])
    k = heads(mm(y, a["wk"]) + a["bk"])
    v = heads(mm(y, a["wv"]) + a["bv"])
    s = mm(q, k.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -1e30)
    ctx = mm(jax.nn.softmax(s, -1), v).transpose(1, 0, 2).reshape(t, h)
    x = x + mm(ctx, a["wo"]) + a["bo"]
    y = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    f = mm(gelu(mm(y, p["fc1"]["weight"]) + p["fc1"]["bias"]),
           p["fc2"]["weight"]) + p["fc2"]["bias"]
    return x + f


@functools.partial(jax.jit, static_argnames=("num_heads", "precision",
                                             "n_out"))
def logits_at(params, ids, first, *, num_heads, n_out, precision="highest"):
    """Next-token logits [n_out, V] of one sequence ``ids`` [T] (padded
    on the right; causal, so padding changes nothing before it) at the
    ``n_out`` positions from ``first`` on: row j scores the token that
    follows position ``first + j``."""
    mm = MATMULS[precision]
    t = ids.shape[0]
    x = params["tok_emb"]["weight"][ids] + params["pos_emb"]["weight"][:t]
    blocks = [params["blocks"][str(i)] for i in range(len(params["blocks"]))]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    x, _ = jax.lax.scan(lambda x, p: (block(x, p, num_heads, mm), None),
                        x, stacked)
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return mm(rows, params["tok_emb"]["weight"].T)
