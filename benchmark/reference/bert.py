"""Plain reference: BERT pretraining (MLM + NSP) forward, loss, gradients
and Adam, in straightforward ``jax.numpy`` and float32.

Written from the published equations (Devlin et al. 2018, arXiv
1810.04805; Adam: Kingma & Ba 2014). It imports nothing of the program
and is handed nothing the program has made: the weights come from
``benchmark.harness.weights`` and the seed. Every matmul goes through
``mm`` at ``highest`` precision (on a TPU a float32 matmul otherwise
runs as one bf16 pass).

Departures from the publication, each because the system under test does
the same and the comparison is of arithmetic, not of recipes:
LayerNorm epsilon 1e-5 (published 1e-12); no dropout (a seeded run must
repeat); plain Adam with a constant rate and no weight decay or warm-up
(the MLPerf job uses LAMB); the MLM head is taken only at the masked
positions (same mathematics, the usual recipe).

Tree layout (the benchmark's, see ``weights.make_params``): the layers'
leaves are stacked on a leading [L] axis under
``encoder/layers/layer``.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def round_int8(x):
    """Symmetric per-tensor int8 rounding (absmax scale)."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def round_fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding: the absmax lands on the
    format's largest finite value, 448."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def lower_precision_matmul(rounding):
    """A matmul whose operands are rounded to a lower precision in the
    forward AND in the backward pass (the cotangent too), as a step
    program that ran its matmuls in that precision would: the CONTROL of
    'How correct is decided'. Everything between the matmuls stays
    float32, which flatters it."""
    @jax.custom_vjp
    def mm(a, b):
        return jnp.matmul(rounding(a), rounding(b), precision=HIGHEST)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(lambda x, y: jnp.matmul(x, y, precision=HIGHEST),
                         rounding(a), rounding(b))
        return vjp(rounding(g))

    mm.defvjp(fwd, bwd)
    return mm


MATMULS = {"highest": mm_highest,
           "int8": lower_precision_matmul(round_int8),
           "fp8": lower_precision_matmul(round_fp8)}


def layer_norm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def attention(x, p, key_mask, num_heads, mm):
    """Multi-head self-attention; ``key_mask`` [B, T] is 1 where a key
    may be attended."""
    b, t, h = x.shape
    hd = h // num_heads

    def heads(y):
        return y.reshape(b, t, num_heads, hd).transpose(0, 2, 1, 3)

    q = heads(mm(x, p["wq"]) + p["bq"])
    k = heads(mm(x, p["wk"]) + p["bk"])
    v = heads(mm(x, p["wv"]) + p["bv"])
    s = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(key_mask[:, None, None, :] > 0, s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    ctx = mm(a, v).transpose(0, 2, 1, 3).reshape(b, t, h)
    return mm(ctx, p["wo"]) + p["bo"]


def encoder_layer(x, p, key_mask, num_heads, mm):
    """Post-LN layer: x = LN(x + attn(x)); x = LN(x + ffn(x))."""
    x = layer_norm(x + attention(x, p["attn"], key_mask, num_heads, mm),
                   p["ln1"]["scale"], p["ln1"]["bias"])
    f = mm(gelu(mm(x, p["fc1"]["weight"]) + p["fc1"]["bias"]),
           p["fc2"]["weight"]) + p["fc2"]["bias"]
    return layer_norm(x + f, p["ln2"]["scale"], p["ln2"]["bias"])


def loss_terms(params, batch, num_heads, mm):
    """(sum of the masked positions' MLM cross-entropies, sum of the
    rows' NSP cross-entropies) for the rows in ``batch``:
    ids [B, T], mlm_labels [B, M], nsp_labels [B], mlm_mask [B, M],
    mask_pos [B, M], attn_mask [B, T], token_type [B, T]."""
    ids, mlm_labels, nsp_labels, mlm_mask, mask_pos, attn_mask, ttype = batch
    enc = params["encoder"]
    t = ids.shape[1]
    x = (enc["tok_emb"]["weight"][ids] + enc["pos_emb"]["weight"][:t][None]
         + enc["seg_emb"]["weight"][ttype])
    x = layer_norm(x, enc["emb_ln"]["scale"], enc["emb_ln"]["bias"])

    @jax.checkpoint
    def body(x, p):
        return encoder_layer(x, p, attn_mask, num_heads, mm), None

    x, _ = jax.lax.scan(body, x, enc["layers"]["layer"])
    hm = jnp.take_along_axis(x, mask_pos[..., None], axis=1)
    hm = gelu(mm(hm, params["mlm_transform"]["weight"])
              + params["mlm_transform"]["bias"])
    hm = layer_norm(hm, params["mlm_ln"]["scale"], params["mlm_ln"]["bias"])
    logits = mm(hm, enc["tok_emb"]["weight"].T) + params["mlm_bias"]
    logp = jax.nn.log_softmax(logits, -1)
    mlm = -jnp.take_along_axis(logp, mlm_labels[..., None], -1)[..., 0]
    pooled = jnp.tanh(mm(x[:, 0], params["pooler"]["weight"])
                      + params["pooler"]["bias"])
    nsp_logits = mm(pooled, params["nsp"]["weight"]) + params["nsp"]["bias"]
    nsp = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, -1),
                               nsp_labels[:, None], -1)[:, 0]
    return jnp.sum(mlm * mlm_mask), jnp.sum(nsp)


@functools.partial(jax.jit, static_argnames=("num_heads", "precision",
                                             "block_rows"))
def loss_and_grads(params, batch, *, num_heads, precision="highest",
                   block_rows=None):
    """Loss = mean MLM CE over the masked positions + mean NSP CE over
    the rows, and its gradient, accumulated over blocks of
    ``block_rows`` rows so that it fits beside whatever else is live."""
    mm = MATMULS[precision]
    rows = batch[0].shape[0]
    block_rows = block_rows or rows
    n_mask = jnp.maximum(jnp.sum(batch[3]), 1.0)

    def block_loss(p, blk):
        mlm, nsp = loss_terms(p, blk, num_heads, mm)
        return mlm / n_mask + nsp / rows

    loss = jnp.float32(0.0)
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    for lo in range(0, rows, block_rows):
        blk = tuple(a[lo:lo + block_rows] for a in batch)
        l, g = jax.value_and_grad(block_loss)(params, blk)
        loss = loss + l
        grads = jax.tree_util.tree_map(jnp.add, grads, g)
    return loss, grads


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam_update(params, grads, m, v, step, *, lr, b1, b2, eps):
    """One bias-corrected Adam update; ``step`` counts from 0."""
    t = (step + 1).astype(jnp.float32)

    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def train_steps(params, batches, *, num_heads, optimizer, precision="highest",
                block_rows=None):
    """Follow ``len(batches)`` steps from ``params``. Returns
    ``{"losses": [...], "grad_norms": tree (first step's gradient, per
    leaf), "update_norms": tree (||p_after - p_before|| per leaf)}``,
    norms as floats. ``params`` is consumed."""
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, grads = loss_and_grads(params, batch, num_heads=num_heads,
                                     precision=precision,
                                     block_rows=block_rows)
        if i == 0:
            grad_norms = jax.device_get(leaf_norms(grads))
        params, m, v = adam_update(
            params, grads, m, v, jnp.int32(i), lr=optimizer["lr"],
            b1=optimizer["beta1"], b2=optimizer["beta2"],
            eps=optimizer["eps"])
        losses.append(float(loss))
    update_norms = jax.device_get(leaf_diff_norms(params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
