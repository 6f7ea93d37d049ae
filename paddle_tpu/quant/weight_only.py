"""Weight-only int8 serving transform.

Ref: the reference's int8 serve pipeline (slim/quantization/
quantization_pass.py:628 QuantizationFreezePass + :764 ConvertToInt8Pass)
rewrites the inference ProgramDesc so conv/mul read real int8 weights.
TPU-first form: a *params-pytree transform* — every nn.Linear kernel and
nn.Embedding table is replaced in place by

    {"weight_q": int8, "weight_scale": f32[channels]}

and the layers consume them directly (nn/layers.py Linear/Embedding, the
GPT tied head): the int8 tensor stays resident in HBM and feeds a
mixed-dtype `lax.dot_general` (or a gathered-row dequant for lookups),
so weight HBM traffic drops 2x vs bf16 / 4x vs f32 — the lever for
weight-bandwidth-bound serving (KV-cache decode reads every parameter
once per token).

Scale axes are chosen so the dequant is algebraically EXACT on the
consuming contraction (no fake-quant round trip at serve time):
  * Linear [in, out]  -> per-out-column scale: x@(q*s) == (x@q)*s
  * MultiHeadAttention wq/wk/wv/wo [E, E] -> per-out-column (same rule;
    the decode_step projections consume them int8-resident, the full
    forward dequantizes once per call)
  * Embedding [vocab, dim] -> per-row scale: works for both the lookup
    (rows[ids]*s[ids]) and the weight-tied head (x@(q*s[:,None]).T ==
    (x@q.T)*s[None,:]) — one table serves both consumers.

Quantization error is the usual symmetric-int8 rounding on the weights
only (activations stay bf16/f32); per-channel abs-max keeps it ~1e-2
relative, the same contract as the reference's channel_wise_abs_max.
"""

import jax.numpy as jnp

from paddle_tpu.nn import layers as L
from paddle_tpu.quant import ops as Q

__all__ = ["quantize_weights_int8"]


def _q8(w, axis):
    scale = Q.abs_max_scale(w, axis)
    q = Q.quantize_to_int(w, scale, 8, axis)
    # stored scale is the DEQUANT step (abs_max / 127): w ~= q * s, so the
    # consuming layers multiply by s alone. Scale keeps the ORIGINAL
    # weight dtype — it defines the dequantized output dtype, and a bf16
    # model must not silently upcast its activation path to f32 (scale
    # rounding in bf16 is far below the int8 step it multiplies).
    return q, (scale / Q.qrange(8)).astype(w.dtype)


def _module_paths(model, path=()):
    yield path, model
    for name, child in model._children.items():
        yield from _module_paths(child, path + (name,))


def quantize_weights_int8(model, params, include_embeddings=True,
                          min_size=4096):
    """Return a new params pytree with every Linear kernel (and, when
    include_embeddings, every Embedding table) replaced by int8 payload
    {"weight_q", "weight_scale"}. Leaves smaller than min_size elements
    stay float (their bandwidth does not matter and tiny layers lose the
    most accuracy). Biases, norms, and everything else pass through
    untouched. The returned tree serves directly through model.apply —
    no architecture changes, no recompile of the float path."""
    # per-module map: param name -> quantization channel axis. Exact types
    # only: subclasses (FC, QuantizedLinear) override forward() with
    # p("weight") reads that do not understand the int8 layout.
    targets = {}
    for path, mod in _module_paths(model):
        if type(mod) is L.Linear:
            targets[path] = {"weight": 1}   # [in, out] -> per-out-column
        elif type(mod) is L.MultiHeadAttention:
            # the four projection kernels [E, E] — a third of a
            # transformer block's weight bytes, read every decode step
            targets[path] = {f"w{n}": 1 for n in ("q", "k", "v", "o")}
        elif include_embeddings and type(mod) is L.Embedding:
            targets[path] = {"weight": 0}   # [vocab, dim] -> per-row

    def walk(node, path=()):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            p = path + (k,)
            axis = targets.get(path, {}).get(k)
            if (axis is not None and hasattr(v, "size")
                    and v.size >= min_size and getattr(v, "ndim", 0) == 2):
                q, s = _q8(v, axis)
                out[f"{k}_q"] = q
                out[f"{k}_scale"] = s
            else:
                out[k] = walk(v, p)
        return out

    return walk(params)
