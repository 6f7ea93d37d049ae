"""Fused-op surface (ref: /root/reference/paddle/fluid/operators/fused/).

The reference hand-wrote these CPU/CUDA fusion kernels because its executor
ran one op at a time; on TPU, XLA's fusion pass composes the same chains
automatically, so each op here is the *mathematical composition* expressed
in one call — same name, same semantics, compiler-owned fusion. (The truly
bandwidth-bound cases that XLA cannot fuse — flash attention, fused
layer-norm — live in ops/pallas/ as real kernels instead.)

Sequence-typed inputs use the framework's padded-batch + lengths
convention (core/ragged.py) rather than LoD.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.registry import GLOBAL_OP_REGISTRY, register_op
from paddle_tpu.ops import activations as A


def _act(name, x):
    if name in (None, "", "identity"):
        return x
    return getattr(A, name)(x)


# ---- chunked/fused softmax-cross-entropy over the vocab axis -------------
# The one loss XLA cannot tile on its own: softmax_with_cross_entropy over
# LM-head logits materializes [batch, seq, vocab] f32 (and nmt_loss adds a
# same-shape one_hot) only to reduce to one scalar per row — ~1.6 GB of HBM
# traffic per GPT step at 16 x 512 x 50k. fused_xent fuses the vocab
# projection INTO the loss: logits exist only as [rows, chunk] tiles, the
# label logit is gathered per chunk, logsumexp runs online across chunks
# (flash-attention style), and label smoothing folds into closed form
# ((sp-sn)*(logz-picked) + sn*(V*logz - sum_logits)) so no one-hot tensor
# is ever built. The custom VJP recomputes per-chunk logits instead of
# saving them (the recompute-over-store discipline of the flash kernels);
# grads match the reference composition exactly.


def fused_xent_enabled():
    """PT_FUSED_XENT env (the documented spelling) wins; else the
    ``fused_xent`` flag (PT_FLAGS_fused_xent / set_flags)."""
    env = os.environ.get("PT_FUSED_XENT")
    if env is not None:
        return env.lower() in ("1", "true", "yes")
    from paddle_tpu.core.flags import get_flag
    return get_flag("fused_xent")


def _vocab_chunks(v, chunk):
    return [(c0, min(c0 + chunk, v)) for c0 in range(0, v, chunk)]


def _chunk_logits(h, w, b, c0, c1, layout):
    """f32 logits for vocab columns [c0, c1): the slice feeds the dot
    directly, so no weight copy and no full-vocab logits ever exist."""
    if layout == "vh":
        wc = jax.lax.slice_in_dim(w, c0, c1, axis=0)          # [Vc, H]
        logits = jax.lax.dot_general(
            h, wc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:                                                      # "hv"
        wc = jax.lax.slice_in_dim(w, c0, c1, axis=1)          # [H, Vc]
        logits = jax.lax.dot_general(
            h, wc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return logits + b[c0:c1].astype(jnp.float32)[None, :]


def _xent_stats_parts(h, w, b, labels, layout, chunk, need_sum):
    """Online (m, s, picked, sum_logits) per row, vocab tiled by `chunk`.

    Out-of-range labels contribute 0 to `picked` — the vocab-sharded
    caller exploits this: each shard passes labels offset by its base, so
    only the owning shard's `picked` is nonzero and a cross-shard psum
    recovers the label logit."""
    n = h.shape[0]
    v = w.shape[0] if layout == "vh" else w.shape[1]
    m = jnp.full((n,), -jnp.inf, jnp.float32)
    s = jnp.zeros((n,), jnp.float32)
    picked = jnp.zeros((n,), jnp.float32)
    sl = jnp.zeros((n,), jnp.float32)
    for c0, c1 in _vocab_chunks(v, chunk):
        logits = _chunk_logits(h, w, b, c0, c1, layout)        # [N, Vc]
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        m = m_new
        local = labels - c0
        inr = (local >= 0) & (local < c1 - c0)
        picked = picked + jnp.where(
            inr, jnp.take_along_axis(
                logits, jnp.clip(local, 0, c1 - c0 - 1)[:, None],
                axis=1)[:, 0], 0.0)
        if need_sum:
            sl = sl + jnp.sum(logits, axis=1)
    return m, s, picked, sl


def _xent_stats_xla(h, w, b, labels, layout, chunk, need_sum):
    """Online (logz, picked, sum_logits) per row, vocab tiled by `chunk`."""
    m, s, picked, sl = _xent_stats_parts(h, w, b, labels, layout, chunk,
                                         need_sum)
    return m + jnp.log(s), picked, sl


def _loss_from_stats(logz, picked, sl, v, ls):
    """The smoothed-CE closed form from the three per-row reductions. `v`
    is the GLOBAL vocab size — under vocab sharding the stats arrive
    already combined across shards but the smoothing constants still span
    the whole vocab."""
    if ls:
        sn = ls / (v - 1)
        sp = 1.0 - ls
        return (sp - sn) * (logz - picked) + sn * (v * logz - sl)
    return logz - picked


def _xent_forward(h, w, b, labels, layout, ls, chunk):
    v = w.shape[0] if layout == "vh" else w.shape[1]
    stats = None
    if layout == "vh":
        from paddle_tpu.ops.pallas.xent import xent_stats
        stats = xent_stats(h, w, b, labels)
    if stats is None:
        stats = _xent_stats_xla(h, w, b, labels, layout, chunk,
                                need_sum=ls != 0.0)
    logz, picked, sl = stats
    return _loss_from_stats(logz, picked, sl, v, ls), logz


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_xent_rows(h, w, b, labels, layout, ls, chunk):
    return _xent_forward(h, w, b, labels, layout, ls, chunk)[0]


def _fx_fwd(h, w, b, labels, layout, ls, chunk):
    loss, logz = _xent_forward(h, w, b, labels, layout, ls, chunk)
    return loss, (h, w, b, labels, logz)


def _xent_bwd_impl(h, w, b, labels, logz, g, layout, sn, sp, chunk,
                   context=""):
    """(dh, dw, db) in f32 for per-row cotangent g [N] f32 — the Pallas
    backward kernels when they apply (vh layout, TPU/interpret, flag on),
    else the chunked XLA recompute. Labels may be out of range (the
    vocab-sharded per-shard call): they never hit, so the one-hot term is
    zero on non-owning shards, exactly the sharded math."""
    if layout == "vh":
        from paddle_tpu.ops.pallas.xent import xent_bwd
        out = xent_bwd(h, w, b, labels, logz, g, sn, sp, context=context)
        if out is not None:
            return out
    v = w.shape[0] if layout == "vh" else w.shape[1]
    dh = jnp.zeros(h.shape, jnp.float32)
    dw_parts, db_parts = [], []
    for c0, c1 in _vocab_chunks(v, chunk):
        logits = _chunk_logits(h, w, b, c0, c1, layout)
        p = jnp.exp(logits - logz[:, None])
        col = c0 + jnp.arange(c1 - c0, dtype=labels.dtype)
        hit = (col[None, :] == labels[:, None]).astype(jnp.float32)
        # dlogits of the smoothed CE: softmax - smoothed one-hot
        gch = (p - sn - (sp - sn) * hit) * g[:, None]          # [N, Vc] f32
        if layout == "vh":
            wc = jax.lax.slice_in_dim(w, c0, c1, axis=0)
            dh = dh + jax.lax.dot_general(
                gch, wc.astype(jnp.float32), (((1,), (0,)), ((), ())))
            dw_parts.append(jax.lax.dot_general(
                gch, h.astype(jnp.float32),
                (((0,), (0,)), ((), ()))))                     # [Vc, H]
        else:
            wc = jax.lax.slice_in_dim(w, c0, c1, axis=1)
            dh = dh + jax.lax.dot_general(
                gch, wc.astype(jnp.float32), (((1,), (1,)), ((), ())))
            dw_parts.append(jax.lax.dot_general(
                h.astype(jnp.float32), gch,
                (((0,), (0,)), ((), ()))))                     # [H, Vc]
        db_parts.append(jnp.sum(gch, axis=0))
    dw = jnp.concatenate(dw_parts, axis=0 if layout == "vh" else 1)
    db = jnp.concatenate(db_parts, axis=0)
    return dh, dw, db


def _smooth_consts(v, ls):
    sn = ls / (v - 1) if ls else 0.0
    sp = 1.0 - ls if ls else 1.0
    return sn, sp


def _fx_bwd(layout, ls, chunk, res, g):
    h, w, b, labels, logz = res
    v = w.shape[0] if layout == "vh" else w.shape[1]
    sn, sp = _smooth_consts(v, ls)
    dh, dw, db = _xent_bwd_impl(h, w, b, labels, logz,
                                g.astype(jnp.float32), layout, sn, sp,
                                chunk)
    return (dh.astype(h.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


_fused_xent_rows.defvjp(_fx_fwd, _fx_bwd)


# ---- vocab-sharded (GSPMD / shard_map) fused cross-entropy ---------------
# The same online-logsumexp math lifted one level: each vocab shard runs
# the intra-chip chunk loop over ITS slice of the projection weight (the
# Pallas kernels apply per shard unchanged), then the running (m, s) pair,
# the label-gather term and the logit sum combine across the mesh axis
# with one pmax + three psums of [rows]-sized vectors. No [rows, V] logits
# and no gathered full-vocab weight ever exist — the collective traffic is
# O(rows), not O(rows x V) or O(V x H). The backward mirrors it: each
# shard recomputes its chunk probabilities from the shared logz, keeps
# dw/db local (they are vocab-sharded like w/b) and psums only the [rows,
# H] partial dh. Autodiff never crosses shard_map — the custom VJP wraps
# both shard_map calls, so no reliance on collective transpose rules.


def _shard_specs(layout, vocab_axis, batch_axis):
    from jax.sharding import PartitionSpec as P
    wspec = (P(vocab_axis, None) if layout == "vh"
             else P(None, vocab_axis))
    return P(batch_axis, None), wspec, P(vocab_axis), P(batch_axis)


def _sharded_fwd(h, w, b, labels, layout, ls, chunk, vocab_axis,
                 batch_axis, mesh):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    v = w.shape[0] if layout == "vh" else w.shape[1]
    need_sum = ls != 0.0

    def local_fwd(h, w, b, lbl):
        vl = w.shape[0] if layout == "vh" else w.shape[1]
        off = (jax.lax.axis_index(vocab_axis) * vl).astype(lbl.dtype)
        lbl_loc = lbl - off
        parts = None
        if layout == "vh":
            from paddle_tpu.ops.pallas.xent import xent_stats
            parts = xent_stats(h, w, b, lbl_loc, return_parts=True,
                               context=f"; requested vocab_axis="
                                       f"{vocab_axis!r} layout={layout!r}")
        if parts is None:
            parts = _xent_stats_parts(h, w, b, lbl_loc, layout, chunk,
                                      need_sum)
        m, s, picked, sl = parts
        m_g = jax.lax.pmax(m, vocab_axis)
        s_g = jax.lax.psum(s * jnp.exp(m - m_g), vocab_axis)
        logz = m_g + jnp.log(s_g)
        picked_g = jax.lax.psum(picked, vocab_axis)
        sl_g = jax.lax.psum(sl, vocab_axis) if need_sum else sl
        return _loss_from_stats(logz, picked_g, sl_g, v, ls), logz

    hspec, wspec, bspec, lspec = _shard_specs(layout, vocab_axis,
                                              batch_axis)
    return shard_map(local_fwd, mesh=mesh,
                     in_specs=(hspec, wspec, bspec, lspec),
                     out_specs=(P(batch_axis), P(batch_axis)),
                     check_vma=False)(h, w, b, labels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_xent_rows_sharded(h, w, b, labels, layout, ls, chunk,
                             vocab_axis, batch_axis, mesh):
    return _sharded_fwd(h, w, b, labels, layout, ls, chunk, vocab_axis,
                        batch_axis, mesh)[0]


def _fxs_fwd(h, w, b, labels, layout, ls, chunk, vocab_axis, batch_axis,
             mesh):
    loss, logz = _sharded_fwd(h, w, b, labels, layout, ls, chunk,
                              vocab_axis, batch_axis, mesh)
    return loss, (h, w, b, labels, logz)


def _fxs_bwd(layout, ls, chunk, vocab_axis, batch_axis, mesh, res, g):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    h, w, b, labels, logz = res
    v = w.shape[0] if layout == "vh" else w.shape[1]
    sn, sp = _smooth_consts(v, ls)
    ctx = f"; requested vocab_axis={vocab_axis!r} layout={layout!r}"

    def local_bwd(h, w, b, lbl, logz, g):
        vl = w.shape[0] if layout == "vh" else w.shape[1]
        off = (jax.lax.axis_index(vocab_axis) * vl).astype(lbl.dtype)
        dh, dw, db = _xent_bwd_impl(h, w, b, lbl - off, logz,
                                    g.astype(jnp.float32), layout, sn, sp,
                                    chunk, context=ctx)
        # dh sums partial per-shard contributions over the vocab axis;
        # dw/db stay vocab-local (sharded exactly like w/b) but sum the
        # row contributions each batch shard computed from its own rows
        if batch_axis is not None:
            dw = jax.lax.psum(dw, batch_axis)
            db = jax.lax.psum(db, batch_axis)
        return jax.lax.psum(dh, vocab_axis), dw, db

    hspec, wspec, bspec, lspec = _shard_specs(layout, vocab_axis,
                                              batch_axis)
    dh, dw, db = shard_map(
        local_bwd, mesh=mesh,
        in_specs=(hspec, wspec, bspec, lspec, P(batch_axis),
                  P(batch_axis)),
        out_specs=(hspec, wspec, bspec), check_vma=False)(
        h, w, b, labels, logz, g)
    return (dh.astype(h.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


_fused_xent_rows_sharded.defvjp(_fxs_fwd, _fxs_bwd)


def _infer_sharded_call(weight, labels, layout):
    """(vocab_axis, batch_axis, mesh) read off CONCRETE array shardings —
    tracers carry no sharding on this jax, so under jit callers pass
    vocab_axis explicitly (the model .loss() entry points plumb it)."""
    try:
        sh = weight.sharding
        spec = tuple(sh.spec)
        mesh = sh.mesh
    except Exception:
        return None, None, None

    def _axis(entry):
        if isinstance(entry, (tuple, list)):
            return entry[0] if entry else None
        return entry

    dim = 0 if layout == "vh" else 1
    vocab_axis = _axis(spec[dim]) if dim < len(spec) else None
    if vocab_axis is None:
        return None, None, None
    batch_axis = None
    try:
        lspec = tuple(labels.sharding.spec)
        batch_axis = _axis(lspec[0]) if lspec else None
    except Exception:
        pass
    return vocab_axis, batch_axis, mesh


@register_op("fused_xent")
def fused_xent(hidden, weight, labels, bias=None, weight_layout="vh",
               label_smoothing=0.0, chunk=None, vocab_axis=None,
               batch_axis=None, mesh=None):
    """Per-position softmax cross entropy WITHOUT materializing logits.

    hidden [..., H]; weight [V, H] ("vh", the tied-embedding layout) or
    [H, V] ("hv", the output-projection layout); labels [...] int (< V);
    bias [V] optional. Returns f32 loss with labels' shape — equal to
    ``softmax_with_cross_entropy(project(hidden), labels)`` (plus the
    label-smoothed soft-label form when label_smoothing > 0), with value
    and gradient fused/tiled over the vocab axis.

    vocab_axis: mesh axis name the VOCAB dim of weight/bias is partitioned
    over (tensor parallelism). The chunk loop then runs per shard inside
    shard_map and the (m, s)/picked/sum stats combine with pmax/psum — no
    full-vocab weight gather, no [rows, V] temporary, O(rows) collective
    traffic. Auto-detected from ``weight.sharding`` when the arrays are
    concrete (eager); under jit pass it explicitly.
    batch_axis: mesh axis the row (batch*seq) dim of hidden/labels is
    sharded over (usually "dp"); None keeps rows replicated per shard.
    mesh: Mesh for the sharded path; defaults to the enclosing
    ``with mesh:`` context, else the weight's own sharding mesh."""
    if chunk is None:
        from paddle_tpu.core.flags import get_flag
        chunk = get_flag("xent_chunk")
    if vocab_axis is None and mesh is None:
        vocab_axis, auto_batch, mesh = _infer_sharded_call(
            weight, labels, weight_layout)
        if batch_axis is None:
            batch_axis = auto_batch
    lead = labels.shape
    h2 = hidden.reshape(-1, hidden.shape[-1])
    lbl = labels.reshape(-1).astype(jnp.int32)
    v = weight.shape[0] if weight_layout == "vh" else weight.shape[1]
    b = bias if bias is not None else jnp.zeros((v,), jnp.float32)
    if vocab_axis is not None:
        from paddle_tpu.core.enforce import enforce
        if mesh is None:
            from paddle_tpu.parallel.mesh import current_mesh
            mesh = current_mesh()
        enforce(mesh is not None,
                "fused_xent(vocab_axis=...) needs a mesh: pass mesh= or "
                "call under `with mesh:`")
        tp = mesh.shape[vocab_axis]
        if tp > 1:
            enforce(v % tp == 0,
                    f"vocab {v} not divisible by mesh axis "
                    f"{vocab_axis!r} size {tp}")
            loss = _fused_xent_rows_sharded(
                h2, weight, b, lbl, weight_layout, float(label_smoothing),
                int(chunk), vocab_axis, batch_axis, mesh)
            return loss.reshape(lead)
    loss = _fused_xent_rows(h2, weight, b, lbl, weight_layout,
                            float(label_smoothing), int(chunk))
    return loss.reshape(lead)


@register_op("fused_elemwise_activation")
def fused_elemwise_activation(x, y, functor_list=("elementwise_add", "relu"),
                              scale=1.0):
    """ref fused/fused_elemwise_activation_op.{cc,h} — exact reference
    composition rules:
      [binary, unary]  ->  Binary(X, Unary(Y))   e.g. add,relu = x+relu(y)
      [unary, binary]  ->  Unary(Binary(X, Y))   e.g. relu,add = relu(x+y)
    Unaries: relu, scale (with the `scale` attr), per the reference's
    supported functor pairs."""
    from paddle_tpu.core.enforce import enforce
    binary_fns = {"elementwise_add": jnp.add, "elementwise_mul": jnp.multiply}

    def unary(name, t):
        if name == "relu":
            return jnp.maximum(t, 0.0)
        if name == "scale":
            return t * scale
        enforce(False, f"unsupported unary functor '{name}' "
                       "(reference supports relu, scale)")

    f0, f1 = functor_list
    if f0 in binary_fns:
        return binary_fns[f0](x, unary(f1, y))    # Binary(X, Unary(Y))
    enforce(f1 in binary_fns,
            f"functor_list {functor_list} has no binary functor")
    return unary(f0, binary_fns[f1](x, y))        # Unary(Binary(X, Y))


@register_op("fused_embedding_seq_pool")
def fused_embedding_seq_pool(table, ids, lengths=None, combiner="sum"):
    """ref fused/fused_embedding_seq_pool_op.cc — lookup + per-sequence sum
    pool. ids: [B, T] padded; lengths: [B] valid counts."""
    emb = jnp.take(table, ids, axis=0)                  # [B, T, D]
    if lengths is not None:
        mask = (jnp.arange(ids.shape[1])[None, :]
                < lengths[:, None]).astype(emb.dtype)
        emb = emb * mask[..., None]
    out = jnp.sum(emb, axis=1)
    if combiner == "mean":
        n = (jnp.maximum(lengths, 1)[:, None].astype(out.dtype)
             if lengths is not None else float(ids.shape[1]))
        out = out / n
    return out


@register_op("fused_fc_elementwise_layernorm")
def fused_fc_elementwise_layernorm(x, w, y, bias=None, scale=None,
                                   shift=None, epsilon=1e-5):
    """ref fused/fused_fc_elementwise_layernorm_op.cc —
    layer_norm(fc(x, w) + y)."""
    h = x @ w
    if bias is not None:
        h = h + bias
    h = h + y
    m = jnp.mean(h, -1, keepdims=True)
    v = jnp.var(h, -1, keepdims=True)
    out = (h - m) * jax.lax.rsqrt(v + epsilon)
    if scale is not None:
        out = out * scale
    if shift is not None:
        out = out + shift
    return out


@register_op("fusion_repeated_fc_relu")
def fusion_repeated_fc_relu(x, weights, biases):
    """ref fused/fusion_repeated_fc_relu_op.cc — a chain of fc+relu."""
    h = x
    for w, b in zip(weights, biases):
        h = jnp.maximum(h @ w + b, 0.0)
    return h


@register_op("fusion_squared_mat_sub")
def fusion_squared_mat_sub(x, y, scalar=1.0):
    """ref fused/fusion_squared_mat_sub_op.cc —
    ((x @ y)^2 - (x^2 @ y^2)) * scalar (the FM interaction trick)."""
    xy = x @ y
    return (xy * xy - (x * x) @ (y * y)) * scalar


@register_op("fusion_transpose_flatten_concat")
def fusion_transpose_flatten_concat(inputs, trans_axis, flatten_axis,
                                    concat_axis=0):
    """ref fused/fusion_transpose_flatten_concat_op.cc — per-input
    transpose -> flatten-from-axis -> concat."""
    outs = []
    for t in inputs:
        t = jnp.transpose(t, trans_axis)
        lead = 1
        for d in t.shape[:flatten_axis]:
            lead *= int(d)
        outs.append(t.reshape(lead, -1))
    return jnp.concatenate(outs, axis=concat_axis)


@register_op("fusion_seqpool_concat")
def fusion_seqpool_concat(inputs, lengths=None, pooltype="SUM"):
    """ref fused/fusion_seqpool_concat_op.cc — seq-pool each input then
    concat along features. inputs: list of [B, T, D] padded;
    pooltype: SUM | AVERAGE | SQRT (sum / sqrt(len), the reference's
    sequence_pool modes)."""
    pooled = []
    for x in inputs:
        n = (jnp.maximum(lengths, 1)[:, None].astype(x.dtype)
             if lengths is not None else float(x.shape[1]))
        if lengths is not None:
            mask = (jnp.arange(x.shape[1])[None, :]
                    < lengths[:, None]).astype(x.dtype)
            x = x * mask[..., None]
        s = jnp.sum(x, axis=1)
        if pooltype == "AVERAGE":
            s = s / n
        elif pooltype == "SQRT":
            s = s / jnp.sqrt(n)
        pooled.append(s)
    return jnp.concatenate(pooled, axis=-1)


@register_op("fusion_seqpool_cvm_concat")
def fusion_seqpool_cvm_concat(inputs, lengths=None, use_cvm=True,
                              pooltype="SUM"):
    """ref fused/fusion_seqpool_cvm_concat_op.cc — seq-pool + CVM transform
    + concat (the Baidu CTR ingest chain)."""
    from paddle_tpu.ops.tail import continuous_value_model
    outs = []
    for x in inputs:
        s = fusion_seqpool_concat([x], lengths, pooltype=pooltype)
        outs.append(continuous_value_model(s, use_cvm=use_cvm))
    return jnp.concatenate(outs, axis=-1)


@register_op("fusion_seqexpand_concat_fc")
def fusion_seqexpand_concat_fc(seq_input, static_inputs, w, bias=None,
                               act="relu"):
    """ref fused/fusion_seqexpand_concat_fc_op.cc — broadcast per-batch
    static features along the sequence, concat with the sequence input,
    one fc + activation. seq_input: [B, T, D0]; static: list of [B, Di]."""
    b, t, _ = seq_input.shape
    parts = [seq_input] + [jnp.broadcast_to(s[:, None, :], (b, t, s.shape[-1]))
                           for s in static_inputs]
    h = jnp.concatenate(parts, axis=-1) @ w
    if bias is not None:
        h = h + bias
    return _act(act, h)


@register_op("fusion_seqconv_eltadd_relu")
def fusion_seqconv_eltadd_relu(x, w, b, context_length, context_start=None,
                               lengths=None):
    """ref fused/fusion_seqconv_eltadd_relu_op.cc —
    relu(sequence_conv(x) + b). x: [B, T, D] padded; w:
    [context_length*D, out]; same window math as ops.sequence.sequence_conv
    (which takes a RaggedBatch)."""
    start = (-((context_length - 1) // 2) if context_start is None
             else context_start)
    B, T, D = x.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    xm = jnp.where(mask[..., None], x, 0.0)
    cols = []
    for k in range(context_length):
        off = start + k
        shifted = jnp.roll(xm, -off, axis=1)
        pos = jnp.arange(T) + off
        valid = (pos >= 0)[None, :] & (pos[None, :] < lengths[:, None])
        cols.append(jnp.where(valid[..., None], shifted, 0.0))
    ctx = jnp.concatenate(cols, axis=-1)
    return jnp.maximum(ctx @ w + b, 0.0)


@register_op("conv_fusion")
def conv_fusion(x, weight, bias=None, residual=None, stride=1, padding=0,
                dilation=1, groups=1, activation="relu",
                data_format="NCHW"):
    """ref fused/conv_fusion_op.cc (cudnnConvolutionBiasActivationForward):
    activation(conv(x, w) + bias + residual)."""
    from paddle_tpu.ops.nn import conv2d
    out = conv2d(x, weight, bias, stride, padding, dilation, groups,
                 data_format=data_format)
    if residual is not None:
        out = out + residual
    return _act(None if activation == "identity" else activation, out)


@register_op("fused_embedding_fc_lstm")
def fused_embedding_fc_lstm(ids, embeddings, h0, c0, w_hh, bias=None,
                            lengths=None):
    """ref fused/fused_embedding_fc_lstm_op.cc — the embedding lookup and
    the LSTM input projection are pre-fused: `embeddings` is the table
    ALREADY multiplied by the input weight ([V, 4H], the op's rearranged
    WeightX@Embeddings input), so the lookup IS the x-projection."""
    from paddle_tpu.ops.rnn import lstm
    xproj = jnp.take(embeddings, ids, axis=0)          # [B, T, 4H]
    ident = jnp.eye(xproj.shape[-1], dtype=xproj.dtype)
    return lstm(xproj, h0, c0, ident, w_hh, b=bias, lengths=lengths)


def register_fused_aliases():
    """Name aliases for fused ops whose base op already covers the fused
    semantics exactly (the hand-fused CPU kernels of the same math)."""
    from paddle_tpu.ops.tail import _alias
    for name, target in (
            ("fusion_gru", "gru"),
            ("fusion_lstm", "lstm"),
            ("fusion_conv_inception", "conv_fusion"),
            ("multihead_matmul", "multihead_attention")):
        _alias(name, target)
