"""Gradient-communication schedules: gradient merge, Local SGD, Geo-SGD,
DC-ASGD.

Ref: /root/reference/paddle/fluid/operators/distributed/communicator.h:276
(AsyncCommunicator — background threads merging grads before send) and :323
(GeoSgdCommunicator — train locally, periodically sync parameter deltas);
transpiler/collective.py:269 (LocalSGD — averaged params every k steps).

TPU-first: there are no background send threads — the schedules become
*functional wrappers* compiled into the train step:

- `GradientMerge` accumulates k micro-grads before one optimizer apply
  (the async communicator's merge, made deterministic).
- Local SGD / Geo-SGD need *divergent* per-group replicas, which GSPMD's
  replicated params can't express; they run under `shard_map` with params
  stacked over the dp axis (each group owns a copy) and sync by `pmean`
  every k steps — the delta ride over ICI replaces the pserver delta RPC.
"""

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.testing.chaos import fault_point


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _pmean_varying(x, axis_name):
    """pmean whose output is typed varying-over-axis: shard_map's
    check_vma needs the explicit pcast so both lax.cond branches carry
    the same type."""
    return lax.pcast(lax.pmean(x, axis_name), axis_name, to="varying")


class GradientMerge:
    """Accumulate `merge_steps` gradients, then apply their mean once.

    Wraps any paddle_tpu Optimizer; state layout:
      {"inner": opt_state, "acc": grads-like, "count": i32}
    Equivalent to `merge_steps`-times larger batch (ref: communicator
    merged-send; also fluid's GradientMergeOptimizer in later versions).
    """

    def __init__(self, optimizer, merge_steps):
        assert merge_steps >= 1
        self.inner = optimizer
        self.merge_steps = merge_steps

    def init(self, params):
        return {
            "inner": self.inner.init(params),
            "acc": _tmap(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def apply_gradients(self, params, grads, state):
        acc = _tmap(lambda a, g: a + g, state["acc"], grads)
        count = state["count"] + 1
        do_apply = count >= self.merge_steps

        def apply_branch(operand):
            params, acc, inner = operand
            mean = _tmap(lambda a: a / self.merge_steps, acc)
            p2, s2 = self.inner.apply_gradients(params, mean, inner)
            return p2, s2, _tmap(jnp.zeros_like, acc), jnp.zeros((), jnp.int32)

        def skip_branch(operand):
            params, acc, inner = operand
            return params, inner, acc, count

        params, inner, acc, count = lax.cond(
            do_apply, apply_branch, skip_branch,
            (params, acc, state["inner"]))
        return params, {"inner": inner, "acc": acc, "count": count}

    def minimize(self, loss_fn, params, state, *args, **kwargs):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, *args, **kwargs)
        params, state = self.apply_gradients(params, grads, state)
        return loss, params, state, aux


def stack_replicas(params, n):
    """Stack n copies of params along a new leading axis (to be sharded over
    the dp/ep axis inside shard_map for divergent-replica schedules)."""
    return _tmap(lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params)


def unstack_replica(params, i=0):
    return _tmap(lambda p: p[i], params)


class LocalSGD:
    """Local SGD: k local optimizer steps per group, then param averaging.

    Ref: transpiler/collective.py:269 (LocalSGD transpiler inserts periodic
    broadcast-averaged params instead of per-step allreduce).

    Use inside shard_map with params carrying a leading sharded dp axis of
    size 1 per shard (see tests / fleet.localized_train_step): `step()` is the
    per-group local update; `sync()` is the periodic pmean.
    """

    def __init__(self, optimizer, sync_steps, axis_name="dp"):
        self.inner = optimizer
        self.sync_steps = sync_steps
        self.axis_name = axis_name

    def init(self, params):
        return {"inner": self.inner.init(params),
                "since_sync": jnp.zeros((), jnp.int32)}

    def step(self, loss_fn, params, state, *args, **kwargs):
        """One local step + conditional sync (call under shard_map).
        Delegates to inner.minimize so AMP/recompute wrappers compose."""
        loss, params, inner, aux = self.inner.minimize(
            loss_fn, params, state["inner"], *args, **kwargs)
        since = state["since_sync"] + 1
        do_sync = since >= self.sync_steps
        params = lax.cond(
            do_sync,
            # pmean output is unvarying over the axis; pcast back to varying
            # so both cond branches carry the same shard_map type
            lambda p: _tmap(
                lambda x: _pmean_varying(x, self.axis_name), p),
            lambda p: p, params)
        since = jnp.where(do_sync, 0, since)
        return loss, params, {"inner": inner, "since_sync": since}, aux


class DCASGD:
    """Delay-compensated async SGD (ref: transpiler/distribute_transpiler.py:174
    — the `dc_asgd` transpiler mode where the pserver applies each late
    gradient compensated for its staleness; Zheng et al. 2017). The
    compensation is the diagonal curvature surrogate:

        g_comp = g + lambda * g ⊙ g ⊙ (w_server − w_stale)

    i.e. a first-order correction of the stale gradient toward the value
    it would have had at the server's CURRENT weights.

    TPU-first redesign: no pserver thread — staleness is modeled
    functionally under `shard_map` with divergent dp replicas (like
    LocalSGD/GeoSGD): each group trains on its last PULLED copy (stale for
    up to `pull_steps` steps) while the shared anchor (= the pserver copy)
    integrates every group's compensated gradient each step; groups re-pull
    the anchor every `pull_steps` steps. `lambda_=0` degrades to plain
    async SGD — the convergence tests compare against exactly that.
    """

    def __init__(self, lr, pull_steps, lambda_=1.0, axis_name="dp"):
        self.lr = lr
        self.pull_steps = pull_steps
        self.lambda_ = lambda_
        self.axis_name = axis_name

    def init(self, params):
        return {"anchor": params,
                "since_pull": jnp.zeros((), jnp.int32)}

    def step(self, loss_fn, params, state, *args, **kwargs):
        """One async round under shard_map: gradient at the stale local
        copy, compensated server update, periodic pull."""
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, *args, **kwargs)
        anchor = state["anchor"]
        comp = _tmap(
            lambda g, a, p: g + self.lambda_ * g * g * (a - p),
            grads, anchor, params)
        mean_comp = _tmap(
            lambda d: _pmean_varying(d, self.axis_name), comp)
        anchor = _tmap(lambda a, d: a - self.lr * d, anchor, mean_comp)
        since = state["since_pull"] + 1
        do_pull = since >= self.pull_steps
        params = lax.cond(do_pull, lambda o: o[1], lambda o: o[0],
                          (params, anchor))
        since = jnp.where(do_pull, 0, since)
        return loss, params, {"anchor": anchor, "since_pull": since}, aux


class GeoSGD:
    """Geo-SGD: k local steps, then communicate the *delta* vs the last
    synced anchor and apply everyone's average delta to the anchor.

    Ref: operators/distributed/communicator.h:323 GeoSgdCommunicator +
    geo_sgd_transpiler.py — local training with periodic delta push/pull
    against the pserver copy; here the anchor is the pserver copy and the
    delta allreduce rides ICI/DCN.
    """

    def __init__(self, optimizer, sync_steps, axis_name="dp"):
        self.inner = optimizer
        self.sync_steps = sync_steps
        self.axis_name = axis_name

    def init(self, params):
        return {"inner": self.inner.init(params),
                "anchor": params,
                "since_sync": jnp.zeros((), jnp.int32)}

    def step(self, loss_fn, params, state, *args, **kwargs):
        loss, params, inner, aux = self.inner.minimize(
            loss_fn, params, state["inner"], *args, **kwargs)
        since = state["since_sync"] + 1
        do_sync = since >= self.sync_steps

        def sync_branch(operand):
            params, anchor = operand
            delta = _tmap(lambda p, a: p - a, params, anchor)
            mean_delta = _tmap(
                lambda d: _pmean_varying(d, self.axis_name), delta)
            new_anchor = _tmap(lambda a, d: a + d, anchor, mean_delta)
            return new_anchor, new_anchor

        params, anchor = lax.cond(
            do_sync, sync_branch, lambda o: o, (params, state["anchor"]))
        since = jnp.where(do_sync, 0, since)
        return loss, params, {"inner": inner, "anchor": anchor,
                              "since_sync": since}, aux


# --- quantized dp all-reduce (the EQuARX direction, arXiv:2506.17615) ----
#
# collective.compressed_psum's int8 variant carries ONE per-tensor scale
# (a pmax round-trip per tensor, and one outlier ruins the whole tensor's
# resolution). The chunked collective below is the planner-visible
# strategy: the flattened gradient is cut into fixed-size chunks, each
# chunk carries its own shared f32 scale (4 bytes of overhead per chunk
# on the wire), values travel as int8 and are summed in int32. The
# autoplan cost model prices exactly this layout (elems x 1B + chunks x
# 4B) so search.py can CHOOSE it where the dp axis crosses slices (DCN
# bandwidth) and reject it on ICI, where the quantize/dequant compute
# overhead exceeds the wire saving. Same stock-XLA caveat as
# compressed_psum: the int32 psum means semantic parity, not true int8
# wire traffic, off EQuARX-capable backends.


def _quant_chunked(flat, chunk):
    n = flat.shape[0]
    nch = -(-n // chunk)
    return jnp.pad(flat, (0, nch * chunk - n)).reshape(nch, chunk), n


def quantized_psum(x, axis_name, chunk=None):
    """Chunked int8 quantize->psum->dequant cross-replica sum. Each chunk
    quantizes against the axis-wide absmax of that chunk (lax.pmax), so
    every shard agrees on the scale and integer sums are exact. Returns
    ``(sum_like_x, clamps)`` — `clamps` counts elements that exceeded the
    int8 range pre-clip (zero in healthy operation; non-zero flags a
    scale gone bad, e.g. non-finite gradients — the guardian's skip-apply
    gate catches the resulting non-finite update)."""
    if chunk is None:
        from paddle_tpu.core.flags import get_flag
        chunk = int(get_flag("quant_allreduce_chunk"))
    flat = x.astype(jnp.float32).reshape(-1)
    xc, n = _quant_chunked(flat, max(int(chunk), 1))
    absmax = lax.pmax(jnp.max(jnp.abs(xc), axis=1), axis_name)   # [nch]
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    qf = jnp.round(xc / scale[:, None])
    clamps = jnp.sum((jnp.abs(qf) > 127.0).astype(jnp.int32))
    q = jnp.clip(qf, -127.0, 127.0).astype(jnp.int8)
    s = lax.psum(q.astype(jnp.int32), axis_name)
    out = (s.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]
    return out.reshape(x.shape).astype(x.dtype), clamps


def quantized_pmean(x, axis_name, chunk=None):
    """Mean-reducing twin of :func:`quantized_psum` (the gradient
    exchange form). Returns ``(mean_like_x, clamps)``."""
    s, clamps = quantized_psum(x, axis_name, chunk=chunk)
    return s / lax.psum(1, axis_name), clamps


def quant_wire_bytes(num_elements, dp, chunk=None):
    """Per-chip wire bytes one quantized all-reduce of `num_elements`
    moves on a dp-way ring: 2(dp-1)/dp passes over int8 payload plus one
    f32 scale per chunk — the same expression autoplan/costmodel.py
    prices, kept here so bench rows and the planner cannot drift."""
    if chunk is None:
        from paddle_tpu.core.flags import get_flag
        chunk = int(get_flag("quant_allreduce_chunk"))
    chunk = max(int(chunk), 1)
    payload = num_elements + (-(-num_elements // chunk)) * 4
    return 2.0 * (dp - 1) / max(dp, 1) * payload


def resolve_quant_allreduce(choice=None, crosses_slices=False):
    """Resolve the `quant_allreduce` flag to a bool for one dp axis:
    'on'/'off' force it; 'auto' quantizes only cross-slice (DCN) dp axes
    — the same rule the autoplan cost model prices, so a forced choice
    and a planned one agree on when quantization pays. The
    ``collective.quant`` fault point sits on this resolution: an
    injected fault degrades the exchange to the exact f32 collective
    (counted, never raised into a step)."""
    if choice is None:
        from paddle_tpu.core.flags import get_flag
        choice = get_flag("quant_allreduce")
    try:
        fault_point("collective.quant")
    except Exception:
        _metrics.counter("collective.quant_degraded").inc()
        return False
    if choice == "on":
        return True
    if choice == "off":
        return False
    return bool(crosses_slices)


def record_quant_traffic(nbytes):
    """Publish one quantized exchange's per-chip wire traffic to the
    ``collective.quant_bytes{direction}`` counter (ring all-reduce moves
    the payload both ways)."""
    c = _metrics.counter("collective.quant_bytes")
    c.inc(nbytes, direction="send")
    c.inc(nbytes, direction="recv")


def publish_clamp_count(state, last=0):
    """Host-side delta publisher for a QuantizedGradSync state's
    cumulative clamp counter -> ``quant.overflow_clamps`` (the
    amp.skipped_steps idiom: the device count lives in the optimizer
    state; the host publishes deltas between reads). Returns the new
    `last` watermark."""
    n = int(state["clamps"])
    if n > last:
        _metrics.counter("quant.overflow_clamps").inc(n - last)
    return n


class QuantizedGradSync:
    """Data-parallel gradient exchange through the chunked int8
    collective. Wraps any paddle_tpu Optimizer; use under shard_map with
    a dp axis (the LocalSGD/GeoSGD discipline): each apply_gradients
    quantize-pmeans every gradient leaf across the axis before the inner
    apply, and accumulates the clamp count in its state
    ({"inner": opt_state, "clamps": i32} — publish_clamp_count turns it
    into the quant.overflow_clamps counter host-side).

    Parity guard: quantization error is bounded (<= scale/2 per element
    pre-mean), but a pathological batch (inf/nan gradients) collapses
    the chunk scale and surfaces as a non-finite update — exactly what
    the guardian's skip-apply gate already rejects, so a quantized step
    can degrade a step to a skip but never corrupt params."""

    def __init__(self, optimizer, axis_name="dp", chunk=None):
        self.inner = optimizer
        self.axis_name = axis_name
        self.chunk = chunk

    def init(self, params):
        return {"inner": self.inner.init(params),
                "clamps": jnp.zeros((), jnp.int32)}

    def apply_gradients(self, params, grads, state):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        synced, clamps = [], state["clamps"]
        for g in leaves:
            m, c = quantized_pmean(g, self.axis_name, chunk=self.chunk)
            synced.append(m)
            clamps = clamps + c
        mean = jax.tree_util.tree_unflatten(treedef, synced)
        params, inner = self.inner.apply_gradients(params, mean,
                                                   state["inner"])
        return params, {"inner": inner, "clamps": clamps}

    def minimize(self, loss_fn, params, state, *args, **kwargs):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, *args, **kwargs)
        params, state = self.apply_gradients(params, grads, state)
        return loss, params, state, aux
