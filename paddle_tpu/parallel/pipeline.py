"""Pipeline parallelism — microbatched stage execution on a mesh axis.

Ref: /root/reference/paddle/fluid/framework/pipeline_trainer.cc +
section_worker.cc:141 (program cut at `cut_list` into sections; Scopes flow
through blocking queues between section threads) and the Python splitter
PipelineOptimizer (/root/reference/python/paddle/fluid/optimizer.py:2985).

TPU-first redesign: no threads or queues — a GPipe-style schedule expressed
as a `lax.scan` over microbatches inside `shard_map` over the "pp" axis.
Each device holds one stage's params; activations hop stage→stage via
`ppermute` (ICI neighbor transfer). The scan pipelines naturally: while
device s processes microbatch m, device s-1 processes m+1 — XLA overlaps
the ppermute with compute. Bubble fraction = (S-1)/(M+S-1), as GPipe.

The reference's SectionWorker sync_steps model-replica averaging is subsumed
by the optimizer running sharded over "pp" (each stage updates its own
params; no cross-replica drift exists).
"""


import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import PP


def pipeline_forward(stage_fn, params, x, axis_name=PP, num_microbatches=None):
    """Run a stage-sharded forward inside shard_map.

    stage_fn(stage_params, h) -> h  — same signature every stage.
    params: stage-stacked pytree (leading dim = n_stages, sharded over pp).
    x: [M, mb, ...] microbatched input; only stage 0 consumes it.
    Returns final-stage outputs stacked [M, mb, ...].

    This is the inner per-device function; wrap with `shard_map` via
    `make_pipeline_fn`.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = x.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    # strip the stage dim (shard_map gives each device its own slice of size 1)
    my_params = jax.tree_util.tree_map(lambda p: p[0], params)

    total_ticks = m + n - 1
    h_shape = jax.eval_shape(lambda p, a: stage_fn(p, a), my_params,
                             jax.ShapeDtypeStruct(x.shape[1:], x.dtype))

    def tick(carry, t):
        inflight, outputs = carry
        # stage 0 injects microbatch t (if any); others use what arrived
        feed = lax.dynamic_index_in_dim(x, jnp.minimum(t, m - 1), 0,
                                        keepdims=False)
        h_in = jnp.where(me == 0, feed, inflight)
        h_out = stage_fn(my_params, h_in)
        # last stage records output for microbatch (t - (n-1))
        out_idx = t - (n - 1)
        valid = (out_idx >= 0) & (out_idx < m)
        outputs = lax.cond(
            valid & (me == n - 1),
            lambda o: lax.dynamic_update_index_in_dim(o, h_out,
                                                      jnp.maximum(out_idx, 0),
                                                      0),
            lambda o: o, outputs)
        inflight = lax.ppermute(h_out, axis_name, perm)
        return (inflight, outputs), None

    inflight0 = jnp.zeros(h_shape.shape, h_shape.dtype)
    outputs0 = jnp.zeros((m,) + h_shape.shape, h_shape.dtype)
    (_, outputs), _ = lax.scan(tick, (inflight0, outputs0),
                               jnp.arange(total_ticks))
    # only the last stage holds real outputs (others zeros) — psum
    # replicates the result across the pp axis
    return lax.psum(outputs, axis_name)


def make_pipeline_fn(mesh, stage_fn, axis_name=PP):
    """Wrap pipeline_forward in shard_map over the pp axis.

    Returns fn(stacked_params, microbatches) -> outputs where stacked_params
    leaves have leading dim n_stages (sharded over pp) and microbatches is
    [M, mb, ...] (replicated input; stage 0 reads it).
    """
    def inner(params, x):
        return pipeline_forward(stage_fn, params, x, axis_name)

    pspec = P(axis_name)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )


def _pipeline_1f1b_loss_and_grads(stage_fn, loss_fn, axis_name):
    """1F1B forward+backward schedule as a single tick scan (per device,
    inside shard_map).

    Ref: /root/reference/paddle/fluid/framework/section_worker.cc:141 — the
    reference's section threads run forward AND backward AND optimizer
    concurrently per section, which bounds in-flight activations by the
    section count instead of the microbatch count. This is the same
    property expressed as data flow: every tick runs ONE forward microstep
    (the GPipe wave) and ONE backward microstep (the reverse wave, lagging
    2(S-1) ticks), so stage s's live activations are bounded by a circular
    buffer of 2S-1 stage inputs — O(S), independent of M — while the
    autodiff-transposed GPipe scan keeps all M microbatch residuals alive.
    Backward recomputes the stage from its saved input (implicit remat, the
    1F1B memory contract).

    Timeline (S stages, M microbatches, ticks t = 0 .. M + 2S - 3):
      forward  of microbatch t - s      at stage s   (valid while < M)
      backward of microbatch t - 2(S-1) + s at stage s
    The last stage's backward of microbatch b starts the same tick as its
    forward (one-F-one-B steady state); cotangents hop stage s -> s-1 via
    reverse ppermute.

    loss_fn is applied per microbatch (outputs[None], y[None]) and the
    per-microbatch losses/gradients averaged — identical to the GPipe path
    whenever loss_fn averages over the leading microbatch axis.
    """
    def inner(params, x, y):
        n = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        m = x.shape[0]
        k = 2 * n - 1  # circular buffer: max residual age is 2(S-1) ticks
        perm_f = [(i, (i + 1) % n) for i in range(n)]
        perm_b = [(i, (i - 1) % n) for i in range(n)]
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        h_sds = jax.eval_shape(lambda p, a: stage_fn(p, a), my_params,
                               jax.ShapeDtypeStruct(x.shape[1:], x.dtype))

        def mb_loss(h_out, y_mb):
            return loss_fn(h_out[None], y_mb[None])

        def tick(carry, t):
            h_fly, g_fly, acts, gacc, lacc = carry
            # ---- forward microstep (the GPipe wave) ----
            feed = lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            h_in = jnp.where(me == 0, feed, h_fly)
            acts = lax.dynamic_update_index_in_dim(
                acts, h_in, jnp.mod(t, k), 0)
            h_out = stage_fn(my_params, h_in)
            # ---- loss head: valid only on the last stage, where the
            # backward of microbatch bl = t-(S-1) starts this same tick ----
            bl = t - (n - 1)
            y_b = lax.dynamic_index_in_dim(
                y, jnp.clip(bl, 0, m - 1), 0, keepdims=False)
            loss_v, dh_out = jax.value_and_grad(mb_loss)(h_out, y_b)
            # ---- backward microstep: stage s handles microbatch b ----
            b = t - 2 * (n - 1) + me
            g_in = jnp.where(me == n - 1, dh_out, g_fly)
            h_saved = lax.dynamic_index_in_dim(
                acts, jnp.mod(b + me, k), 0, keepdims=False)
            _, vjp_fn = jax.vjp(stage_fn, my_params, h_saved)
            dp, dh_prev = vjp_fn(g_in)
            valid_b = (b >= 0) & (b < m)
            gacc = jax.tree_util.tree_map(
                lambda a, d: a + jnp.where(valid_b, d, 0), gacc, dp)
            lacc = lacc + jnp.where(
                (me == n - 1) & (bl >= 0) & (bl < m),
                loss_v.astype(jnp.float32), 0.0)
            h_fly = lax.ppermute(h_out, axis_name, perm_f)
            g_fly = lax.ppermute(dh_prev, axis_name, perm_b)
            return (h_fly, g_fly, acts, gacc, lacc), None

        zeros_h = jnp.zeros(h_sds.shape, h_sds.dtype)
        carry0 = (zeros_h, zeros_h,
                  jnp.zeros((k,) + h_sds.shape, h_sds.dtype),
                  jax.tree_util.tree_map(jnp.zeros_like, my_params),
                  jnp.float32(0.0))
        carry, _ = lax.scan(tick, carry0, jnp.arange(m + 2 * (n - 1)))
        gacc, lacc = carry[3], carry[4]
        loss = lax.psum(lacc, axis_name) / m
        grads = jax.tree_util.tree_map(lambda g: (g / m)[None], gacc)
        return loss, grads

    return inner


def interleave_stage_params(stacked, num_stages, num_chunks):
    """[N = V*S, ...] global-stage-stacked params -> [S, V, ...] device-major
    layout for schedule='interleaved': device s holds global stages
    s, s+S, ..., s+(V-1)S (the Megatron-style round-robin placement that
    lets the pipeline ramp advance one *chunk* per tick instead of one
    full device-stage)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((num_chunks, num_stages) + a.shape[1:])
                   .swapaxes(0, 1), stacked)


def uninterleave_stage_params(inter, num_stages, num_chunks):
    """Inverse of interleave_stage_params: [S, V, ...] -> [V*S, ...]."""
    return jax.tree_util.tree_map(
        lambda a: a.swapaxes(0, 1).reshape((num_chunks * num_stages,)
                                           + a.shape[2:]), inter)


def _pipeline_interleaved_loss_and_grads(stage_fn, loss_fn, num_chunks,
                                         axis_name):
    """Interleaved 1F1B (virtual pipeline chunks) as a single tick scan.

    Ref: /root/reference/paddle/fluid/framework/pipeline_trainer.cc runs
    2k-1 *sections* with per-section concurrency — far more sections than
    devices. The TPU-native analog: each device holds V chunks (global
    stage G = v*S + s on device s, slot v), every forward/backward hop is
    still a ring ppermute (G -> G+1 always crosses to the next device,
    wrapping s=S-1 -> s=0 raises v by one), and the wave advances one
    CHUNK per tick, so the ramp costs ~(S-1) chunk-ticks instead of
    (S-1) full-stage ticks. Tick schedule (m grouped in rounds of S,
    r = m mod S, g = m div S):

      forward  F(s,v,m) = s + v*S + r + g*S*V
      backward B(s,v,m) = N + (S-1-s) + (V-1-v)*S + r + g*S*V   (N = S*V)

    Both are injective per device (one chunk-forward + one chunk-backward
    per tick) and satisfy F(G+1) = F(G)+1 / B(G) = B(G+1)+1, so each
    tick's single ppermute pair delivers exactly on time. Total ticks
    M*V + S*V + S - 1 vs the plain-1f1b equivalent V*(M + 2S - 2) chunk
    pairs — (S-2)(V-1) ticks saved, the interleave ramp win. Activation
    buffer: 2N chunk inputs (live window max 2N-1, +1 slack so a drain
    tick's store can never clobber the slot it is about to read).
    Backward recomputes each chunk from its saved input (remat implied).
    Same per-microbatch loss_fn contract as the 1f1b schedule.
    """
    def inner(params, x, y):
        n = lax.axis_size(axis_name)          # S devices
        me = lax.axis_index(axis_name)
        v_n = num_chunks                      # V chunks per device
        big_n = n * v_n                       # N global stages
        m = x.shape[0]
        k = 2 * big_n
        perm_f = [(i, (i + 1) % n) for i in range(n)]
        perm_b = [(i, (i - 1) % n) for i in range(n)]
        my_params = jax.tree_util.tree_map(lambda a: a[0], params)  # [V,...]
        chunk0 = jax.tree_util.tree_map(lambda a: a[0], my_params)
        h_sds = jax.eval_shape(lambda p, a: stage_fn(p, a), chunk0,
                               jax.ShapeDtypeStruct(x.shape[1:], x.dtype))

        def mb_loss(h_out, y_mb):
            return loss_fn(h_out[None], y_mb[None])

        def fwd_sched(t):
            u = t - me
            g, rem = u // big_n, u % big_n
            v, r = rem // n, rem % n
            mb = g * n + r
            return v, mb, (u >= 0) & (mb >= 0) & (mb < m)

        def bwd_sched(t):
            u = t - big_n - (n - 1 - me)
            g, rem = u // big_n, u % big_n
            v, r = v_n - 1 - rem // n, rem % n
            mb = g * n + r
            return v, mb, (u >= 0) & (mb >= 0) & (mb < m)

        def fwd_tick(v, mb):  # F(me, v, mb)
            return me + v * n + jnp.mod(mb, n) + (mb // n) * big_n

        def pick(tree, idx):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, idx, 0,
                                                   keepdims=False), tree)

        def tick(carry, t):
            h_fly, g_fly, pending_lg, acts, gacc, lacc = carry
            # ---- forward chunk-microstep ----
            vf, mf, fvalid = fwd_sched(t)
            feed = lax.dynamic_index_in_dim(
                x, jnp.clip(mf, 0, m - 1), 0, keepdims=False)
            h_in = jnp.where((me == 0) & (vf == 0), feed, h_fly)
            acts = lax.dynamic_update_index_in_dim(
                acts, h_in, jnp.mod(t, k), 0)
            h_out = stage_fn(pick(my_params, vf), h_in)
            # loss head: device S-1 chunk V-1 is the last global stage;
            # its backward fires one tick after this forward, so the
            # cotangent is carried in pending_lg for exactly one tick
            y_b = lax.dynamic_index_in_dim(
                y, jnp.clip(mf, 0, m - 1), 0, keepdims=False)
            loss_v, dh_out = jax.value_and_grad(mb_loss)(h_out, y_b)
            is_last_fwd = (me == n - 1) & (vf == v_n - 1) & fvalid
            lacc = lacc + jnp.where(is_last_fwd,
                                    loss_v.astype(jnp.float32), 0.0)
            new_pending = jnp.where(is_last_fwd, dh_out,
                                    jnp.zeros_like(dh_out))
            # ---- backward chunk-microstep ----
            vb, mbk, bvalid = bwd_sched(t)
            g_in = jnp.where((me == n - 1) & (vb == v_n - 1), pending_lg,
                             g_fly)
            h_saved = lax.dynamic_index_in_dim(
                acts, jnp.mod(fwd_tick(vb, mbk), k), 0, keepdims=False)
            _, vjp_fn = jax.vjp(stage_fn, pick(my_params, vb), h_saved)
            dp, dh_prev = vjp_fn(g_in)
            gacc = jax.tree_util.tree_map(
                lambda a, d: lax.dynamic_update_index_in_dim(
                    a,
                    lax.dynamic_index_in_dim(a, vb, 0, keepdims=False)
                    + jnp.where(bvalid, d, 0), vb, 0),
                gacc, dp)
            h_fly = lax.ppermute(h_out, axis_name, perm_f)
            g_fly = lax.ppermute(dh_prev, axis_name, perm_b)
            return (h_fly, g_fly, new_pending, acts, gacc, lacc), None

        zeros_h = jnp.zeros(h_sds.shape, h_sds.dtype)
        carry0 = (zeros_h, zeros_h, zeros_h,
                  jnp.zeros((k,) + h_sds.shape, h_sds.dtype),
                  jax.tree_util.tree_map(jnp.zeros_like, my_params),
                  jnp.float32(0.0))
        # last tick = B(stage 0, microbatch M-1) = (2N-1) + f(M-1) where
        # f(m) = (m mod S) + (m div S)*N is the round term (NOT (M-1)
        # collapsed — a partial last round still pays a full-round stride)
        total = 2 * big_n + (m - 1) % n + ((m - 1) // n) * big_n
        carry, _ = lax.scan(tick, carry0, jnp.arange(total))
        gacc, lacc = carry[4], carry[5]
        loss = lax.psum(lacc, axis_name) / m
        grads = jax.tree_util.tree_map(lambda g: (g / m)[None], gacc)
        return loss, grads

    return inner


def make_pipeline_train_step(mesh, stage_fn, loss_fn, opt, axis_name=PP,
                             remat=False, schedule="gpipe", num_chunks=1,
                             dp_axis=None):
    """GPipe-style pipeline-parallel TRAINING step.

    Ref: /root/reference/python/paddle/fluid/optimizer.py:2985
    (PipelineOptimizer: cut program into sections, microbatch, train) and
    section_worker.cc:141 (SectionWorker::TrainFiles runs forward AND
    backward AND optimizer per section).

    TPU-first redesign: the pipelined forward is pure differentiable lax
    (scan over ticks + ppermute hops), so the *backward pipeline schedule
    falls out of autodiff*: JAX transposes each ppermute into the reverse
    hop and the scan into a reverse-tick scan, which is exactly the GPipe
    backward wave; per-stage gradient accumulation across microbatches is
    the scan-transpose's natural cotangent sum. No section threads, no
    queues, no hand-written 1F1B — XLA schedules the waves.

    `remat=True` wraps each stage in jax.checkpoint so activations are
    rebuilt in the backward wave (the memory win 1F1B exists for;
    ref backward.py:576 _append_backward_ops_with_checkpoints_).

    Args:
      mesh: Mesh with `axis_name` of size n_stages.
      stage_fn(stage_params, h) -> h  — same signature every stage.
      loss_fn(outputs, labels) -> scalar, where outputs is [M, mb, ...]
        stacked final-stage activations.
      opt: paddle_tpu Optimizer; state/params are the stage-stacked pytrees
        (leading dim n_stages, sharded over `axis_name`), so each device
        updates its own stage's slice — the reference's per-section
        optimizer ops.

    Returns step(params, opt_state, x, y) -> (loss, params, opt_state)
    where x is [M, mb, ...] microbatches and y the matching labels.

    schedule:
      "gpipe" (default) — forward wave then autodiff-transposed backward
        wave; all M microbatch residuals live across the turnaround
        (remat=True shrinks each residual to the stage input).
      "1f1b"  — one forward + one backward microstep per tick
        (_pipeline_1f1b_loss_and_grads): live activations bounded by
        2S-1 stage inputs regardless of M, backward recomputes from the
        saved input (remat implied). Requires loss_fn to average over
        the microbatch axis (the GPipe path then matches exactly).
      "interleaved" — 1f1b over num_chunks virtual chunks per device
        (_pipeline_interleaved_loss_and_grads): params in the
        interleave_stage_params [S, V, ...] layout; the ramp advances one
        chunk per tick (the reference's many-sections-per-device
        concurrency, pipeline_trainer.cc). Same loss_fn contract.

    dp_axis (1f1b/interleaved only): name of a data-parallel mesh axis to
    compose with the pipeline — each dp replica runs the full pipeline on
    its shard of every microbatch (x/y split on the per-microbatch batch
    dim), gradients/loss psum-averaged across replicas (the reference's
    NCCL-DP x pipeline hybrid, multi_devices_graph_pass + pipeline
    sections). Params replicated over dp, sharded over the pipe axis.
    Requires loss_fn to be a uniform MEAN over the batch rows as well as
    the microbatch axis (mean-of-shard-means == global mean only then;
    a sum over batch rows would come back scaled 1/dp_n).
    """
    if num_chunks != 1 and schedule != "interleaved":
        raise ValueError(
            f"num_chunks={num_chunks} only applies to "
            f"schedule='interleaved' (got {schedule!r}) — a silently "
            "ignored chunk count would misrepresent the configured "
            "parallelism")
    if dp_axis is not None and schedule == "gpipe":
        raise ValueError(
            "dp_axis only applies to the '1f1b'/'interleaved' schedules "
            "— gpipe with dp_axis would silently run every replica on "
            "the full batch")
    pspec = P(axis_name)
    if schedule in ("1f1b", "interleaved"):
        if schedule == "interleaved":
            inner = _pipeline_interleaved_loss_and_grads(
                stage_fn, loss_fn, num_chunks, axis_name)
        else:
            inner = _pipeline_1f1b_loss_and_grads(stage_fn, loss_fn,
                                                  axis_name)
        if dp_axis is None:
            data_spec = P()
            pipe_inner = inner
        else:
            # dp replicas each pipeline their shard of every microbatch
            # ([M, mb, ...] split on the mb dim), then average
            data_spec = P(None, dp_axis)

            def pipe_inner(params, x, y, _inner=inner):
                loss, grads = _inner(params, x, y)
                dp_n = lax.axis_size(dp_axis)
                loss = lax.psum(loss, dp_axis) / dp_n
                grads = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, dp_axis) / dp_n, grads)
                return loss, grads

        fwd_bwd = shard_map(pipe_inner, mesh=mesh,
                            in_specs=(pspec, data_spec, data_spec),
                            out_specs=(P(), pspec), check_vma=False)

        def step(params, opt_state, x, y):
            if schedule == "interleaved":
                # dynamic_index clamps, so a chunk-count/layout mismatch
                # would train silently with wrong gradients — fail at
                # trace time instead (shapes are static)
                for leaf in jax.tree_util.tree_leaves(params):
                    if leaf.ndim < 2 or leaf.shape[1] != num_chunks:
                        raise ValueError(
                            f"interleaved params must have shape "
                            f"[n_stages, num_chunks={num_chunks}, ...] "
                            f"(interleave_stage_params); got leaf shape "
                            f"{leaf.shape}")
            loss, grads = fwd_bwd(params, x, y)
            params, opt_state = opt.apply_gradients(params, grads, opt_state)
            return loss, params, opt_state

        return step
    if schedule != "gpipe":
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(choices: 'gpipe', '1f1b', 'interleaved')")
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def inner(params, x):
        return pipeline_forward(fn, params, x, axis_name)

    fwd = shard_map(inner, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
                    check_vma=False)

    def global_loss(params, x, y):
        return loss_fn(fwd(params, x), y)

    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(global_loss)(params, x, y)
        params, opt_state = opt.apply_gradients(params, grads, opt_state)
        return loss, params, opt_state

    return step


def stack_stage_params(per_stage_params):
    """[{params of stage i}] -> stacked pytree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, 0), *per_stage_params)


def split_microbatches(batch, num_microbatches):
    """[B, ...] -> [M, B/M, ...] (ref: PipelineOptimizer microbatching)."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((num_microbatches, x.shape[0] // num_microbatches)
                            + x.shape[1:]), batch)
