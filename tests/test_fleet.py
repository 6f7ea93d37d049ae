"""Fleet facade + communicator schedules + heartbeat tests.

Ref patterns: the reference's fleet api tests (test_dist_base subprocess
harness asserting trainer-vs-local loss parity) re-done as same-process
8-virtual-chip equivalence checks, and heart_beat_monitor_test.cc."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.parallel import (DistributedStrategy, GeoSGD, GradientMerge,
                                 HeartBeatMonitor, LocalSGD, fleet,
                                 stack_replicas, unstack_replica)
from paddle_tpu.parallel.heartbeat import (COMPLETED, RUNNING, STALLED,
                                           UNINITED, FileHeartbeat,
                                           barrier_with_timeout)


def quadratic_loss(target):
    def loss_fn(params, x):
        pred = x @ params["w"]
        return jnp.mean((pred - x @ target) ** 2), pred
    return loss_fn


class TestDistributedStrategy:
    def test_mesh_axes_infer(self):
        s = DistributedStrategy(dp=-1, tp=2)
        mesh = fleet.build_mesh(s)
        assert mesh.shape["tp"] == 2
        assert mesh.shape["dp"] == 4          # 8 devices / 2

    def test_default_all_dp(self):
        mesh = fleet.build_mesh(DistributedStrategy())
        assert mesh.shape["dp"] == 8

    def test_pipeline_kwargs_feed_train_step(self):
        """pp_schedule/pp_chunks plumb straight into
        make_pipeline_train_step (ref PipelineOptimizer config)."""
        from paddle_tpu.parallel.pipeline import (
            interleave_stage_params, make_pipeline_train_step,
            stack_stage_params)
        s = DistributedStrategy(dp=1, pp=8, pp_schedule="interleaved",
                                pp_chunks=2)
        assert s.pipeline_kwargs() == {"schedule": "interleaved",
                                       "num_chunks": 2}
        # inferred dp (-1 default) must NOT silently shard the batch dim
        s_inf = DistributedStrategy(pp=4, pp_schedule="1f1b")
        assert "dp_axis" not in s_inf.pipeline_kwargs()
        # gpipe has no dp composition path: never emits dp_axis
        s3 = DistributedStrategy(dp=2, pp=4)
        assert "dp_axis" not in s3.pipeline_kwargs()
        mesh = fleet.build_mesh(s)
        stacked = stack_stage_params(
            [{"w": jnp.eye(4) * 0.5} for _ in range(16)])
        opt = pt.optimizer.SGD(0.1)
        step = make_pipeline_train_step(
            mesh, lambda p, h: jnp.tanh(h @ p["w"]),
            lambda o, y: jnp.mean((o - y) ** 2), opt, "pp",
            **s.pipeline_kwargs())
        params = interleave_stage_params(stacked, 8, 2)
        x = jnp.ones((4, 2, 4)) * 0.1
        loss, params, _ = jax.jit(step)(params, opt.init(params), x, x)
        assert np.isfinite(float(loss))
        # EXPLICIT dp>1 + tick schedule -> the emitted kwargs must run
        # the hybrid end-to-end on the strategy's own mesh
        s2 = DistributedStrategy(dp=2, pp=4, pp_schedule="1f1b")
        assert s2.pipeline_kwargs()["dp_axis"] == "dp"
        mesh2 = fleet.build_mesh(s2)
        st2 = stack_stage_params(
            [{"w": jnp.eye(4) * 0.5} for _ in range(4)])
        step2 = make_pipeline_train_step(
            mesh2, lambda p, h: jnp.tanh(h @ p["w"]),
            lambda o, y: jnp.mean((o - y) ** 2), opt, "pp",
            **s2.pipeline_kwargs())
        loss2, _, _ = jax.jit(step2)(st2, opt.init(st2),
                                     jnp.ones((4, 2, 4)) * 0.1,
                                     jnp.ones((4, 2, 4)) * 0.1)
        assert np.isfinite(float(loss2))

    def test_exclusive_schedules_rejected(self):
        s = DistributedStrategy(local_sgd_steps=2, geo_sgd_steps=2)
        with pytest.raises(Exception):
            fleet.distributed_optimizer(pt.optimizer.SGD(0.1), s)

    def test_dgc_requires_dgc_momentum(self):
        with pytest.raises(Exception):
            fleet.distributed_optimizer(pt.optimizer.SGD(0.1),
                                        DistributedStrategy(dgc=True))


class TestGradientMerge:
    def test_equals_large_batch(self):
        rng = np.random.RandomState(0)
        w_t = jnp.asarray(rng.randn(4, 2).astype(np.float32))
        loss_fn = quadratic_loss(w_t)
        params = {"w": jnp.zeros((4, 2))}
        xs = [jnp.asarray(rng.randn(8, 4).astype(np.float32))
              for _ in range(4)]

        # merged: 4 micro-batches, k=4
        gm = GradientMerge(pt.optimizer.SGD(0.1), 4)
        st = gm.init(params)
        p = params
        for x in xs:
            _, p, st, _ = gm.minimize(loss_fn, p, st, x)

        # reference: one step on the mean of the 4 micro-grads
        ref_opt = pt.optimizer.SGD(0.1)
        ref_st = ref_opt.init(params)
        grads = [jax.grad(lambda pp, xx: loss_fn(pp, xx)[0])(params, x)
                 for x in xs]
        mean_g = jax.tree_util.tree_map(
            lambda *g: sum(g) / 4, *grads)
        ref_p, _ = ref_opt.apply_gradients(params, mean_g, ref_st)
        np.testing.assert_allclose(np.asarray(p["w"]),
                                   np.asarray(ref_p["w"]), atol=1e-6)

    def test_no_update_before_k(self):
        gm = GradientMerge(pt.optimizer.SGD(0.1), 3)
        params = {"w": jnp.ones((2, 2))}
        st = gm.init(params)
        loss_fn = quadratic_loss(jnp.zeros((2, 2)))
        x = jnp.ones((4, 2))
        _, p, st, _ = gm.minimize(loss_fn, params, st, x)
        np.testing.assert_allclose(np.asarray(p["w"]), 1.0)   # k=1 of 3
        _, p, st, _ = gm.minimize(loss_fn, p, st, x)
        np.testing.assert_allclose(np.asarray(p["w"]), 1.0)   # k=2 of 3
        _, p, st, _ = gm.minimize(loss_fn, p, st, x)
        assert float(jnp.max(jnp.abs(p["w"] - 1.0))) > 1e-4   # applied


def _replica_schedule_run(schedule_cls, sync_steps, n_steps):
    """Run a divergent-replica schedule over 8 shard_map groups."""
    mesh = pt.parallel.make_mesh({"dp": 8})
    rng = np.random.RandomState(1)
    w_t = jnp.asarray(rng.randn(3, 2).astype(np.float32))
    loss_fn = quadratic_loss(w_t)
    params = {"w": jnp.zeros((3, 2))}
    sched = schedule_cls(pt.optimizer.SGD(0.2), sync_steps)
    stacked = stack_replicas(params, 8)
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (8,) + x.shape)
        if hasattr(x, "shape") else x,
        sched.init(params))
    # distinct per-replica data so replicas genuinely diverge between syncs
    data = jnp.asarray(rng.randn(8, 16, 3).astype(np.float32))

    @jax.jit
    def run(stacked, state, data):
        def body(p, s, x):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            s = jax.tree_util.tree_map(lambda a: a[0], s)
            x = x[0]
            losses = []
            for _ in range(n_steps):
                l, p, s, _ = sched.step(loss_fn, p, s, x)
                losses.append(l)
            add = jax.tree_util.tree_map(lambda a: a[None], (p, s))
            return add[0], add[1], jnp.stack(losses)[None]

        return shard_map(
            body, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp"), P("dp")))(stacked, state, data)

    stacked, state, losses = run(stacked, state, data)
    return stacked, losses


class TestLocalSGD:
    def test_replicas_converge_and_sync(self):
        stacked, losses = _replica_schedule_run(LocalSGD, sync_steps=2,
                                                n_steps=6)
        w = np.asarray(stacked["w"])
        # after a sync step (6 % 2 == 0 -> last step synced), replicas match
        for i in range(1, 8):
            np.testing.assert_allclose(w[i], w[0], atol=1e-5)
        l = np.asarray(losses)
        assert l[:, -1].mean() < l[:, 0].mean()


class TestGeoSGD:
    def test_anchor_delta_sync(self):
        stacked, losses = _replica_schedule_run(GeoSGD, sync_steps=3,
                                                n_steps=6)
        w = np.asarray(stacked["w"])
        for i in range(1, 8):
            np.testing.assert_allclose(w[i], w[0], atol=1e-5)
        l = np.asarray(losses)
        assert l[:, -1].mean() < l[:, 0].mean()


class TestDCASGD:
    """Delay-compensated async SGD (ref distribute_transpiler.py:174
    dc_asgd): staleness modeled as pull_steps-stale worker copies feeding
    a shared anchor; compensation must beat plain async (lambda=0) on the
    same schedule."""

    def _run(self, lambda_, lr=0.25, pull_steps=6, n_steps=40):
        from paddle_tpu.parallel import DCASGD
        mesh = pt.parallel.make_mesh({"dp": 8})
        rng = np.random.RandomState(3)
        w_t = jnp.asarray(rng.randn(3, 2).astype(np.float32))
        loss_fn = quadratic_loss(w_t)
        params = {"w": jnp.zeros((3, 2))}
        sched = DCASGD(lr, pull_steps, lambda_=lambda_)
        stacked = stack_replicas(params, 8)
        state = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (8,) + x.shape)
            if hasattr(x, "shape") else x,
            sched.init(params))
        data = jnp.asarray(rng.randn(8, 16, 3).astype(np.float32))

        @jax.jit
        def run(stacked, state, data):
            def body(p, s, x):
                p = jax.tree_util.tree_map(lambda a: a[0], p)
                s = jax.tree_util.tree_map(lambda a: a[0], s)
                x = x[0]
                for _ in range(n_steps):
                    _, p, s, _ = sched.step(loss_fn, p, s, x)
                add = jax.tree_util.tree_map(lambda a: a[None], (p, s))
                return add[0], add[1]

            return shard_map(
                body, mesh=mesh,
                in_specs=(P("dp"), P("dp"), P("dp")),
                out_specs=(P("dp"), P("dp")))(stacked, state, data)

        stacked, state = run(stacked, state, data)
        # the anchor is the server copy; replicated across groups
        anchor = np.asarray(state["anchor"]["w"])
        for i in range(1, 8):
            np.testing.assert_allclose(anchor[i], anchor[0], atol=1e-5)
        return float(np.linalg.norm(anchor[0] - np.asarray(w_t)))

    def test_converges(self):
        dist = self._run(lambda_=1.0)
        assert dist < 0.1, dist

    def test_compensation_beats_plain_async(self):
        # identical schedule, staleness and data — only the compensation
        # term differs. lr high enough that 6-step-stale gradients make
        # plain async oscillate: the compensated anchor must land closer
        # to w* (the regime DC-ASGD exists for)
        comp = self._run(lambda_=1.0, lr=0.3)
        plain = self._run(lambda_=0.0, lr=0.3)
        assert comp < plain / 2, (comp, plain)


class TestFleetDataParallel:
    def test_matches_single_device(self):
        rng = np.random.RandomState(2)
        w_t = jnp.asarray(rng.randn(4, 3).astype(np.float32))
        loss_fn = quadratic_loss(w_t)
        params = {"w": jnp.zeros((4, 3))}
        x = jnp.asarray(rng.randn(16, 4).astype(np.float32))

        dp = fleet.data_parallel(pt.optimizer.SGD(0.1),
                                 lambda p, batch: loss_fn(p, batch[0]),
                                 DistributedStrategy(dp=-1))
        p8, st8 = dp.init(params)
        p8, st8, loss8, _ = dp.step(p8, st8, (x,))

        opt = pt.optimizer.SGD(0.1)
        st1 = opt.init(params)
        loss1, p1, st1, _ = opt.minimize(loss_fn, params, st1, x)
        np.testing.assert_allclose(np.asarray(p8["w"]), np.asarray(p1["w"]),
                                   atol=1e-5)
        np.testing.assert_allclose(float(loss8), float(loss1), atol=1e-5)


class TestHeartbeat:
    def test_stall_detection_with_fake_clock(self):
        t = [0.0]
        stalls = []
        mon = HeartBeatMonitor(3, timeout_s=10.0, interval_s=1.0,
                               on_stall=lambda w, age: stalls.append(w),
                               clock=lambda: t[0])
        mon.update(0)
        mon.update(1)
        st = mon.check()
        assert st[0][0] == RUNNING and st[2][0] == UNINITED
        t[0] = 5.0
        mon.update(1)
        t[0] = 12.0
        st = mon.check()
        assert st[0][0] == STALLED       # silent for 12s > 10s
        assert st[1][0] == RUNNING       # pinged at t=5, age 7 < 10
        assert stalls == [0]

    def test_completed_not_stalled(self):
        t = [0.0]
        mon = HeartBeatMonitor(1, timeout_s=1.0, clock=lambda: t[0])
        mon.update(0)
        mon.complete(0)
        t[0] = 100.0
        assert mon.check()[0][0] == COMPLETED
        assert mon.all_completed()

    def test_file_heartbeat(self, tmp_path):
        hb = FileHeartbeat(str(tmp_path), 0)
        hb.ping()
        st = FileHeartbeat.scan(str(tmp_path), 2, timeout_s=60.0)
        assert st[0][0] == RUNNING and st[1][0] == UNINITED
        hb.complete()
        st = FileHeartbeat.scan(str(tmp_path), 2, timeout_s=60.0)
        assert st[0][0] == COMPLETED

    def test_barrier_with_timeout(self, tmp_path):
        errs = []

        def worker(i):
            try:
                barrier_with_timeout(str(tmp_path), i, 3, timeout_s=10.0)
            except Exception as e:   # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=15)
        assert not errs

    def test_barrier_timeout_lists_missing(self, tmp_path):
        with pytest.raises(TimeoutError, match=r"missing workers \[1, 2\]"):
            barrier_with_timeout(str(tmp_path), 0, 3, timeout_s=0.3)


class TestStrategyComposition:
    def test_amp_plus_gradient_merge_runs_bf16(self):
        s = DistributedStrategy(amp=True, gradient_merge_steps=2)
        opt = fleet.distributed_optimizer(pt.optimizer.SGD(0.1), s)
        params = {"w": jnp.ones((4, 2))}
        st = opt.init(params)
        seen_dtypes = []

        def loss_fn(p, x):
            seen_dtypes.append(p["w"].dtype)
            return jnp.mean((x @ p["w"].astype(jnp.float32)) ** 2), None

        x = jnp.ones((4, 4))
        _, p, st, _ = opt.minimize(loss_fn, params, st, x)
        assert jnp.bfloat16 in seen_dtypes          # amp cast reached forward
        np.testing.assert_allclose(np.asarray(p["w"]), 1.0)  # merged k=1 of 2
        _, p, st, _ = opt.minimize(loss_fn, p, st, x)
        assert float(jnp.max(jnp.abs(p["w"] - 1.0))) > 1e-4  # applied at k=2

    def test_amp_plus_local_sgd_composes(self):
        s = DistributedStrategy(amp=True, local_sgd_steps=2)
        sched = fleet.distributed_optimizer(pt.optimizer.SGD(0.1), s)
        assert isinstance(sched, LocalSGD)
        seen = []

        def loss_fn(p, x):
            seen.append(p["w"].dtype)
            return jnp.mean((x @ p["w"].astype(jnp.float32)) ** 2), None

        mesh = pt.parallel.make_mesh({"dp": 8})
        params = {"w": jnp.ones((2, 2))}
        state = sched.init(params)
        x = jnp.ones((8, 4, 2))

        def body(p, s_, x_):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            s_ = jax.tree_util.tree_map(lambda a: a[0], s_)
            l, p, s_, _ = sched.step(loss_fn, p, s_, x_[0])
            return jax.tree_util.tree_map(lambda a: a[None], p)

        out = shard_map(
            body, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P("dp")), out_specs=P("dp"))(
            stack_replicas(params, 8),
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (8,) + a.shape)
                if hasattr(a, "shape") else a, state),
            x)
        assert jnp.bfloat16 in seen
        assert np.all(np.isfinite(np.asarray(out["w"])))

    def test_recompute_composes(self):
        s = DistributedStrategy(recompute=True, amp=True)
        opt = fleet.distributed_optimizer(pt.optimizer.SGD(0.1), s)
        params = {"w": jnp.ones((4, 2))}
        st = opt.init(params)

        def loss_fn(p, x):
            return jnp.mean((x @ p["w"].astype(jnp.float32)) ** 2), None

        loss, p, st, _ = opt.minimize(loss_fn, params, st, jnp.ones((4, 4)))
        assert float(jnp.max(jnp.abs(p["w"] - 1.0))) > 1e-4

    def test_dgc_with_amp_accepts_dgc_momentum(self):
        from paddle_tpu.optimizer.wrappers import DGCMomentum
        s = DistributedStrategy(dgc=True, amp=True)
        opt = fleet.distributed_optimizer(DGCMomentum(0.1, 0.9), s)
        assert opt is not None

    def test_data_parallel_rejects_replica_schedules(self):
        with pytest.raises(Exception, match="shard_map"):
            fleet.data_parallel(pt.optimizer.SGD(0.1),
                                lambda p, b: (jnp.zeros(()), None),
                                DistributedStrategy(local_sgd_steps=2))

    def test_fleet_barrier_reusable(self, tmp_path):
        f = pt.parallel.Fleet()
        f.init()
        # single-process worker_num == 1 -> no-op both times
        f.barrier(str(tmp_path))
        f.barrier(str(tmp_path))

    def test_fleet_barrier_generation_survives_restart(self, tmp_path):
        """A worker that restarts (fresh Fleet, gen reset) must resume at
        the generation its peers are waiting on (ADVICE r1: persist the
        generation in the shared dir, not process memory)."""
        import threading

        def mk(worker):
            class FakeWorkerFleet(pt.parallel.Fleet):
                worker_index = worker
                worker_num = 2
            f = FakeWorkerFleet()
            f.init()
            return f

        f0, f1 = mk(0), mk(1)
        for _ in range(3):  # advance both to gen 3
            t = threading.Thread(
                target=lambda: f1.barrier(str(tmp_path), timeout_s=10))
            t.start()
            f0.barrier(str(tmp_path), timeout_s=10)
            t.join()
        assert f0._barrier_gen == 3

        f0b = mk(0)  # "restarted" worker 0: in-memory gen lost
        t = threading.Thread(
            target=lambda: f1.barrier(str(tmp_path), timeout_s=10))
        t.start()
        f0b.barrier(str(tmp_path), timeout_s=10)  # must land on gen 4
        t.join()
        assert f0b._barrier_gen == 4
        assert f1._barrier_gen == 4
