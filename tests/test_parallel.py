"""Distributed tests on the 8-virtual-device CPU mesh.

Ref: the reference's multi-device test strategy (SURVEY.md §4):
parallel_executor_test_base.py compares single- vs multi-device losses;
test_dist_base.py runs subprocess clusters. Here: 1-chip vs 8-chip mesh
equivalence under pjit, collective unit tests under shard_map, ring/Ulysses
attention vs dense attention, pipeline vs sequential, sharded embedding vs
dense gather, DGC compressed allreduce vs dense.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import paddle_tpu as pt
from paddle_tpu.parallel import collective as C


def r(shape, seed=0):
    return np.random.RandomState(seed).rand(*shape).astype(np.float32)


@pytest.fixture(scope="module")
def mesh8():
    return pt.parallel.make_mesh({"dp": 8})


class TestCollectives:
    def test_all_reduce_sum(self, mesh8):
        x = jnp.arange(8, dtype=jnp.float32)
        out = shard_map(lambda v: C.all_reduce(v, "dp"), mesh=mesh8,
                        in_specs=P("dp"), out_specs=P("dp"))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_all_gather(self, mesh8):
        x = jnp.arange(8, dtype=jnp.float32)
        # tiled all_gather: each device ends with the full vector
        out = shard_map(lambda v: C.all_gather(v, "dp"), mesh=mesh8,
                        in_specs=P("dp"), out_specs=P("dp"))(x)
        assert out.shape == (64,)
        np.testing.assert_allclose(np.asarray(out)[:8], np.arange(8.0))
        np.testing.assert_allclose(np.asarray(out)[56:], np.arange(8.0))

    def test_reduce_scatter(self, mesh8):
        x = jnp.ones((8, 8), jnp.float32)
        out = shard_map(lambda v: C.reduce_scatter(v[0], "dp"), mesh=mesh8,
                        in_specs=P("dp", None), out_specs=P("dp"))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))

    def test_broadcast(self, mesh8):
        x = jnp.arange(8, dtype=jnp.float32)
        out = shard_map(lambda v: C.broadcast(v, "dp", root=3), mesh=mesh8,
                        in_specs=P("dp"), out_specs=P("dp"))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))

    def test_ring_shift(self, mesh8):
        x = jnp.arange(8, dtype=jnp.float32)
        out = shard_map(lambda v: C.ring_shift(v, "dp", 1), mesh=mesh8,
                        in_specs=P("dp"), out_specs=P("dp"))(x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.roll(np.arange(8.0), 1))


class TestDataParallelEquivalence:
    """ref: parallel_executor_test_base.py — same model, same data, 1 chip
    vs 8-chip data-parallel must produce the same losses/params."""

    def _setup(self):
        model = pt.models.MLP(num_classes=4, in_dim=8)
        variables = model.init(jax.random.key(0))
        opt = pt.optimizer.Momentum(0.1, 0.9)
        x = jnp.asarray(r((16, 8)))
        y = jnp.asarray(np.random.RandomState(1).randint(0, 4, (16, 1)))

        def loss_fn(params, batch):
            out = model.apply({"params": params, "state": {}}, batch[0])
            return jnp.mean(pt.ops.loss.softmax_with_cross_entropy(
                out, batch[1])), out
        return model, variables, opt, loss_fn, (x, y)

    def test_1chip_vs_8chip_losses_match(self, mesh8):
        model, variables, opt, loss_fn, batch = self._setup()

        # single chip
        p1 = variables["params"]
        s1 = opt.init(p1)
        losses1 = []
        step = jax.jit(lambda p, s, b: opt.minimize(loss_fn, p, s, b))
        for _ in range(5):
            loss, p1, s1, _ = step(p1, s1, batch)
            losses1.append(float(loss))

        # 8-chip data parallel via DataParallel wrapper
        dp = pt.parallel.DataParallel(mesh8, opt, loss_fn)
        p8, s8 = dp.init(variables["params"])
        losses8 = []
        for _ in range(5):
            p8, s8, loss, _ = dp.step(p8, s8, batch)
            losses8.append(float(loss))

        np.testing.assert_allclose(losses1, losses8, rtol=1e-4)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5), p1, p8)


class TestShardingUtils:
    def test_shard_batch_places_on_dp(self, mesh8):
        x = jnp.ones((16, 4))
        out = pt.parallel.shard_batch(mesh8, {"x": x})
        assert out["x"].sharding.spec == P("dp")

    def test_fsdp_sharding_shards_large_params(self):
        mesh = pt.parallel.make_mesh({"fsdp": 8})
        tree = {"big": jnp.ones((64, 128)), "small": jnp.ones((3,))}
        out = pt.parallel.fsdp_sharding(mesh, tree)
        assert out["big"].sharding.spec in (P("fsdp", None), P(None, "fsdp"))
        assert out["small"].sharding.spec == P()

    def test_local_sgd_sync(self, mesh8):
        params = jnp.arange(8, dtype=jnp.float32)
        out = shard_map(
            lambda p: pt.parallel.local_sgd_sync(p, "dp"), mesh=mesh8,
            in_specs=P("dp"), out_specs=P("dp"))(params)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 3.5))


class TestRingAttention:
    def test_matches_dense(self, mesh8):
        from paddle_tpu.parallel.ring_attention import ring_attention
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        q = jnp.asarray(r((2, 2, 32, 8)))
        k = jnp.asarray(r((2, 2, 32, 8), 1))
        v = jnp.asarray(r((2, 2, 32, 8), 2))
        sp_mesh = pt.parallel.make_mesh({"sp": 8})
        ra = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp", causal=True),
            mesh=sp_mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None))
        out = ra(q, k, v)
        ref = scaled_dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_ulysses_matches_dense(self, mesh8):
        from paddle_tpu.parallel.ring_attention import ulysses_attention
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        q = jnp.asarray(r((2, 8, 16, 8)))
        sp_mesh = pt.parallel.make_mesh({"sp": 8})
        ua = shard_map(
            lambda q_, k_, v_: ulysses_attention(q_, k_, v_, "sp"),
            mesh=sp_mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), check_vma=False)
        out = ua(q, q, q)
        ref = scaled_dot_product_attention(q, q, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestPipeline:
    def test_pipeline_matches_sequential(self, mesh8):
        from paddle_tpu.parallel.pipeline import (pipeline_forward,
                                                  stack_stage_params)
        dim = 8
        keys = jax.random.split(jax.random.key(0), 8)
        stage_params = [{"w": jax.random.normal(k, (dim, dim)) * 0.3}
                        for k in keys]
        stacked = stack_stage_params(stage_params)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        micro = jnp.asarray(r((6, 2, dim)))
        pp_mesh = pt.parallel.make_mesh({"pp": 8})
        pipe = shard_map(
            lambda ps, x: pipeline_forward(stage_fn, ps, x, "pp"),
            mesh=pp_mesh, in_specs=({"w": P("pp", None, None)}, P()),
            out_specs=P(), check_vma=False)
        out = pipe(stacked, micro)
        ref = micro
        for sp in stage_params:
            ref = jnp.tanh(ref @ sp["w"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("remat,schedule",
                             [(False, "gpipe"), (True, "gpipe"),
                              (False, "1f1b")])
    def test_pipeline_training_matches_sequential(self, mesh8, remat,
                                                  schedule):
        """8-stage pipelined TRAINING (fwd+bwd+opt) == single-device training.

        Ref capability: optimizer.py:2985 PipelineOptimizer +
        section_worker.cc:141 (sections run backward + optimizer too).
        The 1f1b schedule must produce the same losses and parameters as
        the autodiff-transposed GPipe wave (loss-equivalence half of
        VERDICT r4 #7)."""
        from paddle_tpu.parallel.pipeline import (make_pipeline_train_step,
                                                  split_microbatches,
                                                  stack_stage_params)
        dim, n_stages, n_micro, mb = 8, 8, 4, 2
        keys = jax.random.split(jax.random.key(3), n_stages)
        stage_params = [{"w": jax.random.normal(k, (dim, dim)) * 0.3,
                         "b": jnp.zeros((dim,))} for k in keys]
        stacked = stack_stage_params(stage_params)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def loss_fn(outs, labels):
            return jnp.mean((outs - labels) ** 2)

        x = jnp.asarray(r((n_micro * mb, dim)))
        y = jnp.asarray(r((n_micro * mb, dim)))
        xm = split_microbatches(x, n_micro)
        ym = split_microbatches(y, n_micro)

        pp_mesh = pt.parallel.make_mesh({"pp": n_stages})
        opt = pt.optimizer.Momentum(0.1, 0.9)
        step = jax.jit(make_pipeline_train_step(
            pp_mesh, stage_fn, loss_fn, opt, "pp", remat=remat,
            schedule=schedule))

        # sequential single-device baseline: same stages applied in order
        ref_params = stacked
        ref_opt = pt.optimizer.Momentum(0.1, 0.9)

        def seq_loss(params, x, y):
            h = x
            for i in range(n_stages):
                h = stage_fn(jax.tree_util.tree_map(lambda a: a[i], params), h)
            return jnp.mean((h - y) ** 2)

        @jax.jit
        def seq_step(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(seq_loss)(params, x, y)
            params, opt_state = ref_opt.apply_gradients(params, grads,
                                                        opt_state)
            return loss, params, opt_state

        pp_state = opt.init(stacked)
        ref_state = ref_opt.init(ref_params)
        pp_params = stacked
        for _ in range(3):
            pl, pp_params, pp_state = step(pp_params, pp_state, xm, ym)
            rl, ref_params, ref_state = seq_step(ref_params, ref_state, x, y)
            np.testing.assert_allclose(float(pl), float(rl), atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5),
            pp_params, ref_params)

    def test_pipeline_interleaved_matches_sequential(self, mesh8):
        """schedule='interleaved' (VERDICT r4 #7's virtual-chunk option,
        ref pipeline_trainer.cc's many-sections-per-device concurrency):
        16 global stages round-robined over 8 devices as 2 chunks each
        must train identically to the sequential 16-stage model. M=10 is
        deliberately NOT a multiple of S — the partial last round pays a
        full-round tick stride (regression: a truncated drain silently
        drops the last group's early-stage gradients)."""
        from paddle_tpu.parallel.pipeline import (
            interleave_stage_params, make_pipeline_train_step,
            split_microbatches, stack_stage_params,
            uninterleave_stage_params)
        n_stages, n_chunks, n_micro, dim, mb = 8, 2, 10, 8, 2
        n_global = n_stages * n_chunks
        keys = jax.random.split(jax.random.key(3), n_global)
        stacked = stack_stage_params(
            [{"w": jax.random.normal(k, (dim, dim)) * 0.3,
              "b": jnp.zeros((dim,))} for k in keys])
        inter = interleave_stage_params(stacked, n_stages, n_chunks)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b),
            uninterleave_stage_params(inter, n_stages, n_chunks), stacked)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def loss_fn(outs, labels):
            return jnp.mean((outs - labels) ** 2)

        x = jnp.asarray(r((n_micro * mb, dim)))
        y = jnp.asarray(r((n_micro * mb, dim), 1))
        xm = split_microbatches(x, n_micro)
        ym = split_microbatches(y, n_micro)
        pp_mesh = pt.parallel.make_mesh({"pp": n_stages})
        opt = pt.optimizer.Momentum(0.1, 0.9)
        step = jax.jit(make_pipeline_train_step(
            pp_mesh, stage_fn, loss_fn, opt, "pp", schedule="interleaved",
            num_chunks=n_chunks))

        def seq_loss(params, x, y):
            h = x
            for i in range(n_global):
                h = stage_fn(
                    jax.tree_util.tree_map(lambda a: a[i], params), h)
            return jnp.mean((h - y) ** 2)

        ref_opt = pt.optimizer.Momentum(0.1, 0.9)

        @jax.jit
        def seq_step(params, st, x, y):
            l, g = jax.value_and_grad(seq_loss)(params, x, y)
            params, st = ref_opt.apply_gradients(params, g, st)
            return l, params, st

        pi, sti = inter, opt.init(inter)
        pr, srt = stacked, ref_opt.init(stacked)
        for _ in range(3):
            li, pi, sti = step(pi, sti, xm, ym)
            lr, pr, srt = seq_step(pr, srt, x, y)
            np.testing.assert_allclose(float(li), float(lr), atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5),
            uninterleave_stage_params(pi, n_stages, n_chunks), pr)

    @pytest.mark.parametrize("schedule", ["1f1b", "interleaved"])
    def test_pipeline_1f1b_dp_composed_matches_sequential(self, mesh8,
                                                          schedule):
        """dp(2) x pp(4) hybrid via dp_axis: each replica pipelines its
        shard of every microbatch, grads psum-averaged — must train
        identically to the single-device model on the full batch (the
        reference's NCCL-DP x pipeline-sections hybrid). Covers both
        tick schedules (interleaved runs V=2 chunks = 8 global
        stages)."""
        from paddle_tpu.parallel.pipeline import (
            interleave_stage_params, make_pipeline_train_step,
            split_microbatches, stack_stage_params)
        n_stages, n_dp, dim, n_micro, mb = 4, 2, 8, 4, 4
        n_chunks = 2 if schedule == "interleaved" else 1
        n_global = n_stages * n_chunks
        keys = jax.random.split(jax.random.key(3), n_global)
        stacked = stack_stage_params(
            [{"w": jax.random.normal(k, (dim, dim)) * 0.3} for k in keys])

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        def loss_fn(outs, labels):
            return jnp.mean((outs - labels) ** 2)

        x = jnp.asarray(r((n_micro * mb, dim)))
        y = jnp.asarray(r((n_micro * mb, dim), 1))
        xm = split_microbatches(x, n_micro)
        ym = split_microbatches(y, n_micro)
        mesh = pt.parallel.make_mesh({"dp": n_dp, "pp": n_stages})
        opt = pt.optimizer.Momentum(0.1, 0.9)
        step = jax.jit(make_pipeline_train_step(
            mesh, stage_fn, loss_fn, opt, "pp", schedule=schedule,
            num_chunks=n_chunks, dp_axis="dp"))
        p0 = (interleave_stage_params(stacked, n_stages, n_chunks)
              if schedule == "interleaved" else stacked)

        def seq_loss(params, x, y):
            h = x
            for i in range(n_global):
                h = stage_fn(
                    jax.tree_util.tree_map(lambda a: a[i], params), h)
            return jnp.mean((h - y) ** 2)

        ref_opt = pt.optimizer.Momentum(0.1, 0.9)

        @jax.jit
        def seq_step(params, st, x, y):
            l, g = jax.value_and_grad(seq_loss)(params, x, y)
            params, st = ref_opt.apply_gradients(params, g, st)
            return l, params, st

        pi, sti = p0, opt.init(p0)
        pr, srt = stacked, ref_opt.init(stacked)
        for _ in range(3):
            li, pi, sti = step(pi, sti, xm, ym)
            lr, pr, srt = seq_step(pr, srt, x, y)
            np.testing.assert_allclose(float(li), float(lr), atol=1e-5)

    def test_pipeline_1f1b_activation_memory_bounded(self, mesh8):
        """Memory half of VERDICT r4 #7 (S=8): the 1f1b schedule's compiled
        temp footprint must stay ~flat as M grows (activations bounded by
        the 2S-1 circular buffer), while the GPipe wave — even with remat —
        keeps one residual per microbatch across the turnaround and grows
        O(M). Ref: section_worker.cc:141's section concurrency bounds
        in-flight scopes by the section count the same way."""
        from paddle_tpu.parallel.pipeline import (make_pipeline_train_step,
                                                  stack_stage_params)
        dim, n_stages, mb = 64, 8, 8
        keys = jax.random.split(jax.random.key(3), n_stages)
        stacked = stack_stage_params(
            [{"w": jax.random.normal(k, (dim, dim)) * 0.3,
              "b": jnp.zeros((dim,))} for k in keys])

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def loss_fn(outs, labels):
            return jnp.mean((outs - labels) ** 2)

        pp_mesh = pt.parallel.make_mesh({"pp": n_stages})
        opt = pt.optimizer.SGD(0.1)
        ostate = opt.init(stacked)

        def temp_bytes(schedule, n_micro):
            step = make_pipeline_train_step(
                pp_mesh, stage_fn, loss_fn, opt, "pp", remat=True,
                schedule=schedule)
            xm = jnp.zeros((n_micro, mb, dim))
            compiled = jax.jit(step).lower(stacked, ostate, xm, xm).compile()
            ma = compiled.memory_analysis()
            if ma is None or not hasattr(ma, "temp_size_in_bytes"):
                pytest.skip("backend lacks memory_analysis")
            return ma.temp_size_in_bytes

        m_lo, m_hi = 16, 64
        growth_gpipe = temp_bytes("gpipe", m_hi) - temp_bytes("gpipe", m_lo)
        growth_1f1b = temp_bytes("1f1b", m_hi) - temp_bytes("1f1b", m_lo)
        # GPipe grows ~linearly in M (one saved stage input per microbatch
        # per tick); 1f1b's buffer is M-independent. Measured on the 8-dev
        # CPU mesh: ~295 KB vs ~0.3 KB for this config.
        assert growth_gpipe > 10 * mb * dim * 4, growth_gpipe
        assert growth_1f1b < 0.1 * growth_gpipe, (growth_1f1b, growth_gpipe)


class TestShardedEmbedding:
    def test_matches_dense_gather(self, mesh8):
        from paddle_tpu.parallel.embedding import sharded_embedding_lookup
        vocab, dim = 64, 8
        table = jnp.asarray(r((vocab, dim)))
        ids = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (4, 6)))
        ep_mesh = pt.parallel.make_mesh({"ep": 8})
        emb = shard_map(
            lambda t, i: sharded_embedding_lookup(i, t, "ep", vocab),
            mesh=ep_mesh, in_specs=(P("ep", None), P()), out_specs=P())
        out = emb(table, ids)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(table)[np.asarray(ids)],
                                   atol=1e-6)

    def test_gradient_flows_to_correct_shard(self, mesh8):
        from paddle_tpu.parallel.embedding import sharded_embedding_lookup
        vocab, dim = 16, 4
        table = jnp.asarray(r((vocab, dim)))
        ids = jnp.asarray(np.array([[0, 9]]))
        ep_mesh = pt.parallel.make_mesh({"ep": 8})

        def loss(t):
            emb = shard_map(
                lambda t_, i_: sharded_embedding_lookup(i_, t_, "ep", vocab),
                mesh=ep_mesh, in_specs=(P("ep", None), P()), out_specs=P())
            return jnp.sum(emb(t, ids))

        g = jax.grad(loss)(table)
        gnp = np.asarray(g)
        assert np.allclose(gnp[0], 1.0) and np.allclose(gnp[9], 1.0)
        assert np.allclose(np.delete(gnp, [0, 9], axis=0), 0.0)


class TestDGC:
    def test_topk_sparsify_identity(self):
        from paddle_tpu.parallel.dgc import topk_sparsify
        g = jnp.asarray(r((32,)))
        sparse, residual = topk_sparsify(g, 0.75)
        np.testing.assert_allclose(np.asarray(sparse + residual),
                                   np.asarray(g), atol=1e-6)
        assert int(jnp.sum(sparse != 0)) == 8

    def test_sparse_all_reduce_matches_dense_topk(self, mesh8):
        from paddle_tpu.parallel.dgc import sparse_all_reduce
        g = jnp.asarray(r((8, 16)))  # one row per device

        def inner(gi):
            reduced, residual = sparse_all_reduce(gi[0], "dp", sparsity=0.5)
            return reduced[None], residual[None]

        reduced, residual = shard_map(
            inner, mesh=mesh8, in_specs=P("dp", None),
            out_specs=(P("dp", None), P("dp", None)))(g)
        # every device sees the same reduced tensor = sum of per-device topk
        rnp = np.asarray(reduced)
        np.testing.assert_allclose(rnp[0], rnp[7], atol=1e-6)
        # conservation: reduced + sum(residuals) == sum(g)
        np.testing.assert_allclose(
            rnp[0] + np.asarray(residual).sum(0), np.asarray(g).sum(0),
            atol=1e-5)


class TestLaunch:
    @pytest.mark.slow
    def test_multiprocess_allreduce(self, tmp_path):
        """ref: test_dist_base.py subprocess cluster fixture — 2 local
        processes form one jax.distributed job and allreduce."""
        script = tmp_path / "worker.py"
        script.write_text(
            "import os, sys\n"
            "sys.path.insert(0, '/root/repo')\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from paddle_tpu.parallel import launch\n"
            "launch.init_distributed()\n"
            "import jax.numpy as jnp\n"
            "assert jax.process_count() == 2, jax.process_count()\n"
            "print('rank', jax.process_index(), 'OK')\n")
        import os
        from paddle_tpu.parallel import launch as launch_mod
        port = 20000 + os.getpid() % 10000  # unique per run: no stale-
        ps = launch_mod.launch_local(2, str(script), base_port=port)
        launch_mod.wait_all(ps, timeout=120)


class TestDistributionPlanner:
    """The transpiler-successor planner: plan shardings for an arbitrary
    captured program (ref distribute_transpiler.py:230; assert-on-plan-text
    mirrors test_dist_transpiler.py's assert-on-program-text)."""

    def _bert_problem(self):
        from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                            pretrain_loss)
        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64, max_position=32,
                         dropout=0.0)
        model = BertForPretraining(cfg)
        params = model.init(jax.random.key(0))["params"]
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, 64, (8, 16), dtype=np.int32))
        labels = jnp.asarray(rng.randint(0, 64, (8, 16), dtype=np.int32))

        def step_builder(opt):
            def step(params, opt_state, ids, labels):
                def loss_fn(p):
                    mlm, nsp = model.apply({"params": p, "state": {}}, ids)
                    return pretrain_loss(
                        mlm, nsp, labels,
                        jnp.zeros((ids.shape[0],), jnp.int32),
                        jnp.ones(ids.shape, jnp.float32))
                loss, grads = jax.value_and_grad(loss_fn)(params)
                params, opt_state = opt.apply_gradients(params, grads,
                                                        opt_state)
                return loss, params, opt_state
            return step
        return model, params, ids, labels, step_builder

    def test_plan_rules_and_description(self):
        from paddle_tpu.parallel.planner import DistributionPlanner
        mesh = pt.parallel.make_mesh({"dp": 2, "tp": 4})
        model, params, ids, labels, _ = self._bert_problem()
        planner = DistributionPlanner(mesh, tp_auto=True)
        plan = planner.plan(params, (ids, labels))
        desc = plan.describe()
        assert "tp" in desc
        # every >=2D param with a tp-divisible dim got a tp axis
        import json as jsonlib
        entries = jsonlib.loads(desc)
        n_tp = sum(1 for e in entries.values() if "tp" in e["spec"])
        assert n_tp >= 5
        # inputs shard over dp
        assert plan.input_specs[0] == jax.sharding.PartitionSpec(
            "dp", None)

    @pytest.mark.slow
    def test_planned_step_matches_single_device(self):
        """Transpiled-program equivalence: dp x tp planned training equals
        single-device training (parallel_executor_test_base pattern)."""
        from paddle_tpu.parallel.planner import DistributionPlanner
        model, params, ids, labels, step_builder = self._bert_problem()
        opt = pt.optimizer.Adam(1e-3)
        step = step_builder(opt)

        # single-device reference
        p_ref = params
        o_ref = opt.init(params)
        losses_ref = []
        for _ in range(3):
            loss, p_ref, o_ref = jax.jit(step)(p_ref, o_ref, ids, labels)
            losses_ref.append(float(loss))

        mesh = pt.parallel.make_mesh({"dp": 2, "tp": 4})
        planner = DistributionPlanner(mesh, tp_auto=True)
        jitted, p, o, plan = planner.compile_step(
            step, params, opt.init(params), (ids, labels), donate=False)
        losses = []
        with mesh:
            for _ in range(3):
                loss, p, o = jitted(p, o, ids, labels)
                losses.append(float(loss))
        np.testing.assert_allclose(losses, losses_ref, rtol=2e-4)

    def test_fsdp_planning(self):
        from paddle_tpu.parallel.planner import DistributionPlanner
        mesh = pt.parallel.make_mesh({"dp": 2, "fsdp": 4})
        params = {"big": jnp.zeros((64, 16)), "small": jnp.zeros((4,))}
        planner = DistributionPlanner(mesh, fsdp_min_size=256)
        plan = planner.plan(params)
        assert "fsdp" in plan.entries["big"].spec
        assert plan.entries["small"].spec == (None,)


class TestRingFlashAttention:
    """ring_flash_attention: the Pallas flash kernel as the per-block ring
    engine (interpret mode on the 8-device CPU mesh) must match the dense
    ring_attention math."""

    def _run(self, fn, q, causal):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        import paddle_tpu as pt
        mesh = pt.parallel.make_mesh({"sp": 8})
        f = shard_map(
            lambda q_, k_, v_: fn(q_, k_, v_, "sp", causal=causal),
            mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), check_vma=False)
        return np.asarray(f(q, q, q))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_ring(self, causal):
        from paddle_tpu.core.flags import set_flags
        from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                        ring_flash_attention)
        q = jax.random.normal(jax.random.key(0), (1, 2, 8 * 16, 64),
                              jnp.float32)
        ref = self._run(ring_attention, q, causal)
        set_flags({"pallas_interpret": True})
        try:
            got = self._run(ring_flash_attention, q, causal)
        finally:
            set_flags({"pallas_interpret": False})
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_matches_dense_ring(self, causal):
        # the custom ring VJP (rotating Pallas dq/dkv with towed
        # accumulators) must match autodiff through the dense ring
        from paddle_tpu.core.flags import set_flags
        from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                        ring_flash_attention)
        key = jax.random.key(2)
        kq, kk, kv, kw = jax.random.split(key, 4)
        shape = (1, 2, 8 * 16, 64)
        q = jax.random.normal(kq, shape, jnp.float32)
        k = jax.random.normal(kk, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        w = jax.random.normal(kw, shape, jnp.float32)
        mesh = pt.parallel.make_mesh({"sp": 8})

        def make_loss(fn):
            body = lambda a, b_, c, w_: jax.lax.psum(
                jnp.sum(fn(a, b_, c, "sp", causal=causal) * w_), "sp")
            f = shard_map(body, mesh=mesh,
                          in_specs=(P(None, None, "sp", None),) * 4,
                          out_specs=P(), check_vma=False)
            return lambda q_, k_, v_: f(q_, k_, v_, w)

        grads_ref = jax.grad(make_loss(ring_attention),
                             argnums=(0, 1, 2))(q, k, v)
        set_flags({"pallas_interpret": True})
        try:
            grads = jax.grad(make_loss(ring_flash_attention),
                             argnums=(0, 1, 2))(q, k, v)
        finally:
            set_flags({"pallas_interpret": False})
        for g, gr in zip(grads, grads_ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                       rtol=2e-3, atol=2e-3)

    def test_falls_back_off_tpu(self):
        # without the interpret flag on CPU the flash ring must silently
        # route to the dense ring (same numbers)
        from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                        ring_flash_attention)
        q = jax.random.normal(jax.random.key(1), (1, 1, 8 * 8, 64),
                              jnp.float32)
        got = self._run(ring_flash_attention, q, True)
        ref = self._run(ring_attention, q, True)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


class TestHybridMesh:
    """make_hybrid_mesh: DCN axes outermost, ICI within a slice (the
    multi-slice topology; CPU fallback keeps the same axis-order
    contract)."""

    def test_axis_order_and_training(self):
        import paddle_tpu as pt
        mesh = pt.parallel.make_hybrid_mesh({"tp": 4}, {"dp": 2})
        assert mesh.axis_names == ("dp", "tp")
        assert mesh.devices.shape == (2, 4)
        # a dp x tp train step over the hybrid mesh runs
        from jax.sharding import NamedSharding, PartitionSpec as P
        w = jax.device_put(jnp.ones((8, 8)),
                           NamedSharding(mesh, P(None, "tp")))
        x = jax.device_put(jnp.ones((4, 8)), NamedSharding(mesh, P("dp")))

        @jax.jit
        def step(w, x):
            return jnp.sum((x @ w) ** 2)

        assert np.isfinite(float(step(w, x)))

    def test_inferred_ici_size(self):
        import paddle_tpu as pt
        mesh = pt.parallel.make_hybrid_mesh({"tp": -1}, {"dp": 2})
        assert mesh.devices.shape == (2, 4)


def test_ulysses_grad_matches_dense():
    """Gradients through ulysses_attention (all_to_all reshard + flash
    kernel VJP) must match autodiff through dense attention — the same
    forward-only trap the ring path had (ADVICE r3) must not exist
    here."""
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    from paddle_tpu.parallel.ring_attention import ulysses_attention
    key = jax.random.key(5)
    kq, kk, kv, kw = jax.random.split(key, 4)
    shape = (1, 8, 8 * 8, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    w = jax.random.normal(kw, shape, jnp.float32)
    mesh = pt.parallel.make_mesh({"sp": 8})

    def dense_loss(q_, k_, v_):
        return jnp.sum(scaled_dot_product_attention(
            q_, k_, v_, causal=True) * w)

    body = lambda a, b, c, w_: jax.lax.psum(
        jnp.sum(ulysses_attention(a, b, c, "sp", causal=True) * w_), "sp")
    f = shard_map(body, mesh=mesh,
                  in_specs=(P(None, None, "sp", None),) * 4,
                  out_specs=P(), check_vma=False)
    grads_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    set_flags({"pallas_interpret": True})
    try:
        grads = jax.grad(lambda q_, k_, v_: f(q_, k_, v_, w),
                         argnums=(0, 1, 2))(q, k, v)
    finally:
        set_flags({"pallas_interpret": False})
    for g, gr in zip(grads, grads_ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)


def test_ulysses_flash_kernel_interpret():
    """Ulysses default attention now rides the flash kernel: interpret
    mode must match the dense path (full-sequence per head subset is
    exactly the kernel's layout)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    from paddle_tpu.parallel.ring_attention import ulysses_attention
    q = jax.random.normal(jax.random.key(0), (1, 8, 8 * 8, 64), jnp.float32)
    ref = scaled_dot_product_attention(q, q, q, causal=True)
    sp_mesh = pt.parallel.make_mesh({"sp": 8})
    f = shard_map(
        lambda q_, k_, v_: ulysses_attention(q_, k_, v_, "sp", causal=True),
        mesh=sp_mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    set_flags({"pallas_interpret": True})
    try:
        got = f(q, q, q)
    finally:
        set_flags({"pallas_interpret": False})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


class TestCompressedPsum:
    """compressed_psum: bounded-error bandwidth-compressed allreduce
    (EQuARX direction) — bf16 and int8 variants vs the exact sum."""

    def _run(self, compress):
        from paddle_tpu.parallel.collective import compressed_psum
        mesh = pt.parallel.make_mesh({"dp": 8})
        x = jax.random.normal(jax.random.key(0), (8, 64, 32), jnp.float32)
        f = shard_map(
            lambda x_: compressed_psum(x_[0], "dp", compress)[None],
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = np.asarray(f(x))
        exact = np.asarray(x).sum(0)
        # every replica holds the same compressed sum
        for i in range(1, 8):
            np.testing.assert_allclose(out[i], out[0], atol=0)
        return out[0], exact, float(np.abs(np.asarray(x)).max())

    def test_none_is_exact(self):
        got, exact, _ = self._run("none")
        np.testing.assert_allclose(got, exact, rtol=1e-6, atol=1e-6)

    def test_bf16_error_bounded(self):
        got, exact, _ = self._run("bf16")
        scale = np.abs(exact).max()
        assert np.max(np.abs(got - exact)) < 0.02 * scale

    def test_int8_error_bounded(self):
        got, exact, xmax = self._run("int8")
        # per-element error <= n_replicas * scale/127 (rounding each term)
        assert np.max(np.abs(got - exact)) <= 8 * xmax / 127 + 1e-6

    def test_unknown_compress_raises(self):
        from paddle_tpu.core.enforce import EnforceError
        with pytest.raises(EnforceError, match="unknown compress"):
            self._run("fp4")


def test_planner_expert_parallel_rule():
    """DistributionPlanner ep_patterns: expert-stacked params shard their
    leading [E, ...] dim over "ep" and WIN over the fsdp sweep; the gate
    stays fsdp-eligible; an explicit ep match with an indivisible expert
    dim records an inspectable skip."""
    from paddle_tpu.parallel.planner import DistributionPlanner
    mesh = pt.parallel.make_mesh({"ep": 4, "fsdp": 2})
    params = {"blocks": {"0": {"mlp": {
        "w_gate": jnp.zeros((16, 4)),
        "w1": jnp.zeros((4, 16, 32)),
        "b1": jnp.zeros((4, 32)),
        "w2": jnp.zeros((4, 32, 16)),
        "b2": jnp.zeros((4, 16)),
    }}}, "odd": jnp.zeros((6, 16, 32))}
    planner = DistributionPlanner(
        mesh, ep_patterns=(r"mlp/(w|b)[12]$", r"^odd$"),
        fsdp_min_size=1)
    plan = planner.plan(params)
    e = plan.entries
    for name in ("blocks/0/mlp/w1", "blocks/0/mlp/b1",
                 "blocks/0/mlp/w2", "blocks/0/mlp/b2"):
        assert e[name].spec[0] == "ep", (name, e[name])
        assert "fsdp" not in e[name].spec, (name, e[name])
    # non-matching param still gets the fsdp sweep
    assert "fsdp" in e["blocks/0/mlp/w_gate"].spec
    # 6 experts on ep=4: explicit match skipped, reason says so, fsdp
    # takes over on a divisible dim
    assert "ep SKIPPED" in e["odd"].reason, e["odd"]
    assert "fsdp" in e["odd"].spec
