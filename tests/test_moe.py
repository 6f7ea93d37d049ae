"""Mixture-of-Experts layer: routing correctness, capacity drops,
load-balance aux, and expert-parallel (ep) equivalence on the 8-device
mesh. (No reference counterpart; the ep successor of pserver sharding.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.nn.moe import MoE, top_k_gating


class TestGating:
    def test_topk_positions_and_weights(self):
        logits = jnp.asarray([[5.0, 0.0, 0.0],
                              [5.0, 1.0, 0.0],
                              [0.0, 5.0, 0.0]])
        dispatch, combine, aux = top_k_gating(logits, k=1, capacity=2)
        d = np.asarray(dispatch)
        # tokens 0,1 -> expert 0 at positions 0,1; token 2 -> expert 1 pos 0
        assert d[0, 0, 0] == 1 and d[1, 0, 1] == 1 and d[2, 1, 0] == 1
        probs = np.asarray(jax.nn.softmax(logits, -1))
        c = np.asarray(combine)
        np.testing.assert_allclose(c[0, 0, 0], probs[0, 0], rtol=1e-6)

    def test_capacity_overflow_dropped(self):
        # 3 tokens all prefer expert 0, capacity 2 -> third token dropped
        logits = jnp.asarray([[5.0, 0.0]] * 3)
        dispatch, combine, _ = top_k_gating(logits, k=1, capacity=2)
        assert np.asarray(dispatch)[2].sum() == 0
        assert np.asarray(combine)[2].sum() == 0

    def test_second_choice_packs_after_first(self):
        # k=2: the second-choice tokens go after first-choice occupancy
        logits = jnp.asarray([[5.0, 1.0], [1.0, 5.0]])
        dispatch, _, _ = top_k_gating(logits, k=2, capacity=2)
        d = np.asarray(dispatch)
        # expert 0: token 0 (first choice) pos 0, token 1 (second) pos 1
        assert d[0, 0, 0] == 1 and d[1, 0, 1] == 1
        assert d[1, 1, 0] == 1 and d[0, 1, 1] == 1

    def test_balanced_router_aux_near_one(self):
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(512, 8).astype(np.float32) * 0.01)
        _, _, aux = top_k_gating(logits, k=1, capacity=128)
        assert 0.9 < float(aux) < 1.2, float(aux)


class TestMoELayer:
    def _layer(self, **kw):
        m = MoE(dim=8, hidden=16, num_experts=4, k=1,
                capacity_factor=4.0, **kw)
        v = m.init(jax.random.key(0))
        return m, v

    def test_matches_per_token_expert_ffn(self):
        # ample capacity + k=1: y[t] = gate_prob * FFN_{argmax}(x[t])
        m, v = self._layer()
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(2, 4, 8).astype(np.float32))
        y = np.asarray(m.apply(v, x))
        p = v["params"]
        xf = np.asarray(x).reshape(8, 8)
        logits = xf @ np.asarray(p["w_gate"])
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
        ref = np.zeros_like(xf)
        for t in range(8):
            e = int(np.argmax(logits[t]))
            h = np.asarray(jax.nn.gelu(jnp.asarray(
                xf[t] @ np.asarray(p["w1"])[e] + np.asarray(p["b1"])[e])))
            ref[t] = probs[t, e] * (h @ np.asarray(p["w2"])[e]
                                    + np.asarray(p["b2"])[e])
        np.testing.assert_allclose(y.reshape(8, 8), ref, rtol=2e-4,
                                   atol=2e-5)

    def test_aux_loss_differentiable(self):
        m, v = self._layer()
        x = jnp.asarray(np.random.RandomState(2).randn(1, 8, 8)
                        .astype(np.float32))

        def loss(params):
            y, aux = m.apply({"params": params, "state": {}}, x,
                             method="forward_with_aux")
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.grad(loss)(v["params"])
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
        assert np.abs(np.asarray(g["w_gate"])).sum() > 0

    def test_expert_parallel_matches_single_device(self):
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        m, v = self._layer()
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(1, 16, 8).astype(np.float32))
        ref = np.asarray(m.apply(v, x))

        m_ep = MoE(dim=8, hidden=16, num_experts=4, k=1,
                   capacity_factor=4.0, ep_axis="ep")
        mesh = pt.parallel.make_mesh({"ep": 4}, jax.devices()[:4])
        p = v["params"]
        shard = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        params = {
            "w_gate": shard(p["w_gate"], P()),
            "w1": shard(p["w1"], P("ep")),
            "b1": shard(p["b1"], P("ep")),
            "w2": shard(p["w2"], P("ep")),
            "b2": shard(p["b2"], P("ep")),
        }
        f = shard_map(
            lambda pp, xx: m_ep.apply({"params": pp, "state": {}}, xx),
            mesh=mesh,
            in_specs=({"w_gate": P(), "w1": P("ep"), "b1": P("ep"),
                       "w2": P("ep"), "b2": P("ep")}, P()),
            out_specs=P(), check_vma=False)
        got = np.asarray(f(params, x))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


class TestGPTMoE:
    def test_gpt_with_moe_ffn_trains(self):
        from paddle_tpu.models.gpt import GPT, GPTConfig, lm_loss
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, intermediate_size=64, max_position=32,
                        dropout=0.0, use_flash=False, moe_experts=4,
                        moe_k=2)
        model = GPT(cfg)
        v = model.init(jax.random.key(0))
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, 128, (2, 16), dtype=np.int32))

        def loss(params):
            logits = model.apply({"params": params, "state": {}}, ids)
            return lm_loss(logits, ids)

        l0 = float(loss(v["params"]))
        g = jax.grad(loss)(v["params"])
        import paddle_tpu as pt
        opt = pt.optimizer.Adam(1e-2)
        st = opt.init(v["params"])
        params = v["params"]
        step = jax.jit(lambda p, s: opt.minimize(
            lambda pp: (loss(pp), 0.0), p, s))
        for _ in range(8):
            l, params, st, _ = step(params, st)
        assert float(l) < l0, (float(l), l0)
