"""Quantization tests — QAT fake-quant ops, model transform, PTQ pipeline.

Mirrors the reference's test_quantization_pass.py intent (contrib/slim
tests): quantized graph still trains, freeze/int8 export preserves outputs.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import quant
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.module import Module


class TestFakeQuantOps:
    def test_roundtrip_error_bound(self):
        x = jnp.asarray(np.random.RandomState(0).uniform(-3, 3, (64,)),
                        jnp.float32)
        y = quant.fake_quant_abs_max(x, bits=8)
        step = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(y - x))) <= step / 2 + 1e-6

    def test_more_bits_less_error(self):
        x = jnp.asarray(np.random.RandomState(1).uniform(-1, 1, (256,)),
                        jnp.float32)
        e4 = float(jnp.mean((quant.fake_quant_abs_max(x, 4) - x) ** 2))
        e8 = float(jnp.mean((quant.fake_quant_abs_max(x, 8) - x) ** 2))
        assert e8 < e4

    def test_ste_gradient(self):
        # grad passes through inside the clip range, zero outside
        scale = jnp.float32(1.0)
        g = jax.grad(lambda x: jnp.sum(
            quant.fake_quant_dequant(x, scale, 8)))(
                jnp.asarray([0.5, -0.3, 2.0, -5.0], jnp.float32))
        np.testing.assert_allclose(np.asarray(g), [1, 1, 0, 0])

    def test_channel_wise_beats_per_tensor(self):
        rs = np.random.RandomState(2)
        # two output channels at wildly different magnitudes
        w = np.stack([rs.uniform(-1, 1, 64), rs.uniform(-100, 100, 64)],
                     axis=0).astype(np.float32)
        per_tensor = quant.fake_quant_abs_max(jnp.asarray(w), 8)
        per_chan = quant.fake_quant_abs_max(jnp.asarray(w), 8, channel_axis=0)
        err_t = float(jnp.mean((per_tensor[0] - w[0]) ** 2))
        err_c = float(jnp.mean((per_chan[0] - w[0]) ** 2))
        assert err_c < err_t / 10

    def test_int8_roundtrip(self):
        w = jnp.asarray(np.random.RandomState(3).uniform(-2, 2, (8, 16)),
                        jnp.float32)
        scale = quant.abs_max_scale(w, channel_axis=1)
        q = quant.quantize_to_int(w, scale, 8, channel_axis=1)
        assert q.dtype == jnp.int8
        deq = quant.dequantize_from_int(q, scale, 8, channel_axis=1)
        assert float(jnp.max(jnp.abs(deq - w))) < float(jnp.max(scale)) / 100

    def test_moving_average_scale(self):
        s = jnp.float32(1.0)
        x = jnp.full((4,), 3.0)
        s2 = quant.moving_average_scale(s, x, rate=0.9)
        np.testing.assert_allclose(float(s2), 0.9 * 1.0 + 0.1 * 3.0,
                                   rtol=1e-6)

    def test_range_abs_max_window_reset(self):
        s = jnp.float32(10.0)
        x = jnp.full((4,), 2.0)
        # at window boundary: reset to current abs max
        s_b = quant.range_abs_max_scale(s, x, step=0, window_size=100)
        np.testing.assert_allclose(float(s_b), 2.0)
        # inside window: running max
        s_i = quant.range_abs_max_scale(s, x, step=5, window_size=100)
        np.testing.assert_allclose(float(s_i), 10.0)


class _TinyNet(Module):
    def __init__(self):
        super().__init__()
        self.conv = L.Conv2D(1, 4, 3, padding=1)
        self.fc = L.Linear(4 * 8 * 8, 10)

    def forward(self, x):
        h = jax.nn.relu(self.conv(x))
        return self.fc(h.reshape(h.shape[0], -1))


class TestQAT:
    def _data(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(8, 1, 8, 8), jnp.float32)
        y = jnp.asarray(rs.randint(0, 10, (8, 1)))
        return x, y

    def test_quantize_model_swaps_layers(self):
        m = quant.quantize_model(_TinyNet(), quant.QuantConfig())
        assert isinstance(m._children["conv"], quant.QuantizedConv2D)
        assert isinstance(m._children["fc"], quant.QuantizedLinear)

    def test_quantized_forward_close_to_float(self):
        key = jax.random.key(0)
        fm = _TinyNet()
        fv = fm.init(key)
        qm = quant.quantize_model(_TinyNet(), quant.QuantConfig(
            activation_quantize_type="abs_max"))
        qv = quant.upgrade_variables(qm, fv, key)
        x, _ = self._data()
        fo = fm.apply(fv, x)
        qo = qm.apply(qv, x)
        rel = float(jnp.linalg.norm(qo - fo) / (jnp.linalg.norm(fo) + 1e-8))
        assert rel < 0.1, rel

    def test_qat_trains(self):
        key = jax.random.key(1)
        qm = quant.quantize_model(_TinyNet(), quant.QuantConfig())
        var = qm.init(key)
        x, y = self._data()
        opt = pt.optimizer.Momentum(0.05, 0.9)
        opt_state = opt.init(var["params"])

        def loss_fn(params, state):
            out, new_state = qm.apply({"params": params, "state": state},
                                      x, training=True)
            loss = jnp.mean(pt.ops.loss.softmax_with_cross_entropy(out, y))
            return loss, new_state

        @jax.jit
        def step(params, opt_state, state):
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state)
            params, opt_state = opt.apply_gradients(params, grads, opt_state)
            return params, opt_state, new_state, loss

        params, state = var["params"], var["state"]
        losses = []
        for _ in range(12):
            params, opt_state, state, loss = step(params, opt_state, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        # moving-average activation scale moved off its init value
        assert float(state["fc"]["input_quant"]["scale"]) != 1.0

    def test_ptq_pipeline(self):
        key = jax.random.key(2)
        fm = _TinyNet()
        fv = fm.init(key)
        qm = quant.quantize_model(_TinyNet(), quant.QuantConfig())
        qv = quant.upgrade_variables(qm, fv, key)
        x, _ = self._data()
        qv = quant.calibrate(qm, qv, [x, x])
        qv = quant.freeze(qm, qv)
        out_frozen = qm.apply(qv, x)
        fo = fm.apply(fv, x)
        rel = float(jnp.linalg.norm(out_frozen - fo) /
                    (jnp.linalg.norm(fo) + 1e-8))
        assert rel < 0.15, rel

        payload = quant.export_int8(qm, qv)
        assert "fc" in payload and "conv" in payload
        assert payload["fc"]["weight_int8"].dtype == jnp.int8
        # int8 serving matmul matches the frozen fake-quant linear closely
        h = jax.nn.relu(qm._children["conv"].apply(
            {"params": qv["params"]["conv"],
             "state": qv["state"].get("conv", {})}, x))
        y_int8 = quant.int8_linear(h.reshape(h.shape[0], -1), payload["fc"])
        assert y_int8.shape == (8, 10)

    def test_bad_config_rejected(self):
        with pytest.raises(Exception):
            quant.QuantConfig(weight_quantize_type="nope")

    def test_quantize_root_module(self):
        qlin = quant.quantize_model(L.Linear(4, 3), quant.QuantConfig())
        assert isinstance(qlin, quant.QuantizedLinear)
        var = qlin.init(jax.random.key(0))
        out = qlin.apply(var, jnp.ones((2, 4)))
        assert out.shape == (2, 3)
        # freeze/export must see the quantized root too
        frozen = quant.freeze(qlin, var)
        assert not np.array_equal(np.asarray(frozen["params"]["weight"]),
                                  np.asarray(var["params"]["weight"]))
        payload = quant.export_int8(qlin, frozen)
        assert "" in payload and payload[""]["weight_int8"].dtype == jnp.int8

    def test_training_and_calibrating_rejected(self):
        net = _TinyNet()
        var = net.init(jax.random.key(9))
        with pytest.raises(Exception):
            net.apply(var, jnp.ones((1, 1, 8, 8)), training=True,
                      calibrating=True)

    def test_freeze_does_not_mutate_input(self):
        key = jax.random.key(3)
        qm = quant.quantize_model(_TinyNet(), quant.QuantConfig())
        qv = qm.init(key)
        before = np.asarray(qv["params"]["fc"]["weight"]).copy()
        qv2 = quant.freeze(qm, qv)
        np.testing.assert_array_equal(
            np.asarray(qv["params"]["fc"]["weight"]), before)
        assert not np.array_equal(
            np.asarray(qv2["params"]["fc"]["weight"]), before)

    def test_calibrate_keeps_eval_behavior(self):
        # dropout model: calibration must not need PRNG keys nor touch BN
        class Net(Module):
            def __init__(self):
                super().__init__()
                self.fc = L.Linear(8, 8)
                self.drop = L.Dropout(0.5)
                self.bn = L.BatchNorm(8, data_format="NHWC")

            def forward(self, x):
                return self.bn(self.drop(self.fc(x)))

        qm = quant.quantize_model(Net(), quant.QuantConfig())
        qv = qm.init(jax.random.key(4))
        bn_mean_before = np.asarray(qv["state"]["bn"]["mean"]).copy()
        x = jnp.asarray(np.random.RandomState(5).randn(4, 8), jnp.float32)
        qv = quant.calibrate(qm, qv, [x, x])  # no rngs → would crash if
        # dropout ran in training mode
        np.testing.assert_array_equal(
            np.asarray(qv["state"]["bn"]["mean"]), bn_mean_before)
        # quantizer scale did update
        assert float(qv["state"]["fc"]["input_quant"]["scale"]) != 1.0

    def test_calibrate_model_with_tuple_output(self):
        class Net(Module):
            def __init__(self):
                super().__init__()
                self.fc = L.Linear(8, 4)

            def forward(self, x):
                out = self.fc(x)
                return out, jnp.sum(out)

        qm = quant.quantize_model(Net(), quant.QuantConfig(
            activation_quantize_type="abs_max"))
        qv = qm.init(jax.random.key(6))
        x = jnp.ones((2, 8))
        qv2 = quant.calibrate(qm, qv, [x])
        # state must still be a dict tree, not a model output
        assert isinstance(qv2["state"], dict)


class TestInt8Serving:
    def test_save_int8_inference_model_roundtrip(self, tmp_path):
        """int8 serving artifact: params.bin carries REAL int8 weights; the
        exported program dequantizes inline and reproduces the quantized
        forward (ref ConvertToInt8Pass + C++ int8 serve path)."""
        import paddle_tpu as pt
        from paddle_tpu.io.inference import read_params_bin

        key = jax.random.key(0)
        qm = quant.quantize_model(_TinyNet(), quant.QuantConfig(
            activation_quantize_type="abs_max"))
        fv = _TinyNet().init(key)
        qv = quant.upgrade_variables(qm, fv, key)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(4, 1, 8, 8), jnp.float32)

        path = str(tmp_path / "int8_export")
        quant.save_int8_inference_model(path, qm, qv, (x,),
                                        float_model=_TinyNet())

        # int8 weights really stored as int8 in the C++ params archive
        arrs = read_params_bin(os.path.join(path, "params.bin"))
        int8_arrs = [a for a in arrs if a.dtype == np.int8]
        assert len(int8_arrs) == 2  # conv + fc weights

        # served program output matches dequantized-weight reference
        pred = pt.io.load_inference_model(path)
        got = np.asarray(pred(x))

        frozen = quant.freeze(qm, qv)
        ref = np.asarray(_TinyNet().apply(
            {"params": frozen["params"], "state": {}}, x))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


class TestWeightOnlyInt8:
    """quant.quantize_weights_int8 — int8-resident serving weights
    consumed by mixed-dtype dots (nn/layers.py Linear/Embedding, the GPT
    tied head). Ref: ConvertToInt8Pass writes real int8 weights into the
    serving program (quantization_pass.py:764)."""

    def test_linear_exact_dequant_identity(self):
        """(x @ q) * s must equal x @ (q * s) — the per-out-column scale
        commutes with the contraction, so the int8 path's only error is
        weight rounding, identical to explicit dequantization."""
        rs = np.random.RandomState(0)
        lin = L.Linear(32, 16)
        v = lin.init(jax.random.key(0))
        x = jnp.asarray(rs.randn(4, 32), jnp.float32)
        qp = quant.quantize_weights_int8(lin, v["params"], min_size=1)
        assert qp["weight_q"].dtype == jnp.int8
        got = lin.apply({"params": qp, "state": {}}, x)
        wd = (qp["weight_q"].astype(np.float32)
              * np.asarray(qp["weight_scale"])[None, :])
        ref = x @ wd + v["params"]["bias"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # rounding error vs the float weight is bounded by the int8 step
        step = np.abs(np.asarray(v["params"]["weight"])).max(0) / 127.0
        assert np.all(np.abs(wd - np.asarray(v["params"]["weight"]))
                      <= step[None, :] * 0.5 + 1e-7)

    def test_min_size_keeps_small_layers_float(self):
        lin = L.Linear(4, 4)
        v = lin.init(jax.random.key(0))
        qp = quant.quantize_weights_int8(lin, v["params"], min_size=4096)
        assert "weight" in qp and "weight_q" not in qp

    def test_gpt_decode_int8_matches_float(self):
        """End-to-end: GPT decode with int8-resident weights — logits
        within ~2% and identical greedy continuations."""
        from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        model = GPTDecoder(cfg)
        v = model.init(jax.random.key(0))
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 16),
                                     dtype=np.int32))
        logits_f = model.apply({"params": v["params"], "state": {}}, ids)
        qp = quant.quantize_weights_int8(model, v["params"], min_size=16)
        logits_q = model.apply({"params": qp, "state": {}}, ids)
        rel = float(jnp.max(jnp.abs(logits_q - logits_f))
                    / jnp.max(jnp.abs(logits_f)))
        assert rel < 0.05, rel
        # coverage: FFN Linears AND the 4 attention projections per block
        # AND both embeddings must be int8 (a silent skip of the attention
        # kernels would fake the decode row's bandwidth story)
        n_int8 = sum(1 for l in jax.tree_util.tree_leaves(qp)
                     if l.dtype == jnp.int8)
        assert n_int8 == 2 * cfg.num_layers + 4 * cfg.num_layers + 2, n_int8
        gen = jax.jit(lambda p, x: model.apply(
            {"params": p, "state": {}}, x, 8, method="generate"))
        of = gen(v["params"], ids[:, :4])
        oq = gen(qp, ids[:, :4])
        np.testing.assert_array_equal(np.asarray(of), np.asarray(oq))

    def test_bert_tied_head_and_bf16_dtype(self):
        """BERT's weight-tied MLM head must serve int8 tables
        (nn.tied_vocab_head), and a bf16 model must stay bf16 after
        quantization (the scale carries the table dtype)."""
        from paddle_tpu.models.bert import BertConfig, BertForPretraining
        cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, intermediate_size=64,
                         max_position=64)
        cfg.dropout = 0.0
        model = BertForPretraining(cfg)
        v = model.init(jax.random.key(0))
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (2, 16), dtype=np.int32))
        mlm_f, nsp_f = model.apply({"params": v["params"], "state": {}}, ids)
        qp = quant.quantize_weights_int8(model, v["params"], min_size=16)
        mlm_q, nsp_q = model.apply({"params": qp, "state": {}}, ids)
        rel = float(jnp.max(jnp.abs(mlm_q - mlm_f))
                    / jnp.max(jnp.abs(mlm_f)))
        assert rel < 0.1, rel
        # bf16 embedding stays bf16 through the quantized lookup
        emb = L.Embedding(64, 8)
        vb = emb.init(jax.random.key(2), dtype=jnp.bfloat16)
        qb = quant.quantize_weights_int8(emb, vb["params"], min_size=1)
        out = emb.apply({"params": qb, "state": {}}, jnp.asarray([[1, 2]]))
        assert out.dtype == jnp.bfloat16, out.dtype

    def test_subclass_layers_left_alone(self):
        """FC/QuantizedLinear override forward() with p('weight') reads —
        the transform must not touch them (exact-type targeting)."""
        fc = L.FC(16, 8)
        v = fc.init(jax.random.key(0))
        qp = quant.quantize_weights_int8(fc, v["params"], min_size=1)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 16), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(fc.apply({"params": qp, "state": {}}, x)),
            np.asarray(fc.apply({"params": v["params"], "state": {}}, x)))

    def test_embedding_padding_idx_stays_zero(self):
        emb = L.Embedding(64, 8, padding_idx=0)
        v = emb.init(jax.random.key(1))
        qp = quant.quantize_weights_int8(emb, v["params"], min_size=1)
        ids = jnp.asarray([[0, 3, 0, 5]])
        out = emb.apply({"params": qp, "state": {}}, ids)
        np.testing.assert_allclose(np.asarray(out)[0, 0], 0.0)
        np.testing.assert_allclose(np.asarray(out)[0, 2], 0.0)
        ref = emb.apply({"params": v["params"], "state": {}}, ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0.05, atol=0.02)
