"""Native (C++) component tests: dataio pipeline + predictor artifact path.

Ref: the reference's C++-side tests (data_feed tests, inference/tests).
Skipped when csrc/build is absent (build: cd csrc && cmake -B build -G Ninja
&& ninja -C build).
"""

import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.data import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="csrc not built")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestNativeDataIO:
    def test_roundtrip(self, tmp_path):
        recs = [b"hello", b"", b"world" * 100]
        f = str(tmp_path / "a.rec")
        native.write_record_file(f, recs)
        reader = native.NativeRecordReader([f], num_threads=1)
        out = list(reader)
        assert sorted(out) == sorted(recs)

    def test_multifile_multithread(self, tmp_path):
        files = []
        expected = []
        for i in range(4):
            recs = [bytes([i]) * (j + 1) for j in range(50)]
            expected += recs
            f = str(tmp_path / f"f{i}.rec")
            native.write_record_file(f, recs)
            files.append(f)
        reader = native.NativeRecordReader(files, num_threads=4)
        out = list(reader)
        assert sorted(out) == sorted(expected)

    def test_epochs(self, tmp_path):
        f = str(tmp_path / "e.rec")
        native.write_record_file(f, [b"x", b"y"])
        reader = native.NativeRecordReader([f], num_threads=1, epochs=3)
        assert len(list(reader)) == 6

    def test_missing_file_raises(self):
        with pytest.raises(IOError):
            native.NativeRecordReader(["/nonexistent/file.rec"])

    def test_numpy_record_roundtrip(self, tmp_path):
        sample = (np.arange(6, dtype=np.float32).reshape(2, 3),
                  np.array([1], np.int64))
        rec = native.numpy_records(sample)
        f = str(tmp_path / "n.rec")
        native.write_record_file(f, [rec])
        out = list(native.NativeRecordReader([f], num_threads=1))
        a, b = native.unpack_numpy_record(out[0])
        np.testing.assert_allclose(a, sample[0])
        assert int(b[0]) == 1


class TestPredictorArtifact:
    def test_predictor_validates_artifact(self, tmp_path):
        """pt_predictor loads the exported artifact and exits 2 without a
        plugin (full execution needs libtpu/PJRT plugin on the host)."""
        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor not built")
        import paddle_tpu as pt
        from paddle_tpu import models

        m = models.MLP(num_classes=3, in_dim=4)
        v = m.init(jax.random.key(0))
        path = str(tmp_path / "export")
        pt.io.save_inference_model(
            path, lambda p, x: m.apply({"params": p, "state": {}}, x),
            (jnp.ones((2, 4)),), v["params"])
        proc = subprocess.run([binary, "--model_dir", path],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 2, proc.stderr
        assert "6 params" in proc.stderr

    def test_predictor_rejects_bad_artifact(self, tmp_path):
        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor not built")
        bad = tmp_path / "bad"
        bad.mkdir()
        (bad / "model.stablehlo").write_text("module {}")
        (bad / "params.bin").write_bytes(b"XXXX" + b"\x01\x00\x00\x00" * 2)
        proc = subprocess.run([binary, "--model_dir", str(bad)],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert "magic" in proc.stderr


class TestTrainArtifact:
    def test_save_train_program_artifact(self, tmp_path):
        """Exported train step: flat-state program + feedback signature; the
        Python replay of the exported semantics converges (ref:
        fluid/train C++ training demo, re-done over StableHLO/PJRT)."""
        import json
        import paddle_tpu as pt

        rng = np.random.RandomState(0)
        X = jnp.asarray(rng.randn(32, 4).astype(np.float32))
        w_t = jnp.asarray(np.array([1.0, -2.0, 0.5, 3.0], np.float32))
        y = X @ w_t

        opt = pt.optimizer.SGD(0.1)
        params = {"w": jnp.zeros((4,))}
        state = {"params": params, "opt": opt.init(params)}

        def train_step(state, X, y):
            def loss_fn(p):
                return jnp.mean((X @ p["w"] - y) ** 2), None
            loss, p, o, _ = opt.minimize(
                lambda p: loss_fn(p), state["params"], state["opt"])
            return loss, {"params": p, "opt": o}

        path = str(tmp_path / "train_export")
        pt.io.save_train_program(path, train_step, state, (X, y))

        sig = json.load(open(os.path.join(path, "signature.json")))
        assert sig["mode"] == "train"
        n = sig["num_params"]
        assert sig["feedback"] == [[1 + j, j] for j in range(n)]
        for fname in ("model.stablehlo", "params.bin", "inputs.bin"):
            assert os.path.exists(os.path.join(path, fname)), fname

        # the exported program text declares 1 + n outputs (loss + state)
        hlo = open(os.path.join(path, "model.stablehlo")).read()
        assert "stablehlo" in hlo or "func.func" in hlo

    def test_predictor_train_mode_validates(self, tmp_path):
        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor not built")
        import paddle_tpu as pt

        opt = pt.optimizer.SGD(0.1)
        params = {"w": jnp.zeros((3,))}
        state = {"params": params, "opt": opt.init(params)}
        X = jnp.ones((8, 3))
        y = jnp.ones((8,))

        def train_step(state, X, y):
            def loss_fn(p):
                return jnp.mean((X @ p["w"] - y) ** 2), None
            loss, p, o, _ = opt.minimize(
                lambda p: loss_fn(p), state["params"], state["opt"])
            return loss, {"params": p, "opt": o}

        path = str(tmp_path / "texp")
        pt.io.save_train_program(path, train_step, state, (X, y))
        proc = subprocess.run([binary, "--model_dir", path, "--train"],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 2, proc.stderr
        assert "train mode" in proc.stderr

    def test_train_flag_without_inputs_bin_dies(self, tmp_path):
        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor not built")
        import paddle_tpu as pt
        from paddle_tpu import models

        m = models.MLP(num_classes=3, in_dim=4)
        v = m.init(jax.random.key(0))
        path = str(tmp_path / "iexp")
        pt.io.save_inference_model(
            path, lambda p, x: m.apply({"params": p, "state": {}}, x),
            (jnp.ones((2, 4)),), v["params"])
        os.remove(os.path.join(path, "inputs.bin"))
        proc = subprocess.run([binary, "--model_dir", path, "--train"],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert "inputs.bin" in proc.stderr


def _site_packages():
    import sysconfig
    return sysconfig.get_paths()["purelib"]


def _pjrt_plugin():
    """(plugin_path, env_overrides) for a usable PJRT plugin, or None.

    Preference order:
      1. PT_PJRT_PLUGIN env override (any PJRT C-API plugin)
      2. csrc/build/libpycpu_pjrt.so — the embedded-CPython CPU plugin
         built from this repo, always runnable. It needs PYTHONPATH
         pointed at the venv site-packages.
    The predictor with libtpu as its plugin is not run anywhere yet: a
    child process cannot take the chip from a parent that holds it.
    """
    p = os.environ.get("PT_PJRT_PLUGIN")
    if p:
        return p, {}
    pycpu = os.path.join(REPO, "csrc", "build", "libpycpu_pjrt.so")
    if os.path.exists(pycpu):
        env = dict(os.environ)
        env["PYTHONPATH"] = _site_packages()
        return pycpu, env
    return None


class TestPredictorEndToEnd:
    """Real PJRT execution through the C++ binary: load -> compile ->
    execute -> outputs match the Python forward (ref:
    inference/tests/api per-model regressions;
    train/test_train_recognize_digits.cc C++ train loop)."""

    @pytest.fixture(scope="class")
    def plugin(self):
        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor not built")
        try:
            p = _pjrt_plugin()
        except subprocess.TimeoutExpired:
            p = None
        if p is None:
            pytest.skip("no PJRT plugin built (csrc pycpu_pjrt missing "
                        "and no live TPU)")
        path, env = p
        return path, (env or None)

    def test_infer_outputs_match_python(self, plugin, tmp_path):
        plugin, penv = plugin
        import paddle_tpu as pt
        from paddle_tpu.io.inference import read_params_bin
        from paddle_tpu.models.mnist import ConvNet

        model = ConvNet()
        v = model.init(jax.random.key(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.rand(4, 1, 28, 28).astype(np.float32))

        def fwd(p, xx):
            return model.apply({"params": p, "state": {}}, xx)

        path = str(tmp_path / "mnist_export")
        pt.io.save_inference_model(path, fwd, (x,), v["params"])
        expected = np.asarray(fwd(v["params"], x))

        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        dump = str(tmp_path / "outs.ptpb")
        r = subprocess.run(
            [binary, "--model_dir", path, "--plugin", plugin,
             "--dump_outputs", dump],
            capture_output=True, text=True, timeout=420, env=penv)
        assert r.returncode == 0, r.stderr[-2000:]
        outs = read_params_bin(dump)
        assert len(outs) == 1
        np.testing.assert_allclose(outs[0], expected, rtol=2e-2, atol=2e-2)

    def test_train_loop_decreases_loss(self, plugin, tmp_path):
        plugin, penv = plugin
        import json as jsonlib

        import paddle_tpu as pt
        from paddle_tpu.models.mnist import MLP

        model = MLP(num_classes=10, in_dim=64)
        v = model.init(jax.random.key(0))
        opt = pt.optimizer.SGD(0.5)
        state = {"params": v["params"], "opt": opt.init(v["params"])}
        rng = np.random.RandomState(0)
        xb = jnp.asarray(rng.rand(16, 64).astype(np.float32))
        yb = jnp.asarray(rng.randint(0, 10, (16, 1)).astype(np.int32))

        def train_step(st, x, y):
            def loss_fn(p):
                logits = model.apply({"params": p, "state": {}}, x)
                return jnp.mean(pt.ops.loss.softmax_with_cross_entropy(
                    logits, y))
            loss, grads = jax.value_and_grad(loss_fn)(st["params"])
            params, opt_state = opt.apply_gradients(st["params"], grads,
                                                    st["opt"])
            return loss.astype(jnp.float32), {"params": params,
                                              "opt": opt_state}

        path = str(tmp_path / "train_export")
        pt.io.save_train_program(path, train_step, state, (xb, yb))

        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        r = subprocess.run(
            [binary, "--model_dir", path, "--plugin", plugin,
             "--train", "--iters", "20"],
            capture_output=True, text=True, timeout=420, env=penv)
        assert r.returncode == 0, r.stderr[-2000:]
        res = jsonlib.loads(r.stdout.strip().splitlines()[-1])
        first = [float(l.split("loss")[1]) for l in r.stderr.splitlines()
                 if l.startswith("iter 1 ")][0]
        assert res["final_loss"] < first, (first, res)

    def test_library_link_serving(self, plugin, tmp_path):
        """The LIBRARY surface (pt_predictor.h, ref paddle_api.h:204):
        pt_predictor_test is a separate translation unit linking
        libptpredictor — Create-from-dir, two Run() calls over the same
        staged params (must agree), outputs must match the Python
        forward."""
        plugin, penv = plugin
        import paddle_tpu as pt
        from paddle_tpu.io.inference import read_params_bin
        from paddle_tpu.models.mnist import MLP

        binary = os.path.join(REPO, "csrc", "build", "pt_predictor_test")
        if not os.path.exists(binary):
            pytest.skip("pt_predictor_test not built")
        model = MLP(num_classes=10, in_dim=32)
        v = model.init(jax.random.key(0))
        x = jnp.asarray(np.random.RandomState(0).rand(4, 32), jnp.float32)

        def fwd(p, xx):
            return model.apply({"params": p, "state": {}}, xx)

        path = str(tmp_path / "export")
        pt.io.save_inference_model(path, fwd, (x,), v["params"])
        expected = np.asarray(fwd(v["params"], x))
        dump = str(tmp_path / "outs.ptpb")
        r = subprocess.run([binary, path, plugin, dump],
                           capture_output=True, text=True, timeout=420,
                           env=penv)
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
        assert '"ok": true' in r.stdout
        outs = read_params_bin(dump)
        np.testing.assert_allclose(outs[0], expected, rtol=2e-2, atol=2e-2)

    def test_int8_serving_outputs_match(self, plugin, tmp_path):
        """int8 artifact (real int8 weights in params.bin) served by the
        C++ predictor matches the frozen-model Python forward."""
        plugin, penv = plugin
        import paddle_tpu as pt
        from paddle_tpu import quant
        from paddle_tpu.io.inference import read_params_bin
        from paddle_tpu.nn import layers as L
        from paddle_tpu.nn.module import Module

        class Net(Module):
            def __init__(self):
                super().__init__()
                self.fc1 = L.Linear(16, 32, act="relu")
                self.fc2 = L.Linear(32, 4)

            def forward(self, x):
                return self.fc2(self.fc1(x))

        key = jax.random.key(0)
        qm = quant.quantize_model(Net(), quant.QuantConfig(
            activation_quantize_type="abs_max"))
        qv = quant.upgrade_variables(qm, Net().init(key), key)
        x = jnp.asarray(np.random.RandomState(0).rand(4, 16), jnp.float32)
        path = str(tmp_path / "int8")
        quant.save_int8_inference_model(path, qm, qv, (x,),
                                        float_model=Net())
        frozen = quant.freeze(qm, qv)
        expected = np.asarray(Net().apply(
            {"params": frozen["params"], "state": {}}, x))

        binary = os.path.join(REPO, "csrc", "build", "pt_predictor")
        dump = str(tmp_path / "outs.ptpb")
        r = subprocess.run(
            [binary, "--model_dir", path, "--plugin", plugin,
             "--dump_outputs", dump],
            capture_output=True, text=True, timeout=420, env=penv)
        assert r.returncode == 0, r.stderr[-2000:]
        outs = read_params_bin(dump)
        np.testing.assert_allclose(outs[0], expected, rtol=2e-2, atol=2e-2)


class TestCAPI:
    """The pure-C binding (pt_predictor_c.h; ref inference/capi/) driven
    from Python through ctypes — the exact path a Go/Rust deployment
    takes: C structs in, library-owned outputs out."""

    def _lib(self):
        import ctypes
        path = os.path.join(REPO, "csrc", "build", "libptpredictor.so")
        if not os.path.exists(path):
            pytest.skip("libptpredictor not built")
        lib = ctypes.CDLL(path)

        class PT_Tensor(ctypes.Structure):
            _fields_ = [("dtype", ctypes.c_uint32),
                        ("ndim", ctypes.c_int32),
                        ("dims", ctypes.c_int64 * 8),
                        ("data", ctypes.POINTER(ctypes.c_uint8)),
                        ("nbytes", ctypes.c_size_t)]

        lib.PT_PredictorCreate.restype = ctypes.c_void_p
        lib.PT_PredictorCreate.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_size_t]
        lib.PT_PredictorRun.restype = ctypes.c_int
        lib.PT_PredictorRun.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(PT_Tensor), ctypes.c_size_t,
            ctypes.POINTER(ctypes.POINTER(PT_Tensor)),
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_char_p,
            ctypes.c_size_t]
        lib.PT_PredictorNumParams.restype = ctypes.c_size_t
        lib.PT_PredictorNumParams.argtypes = [ctypes.c_void_p]
        lib.PT_OutputsFree.argtypes = [ctypes.POINTER(PT_Tensor),
                                       ctypes.c_size_t]
        lib.PT_PredictorFree.argtypes = [ctypes.c_void_p]
        return lib, PT_Tensor

    def test_create_errors_are_reported(self, tmp_path):
        import ctypes
        lib, _ = self._lib()
        err = ctypes.create_string_buffer(512)
        h = lib.PT_PredictorCreate(str(tmp_path).encode(), b"", 0, err, 512)
        assert not h
        assert b"cannot open" in err.value

    def test_validate_only_inspection(self, tmp_path):
        import ctypes
        lib, _ = self._lib()
        import paddle_tpu as pt
        from paddle_tpu.models.mnist import MLP
        m = MLP(num_classes=3, in_dim=4)
        v = m.init(jax.random.key(0))
        path = str(tmp_path / "exp")
        pt.io.save_inference_model(
            path, lambda p, x: m.apply({"params": p, "state": {}}, x),
            (jnp.ones((2, 4)),), v["params"])
        err = ctypes.create_string_buffer(512)
        h = lib.PT_PredictorCreate(path.encode(), b"", 0, err, 512)
        assert h, err.value
        assert lib.PT_PredictorNumParams(h) == 6
        lib.PT_PredictorFree(h)

    def test_run_matches_python_forward(self, tmp_path):
        """Full C-API serving e2e in a CHILD interpreter: the pycpu plugin
        embeds CPython and cannot be initialized inside this pytest
        process (same reason the CLI e2e tests use subprocess)."""
        plugin = os.path.join(REPO, "csrc", "build", "libpycpu_pjrt.so")
        lib_path = os.path.join(REPO, "csrc", "build", "libptpredictor.so")
        if not (os.path.exists(plugin) and os.path.exists(lib_path)):
            pytest.skip("library or pycpu plugin not built")
        import paddle_tpu as pt
        from paddle_tpu.models.mnist import MLP
        m = MLP(num_classes=5, in_dim=8)
        v = m.init(jax.random.key(0))
        x = np.random.RandomState(0).rand(3, 8).astype(np.float32)
        path = str(tmp_path / "exp")
        pt.io.save_inference_model(
            path, lambda p, xx: m.apply({"params": p, "state": {}}, xx),
            (jnp.asarray(x),), v["params"])
        expected = np.asarray(m.apply(
            {"params": v["params"], "state": {}}, jnp.asarray(x)))
        np.save(str(tmp_path / "x.npy"), x)
        np.save(str(tmp_path / "expected.npy"), expected)

        script = tmp_path / "capi_driver.py"
        script.write_text(f"""
import ctypes, sys
import numpy as np

class PT_Tensor(ctypes.Structure):
    _fields_ = [("dtype", ctypes.c_uint32), ("ndim", ctypes.c_int32),
                ("dims", ctypes.c_int64 * 8),
                ("data", ctypes.POINTER(ctypes.c_uint8)),
                ("nbytes", ctypes.c_size_t)]

lib = ctypes.CDLL({lib_path!r})
lib.PT_PredictorCreate.restype = ctypes.c_void_p
lib.PT_PredictorCreate.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                   ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_size_t]
lib.PT_PredictorRun.restype = ctypes.c_int
lib.PT_PredictorRun.argtypes = [
    ctypes.c_void_p, ctypes.POINTER(PT_Tensor), ctypes.c_size_t,
    ctypes.POINTER(ctypes.POINTER(PT_Tensor)),
    ctypes.POINTER(ctypes.c_size_t), ctypes.c_char_p, ctypes.c_size_t]
lib.PT_OutputsFree.argtypes = [ctypes.POINTER(PT_Tensor), ctypes.c_size_t]
lib.PT_PredictorFree.argtypes = [ctypes.c_void_p]

x = np.load({str(tmp_path / 'x.npy')!r})
expected = np.load({str(tmp_path / 'expected.npy')!r})
err = ctypes.create_string_buffer(1024)
h = lib.PT_PredictorCreate({path!r}.encode(), {plugin!r}.encode(), 0,
                           err, 1024)
assert h, err.value
buf = ctypes.create_string_buffer(x.tobytes(), x.nbytes)
inp = PT_Tensor()
inp.dtype = 11                      # PJRT_Buffer_Type_F32
inp.ndim = 2
inp.dims[0], inp.dims[1] = x.shape
inp.data = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))
inp.nbytes = x.nbytes
outs = ctypes.POINTER(PT_Tensor)()
n = ctypes.c_size_t()
rc = lib.PT_PredictorRun(h, ctypes.byref(inp), 1, ctypes.byref(outs),
                         ctypes.byref(n), err, 1024)
assert rc == 0, err.value
assert n.value == 1
o = outs[0]
assert o.dtype == 11 and o.ndim == 2, (o.dtype, o.ndim)
assert (o.dims[0], o.dims[1]) == expected.shape
got = np.frombuffer(ctypes.string_at(o.data, o.nbytes),
                    np.float32).reshape(expected.shape)
np.testing.assert_allclose(got, expected, rtol=2e-2, atol=2e-2)
lib.PT_OutputsFree(outs, n.value)

# Zero-copy run (ref paddle_api.h:148): input borrowed from the numpy
# buffer, output written into a caller-allocated array; must match Run()
lib.PT_PredictorRunZeroCopy.restype = ctypes.c_int
lib.PT_PredictorRunZeroCopy.argtypes = [
    ctypes.c_void_p, ctypes.POINTER(PT_Tensor), ctypes.c_size_t,
    ctypes.POINTER(PT_Tensor), ctypes.c_size_t, ctypes.c_char_p,
    ctypes.c_size_t]
zc_out = np.zeros(expected.shape, np.float32)
ot = PT_Tensor()
ot.data = zc_out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
ot.nbytes = zc_out.nbytes
rc = lib.PT_PredictorRunZeroCopy(h, ctypes.byref(inp), 1,
                                 ctypes.byref(ot), 1, err, 1024)
assert rc == 0, err.value
assert ot.nbytes == zc_out.nbytes and ot.dtype == 11
np.testing.assert_array_equal(zc_out, got)
# too-small capacity: fails naming the required bytes, reports nbytes
ot2 = PT_Tensor()
small = np.zeros(1, np.uint8)
ot2.data = small.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
ot2.nbytes = 1
rc = lib.PT_PredictorRunZeroCopy(h, ctypes.byref(inp), 1,
                                 ctypes.byref(ot2), 1, err, 1024)
assert rc != 0 and str(zc_out.nbytes).encode() in err.value, err.value
assert ot2.nbytes == zc_out.nbytes

# Clone: shared executable + weights; parent freed FIRST, clone must
# still serve identical outputs (ref paddle_api.h:271)
lib.PT_PredictorClone.restype = ctypes.c_void_p
lib.PT_PredictorClone.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_size_t]
c = lib.PT_PredictorClone(h, err, 1024)
assert c, err.value
lib.PT_PredictorFree(h)
outs2 = ctypes.POINTER(PT_Tensor)()
n2 = ctypes.c_size_t()
rc = lib.PT_PredictorRun(c, ctypes.byref(inp), 1, ctypes.byref(outs2),
                         ctypes.byref(n2), err, 1024)
assert rc == 0, err.value
got2 = np.frombuffer(ctypes.string_at(outs2[0].data, outs2[0].nbytes),
                     np.float32).reshape(expected.shape)
np.testing.assert_array_equal(got2, got)
lib.PT_OutputsFree(outs2, n2.value)
lib.PT_PredictorFree(c)
print("CAPI_E2E_OK")
""")
        env = dict(os.environ)
        env["PYTHONPATH"] = _site_packages()
        r = subprocess.run(["python", str(script)], capture_output=True,
                           text=True, timeout=420, env=env)
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
        assert "CAPI_E2E_OK" in r.stdout
