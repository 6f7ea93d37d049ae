"""chip_smoke.py off the chip: its phases at tiny sizes, its refusal to
run without a TPU, and the device-kind map it (and perf / autoplan /
autotune) reads the chip from.

The script has no rehearsal option and no size switch: this file imports
it and calls the phase functions with tiny configs, Pallas kernels in
interpret mode (set here, by the test). Every check a phase makes must
hold on the CPU except the one only a chip can meet — a Mosaic
``tpu_custom_call`` in the compiled HLO.
"""

import dataclasses
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret():
    """Kernels through the Pallas interpreter, and a clean refusal
    count: ``pallas.fallback`` is process-wide, and earlier test files
    in this worker refuse kernels on purpose."""
    from paddle_tpu.core import flags
    from paddle_tpu.observability import metrics
    was = flags.get_flag("pallas_interpret")
    flags.set_flags({"pallas_interpret": True})
    metrics.counter("pallas.fallback").reset()
    yield
    flags.set_flags({"pallas_interpret": was})


def _tiny(config_cls):
    """Two layers, two heads of 64 (the flash kernel's lane width)."""
    return config_cls(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=2, intermediate_size=256, max_position=128)


def _failed(record):
    return sorted(k for k, ok in record["checks"].items() if not ok)


def _only_the_chip_is_missing(record, programs):
    """Every check passes but the Mosaic-kernel evidence, which no CPU
    compile can give — and which must then be what fails."""
    assert _failed(record) == sorted(
        f"kernels_present.{p}" for p in programs), record
    assert not record["ok"]
    assert record["pallas_fallback"] == {}


def test_train_phase_tiny(interpret):
    from paddle_tpu.models.bert import BertConfig
    rec = chip_smoke.phase_train(_tiny(BertConfig), batch=4, seq=64,
                                 steps=3)
    _only_the_chip_is_missing(rec, ["train_step"])
    assert len(rec["losses"]) == 3
    assert rec["loss_rel_diff"] <= chip_smoke.LOSS_TOL
    assert rec["grad_rel_err"] <= chip_smoke.GRAD_TOL
    assert set(rec["compile"]) == {"value_and_grad", "value_and_grad_twin",
                                   "train_step"}


def test_train_causal_phase_tiny(interpret):
    from paddle_tpu.models.gpt import GPTConfig
    rec = chip_smoke.phase_train_causal(_tiny(GPTConfig), batch=2, seq=64,
                                        steps=2)
    _only_the_chip_is_missing(rec, ["train_step"])
    assert rec["phase"] == "train-causal" and len(rec["losses"]) == 2


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_serve_phase_tiny(interpret, kv):
    from paddle_tpu.models.gpt import GPTConfig
    rec = chip_smoke.phase_serve(_tiny(GPTConfig), kv=kv, slots=3, page=16,
                                 prefill_len=32, max_len=64, max_new=6)
    _only_the_chip_is_missing(rec, ["prefill", "decode"])
    assert rec["requests"] == 12 and rec["statuses"] == ["done"]
    assert rec["recoveries"] == 0 and rec["prefix_hits"] >= 1
    assert max(rec["prompt_lengths"]) > 32      # one chunked prefill


def test_mesh_phase_tiny_on_four_virtual_devices(interpret):
    """--chips 4's phase on four of conftest's virtual CPU devices: the
    sharding assertions and the one-chip comparison, end to end."""
    from paddle_tpu.models.gpt import GPTConfig
    rec = chip_smoke.phase_mesh(_tiny(GPTConfig), batch=4, seq=64, steps=3)
    _only_the_chip_is_missing(rec, ["mesh_step"])
    assert rec["spread"]["param_devices"] == 4
    assert rec["spread"]["table_shard_on_device0"] == [256, 128]
    assert rec["all_reduces"] > 0


def test_kernel_counts_reads_names_from_hlo():
    def call(name):
        return ('  %x.1 = f32[8,8]{1,0} custom-call(%a), '
                'custom_call_target="tpu_custom_call", metadata={op_name='
                f'"jit(step)/{name}/pallas_call" stack_frame_id=7}}, '
                'backend_config={}')

    hlo = "\n".join([call("mlp"), call("transpose(jvp(xent_bwd_dwb))"),
                     call("mlp"),
                     '  %y = f32[8] custom-call(%b), custom_call_target='
                     '"Sharding"'])
    assert chip_smoke.kernel_counts(hlo) == {"mlp": 2, "xent_bwd_dwb": 1}


def test_script_fails_without_a_tpu(tmp_path):
    """``python chip_smoke.py`` on the CPU: non-zero exit, a traceback,
    and no result line — no CPU carry-on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=300, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ------------------------------------------------- the device-kind map

@dataclasses.dataclass
class FakeDevice:
    platform: str
    device_kind: str


V5E = FakeDevice("tpu", "TPU v5 lite")


def test_device_kind_map():
    from paddle_tpu.parallel.autoplan.topology import (chip_name,
                                                       peak_bf16_flops)
    assert chip_name(V5E) == "v5e"          # NOT anything with "v5e" in it
    assert peak_bf16_flops(V5E) == 197e12
    assert chip_name(FakeDevice("cpu", "cpu")) == "cpu"
    assert peak_bf16_flops(FakeDevice("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="TPU v9 mega"):
        chip_name(FakeDevice("tpu", "TPU v9 mega"))
    with pytest.raises(ValueError):
        chip_name(FakeDevice("gpu", "NVIDIA H100"))


def test_one_map_feeds_topology_autotune_and_perf():
    from paddle_tpu.observability import perf
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.parallel.autoplan import topology
    topo = topology.detect([V5E] * 4)
    assert topo.name == "detected:v5e4" and topo.num_chips == 4
    assert topo.hbm_bytes == 16 * 2 ** 30 and topo.peak_flops == 197e12
    assert autotune.chip_key([V5E]) == "v5e"
    with pytest.raises(ValueError):
        topology.detect([FakeDevice("tpu", "TPU v9 mega")])
    # the live device here is the CPU: it has no peak, so no utilization
    assert perf.peak_flops() is None
    assert perf.mfu(1e12, 1.0) is None


def test_cost_flops_does_not_swallow_a_failing_step():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import perf
    assert perf.cost_flops(jax.jit(lambda a: a @ a), jnp.ones((8, 8))) > 0

    def broken(a):
        raise RuntimeError("this step cannot trace")

    with pytest.raises(RuntimeError, match="cannot trace"):
        perf.cost_flops(jax.jit(broken), jnp.ones((8, 8)))


# ---------------------------------------------------- the compile cache

def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from paddle_tpu.core import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert calls == []                      # the environment placed it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed)]
