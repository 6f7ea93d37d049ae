"""The harness end to end at tiny sizes on the CPU: each window function
once through the real ``Trainer`` and ``ServingEngine``; the refusal of a
backend that is not a TPU; and the proof that ``correct`` can come out
false: the lower-precision control, and the timed path broken underneath
a run (a step that returns its state unchanged, half of the batch left
out, a token altered where it is produced)."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import (compare, device, serve_window,  # noqa: E402
                               train_window)
from benchmark.tasks import mlm  # noqa: E402

SEED = 2 ** 31 + 1     # the driver's seeds pass 32 signed bits


def run_tiny(root, workload, trace=0, seconds=1.5):
    return bench_run.run_cell(workload, SEED, seconds, trace, root=root,
                              need_chip=False)


def check_line(line, metrics):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    json.dumps(line)


def test_a_backend_that_is_not_a_tpu_is_refused_before_any_result(
        tiny_root, capsys):
    with pytest.raises(device.NoChip, match="needs a TPU"):
        bench_run.run_cell("bert_large.pretrain", 1, 1.0, 0, root=tiny_root)
    assert capsys.readouterr().out == ""
    with pytest.raises(SystemExit, match="no cell"):
        bench_run.find_cell(tiny_root, "no.such.cell")


def test_training_window_through_the_real_trainer(tiny_root):
    line = run_tiny(tiny_root, "bert_large.pretrain")
    check_line(line, ["train_tokens_per_s", "setup_s"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "update_gap",
                                     "last_loss_finite"}
    # the traced run reports the per-layer metrics whose readers found
    # something (no device plane on the CPU: only the counter's)
    line = run_tiny(tiny_root, "bert_large.pretrain", trace=1)
    assert set(line["metrics"]) == {"trainer.ingest_stall_ms"}
    assert line["correct"] is True


def test_serving_window_through_the_real_engine(tiny_root):
    line = run_tiny(tiny_root, "gpt2_medium.chat", seconds=2.0)
    check_line(line, ["serve_tokens_per_s", "ttft_p90_ms", "gap_p90_ms",
                      "setup_s"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert set(line["compared"]) == {"served_gap", "never_answered"}
    line = run_tiny(tiny_root, "gpt2_medium.chat", trace=1)
    assert {"serve.queue_wait_ms_p50", "serve.slot_occupancy",
            "serve.gap_mean_ms", "serve.kv_pool_live"} == set(line["metrics"])


@pytest.fixture(scope="module")
def tiny_long_root(tiny_root, tmp_path_factory):
    """The tiny root with ``chat``'s answers allowed to run to the
    engine's ``max_len`` (128 here), as the real mix lets them run to
    GPT-2's 1024 positions since PR 31."""
    import shutil
    root = str(tmp_path_factory.mktemp("tiny_long") / "root")
    shutil.copytree(tiny_root, root)
    path = os.path.join(root, "benchmark", "traffic", "chat.json")
    with open(path) as f:
        mix = json.load(f)
    mix["answer"] = {"mean": 40, "min": 2, "max": 128}
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_answers_as_long_as_the_context_are_compared_row_for_row(
        tiny_long_root, monkeypatch):
    """Where the longest answer allowed is ``max_len`` itself the
    reference cannot score that many positions FROM the prompt's end (a
    dynamic slice would clamp the start in silence and every row would
    be another position's): it starts earlier, and the comparison reads
    the served tokens' own rows. Sound tokens read as in the short mix;
    an altered token still fails."""
    line = run_tiny(tiny_long_root, "gpt2_medium.chat", seconds=2.0)
    assert line["failed"] == 0 and line["attempted"] >= 10
    assert line["correct"] is True
    row = line["compared"]["served_gap"]
    assert row["value"] <= row["limit"]
    token_altered(monkeypatch)
    line = run_tiny(tiny_long_root, "gpt2_medium.chat", seconds=2.0)
    assert line["correct"] is False


def state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    def call(self, state, *batch):
        loss, _ = self.exe(jax.tree_util.tree_map(jnp.copy, state), *batch)
        self.losses.append(loss)
        return loss, state
    monkeypatch.setattr(train_window.StepDriver, "__call__", call)


def half_batch(monkeypatch):
    sound = mlm.bind_loss

    def bind(model):
        loss = sound(model)
        return lambda p, *batch: loss(
            p, *(a[:a.shape[0] // 2] for a in batch))
    monkeypatch.setattr(mlm, "bind_loss", bind)


def token_altered(monkeypatch):
    sound = serve_window.build

    def build(ctx):
        engine = sound(ctx)
        vocab = ctx["config"]["shapes"]["vocab_size"]
        decode = engine._decode_jit

        def altered(*args):
            toks, caches = decode(*args)
            return (toks + 1) % vocab, caches
        engine._decode_jit = altered
        return engine
    monkeypatch.setattr(serve_window, "build", build)


@pytest.mark.parametrize("workload,fault,fails", [
    ("bert_large.pretrain", state_unchanged, "update_gap"),
    ("bert_large.pretrain", half_batch, "grad_gap"),
    ("gpt2_medium.chat", token_altered, "served_gap"),
])
def test_a_broken_timed_path_comes_out_as_not_correct(
        tiny_root, monkeypatch, workload, fault, fails):
    fault(monkeypatch)
    line = run_tiny(tiny_root, workload)
    assert line["correct"] is False
    row = line["compared"][fails]
    assert row["value"] > row["limit"]


@pytest.mark.parametrize("workload,precision,number", [
    ("bert_large.pretrain", "int8", "update_gap"),
    ("gpt2_medium.chat", "fp8", "served_gap"),
])
def test_the_lower_precision_control_comes_out_as_not_correct(
        tiny_root, workload, precision, number):
    import jax
    _, cell, config, traffic, own = bench_run.find_cell(tiny_root, workload)
    window = importlib.import_module(
        f"benchmark.harness.{traffic['kind']}_window")
    ctx = bench_run.make_ctx(tiny_root, workload, config, traffic, own,
                             jax.devices()[:1], 1, 1.5,
                             control_precision=precision)
    out = window.control(ctx)
    _, program_ok = compare.judge(out["program"], own["limits"])
    rows, control_ok = compare.judge(out["control"], own["limits"])
    assert program_ok and not control_ok
    assert [n for n, _, _, ok in rows if not ok] == [number] or \
        number in [n for n, _, _, ok in rows if not ok]
