"""The trace reduction on a small recorded trace: two steps of
``bert_large.pretrain`` cut out of PR 24's first traced run on the v5e
(``fixtures/train_two_steps.json.gz``), and on a hand-made one whose
answers can be worked out on paper."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import peaks, readers, trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "train_two_steps.json.gz")
MS = 1_000_000


def hand_made():
    """10 ms window; the device runs 1-3 ms and 5-9 ms (a loop op spans
    5-9 and holds two kernel calls); the host slept 3-5 ms."""
    ops = [["%fusion.1 = f32[8] fusion(f32[8] %mlp.2)", 1 * MS, 2 * MS],
           ["%while.3 = (s32[]) while(...)", 5 * MS, 4 * MS],
           ["%mlp.2 = bf16[8,8] custom-call(...)", 5 * MS, 1 * MS],
           ["%mlp.2 = bf16[8,8] custom-call(...)", 7 * MS, 2 * MS]]
    mods = [["jit_step(1)", 1 * MS, 2 * MS], ["jit_step(1)", 5 * MS, 4 * MS]]
    host = [["bench.window", 0, 10 * MS], ["bench.sleep", 3 * MS, 2 * MS],
            ["bench.step", 0, 3 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def test_busy_union_and_idle_share_by_hand():
    t = hand_made()
    busy, window = tr.busy_and_window(t)
    assert (busy, window) == (pytest.approx(0.006), pytest.approx(0.010))
    assert readers.idle_share({}, {"trace": t}) == pytest.approx(40.0)
    # the whole step's share of the peak is taken over the 10 ms of the
    # traced window, the 4 ms in which the device waited included
    v5e = peaks.peaks_for("TPU v5 lite")
    half = {"trace": t, "peaks": v5e,
            "facts": {"ops": 0.5 * 0.010 * v5e["bf16_flops_per_s"]}}
    assert readers.ops_share_of_peak({"ops": "ops"}, half) == pytest.approx(
        50.0)
    # a kernel's time is summed over its OWN events: the fusion that
    # mentions %mlp.2 as an operand and the loop that contains it are not it
    assert tr.op_durations(t, r"^mlp(\.\d+)?$") == pytest.approx(
        [0.001, 0.002])
    assert [d for _, d in tr.module_runs(t, "^jit_step")] == pytest.approx(
        [0.002, 0.004])
    assert readers.module_ms_p50({"module": "^jit_step"},
                                 {"trace": t}) == pytest.approx(3.0)
    top = tr.top_device_ops(t)
    assert top[0] == ["mlp bf16[8,8]", pytest.approx(0.003)]
    assert not any(name.startswith("while") for name, _ in top)
    gaps = dict(tr.idle_gaps(t))
    assert gaps["bench.sleep"] == pytest.approx(0.002)
    assert gaps["bench.step"] == pytest.approx(0.001)
    assert gaps["unattributed"] == pytest.approx(0.001)


def test_a_reader_with_nothing_to_read_returns_nothing():
    t = hand_made()
    run = {"trace": t, "peaks": peaks.peaks_for("TPU v5 lite"),
           "traffic": {}, "config": {}, "facts": {}}
    spec = {"kernels": {r"^flash_attention(\.\d+)?$": ["flash_forward", {
        "batch": 1, "heads": 1, "seq_q": 8, "seq_k": 8, "head_dim": 8}]}}
    assert readers.kernel_roofline(spec, run) is None
    assert readers.module_ms_p50({"module": "^jit_decode"}, run) is None
    assert readers.fact({"fact": "absent"}, run) is None
    assert readers.idle_share({}, {"trace": None}) is None


def test_recorded_v5e_trace_reduces_to_what_the_chip_run_read():
    t = tr.load_json(FIXTURE)
    busy, window = tr.busy_and_window(t)
    assert 0.99 < busy / window <= 1.0          # the run read 0.14% idle
    steps = tr.module_runs(t, "^jit_train_step")
    assert len(steps) == 2
    assert all(0.200 < d < 0.203 for _, d in steps)   # 201.15 ms a step
    mlp = tr.op_durations(t, r"^mlp(\.\d+)?$")
    assert len(mlp) == 48                       # 24 layers x 2 steps
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "bert_large.json")) as f:
        shapes = json.load(f)["shapes"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "mlm_b16_s512.json")) as f:
        traffic = json.load(f)
    run = {"trace": t, "peaks": peaks.peaks_for("TPU v5 lite"),
           "traffic": traffic, "config": shapes,
           "facts": {"tokens_per_step": 8192, "steps": 2}}

    def read(name):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        module, fn = spec["reader"].split(":")
        return getattr(__import__(module, fromlist=[fn]), fn)(spec, run)

    # the whole trace read 70.2%, 19.6% and 41.2% (PERF.md, PR 24)
    assert read("train_mlp_roofline") == pytest.approx(70.2, abs=1.0)
    assert read("train_flash_attention_roofline") == pytest.approx(19.6,
                                                                   abs=1.0)
    assert read("mfu.train") == pytest.approx(41.2, abs=0.5)
    for name in ("train_mlp_roofline", "train_flash_attention_roofline",
                 "mfu.train"):
        assert 0 < read(name) < 100
    assert read("device.idle_share.train") < 1.0
    assert tr.top_device_ops(t)[0][0] == "mlp bf16[8192,1024]"


def test_loading_leaves_out_the_device_events_after_the_windows_close(
        monkeypatch):
    """A serving run's profiler stops after the drain (PR 31), so its
    ``.xplane.pb`` goes on past ``bench.window``. ``load_xplane`` keeps
    the device events that begin before the window's end, whatever the
    order of the planes in the file, and the reductions read what they
    read from the window alone."""
    from types import SimpleNamespace as NS
    import jax.profiler

    def plane(p):
        return NS(name=p["name"], lines=[
            NS(name=line["name"], events=[
                NS(name=n, start_ns=s, duration_ns=d)
                for n, s, d in line["events"]])
            for line in p["lines"]])
    trace = hand_made()
    whole = tr.busy_and_window(trace), tr.top_device_ops(trace)
    dev, host = trace["planes"]
    # the drain: a round after the close at 10 ms, and host work there
    dev["lines"][0]["events"].append(
        ["%mlp.2 = bf16[8,8] custom-call(...)", 12 * MS, 2 * MS])
    dev["lines"][1]["events"].append(["jit_step(1)", 12 * MS, 2 * MS])
    host["lines"][0]["events"].append(["bench.step", 11 * MS, 4 * MS])
    host["lines"][0]["events"].append(["not.kept", 1 * MS, 1 * MS])
    other = {"name": "/host:metadata", "lines": []}
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: NS(planes=[
            plane(dev), NS(name="Task Environment", lines=[]), plane(host),
            plane(other)])))
    got = tr.load_xplane("any.xplane.pb")
    assert [p["name"] for p in got["planes"]] == [
        "/host:CPU", "/host:metadata", "/device:TPU:0"]
    ops, mods = (line["events"] for line in got["planes"][2]["lines"])
    assert len(ops) == 4 and len(mods) == 2           # the drain's are out
    names = [e[0] for e in got["planes"][0]["lines"][0]["events"]]
    assert names == ["bench.window", "bench.sleep", "bench.step",
                     "bench.step"]                    # the host's stay
    assert (tr.busy_and_window(got), tr.top_device_ops(got)) == whole
