"""tools/run_report.py — the RunLog + trace join CLI.

The --selftest subprocess is the tier-1 smoke (marker `perf`, like the
compile smokes): a tiny GPT trained through the Trainer with telemetry
on must produce a complete RunLog (wall time, tokens/s, MFU, loss,
memory, pallas-fallback + checkpoint counters) and this CLI must render
it — so the telemetry path can never silently rot."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_REPORT = os.path.join(REPO, "tools", "run_report.py")


def _records():
    steps = [{"step": s, "time": 100.0 + s, "wall_s": 0.01 + 0.001 * s,
              "tokens_per_s": 1000.0 - s, "mfu": 0.3 + 0.01 * s,
              "loss": 5.0 - 0.1 * s, "grad_norm": None,
              "memory": {"peak_bytes_in_use": 1 << 20}}
             for s in range(1, 11)]
    final = {"final": True, "steps": 10,
             "counters": {"checkpoint.saves": 2,
                          "pallas.fallback": {"kernel=xent_stats": 3}},
             "spans": [{"name": "step", "calls": 10, "total_s": 0.5,
                        "p50_ms": 10.0, "p95_ms": 20.0}]}
    return steps + [final]


def test_render_report_sections():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from run_report import render_report
    finally:
        sys.path.pop(0)
    rep = render_report(_records())
    assert "step records: 10" in rep
    assert "p50=" in rep and "p95=" in rep and "p99=" in rep
    assert "MFU curve:" in rep
    assert "loss:" in rep and "first=4.900000" in rep
    assert "memory peak: 1.0 MiB" in rep
    assert "pallas.fallback{kernel=xent_stats}" in rep
    assert "checkpoint.saves" in rep
    assert "spans:" in rep


def test_cli_renders_runlog(tmp_path):
    p = tmp_path / "run.jsonl"
    with open(p, "w") as f:
        for r in _records():
            f.write(json.dumps(r) + "\n")
    proc = subprocess.run(
        [sys.executable, RUN_REPORT, str(p)], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "RUN REPORT" in proc.stdout
    assert "checkpoint.saves" in proc.stdout


def test_cli_counter_deltas_across_snapshots(tmp_path):
    """Two final snapshots (a resumed run appending to one RunLog) ->
    the report shows deltas since the first."""
    recs = _records()
    recs.append({"final": True, "steps": 20,
                 "counters": {"checkpoint.saves": 5,
                              "pallas.fallback": {"kernel=xent_stats": 3}}})
    p = tmp_path / "run.jsonl"
    with open(p, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    proc = subprocess.run(
        [sys.executable, RUN_REPORT, str(p)], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "delta since first snapshot" in proc.stdout
    assert "(+3)" in proc.stdout        # saves went 2 -> 5


def _serve_records():
    """Synthetic serve RunLog: req 0 sails through; req 1 is preempted
    off slot 0 and resumes on slot 1; per-step records ride along."""
    def ev(event, req, t, slot=None, **extra):
        rec = {"event": event, "req": req, "trace": f"abc123/{req}",
               "t": t, "at_step": 0}
        if slot is not None:
            rec["slot"] = slot
        rec.update(extra)
        return rec

    events = [
        ev("submitted", 0, 100.00, prompt_len=5, max_new=8),
        ev("submitted", 1, 100.01, prompt_len=7, max_new=10),
        ev("admitted", 0, 100.02, slot=0),
        ev("prefill_done", 0, 100.05, slot=0),
        ev("first_token", 0, 100.05, slot=0),
        ev("admitted", 1, 100.06, slot=1),
        ev("prefill_done", 1, 100.09, slot=1),
        ev("first_token", 1, 100.09, slot=1),
        ev("retired", 0, 100.30, slot=0, reason="eos", tokens=6,
           slo_ok=True, preemptions=0),
        ev("preempted", 1, 100.35, slot=1, tokens_dropped=4),
        ev("resumed", 1, 100.45, slot=0),
        ev("prefill_done", 1, 100.47, slot=0),
        ev("first_token", 1, 100.47, slot=0),
        ev("retired", 1, 100.80, slot=0, reason="length", tokens=10,
           slo_ok=False, preemptions=1),
    ]
    steps = [{"phase": "serve", "step": s, "wall_s": 0.02,
              "new_tokens": 2, "active": 2, "queue_depth": 0,
              "goodput": 1.0} for s in range(10)]
    final = {"final": True, "phase": "serve",
             "counters": {"serve.tokens": 16},
             "slo": {"goodput": 0.5, "retired": 2, "slo_ttft_s": 0.5,
                     "slo_token_latency_s": None,
                     "violations": {"ttft": 1, "token_latency": 0}}}
    return events + steps + [final]


def _import_run_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import run_report
    finally:
        sys.path.pop(0)
    return run_report


class TestServeReport:
    def test_sections_and_accounting(self):
        rep = _import_run_report().render_serve_report(_serve_records())
        assert "SERVE REPORT" in rep
        assert "requests: 2 submitted, 2 retired (eos 1, length 1), " \
            "1 preempted" in rep
        # TTFT: req0 50ms, req1 460ms (last first_token after resume)
        assert "TTFT:" in rep and "p50=255.0ms" in rep
        assert "goodput:        0.5000 over 2 retired" in rep
        assert "slo_ttft_s=0.5" in rep and "ttft=1" in rep
        assert "serve steps:    10 (20 tokens)" in rep

    def test_gantt_and_preemption_attribution(self):
        rep = _import_run_report().render_serve_report(_serve_records())
        lines = rep.splitlines()
        g0 = [ln for ln in lines if ln.startswith("  slot  0")][0]
        g1 = [ln for ln in lines if ln.startswith("  slot  1")][0]
        assert "0" in g0 and "1" in g0      # req1 resumed onto slot 0
        assert "!" in g1                    # preemption marker on slot 1
        assert "req 1: preempted at slot 1 (4 tokens dropped, " \
            "resumed +0.100s later)" in rep
        vic = [ln for ln in lines if ln.strip().startswith("req 1 [")][0]
        assert "SLO MISS" in vic
        for evname in ("submitted", "admitted", "preempted", "resumed",
                       "retired"):
            assert evname in vic

    def test_cli_serve_flag(self, tmp_path):
        p = tmp_path / "serve.jsonl"
        with open(p, "w") as f:
            for r in _serve_records():
                f.write(json.dumps(r) + "\n")
        proc = subprocess.run(
            [sys.executable, RUN_REPORT, str(p), "--serve"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=120, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert "SERVE REPORT" in proc.stdout
        assert "slot timeline" in proc.stdout

    def test_no_events_degrades_gracefully(self):
        rep = _import_run_report().render_serve_report(_records())
        assert "no serve trace events" in rep


def _fleet_records():
    """A ramp row (ops_log + version_stats + curve) plus one raw ops
    event record."""
    ops = [
        {"event": "deploy_start", "t": 10.0, "at_step": 3,
         "version": "v1", "canary": False, "targets": [0, 1]},
        {"event": "swap", "t": 10.4, "at_step": 9, "replica": 0,
         "version": "v1", "prev": "v0"},
        {"event": "swap", "t": 10.9, "at_step": 15, "replica": 1,
         "version": "v1", "prev": "v0"},
        {"event": "deploy_done", "t": 10.9, "at_step": 15,
         "version": "v1", "canary": False, "baseline": "v1",
         "replicas": [0, 1]},
        {"event": "scale_up", "t": 12.0, "at_step": 20, "replica": 2,
         "backlog": 7},
    ]
    row = {
        "metric": "gpt_serve_fleet_ramp_peak_tokens_per_sec",
        "ops_log": ops,
        "version_stats": {
            "v0": {"retired": 12, "slo_ok": 10, "goodput": 0.8333},
            "v1": {"retired": 20, "slo_ok": 19, "goodput": 0.95}},
        "curve": [
            {"offered": 2, "completed": 2, "goodput": 1.0,
             "replicas": 1, "tokens_per_sec": 90.0, "deploy_s": 0.0},
            {"offered": 8, "completed": 8, "goodput": 0.75,
             "replicas": 3, "tokens_per_sec": 220.0,
             "deploy_s": 0.41}],
    }
    raw = {"event": "scale_down", "t": 15.0, "at_step": 44,
           "replica": 2}
    return [row, raw]


class TestFleetReport:
    def test_timeline_versions_and_curve(self):
        rep = _import_run_report().render_fleet_report(_fleet_records())
        assert "FLEET REPORT" in rep
        # timeline is time-ordered and folds raw + ops_log events
        assert rep.index("deploy_start") < rep.index("deploy_done")
        assert rep.index("deploy_done") < rep.index("scale_down")
        assert "replica=0, version=v1, prev=v0" in rep
        # per-version goodput table
        assert "per-version goodput" in rep
        lines = rep.splitlines()
        v0 = [ln for ln in lines if ln.strip().startswith("v0")][0]
        assert "12" in v0 and "0.8333" in v0
        # offered-load ramp with replica-count + deploy-overhead cols
        assert "offered-load ramp" in rep
        ramp8 = [ln for ln in lines if ln.strip().startswith("8 ")][0]
        assert "3" in ramp8 and "0.41" in ramp8

    def test_version_stats_reconstructed_from_trace(self):
        recs = [
            {"event": "retired", "req": 0, "t": 1.0, "version": "v0",
             "slo_ok": True, "reason": "eos", "tokens": 4},
            {"event": "retired", "req": 1, "t": 2.0, "version": "v0",
             "slo_ok": False, "reason": "eos", "tokens": 4},
        ]
        rep = _import_run_report().render_fleet_report(recs)
        assert "per-version goodput" in rep
        assert "0.5000" in rep

    def test_cli_fleet_flag(self, tmp_path):
        p = tmp_path / "fleet.jsonl"
        with open(p, "w") as f:
            for r in _fleet_records():
                f.write(json.dumps(r) + "\n")
        proc = subprocess.run(
            [sys.executable, RUN_REPORT, str(p), "--fleet"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=120, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert "FLEET REPORT" in proc.stdout
        assert "deploy timeline" in proc.stdout

    def test_no_fleet_data_degrades_gracefully(self):
        rep = _import_run_report().render_fleet_report(_records())
        assert "no fleet ops events" in rep


def _fleet_trace_lists():
    """Two replica logs with skewed monotonic epochs and one request
    failed over from r0 to r1 under a single trace id."""
    tid = "abcd1234/0"
    r0 = [
        {"anchor": {"wall": 1000.0, "mono": 10.0}, "pid": 1},
        {"event": "adopted", "req": 0, "trace": tid, "t": 11.0,
         "at_step": 0, "replica": 0, "version": "m@v0",
         "span": "hop0", "parent_span": "root", "origin": "dispatch"},
        {"event": "admitted", "req": 0, "trace": tid, "t": 11.2,
         "at_step": 1, "replica": 0, "span": "hop0"},
        {"event": "prefill_done", "req": 0, "trace": tid, "t": 11.4,
         "at_step": 1, "replica": 0, "span": "hop0"},
        {"event": "first_token", "req": 0, "trace": tid, "t": 11.4,
         "at_step": 1, "replica": 0, "span": "hop0"},
    ]
    r1 = [
        {"anchor": {"wall": 1000.0, "mono": 900.0}, "pid": 2},
        {"event": "adopted", "req": 0, "trace": tid, "t": 912.0,
         "at_step": 0, "replica": 1, "version": "m@v0",
         "span": "hop1", "parent_span": "hop0", "origin": "failover"},
        {"event": "resumed", "req": 0, "trace": tid, "t": 912.1,
         "at_step": 1, "replica": 1, "span": "hop1"},
        {"event": "prefill_done", "req": 0, "trace": tid, "t": 912.3,
         "at_step": 1, "replica": 1, "span": "hop1"},
        {"event": "first_token", "req": 0, "trace": tid, "t": 912.3,
         "at_step": 1, "replica": 1, "span": "hop1"},
        {"event": "retired", "req": 0, "trace": tid, "t": 913.0,
         "at_step": 2, "replica": 1, "span": "hop1", "reason": "eos",
         "tokens": 6, "slo_ok": True},
    ]
    return {"r0": r0, "r1": r1}


class TestFleetTraceReport:
    def test_skew_gantt_and_critical_path(self):
        rep = _import_run_report().render_fleet_trace(
            _fleet_trace_lists())
        assert "FLEET TRACE" in rep
        assert "clock-skew report" in rep
        assert "abcd1234/0" in rep
        # one trace, two replica rows, the failover adoption marked
        assert "hop0" in rep and "hop1" in rep
        assert "F" in rep and "[m@v0]" in rep
        assert "critical-path breakdown" in rep
        for phase in ("queue", "prefill", "first_token", "decode",
                      "total"):
            assert phase in rep

    def test_cli_fleet_trace_flag(self, tmp_path):
        paths = []
        for src, recs in _fleet_trace_lists().items():
            p = tmp_path / f"serve.{src}.jsonl"
            with open(p, "w") as f:
                for r in recs:
                    f.write(json.dumps(r) + "\n")
            paths.append(str(p))
        proc = subprocess.run(
            [sys.executable, RUN_REPORT, *paths, "--fleet-trace"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=120, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert "FLEET TRACE" in proc.stdout
        assert "skew" in proc.stdout

    def test_extra_runlogs_require_fleet_trace(self, tmp_path):
        p = tmp_path / "a.jsonl"
        p.write_text("{}\n")
        proc = subprocess.run(
            [sys.executable, RUN_REPORT, str(p), str(p)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=120, cwd=REPO)
        assert proc.returncode != 0
        assert "--fleet-trace" in proc.stderr

    def test_no_trace_events_degrades_gracefully(self):
        rep = _import_run_report().render_fleet_trace(
            {"r0": _records()})
        assert "no request trace events" in rep


@pytest.mark.perf
def test_run_report_selftest_smoke():
    """Tier-1: tiny GPT through the Trainer with telemetry on (CPU),
    RunLog completeness asserted, report rendered — end to end in a
    child process (the acceptance-criteria path)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN_REPORT, "--selftest"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert "SELFTEST OK" in proc.stdout
    assert "RUN REPORT" in proc.stdout
    assert "pallas.fallback" in proc.stdout
