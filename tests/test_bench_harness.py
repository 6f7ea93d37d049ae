"""bench.py as a command: what it prints and how it exits.

A row runs in the process that was asked for it; the failure paths only
otherwise execute inside a driver's bench window, which is exactly when
a regression is most expensive, so the suite covers them on CPU. Ref:
the reference's CI treats its benchmark harnesses as tested code
(paddle/fluid/operators/benchmark/op_tester.cc has its own test main).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(args, timeout=240):
    """(exit code, last stdout line as JSON) of ``python bench.py``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=timeout, env=env, cwd=REPO)
    lines = proc.stdout.strip().splitlines()
    assert lines, (proc.returncode, proc.stderr[-1500:])
    return proc.returncode, json.loads(lines[-1])


def test_crash_is_bench_failed_and_nonzero_exit():
    """A row that raises must surface as bench_failed AND a non-zero
    exit — never a replayed number, never exit 0. dp3 divides no device
    count the test host has, so building the mesh raises."""
    rc, row = _run_bench(["--model", "gpt", "--tiny", "--mesh", "dp3"])
    assert rc != 0
    assert row["metric"] == "bench_failed"
    assert "AssertionError" in row["error"]


def test_compile_only_emits_marker_row(tmp_path):
    run_log = tmp_path / "bench_run.jsonl"
    rc, row = _run_bench(["--model", "ctr", "--compile-only",
                          "--run-log", str(run_log)], timeout=420)
    assert rc == 0
    assert row["metric"] == "ctr_compile_only"
    assert row["unit"] == "compiled" and row["compile_s"] >= 0
    # every row names the device it ran on, and on the CPU no row
    # carries a utilization against some chip's peak
    assert row["device"]["platform"] == "cpu" and row["device"]["count"] >= 1
    assert row.get("mfu") is None
    # every row is self-describing: registry counter snapshot rides
    # along (observability satellite), and --run-log streamed the
    # final record
    assert "telemetry" in row and "counters" in row["telemetry"]
    recs = [json.loads(line) for line in
            run_log.read_text().splitlines()]
    assert recs and recs[-1]["final"] is True
