"""Find a serving cell's knee: the highest rate the system sustains.

Run once, by hand, on the chip, when a cell is defined (and again when an
optimisation has moved the knee): one engine, one window per rate, a
table out. The cell's own file (``benchmark/cells/<cell>.json``) then
fixes its rate inside 0.7-0.8 of the knee (where, the ladder of the gaps
on each window's standard error decides; README.md); the benchmark
itself never searches. Windows of the cell's own length: the longest
request lives for tens of seconds, and a shorter sweep measures the
warm-up.

    python3 benchmark/sweep.py --workload gpt2_medium.chat \\
        --rates 2.0,2.3,2.6,2.9 --seconds 51 [--seed 1]

A rate is sustained when every request due in the window finished, the
requests due in the window's last quarter waited no more than twice as
long for their first token as those of its first quarter (the queue does
not grow), and the slots were under 95% occupied. The drain after the
close says nothing: it lasts as long as the longest answer still running.
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import device, stats  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _, cell, config, traffic, own = bench_run.find_cell(ROOT, args.workload)
    devices = device.require_tpu(cell["chips"])
    window = importlib.import_module(
        f"benchmark.harness.{traffic['kind']}_window")
    ctx = bench_run.make_ctx(ROOT, args.workload, config, traffic, own,
                             devices, args.seed, args.seconds)
    engine = window.build(ctx)
    window.prewarm(engine, config["shapes"]["vocab_size"], args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t_start = time.perf_counter()
        # a seed of its own for each rate: the same token ids twice
        # would be served from the prefix cache the second time
        ctx["seed"] = args.seed + i
        out = window.measure(engine, ctx, dict(own, rate_per_s=rate),
                             args.seconds)
        took = time.perf_counter() - t_start
        drain = took - own["warmup_seconds"] - args.seconds
        t = out["ttft_ms"]
        q = max(1, len(t) // 4)
        first, last = stats.percentile(t[:q], 50), stats.percentile(t[-q:], 50)
        row = {"rate_per_s": rate, "due": out["attempted"],
               "failed": out["failed"], "drain_s": drain,
               "ttft_p50_first_quarter_ms": first,
               "ttft_p50_last_quarter_ms": last,
               "slot_occupancy_pct": out["facts"]["slot_occupancy_pct"],
               "queue_wait_ms_p50": out["facts"]["queue_wait_ms_p50"],
               "kv_pool_live_pct": out["facts"]["kv_pool_live_pct"],
               **out["e2e"]}
        row["sustained"] = (out["failed"] == 0
                            and last <= 2.0 * max(first, 1.0)
                            and row["slot_occupancy_pct"] < 95.0)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    summary = {"workload": args.workload, "knee_per_s": knee,
               "band": None if knee is None else [0.7 * knee, 0.8 * knee],
               "device": device.describe(devices), "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in
                      ("workload", "knee_per_s", "band")}))


if __name__ == "__main__":
    main()
