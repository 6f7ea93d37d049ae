"""The readings that the limits of ``correct`` are set from: the program,
the lower-precision control and the planted faults, at a cell's own size,
on several seeds in one process. Run by hand on the chip when a cell is
defined or its limits are looked at again; the benchmark's own runs never
run it. One JSON line per seed.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 8]

``--base-seeds 5,5,6`` gives each seed's window another schedule than the
mix's own (a serving mix fixes every request's due time and lengths by
its ``base_seed``): how the tails read on other bursts, for PERF.md.
"""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import device  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--precision", default="int8")
    ap.add_argument("--base-seeds", default=None)
    args = ap.parse_args(argv)
    _, cell, config, traffic, own = bench_run.find_cell(ROOT, args.workload)
    devices = device.require_tpu(cell["chips"])
    window = importlib.import_module(
        f"benchmark.harness.{traffic['kind']}_window")
    seeds = [int(s) for s in args.seeds.split(",")]
    bases = ([int(s) for s in args.base_seeds.split(",")]
             if args.base_seeds else [None] * len(seeds))
    for seed, base in zip(seeds, bases, strict=True):
        mix = traffic if base is None else dict(traffic, base_seed=base)
        ctx = bench_run.make_ctx(ROOT, args.workload, config, mix, own,
                                 devices, seed, args.seconds,
                                 control_precision=args.precision)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "base_seed": mix.get("base_seed"),
                          **window.control(ctx)}), flush=True)


if __name__ == "__main__":
    main()
