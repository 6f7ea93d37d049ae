"""The benchmark's command: one cell, one seed, one window, one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is found by name, and nothing here lists a
cell, a configuration, a traffic mix or a metric:

    BENCHMARK.json workloads[<cell>]            -> config, traffic, chips
    BENCHMARK.json configs[<config>].file       -> the sizes as they are run
    benchmark/cells/<cell>.json                 -> what is this cell's alone:
                                                   the limits of ``correct``,
                                                   a serving cell's rate
    benchmark/traffic/<traffic>.json            -> the mix; its "kind" picks
    benchmark/harness/<kind>_window.py          -> the driver of the window
    benchmark/metrics/<per-layer metric>.json   -> that metric's reader

The last line of standard output is the result (see README.md). The
run fails, and prints no result, on any backend but a TPU.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg):
    print(f"[bench {time.perf_counter() - T_PROCESS:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(root, workload):
    """(bench, cell, config, traffic, own) for a cell's name: its entry
    in BENCHMARK.json, its configuration, its traffic mix, and the file
    of what is the cell's own."""
    bench = read_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(root, entry["file"])
    traffic = read_json(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    own = read_json(root, "benchmark", "cells", workload + ".json")
    return bench, cell, config, traffic, own


def metrics_of(bench, cell_name, group):
    """The metrics of ``group`` that this cell reports: those with no
    ``workloads`` key, or with the cell in it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(spec):
    module, name = spec["reader"].split(":")
    return getattr(importlib.import_module(module), name)


def read_per_layer(root, bench, cell_name, run):
    """{name: {"value", "unit"}} for every per-layer metric of the cell
    whose reader found something to read."""
    out = {}
    for m in metrics_of(bench, cell_name, "per_layer"):
        spec = read_json(root, "benchmark", "metrics", m["name"] + ".json")
        value = load_reader(spec)(spec, run)
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def make_ctx(root, workload, config, traffic, own, devices, seed, seconds,
             trace=False, keep_trace=False, t_process=None, **more):
    """What a window function is handed: the cell, the seed, the clock's
    origin, the compile cache (turned on here), the compile counter and
    the tracer. ``sweep.py`` and ``control.py`` build theirs here too."""
    from benchmark.harness import device, tracing
    return {
        "workload": workload, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "config": config, "traffic": traffic,
        "cell": own, "devices": devices, "root": root, "log": log,
        "t_process": time.perf_counter() if t_process is None else t_process,
        "cache_dir": device.enable_compile_cache(root),
        "compiles": device.CompileCounter(),
        "tracer": tracing.Tracer(root, workload, keep=keep_trace),
        "describe": lambda: device.describe(devices), **more}


def run_cell(workload, seed, seconds, trace, root=ROOT, need_chip=True,
             keep_trace=False, t_process=T_PROCESS):
    """Run one cell and return the result line as a dict. ``need_chip``
    is False only in the tests that drive the rest of a run on the CPU."""
    from benchmark.harness import compare, device, peaks

    bench, cell, config, traffic, own = find_cell(root, workload)
    window = importlib.import_module(
        f"benchmark.harness.{traffic['kind']}_window")
    # the program is imported BEFORE JAX takes the chip: its import is
    # most of a run's set-up, and it takes longer beside the chip's
    # runtime threads (PERF.md section 6, PR 24)
    importlib.import_module(config["constructor"]["model"].split(":")[0])
    log("program imported")
    import jax
    devices = (device.require_tpu(cell["chips"]) if need_chip
               else jax.devices()[:cell["chips"]])
    ctx = make_ctx(root, workload, config, traffic, own, devices, seed,
                   seconds, trace, keep_trace=keep_trace,
                   t_process=t_process)
    compiles = ctx["compiles"]
    log(f"{workload} seed {seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {ctx['cache_dir']}")
    out = window.run(ctx)
    log(f"programs built: {compiles.compiles}, of them from the compile "
        f"cache: {compiles.cache_hits}")
    if out["programs_built_in_window"]:
        raise SystemExit(f"{out['programs_built_in_window']} program(s) "
                         f"were built inside the measured window: the "
                         f"warm-up misses a shape")

    rows, correct = compare.judge(out["numbers"], own["limits"])
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    dev = dict(out["device"])
    if trace:
        from benchmark.harness import trace_reduce
        t = ctx["tracer"].load()
        if not trace_reduce.device_planes(t):
            if need_chip:
                raise SystemExit("the trace holds no device plane")
            t = None                      # a rehearsal on the CPU
        else:
            dev["busy_s"], dev["window_s"] = trace_reduce.busy_and_window(t)
        run = {"trace": t, "facts": out["facts"], "config": config["shapes"],
               "traffic": traffic,
               "peaks": None if not need_chip
               else peaks.peaks_for(devices[0].device_kind)}
        result["metrics"] = read_per_layer(root, bench, workload, run)
        result["device"] = dev
        if t is not None:
            result["breakdown"] = {
                "device_ops": trace_reduce.top_device_ops(t),
                "idle_gaps": trace_reduce.idle_gaps(t)}
    else:
        values = dict(out["e2e"], setup_s=ctx["setup_s"])
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in metrics_of(bench, workload, "end_to_end")}
        result["device"] = dev
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim, _ in rows}
    for n, v, lim, ok in rows:
        print(f"compared {n} = {v!r} limit {lim!r} "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .bench_trace")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
