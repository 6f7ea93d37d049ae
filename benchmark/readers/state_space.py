"""Per-layer metrics of a model with recurrent (state-space) layers.

``scan_roofline``: a scan kernel's share of its roofline. The least time
the chip could take for the work the window NEEDED (the configuration's
``work`` module, from the facts the window counted: real prompt tokens
and prefill calls, or positions decoded and rounds), times the layers
that run the kernel, over the summed device time of the kernel's events
in the trace. Padding of a chunk and slots that run nothing are time
the kernel spends and work nobody needs: they lower the share. A trace
without such events (the parent of the PR that brought the kernels, any
other configuration) reads nothing.

``state_pool_in_use``: the share of the per-slot recurrent state that
running requests hold, ``state_bytes / state_bytes_reserved`` from the
``serve.step`` span's counts (``readers/engine_spans.py`` says where
those records come from and how they are laid on the trace's clock),
mean over the session's rounds. A program whose ``serve.step`` carries
no such counts, or a model without state (reserved 0), reads nothing.
"""

import importlib
import statistics

from benchmark.harness import flops, trace_reduce
from benchmark.readers import engine_spans


def scan_roofline(spec, run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = trace_reduce.op_durations(run["trace"], spec["pattern"])
    facts = [run["facts"].get(name) for name in spec["facts"]]
    if not durs or not all(facts):
        return None
    work = importlib.import_module(spec["work_module"])
    cfg = run["config"]
    ops, nbytes = getattr(work, spec["work"])(cfg, *facts)
    t_min, _ = flops.least_seconds(ops, nbytes, run["peaks"])
    layers, _ = work.layer_counts(cfg)
    return 100.0 * t_min * layers / sum(durs)


def state_pool_in_use(spec, run):
    got = engine_spans.session(run)
    if got is None:
        return None
    counts = [s["counts"] for s in engine_spans.named(got[0], "serve.step")
              if s["counts"].get("state_bytes_reserved")]
    if not counts:
        return None
    engine_spans.say(
        f"recurrent state over {len(counts)} rounds, mean: "
        f"{statistics.fmean(c['state_slots'] for c in counts):.1f} slots "
        f"hold {statistics.fmean(c['state_bytes'] for c in counts) / 1e6:.1f}"
        f" MB of {counts[0]['state_bytes_reserved'] / 1e6:.1f} MB reserved")
    return 100.0 * statistics.fmean(
        c["state_bytes"] / c["state_bytes_reserved"] for c in counts)
