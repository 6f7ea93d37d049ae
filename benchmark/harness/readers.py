"""The per-layer metrics' readers. A metric is one file
``benchmark/metrics/<name>.json`` that names a reader
(``"module:function"``) and gives it its arguments; the reader gets that
file's dict and the run's outcome (``trace`` in the neutral form of
``trace_reduce``, the window's ``facts``, ``config``, ``traffic``,
``peaks``) and returns a number, or None where it finds nothing to read:
the harness then leaves the metric out of the line. No reader returns 0
for a share it could not measure.

A later PR adds a reader as a new module (say ``benchmark/readers/x.py``)
and names it in its metric's file; nothing here is edited.
"""

import statistics

from benchmark.harness import flops, trace_reduce


def fact(spec, run):
    """A number the window counted itself (``facts[spec["fact"]]``),
    times ``scale``."""
    value = run["facts"].get(spec["fact"])
    if value is None:
        return None
    return value * spec.get("scale", 1.0)


def fact_per_step(spec, run):
    """A summed fact over the window's steps."""
    steps = run["facts"].get("steps")
    value = run["facts"].get(spec["fact"])
    if not steps or value is None:
        return None
    return value * spec.get("scale", 1.0) / steps


def module_ms_p50(spec, run):
    """Median device duration (ms) of the runs of one compiled program."""
    if run["trace"] is None:
        return None
    runs = trace_reduce.module_runs(run["trace"], spec["module"])
    if not runs:
        return None
    return 1e3 * statistics.median(d for _, d in runs)


def idle_share(spec, run):
    """1 - busy / window, in percent, from the device trace."""
    if run["trace"] is None:
        return None
    busy, window = trace_reduce.busy_and_window(run["trace"])
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


def mfu_train(spec, run):
    """Operations the model needs (forward + backward, from shapes, no
    recomputation) for the steps the trace holds whole, over the time
    from the first one's start to the last one's end and the chip's
    peak."""
    if run["trace"] is None or run["peaks"] is None:
        return None
    runs = trace_reduce.module_runs(run["trace"], spec["module"])
    if len(runs) < 2:
        return None
    t = run["traffic"]
    per_token = flops.bert_train_flops_per_token(
        run["config"], t["seq"], t["masked_per_row"])
    span = runs[-1][0] + runs[-1][1] - runs[0][0]
    ops = per_token * run["facts"]["tokens_per_step"] * len(runs)
    return 100.0 * ops / span / run["peaks"]["bf16_flops_per_s"]


def kernel_roofline(spec, run):
    """Least time the chip could take for the kernel's calls (operations
    and bytes from ``flops.py`` at the cell's shapes) over the summed
    device time of its events, in percent. ``kernels`` maps an event-name
    pattern to ``[work function in flops.py, its arguments by name]``;
    arguments that are strings are looked up in the traffic and the
    configuration."""
    if run["trace"] is None or run["peaks"] is None:
        return None
    least = spent = 0.0
    for pattern, (fn, args) in spec["kernels"].items():
        durs = trace_reduce.op_durations(run["trace"], pattern)
        if not durs:
            continue
        ops, nbytes = getattr(flops, fn)(**resolve(args, run))
        t_min, _ = flops.least_seconds(ops, nbytes, run["peaks"])
        least += t_min * len(durs)
        spent += sum(durs)
    if spent <= 0:
        return None
    return 100.0 * least / spent


def resolve(args, run):
    """Shape arguments by name: ``"batch*seq"`` multiplies looked-up
    names; a name is found in the traffic, then the configuration, then
    the facts."""
    out = {}
    for k, v in args.items():
        if isinstance(v, str):
            value = 1
            for part in v.split("*"):
                part = part.strip()
                for src in (run["traffic"], run["config"], run["facts"]):
                    if part in src:
                        value = value * src[part]
                        break
                else:
                    value = value * float(part)
            v = value
        out[k] = v
    return out


def ops_share_of_peak(spec, run):
    """Operations the model needed in the traced window (a fact the
    window counted from shapes with ``flops.py``) over that window's
    seconds and the chip's peak, in percent: the whole step's share,
    beside the kernels' rooflines."""
    ops = run["facts"].get(spec["ops"])
    if not ops or run["trace"] is None or run["peaks"] is None:
        return None
    busy, window = trace_reduce.busy_and_window(run["trace"])
    if busy <= 0 or window <= 0:
        return None
    return 100.0 * ops / window / run["peaks"]["bf16_flops_per_s"]


def kernel_roofline_total(spec, run):
    """As ``kernel_roofline``, for a kernel whose work changes from call
    to call: the work function gets the WINDOW's total (a fact), times
    ``times`` (say, the layers that each run it), against the summed
    device time of the kernel's events."""
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = trace_reduce.op_durations(run["trace"], spec["pattern"])
    fn, args = spec["work"]
    args = resolve(args, run)
    if not durs or not all(args.values()):
        return None
    ops, nbytes = getattr(flops, fn)(**args)
    t_min, _ = flops.least_seconds(ops, nbytes, run["peaks"])
    times = resolve({"n": spec.get("times", 1)}, run)["n"]
    return 100.0 * t_min * times / sum(durs)
