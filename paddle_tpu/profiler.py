"""Profiling — trace collection + op-level annotation.

Ref: /root/reference/paddle/fluid/platform/profiler.h:81 (RAII RecordEvent
around every op run), :166 EnableProfiler/DisableProfiler with sorted event
tables, CUPTI DeviceTracer → chrome-trace (device_tracer.cc, tools/
timeline.py), and the Python context manager
python/paddle/fluid/profiler.py.

TPU-first: jax.profiler (XPlane) replaces CUPTI — traces open in
TensorBoard/Perfetto; `record_event` maps to TraceAnnotation so framework-
level scopes show up inside device traces; a light host-side EventRecorder
keeps the reference's sorted-table text report.
"""

import contextlib
import os
import time
from collections import defaultdict

import jax

from paddle_tpu.core.flags import get_flag


@contextlib.contextmanager
def profiler(output_dir=None):
    """ref: fluid.profiler.profiler context manager — wraps a region,
    writes a TensorBoard/Perfetto trace."""
    out = output_dir or get_flag("profiler_dir")
    jax.profiler.start_trace(out)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()


def record_event(name):
    """RAII op annotation (ref: platform/profiler.h:81 RecordEvent).
    Shows up as a named range in the XPlane trace."""
    return jax.profiler.TraceAnnotation(name)


def span(name, rid=None):
    """record_event promoted: the registry-backed span
    (observability/spans.py) — times the scope into a metrics histogram
    AND the device trace, and records it while a profiler session is
    on."""
    from paddle_tpu.observability.spans import span as _span
    return _span(name, rid)


def annotate_fn(name):
    def deco(fn):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return wrapped
    return deco


class EventRecorder:
    """Host-side timing table (ref: profiler.cc event tables printed by
    DisableProfiler). Times python-visible spans (incl. dispatch+block).

    `add()` is the non-context entry, `reset()` starts a fresh epoch
    (state is otherwise append-forever: observability.span() no longer
    feeds one), and summary/report carry p50/p95 alongside min/max —
    the tail is where step-time regressions live."""

    def __init__(self):
        self._events = defaultdict(list)

    @contextlib.contextmanager
    def record(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def add(self, name, seconds):
        """Record one externally-timed occurrence of `name`."""
        self._events[name].append(seconds)

    def reset(self):
        """Drop all recorded events (ref: ResetProfiler)."""
        self._events.clear()

    @staticmethod
    def _pctl(sorted_times, q):
        idx = (len(sorted_times) - 1) * q
        lo, hi = int(idx), min(int(idx) + 1, len(sorted_times) - 1)
        frac = idx - lo
        return sorted_times[lo] * (1.0 - frac) + sorted_times[hi] * frac

    def summary(self, sort_by="total"):
        rows = []
        for name, times in self._events.items():
            ts = sorted(times)
            rows.append({
                "name": name, "calls": len(times),
                "total_s": sum(times),
                "avg_ms": 1e3 * sum(times) / len(times),
                "min_ms": 1e3 * ts[0], "max_ms": 1e3 * ts[-1],
                "p50_ms": 1e3 * self._pctl(ts, 0.50),
                "p95_ms": 1e3 * self._pctl(ts, 0.95),
            })
        rows.sort(key=lambda r: -r["total_s"])
        return rows

    def report(self):
        return event_table(self.summary())


def event_table(rows):
    """The sorted text table of summary rows (EventRecorder.summary's,
    or observability.span_summary's)."""
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(ms)':>12}"
             f"{'p50(ms)':>12}{'p95(ms)':>12}"
             f"{'Min(ms)':>12}{'Max(ms)':>12}"]
    for r in rows:
        lines.append(f"{r['name']:<40}{r['calls']:>8}{r['total_s']:>12.4f}"
                     f"{r['avg_ms']:>12.3f}{r['p50_ms']:>12.3f}"
                     f"{r['p95_ms']:>12.3f}{r['min_ms']:>12.3f}"
                     f"{r['max_ms']:>12.3f}")
    return "\n".join(lines)


def trace_op_table(trace_dir, device_filter="TPU", top=30, steps=1):
    """Aggregate a jax.profiler trace into a per-op duration table.

    Ref: the reference's EnableProfiler/DisableProfiler sorted event tables
    (platform/profiler.h:166, profiler.cc) and tools/timeline.py — here the
    source is the XPlane chrome-trace JSON that jax.profiler writes.

    trace_dir: the directory passed to jax.profiler.trace / pt.profiler.
    device_filter: substring of the process/device lane name to aggregate
    ("TPU" for device ops; "CPU" on the host platform; None = every
    lane, including events whose pid has no process_name metadata —
    some XPlane exports name only a subset of lanes).
    steps: divide totals by this to report per-step time.

    Returns a list of {"name", "total_us", "per_step_us", "count"} sorted
    by time, truncated to `top` (None = all).
    """
    import collections
    import glob
    import gzip
    import json

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz")))
    if not files:
        raise FileNotFoundError(
            f"no trace.json.gz under {trace_dir}/plugins/profile/")
    with gzip.open(files[-1]) as f:
        data = json.load(f)
    ev = data.get("traceEvents", [])
    # metadata events may carry no "args" dict at all (observed in real
    # XPlane exports) — e.get("args", {}) instead of e["args"], and a
    # lane without a pid key is simply unnamed
    lanes = {e.get("pid"): e.get("args", {}).get("name", "")
             for e in ev if e.get("ph") == "M"
             and e.get("name") == "process_name"}
    dur = collections.Counter()
    cnt = collections.Counter()
    for e in ev:
        if e.get("ph") != "X" or "name" not in e:
            continue
        if device_filter is not None:
            # events whose pid never got a process_name lane fall
            # through as "" — they match only an empty/None filter
            if device_filter not in lanes.get(e.get("pid"), ""):
                continue
        dur[e["name"]] += e.get("dur", 0)
        cnt[e["name"]] += 1
    rows = [{"name": n, "total_us": d, "per_step_us": d / max(steps, 1),
             "count": cnt[n]} for n, d in dur.most_common(top)]
    return rows


def print_op_table(trace_dir, **kw):
    """Human-readable twin of trace_op_table (the reference's profiler
    report print)."""
    rows = trace_op_table(trace_dir, **kw)
    width = max((len(r["name"]) for r in rows), default=10)
    print(f"{'op':<{width}}  {'total_us':>12}  {'per_step':>10}  {'count':>6}")
    for r in rows:
        print(f"{r['name']:<{width}}  {r['total_us']:>12.0f}  "
              f"{r['per_step_us']:>10.1f}  {r['count']:>6d}")
    return rows
