"""From the profiler's trace to numbers: device busy and idle time, a
kernel's summed time, a program's runs, the longest idle gaps by what
the host was doing.

The reduction works on a neutral form, so that a small recorded trace
can be kept as a JSON fixture and every PR computes the same number the
same way:

    {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, start_ns, dur_ns], ...]}]}]}

``load_xplane`` makes that form from the ``.xplane.pb`` the JAX profiler
writes, with nothing but JAX (``jax.profiler.ProfileData``).

What a v5e trace looks like (looked at by hand in PR 24, see PERF.md):
one plane per chip ``/device:TPU:<n>`` with the lines ``XLA Modules``
(one event per run of a compiled program, named ``jit_<fn>(<id>)``),
``XLA Ops`` (one event per HLO op run, named by the op's whole HLO text,
``%mlp.4 = bf16[8192,1024]... custom-call(...)``: a kernel's own name is
what stands before `` = ``, and other ops' texts mention it as an
operand, so patterns are matched against that own name only; ``%while``
ops contain their bodies' events, so sums are taken over leaves and busy
time over the union), ``Async XLA Ops`` (copies in flight, not counted
as busy) and ``Steps``; the host is ``/host:CPU`` with one line per
thread, where ``TraceAnnotation`` spans land on the line ``python3``.
All planes share one clock (ns from the start of the trace).
"""

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: an op's HLO text is kept this far: its own name and its result's shape
NAME_CHARS = 120
#: the longest label of an op in the breakdown
OP_LABEL_CHARS = 64
#: ops that only contain other ops' events
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, keep_host=re.compile(r"^bench\.|^(ingest|stage|step)$")):
    """The neutral form of one ``.xplane.pb``. Device planes keep the
    events of their ops and modules lines that begin before the end of
    ``bench.window`` (all of them in a trace without one); host planes
    keep only the benchmark's and the program's span annotations
    (``keep_host``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    # the host planes first: a serving run's trace goes on through the
    # drain (``Tracer.close_window``), every reader cuts at the end of
    # ``bench.window``, and most of loading is the device events' names
    planes, close_ns = [], None
    for plane in ([p for p in data.planes if p.name.startswith("/host:")]
                  + [p for p in data.planes if DEVICE_PLANE.match(p.name)]):
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if is_dev:
                    if close_ns is not None and ev.start_ns > close_ns:
                        continue
                elif not keep_host.search(ev.name):
                    continue
                elif ev.name == "bench.window":
                    close_ns = ev.start_ns + ev.duration_ns
                events.append([ev.name[:NAME_CHARS], int(ev.start_ns),
                               int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path):
    """A trace kept in the neutral form (``.json`` or ``.json.gz``)."""
    import gzip
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def cut(trace, lo_ns, hi_ns):
    """The events that lie wholly inside [lo_ns, hi_ns]: how a recorded
    trace is cut down to a fixture."""
    planes = []
    for p in trace["planes"]:
        lines = []
        for line in p["lines"]:
            ev = [e for e in line["events"]
                  if e[1] >= lo_ns and e[1] + e[2] <= hi_ns]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------- selection

def device_planes(trace):
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(trace, pattern):
    """Every host annotation whose name matches, as (name, start, end)."""
    rx = re.compile(pattern)
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            out.extend((n, s, s + d) for n, s, d in line["events"]
                       if rx.search(n))
    return sorted(out, key=lambda e: e[1])


def window_of(trace):
    """(start_ns, end_ns) of the traced window: the benchmark's own
    ``bench.window`` annotation where the trace has it, else the span of
    the device's events."""
    spans = host_spans(trace, r"^bench\.window$")
    if spans:
        return spans[0][1], spans[-1][2]
    starts, ends = [], []
    for p in device_planes(trace):
        for line in p["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device event")
    return min(starts), max(ends)


def clip(events, lo, hi):
    """Events cut to [lo, hi]."""
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


# ------------------------------------------------------------ reductions

def union_intervals(events):
    """Merged [start, end] intervals of (name, start, dur) events."""
    merged = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_and_window(trace):
    """(busy_s averaged over the chips, window_s): the union of the
    intervals in which an op ran on each device, inside the window."""
    lo, hi = window_of(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = []
    for p in planes:
        ivs = union_intervals(clip(line_events(p, OPS_LINE), lo, hi))
        busy.append(sum(e - s for s, e in ivs))
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def own_name(name):
    """'%mlp.4 = bf16[...] custom-call(...)' -> 'mlp.4'."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_durations(trace, pattern):
    """Durations (s) of every device op event wholly inside the window
    whose OWN name (``own_name``) matches ``pattern``."""
    rx = re.compile(pattern)
    lo, hi = window_of(trace)
    out = []
    for p in device_planes(trace):
        for n, s, d in line_events(p, OPS_LINE):
            if s >= lo and s + d <= hi and rx.search(own_name(n)):
                out.append(d / 1e9)
    return out


def module_runs(trace, pattern):
    """(start_s, dur_s) of every run of the compiled programs whose
    name matches, wholly inside the window, on the first device."""
    rx = re.compile(pattern)
    lo, hi = window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    return [(s / 1e9, d / 1e9) for n, s, d in line_events(planes[0],
                                                          MODULES_LINE)
            if rx.search(n) and s >= lo and s + d <= hi]


def op_label(name):
    """'%copy.1133 = bf16[1024,16,64,64]{3,1,2,0:T(8,128)} copy(...)' ->
    'copy bf16[1024,16,64,64]': the kind of op and the shape of its
    (first) result, so that the 48 copies of 48 page pools are one row
    and two fusions of different shapes are two."""
    kind = short_name(name)
    if " = " not in name:
        return kind
    result = name.split(" = ", 1)[1].split("{", 1)[0].split(" ", 1)[0]
    return f"{kind} {result.lstrip('(')}"[:OP_LABEL_CHARS]


def top_device_ops(trace, k=10):
    """[[label, seconds], ...]: the ops that took most device time in the
    window, summed by ``op_label`` and averaged over the chips."""
    lo, hi = window_of(trace)
    total = {}
    for p in device_planes(trace):
        for n, s, d in clip(line_events(p, OPS_LINE), lo, hi):
            if CONTAINERS.match(own_name(n)):
                continue
            key = op_label(n)
            total[key] = total.get(key, 0.0) + d / 1e9
    n_dev = max(1, len(device_planes(trace)))
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_dev] for n, t in rows]


def short_name(name):
    """'%fusion.123 = ...' -> 'fusion': the kind of op, for a summary."""
    return re.sub(r"(\.\d+)+$", "", own_name(name)) or name


def idle_gaps(trace, k=10):
    """[[what the host was doing, seconds], ...]: the device's idle time
    inside the window, attributed to the host annotation (``bench.*`` or
    a program span) that covers most of each gap, summed by annotation,
    the longest first. A gap no annotation covers is 'unattributed'."""
    lo, hi = window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    ivs = union_intervals(clip(line_events(planes[0], OPS_LINE), lo, hi))
    gaps, cur = [], lo
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [sp for sp in host_spans(trace, r".") if sp[0] != "bench.window"]
    total = {}
    j = 0
    for a, b in gaps:
        while j < len(spans) and spans[j][2] <= a:
            j += 1
        best, best_cover = "unattributed", 0
        i = j
        while i < len(spans) and spans[i][1] < b:
            cover = min(b, spans[i][2]) - max(a, spans[i][1])
            if cover > best_cover:
                best, best_cover = spans[i][0], cover
            i += 1
        total[best] = total.get(best, 0.0) + (b - a) / 1e9
    return [[n, t] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
