"""Per-layer metrics of the host's own time, read from inside the
program: the interpreter's garbage collections in the traced window, and
set-up split into the program's import and the building of its programs.

**Where the records come from** (``paddle_tpu/observability/spans.py``).
Beside the span tree the store keeps two lists of its own:

    host.gc           while a profiler session is on, and only then, one
                      record a collection of the interpreter (start and
                      end on ``time.perf_counter``; counts generation,
                      collected, uncollectable, thread), and a
                      ``host.gc`` annotation on the device trace;
                      ``spans.gc_records()``, emptied when a session
                      begins. The hook goes in when the store first sees
                      the session, at the first span after
                      ``start_trace``: a collection before that is missed
    setup.import      the body of ``paddle_tpu/__init__.py``
    setup.jit.<stage> one a ``jax.monitoring`` duration of building a
                      program (``trace``, ``lower``, ``compile``,
                      ``cache_load``), stamped when it ended;
                      ``spans.process_records()``, kept across sessions

A program without them (the parent of the PR that added them) has no
``spans.gc_records`` / ``spans.process_records``, and every reader here
returns None there. On the CPU rehearsal (``run["trace"]`` None) every
reader returns None too.

**The metrics** (``benchmark/metrics/{host,setup}.*.json`` name them):

    host.gc_share.train     seconds of host.gc inside [t_open, t_close)
                            of the training window's facts, over the
                            window, in percent
    host.gc_share.serve     the same over the traced serving window,
                            ``bench.window`` laid on ``perf_counter`` by
                            ``engine_spans``' offset
    host.gc_longest_ms.serve
                            the longest collection in that window (its
                            part inside it)
    host.gc_idle_share.serve
                            of the device's idle time in that window (the
                            first device's gaps between the union of its
                            ops, as ``engine_spans.idle_by_span`` takes
                            them), the share that lies under a host.gc
                            record, in percent
    setup.import_s          the setup.import record's duration
    setup.programs_s        the union of the setup.jit.* records that
                            ended before the store saw the traced session
                            begin: the time set-up spent building
                            programs. A union and not a sum, because a
                            fetch from the persistent cache runs inside
                            its ``compile`` and a nested jit's trace
                            inside its caller's; the programs built, the
                            cache hits and each stage's own sum go to
                            standard error

A share reads 0.0 where the program has the hook and nothing was
collected; none reads 0 for what it could not see.
"""

import sys

from benchmark.harness import trace_reduce
from benchmark.readers import engine_spans

GC = "host.gc"


def say(msg):
    print(f"[host] {msg}", file=sys.stderr, flush=True)


def program_spans(has):
    """The program's ``observability.spans`` where it has ``has``."""
    spans = engine_spans.program_spans()
    return spans if spans is not None and hasattr(spans, has) else None


def gc_intervals(lo, hi):
    """[(start, end)] of the host.gc records cut to [lo, hi)
    (``perf_counter`` seconds), sorted, or None where the program keeps
    no such records."""
    spans = program_spans("gc_records")
    if spans is None:
        return None
    out = sorted((max(r["start"], lo), min(r["end"], hi))
                 for r in spans.gc_records() if r["name"] == GC)
    return [(a, b) for a, b in out if b > a]


def gc_share_train(spec, run):
    facts = run["facts"]
    if run["trace"] is None or "t_open" not in facts:
        return None
    lo, hi = facts["t_open"], facts["t_close"]
    got = gc_intervals(lo, hi)
    if got is None:
        return None
    return 100.0 * sum(b - a for a, b in got) / (hi - lo)


def traced_window(run):
    """(lo, hi, [host.gc intervals inside], offset) of the traced serving
    window on ``perf_counter``, or None. Worked out and printed once a
    run."""
    if "host_gc" not in run:
        run["host_gc"] = None
        got = engine_spans.session(run)
        if got is not None:
            offset = got[1]
            lo, hi = (t / 1e9 + offset
                      for t in trace_reduce.window_of(run["trace"]))
            pauses = gc_intervals(lo, hi)
            if pauses is not None:
                run["host_gc"] = lo, hi, pauses, offset
                spent = [b - a for a, b in pauses]
                say(f"{len(spent)} collection(s) in the window of "
                    f"{hi - lo:.3f} s: {1e3 * sum(spent):.3f} ms in all, "
                    f"the longest {1e3 * max(spent, default=0.0):.3f} ms")
    return run["host_gc"]


def gc_share_serve(spec, run):
    got = traced_window(run)
    if got is None:
        return None
    lo, hi, pauses, _ = got
    return 100.0 * sum(b - a for a, b in pauses) / (hi - lo)


def gc_longest_ms_serve(spec, run):
    got = traced_window(run)
    if got is None:
        return None
    return 1e3 * max((b - a for a, b in got[2]), default=0.0)


def idle_gaps_ns(trace):
    """[(start_ns, end_ns)] of the first device's idle time inside the
    window: what lies between the union of its ops."""
    lo, hi = trace_reduce.window_of(trace)
    plane = trace_reduce.device_planes(trace)[0]
    busy = trace_reduce.union_intervals(trace_reduce.clip(
        trace_reduce.line_events(plane, trace_reduce.OPS_LINE), lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def gc_idle_share_serve(spec, run):
    got = traced_window(run)
    if got is None:
        return None
    _, _, pauses, offset = got
    gaps = idle_gaps_ns(run["trace"])
    idle = sum(b - a for a, b in gaps) / 1e9
    under = {}
    engine_spans.covered(gaps, [((a - offset) * 1e9, (b - offset) * 1e9, GC)
                                for a, b in pauses], under)
    say(f"device idle {1e3 * idle:.3f} ms in the window, "
        f"{1e3 * under.get(GC, 0.0):.3f} ms of it under a collection")
    return 100.0 * under.get(GC, 0.0) / idle if idle > 0 else 0.0


def import_s(spec, run):
    spans = program_spans("process_records")
    if run["trace"] is None or spans is None:
        return None
    got = [r for r in spans.process_records() if r["name"] == "setup.import"]
    return got[-1]["end"] - got[-1]["start"] if got else None


def programs_s(spec, run):
    spans = program_spans("process_records")
    if run["trace"] is None or spans is None:
        return None
    cut = spans.opened()
    jit = [r for r in spans.process_records()
           if r["name"].startswith("setup.jit.")
           and (cut is None or r["end"] <= cut)]
    stages = {}
    for r in jit:
        stages[r["name"]] = stages.get(r["name"], 0.0) + r["end"] - r["start"]
    union = trace_reduce.union_intervals(
        [(None, r["start"], r["end"] - r["start"]) for r in jit])
    built = sum(r["name"] == "setup.jit.compile" for r in jit)
    hits = sum(r["name"] == "setup.jit.cache_load" for r in jit)
    say(f"set-up built {built} program(s), {hits} of them from the "
        f"persistent cache, in {len(jit)} records; each stage's own sum: "
        + ", ".join(f"{name[len('setup.jit.'):]} {s:.3f} s"
                    for name, s in sorted(stages.items())))
    return sum(e - s for s, e in union)
