"""Per-layer metrics read from inside the program: the serving engine's
spans, lifecycle events and counts (PR 25), laid on the device trace's
clock.

**Where the records come from.** ``paddle_tpu.observability.spans``
keeps, while a profiler session is on and only then, one record for
every span and lifecycle event in a bounded in-memory store:

    {"id", "name", "path", "start", "end", "parent", "rid", "counts"}

``start`` / ``end`` are ``time.perf_counter`` seconds, ``parent`` the id
of the enclosing span, ``rid`` the request's id, ``counts`` what the span
counted at its boundary; an event has ``path`` None and ``start == end``.
The store empties itself when a session begins, so after a traced run
it holds that session's records (the window's, then the drain's) and
nothing else. The readers run in the benchmark's process after the
window and take ``spans.records()``; against a program that has no such
store (the parent of PR 25) they find nothing and return None.

**The engine's spans** (``paddle_tpu/serving/engine.py``):

    serve.submit          the body of submit() / adopt()           rid
    serve.step            the body of step(); counts pages_in_use,
                          pages_cached, num_pages
      serve.admit         shedding + the admission loop
        serve.prefill     one admission: staging, every chunk's
                          prefill call, publishing the prefix     rid
          serve.prefill.fetch   the sync after each chunk           rid
      serve.grow          page growth + preemption
      serve.decode        the decode step's dispatch
      serve.fetch         the one sync a round: the host waits
      serve.advance       the per-slot loop: tokens, retirement

and the events ``submitted``, ``admitted``, ``resumed``,
``prefill_done``, ``first_token``, ``preempted``, ``retired`` (``rid``).
What is left of ``serve.step`` after its children is its self time:
gauges, RunLog, watchdog.

**The clock.** ``serve_window.drive`` opens the profiler session and
closes ``bench.window`` between two engine steps (the session itself
ends after the drain: stopping the profiler takes seconds), so the
store's first ``serve.step`` records and the trace's ``bench.step``
annotations inside ``bench.window`` are the same rounds, one to one, in
order; the records after the window's close are the drain's and are
left out. ``pair_clocks`` pairs the k-th with the k-th, takes the
median of ``start(serve.step) - start(bench.step)`` as the offset
between ``perf_counter`` and the trace's clock (ns from the start of
the trace), and refuses if the store holds fewer steps than the window
has rounds, if a surplus step begins before the window's close, or if
any pair lies more than ``PAIR_TOLERANCE_S`` from that offset: the
records are then not this window's. Every reader here reads
only records that passed it, so a run without a device trace (a
rehearsal on the CPU) reads nothing.

**The metrics** (``benchmark/metrics/serve.*.json`` name the reader):

    serve.admit_wait_ms_p50   end of a request's serve.submit (it is in
                              the queue) -> its event admitted, median
                              over the session's requests: the wait in
                              the queue. A request that was queued when
                              the session opened, or was not admitted
                              when it closed, counts with the part of
                              its wait that the session saw (a lower
                              bound); the sample's size and the number
                              of such bounds go to standard error
    serve.prefill_ms_p50      duration of serve.prefill, median: what an
                              admission costs the host
    serve.round_host_ms_p50   per serve.step that ran a decode, its
                              duration less its serve.fetch and
                              serve.prefill.fetch descendants, median:
                              the host's own work in a round
    serve.idle_ms_per_round.<phase>
                              the device's idle time inside the window
                              (union of XLA Ops, as idle_share takes it)
                              split by what the host was in
                              (``idle_by_span``), over the session's
                              serve.step count: the rows that the
                              metric's file lists as ``under`` (admit,
                              decode, fetch, advance, step), or all but
                              those it lists as ``not_under`` (outside).
                              The six sum to device.idle_share.serve x
                              window_s / rounds; the whole table is
                              printed on standard error
    serve.kv_pool_in_use      mean over the serve.step records of
                              pages_in_use / num_pages, in percent

``idle_by_span`` gives every idle nanosecond to the innermost program
span that covers it (a span's own row holds its self time); what no
program span covers goes to ``outside step`` where a ``bench.*``
annotation covers it (``bench.step``'s own edges, ``bench.account``,
``bench.submit``, ``bench.sleep``) and to ``unattributed`` otherwise. An
idle gap usually runs from the end of one round's device work to the
start of the next one's, across several phases of the host, so it is
split and not put down whole to the phase that covers most of it.
"""

import statistics
import sys

from benchmark.harness import trace_reduce

#: a pair of (serve.step, bench.step) starts may lie this far from the
#: median offset of all pairs
PAIR_TOLERANCE_S = 0.2e-3
OUTSIDE, UNATTRIBUTED = "outside step", "unattributed"


def say(msg):
    print(f"[engine_spans] {msg}", file=sys.stderr, flush=True)


def program_spans():
    """``paddle_tpu.observability.spans``, or None where the program has
    no span store."""
    try:
        from paddle_tpu.observability import spans
    except ImportError:
        return None
    return spans if hasattr(spans, "records") else None


def program_records():
    spans = program_spans()
    return spans.records() if spans is not None else []


def named(records, name):
    return [r for r in records if r["name"] == name]


def pair_clocks(records, trace):
    """Seconds to take from a record's ``perf_counter`` time to stand on
    the trace's clock, or None (and why, on standard error) where the
    records cannot be this trace's window. The session outlives the
    window (the profiler stops after the drain, ``Tracer.close_window``),
    so the store may hold more steps than the window has rounds: its
    first steps are then the window's, and the others have to begin
    after the window's close."""
    lo, hi = trace_reduce.window_of(trace)
    bench = [s for s in trace_reduce.host_spans(trace, r"^bench\.step$")
             if s[1] >= lo and s[2] <= hi]
    every = named(records, "serve.step")
    steps, drain = every[:len(bench)], every[len(bench):]
    counts = (f"{len(every)} serve.step record(s) against {len(bench)} "
              f"bench.step annotation(s) in the window")
    if not steps or len(steps) != len(bench):
        say(f"no pairing: {counts}")
        return None
    diffs = [r["start"] - b[1] / 1e9 for r, b in zip(steps, bench)]
    offset = statistics.median(diffs)
    worst = max(abs(d - offset) for d in diffs)
    if worst > PAIR_TOLERANCE_S:
        say(f"no pairing: a serve.step lies {1e3 * worst:.3f} ms from the "
            f"median offset of {len(diffs)} pairs")
        return None
    if drain and (drain[0]["start"] - offset) * 1e9 < hi:
        say(f"no pairing: {counts}, and the next record begins before "
            f"the window's close")
        return None
    say(f"{len(diffs)} serve.step records paired with bench.step: offset "
        f"{offset:.6f} s, widest departure {1e6 * worst:.1f} us")
    return offset


def session(run):
    """(records, offset) of the traced window, or None. Worked out once
    a run and kept in ``run`` for the other readers."""
    if "engine_spans" not in run:
        run["engine_spans"] = read_session(run["trace"])
    return run["engine_spans"]


def read_session(trace):
    if trace is None:
        return None
    records = program_records()
    if not records:
        say("the program's span store is empty")
        return None
    offset = pair_clocks(records, trace)
    if offset is None:
        return None
    # the window's records alone: the loop closes ``bench.window``
    # between two engine steps, so no span straddles the close
    close = trace_reduce.window_of(trace)[1] / 1e9 + offset
    inside = [r for r in records if r["end"] <= close]
    if len(inside) < len(records):
        say(f"{len(records) - len(inside)} record(s) after the window's "
            f"close (the drain) left out")
    return inside, offset


def median_ms(seconds):
    return 1e3 * statistics.median(seconds) if seconds else None


# ---------------------------------------------------------------- readers

def admit_waits(records):
    """([seconds], number of them that are lower bounds): for every
    request that was in the queue during the session, from the end of
    its serve.submit span to its ``admitted`` event, cut to the session
    (its first record's start to its last one's end)."""
    queued, admitted, left = {}, {}, set()
    for r in records:
        if r["name"] == "serve.submit":
            queued[r["rid"]] = r["end"]
        elif r["path"] is None and r["name"] == "admitted":
            admitted[r["rid"]] = r["start"]
        elif r["path"] is None and r["name"] == "retired":
            left.add(r["rid"])
    opened = min(r["start"] for r in records)
    closed = max(r["end"] for r in records)
    waits, bounds = [], 0
    for rid, t in admitted.items():
        waits.append(t - queued.get(rid, opened))
        bounds += rid not in queued
    for rid, t in queued.items():
        if rid not in admitted and rid not in left:   # still queued
            waits.append(closed - t)
            bounds += 1
    return waits, bounds


def admit_wait_ms_p50(spec, run):
    got = session(run)
    if got is None:
        return None
    waits, bounds = admit_waits(got[0])
    say(f"serve.admit_wait_ms_p50 is the median of {len(waits)} waits, "
        f"{bounds} of them lower bounds (queued when the session opened "
        f"or not admitted when it closed)")
    return median_ms(waits)


def prefill_ms_p50(spec, run):
    got = session(run)
    if got is None:
        return None
    return median_ms([r["end"] - r["start"]
                      for r in named(got[0], "serve.prefill")])


def round_host_seconds(records):
    """For every serve.step that ran a decode: its duration less the
    time its descendants spent waiting for the device."""
    by_id = {r["id"]: r for r in records}

    def step_of(r):
        while r is not None and r["name"] != "serve.step":
            r = by_id.get(r["parent"])
        return r

    waited, decoded = {}, set()
    for r in records:
        if r["name"] in ("serve.fetch", "serve.prefill.fetch"):
            step = step_of(r)
            if step is not None:
                waited[step["id"]] = (waited.get(step["id"], 0.0)
                                      + r["end"] - r["start"])
        elif r["name"] == "serve.decode":
            decoded.add(r["parent"])
    return [s["end"] - s["start"] - waited.get(s["id"], 0.0)
            for s in named(records, "serve.step") if s["id"] in decoded]


def round_host_ms_p50(spec, run):
    got = session(run)
    if got is None:
        return None
    return median_ms(round_host_seconds(got[0]))


def kv_pool_in_use(spec, run):
    got = session(run)
    if got is None:
        return None
    counts = [s["counts"] for s in named(got[0], "serve.step")
              if s["counts"].get("num_pages")]
    if not counts:
        return None
    say(f"K/V pages over {len(counts)} rounds, mean: in use "
        f"{statistics.fmean(c['pages_in_use'] for c in counts):.1f}, kept "
        f"by the prefix cache for no running request "
        f"{statistics.fmean(c['pages_cached'] for c in counts):.1f}, of "
        f"{counts[0]['num_pages']}")
    return 100.0 * statistics.fmean(
        c["pages_in_use"] / c["num_pages"] for c in counts)


def idle_table(run):
    """({what the host was in: seconds of device idle time}, rounds) of
    the traced window, or None. Worked out and printed once a run."""
    got = session(run)
    if got is None:
        return None
    if "engine_spans_idle" not in run:
        records, offset = got
        trace = run["trace"]
        busy_s, window_s = trace_reduce.busy_and_window(trace)
        rounds = len(named(records, "serve.step"))
        table = idle_by_span(records, offset, trace)
        total = sum(table.values())
        say(f"device idle {1e3 * (window_s - busy_s):.3f} ms of a window "
            f"of {1e3 * window_s:.1f} ms over {rounds} rounds; by what the "
            f"host was in (sums to {1e3 * total:.3f} ms):")
        for name, s in sorted(table.items(), key=lambda kv: -kv[1]):
            say(f"  {name:<22}{1e3 * s:10.3f} ms{1e3 * s / rounds:9.3f} a "
                f"round{100 * s / total if total else 0:7.1f}%")
        run["engine_spans_idle"] = table, rounds
    return run["engine_spans_idle"]


def idle_ms_per_round(spec, run):
    """The table's rows that ``spec["under"]`` lists, or every row but
    those ``spec["not_under"]`` lists, in ms a round."""
    got = idle_table(run)
    if got is None:
        return None
    table, rounds = got
    if "under" in spec:
        rows = spec["under"]
    else:
        rows = [name for name in table if name not in spec["not_under"]]
    return 1e3 * sum(table.get(name, 0.0) for name in rows) / rounds


# ------------------------------------------------------------ attribution

def self_segments(records, offset):
    """[(start_ns, end_ns, name)] on the trace's clock: for every span
    the parts of its interval that no child span covers
    (``spans.self_segments``, the program's own reckoning), sorted."""
    name = {r["id"]: r["name"] for r in records}
    return sorted(((a - offset) * 1e9, (b - offset) * 1e9, name[i])
                  for i, own in program_spans().self_segments(records).items()
                  for a, b in own)


def covered(gaps, segments, total):
    """Add to ``total[name]`` the seconds of each gap that lie under each
    (sorted, non-overlapping) segment; returns what of the gaps no
    segment covers, as gaps again."""
    left, j = [], 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        cur, i = a, j
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            s, e = max(s, cur), min(e, b)
            if e > s:
                if s > cur:
                    left.append((cur, s))
                total[name] = total.get(name, 0.0) + (e - s) / 1e9
                cur = e
            i += 1
        if b > cur:
            left.append((cur, b))
    return left


def idle_by_span(records, offset, trace):
    """{what the host was in: seconds} over the device's idle time inside
    the window (the first device's, as ``trace_reduce.idle_gaps``)."""
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
    total = {}
    left = covered(gaps, self_segments(records, offset), total)
    bench = trace_reduce.union_intervals(
        [(n, s, e - s) for n, s, e in trace_reduce.host_spans(
            trace, r"^bench\.(?!window$)")])
    left = covered(left, [(s, e, OUTSIDE) for s, e in bench], total)
    if left:
        total[UNATTRIBUTED] = sum(b - a for a, b in left) / 1e9
    return total
