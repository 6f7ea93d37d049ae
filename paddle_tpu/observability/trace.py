"""Fleet-wide distributed tracing: durable trace contexts and the
clock-skew-corrected cross-replica timeline merge.

The serving fleet re-homes requests across engine replicas — dispatch,
``adopt()`` failover, deploy drains, re-admission after crash recovery —
and before this module every hop re-minted the engine-run-scoped trace
id, so no single id covered a request's life. Here the ROUTER mints the
durable context (``trace_id`` / ``span_id`` / ``parent_span_id``) once
at ``FleetRouter.submit()``; the context rides the ``FleetRequest``
through every dispatch path (including the subprocess JSON wire) and
lands in ``engine.adopt()``, which stamps it on the engine-local
request instead of minting a fresh one. Every trace event then carries
the same ``trace`` across replicas, plus ``replica`` and
``model_version`` tags identifying who served the hop.

Merging is the second half: each process's RunLog event times come off
``time.perf_counter()`` — monotonic, but with a per-process epoch — so
per-replica logs cannot be interleaved by raw ``t``. Every RunLog
therefore opens with an ANCHOR record pairing one ``time.time()`` wall
reading with one ``perf_counter()`` reading taken back-to-back;
``merge_fleet_trace`` rebases each log's events onto the wall clock via
its anchor offset and returns one causally ordered timeline plus a
skew report. Rendering lives in ``tools/run_report.py --fleet-trace``.

Everything here is host-side stdlib: no jax imports, no device work —
the ``hot-path-sync`` lint runs over this module.
"""

import os
import threading
import time
import uuid

from paddle_tpu.observability import flight

# --------------------------------------------------------------------------
# event catalog
# --------------------------------------------------------------------------

# Every event kind the trace plane writes — engine ``_trace_event``
# sites and flight-ring ``note_event`` sites. The ``event-drift``
# graft-lint rule checks this dict against the literal call sites in
# both directions: an unregistered emit is invisible to the collector's
# consumers, and a registered kind with no emitter documents nothing.
EVENTS = {
    "adopted": "request adopted by an engine (fleet dispatch, failover "
               "re-route, or drain re-admission)",
    "admitted": "request admitted to a decode slot for its first prefill",
    "anchor": "per-process wall/monotonic clock anchor (skew correction)",
    "anomaly": "watchdog anomaly observed by the flight recorder",
    "first_token": "first generated token left the engine",
    "flight_dump": "flight-recorder bundle dump started",
    "prefill_done": "prompt (+ replayed tokens) fully prefilled",
    "preempted": "running request preempted back to the queue",
    "requeued": "request returned to the queue after a recovery",
    "resumed": "preempted/recovered request re-admitted to a slot",
    "retired": "request reached a terminal status",
    "span": "host-side span completion linked into the active context",
    "submitted": "request accepted (engine-local or fleet submit)",
}


# --------------------------------------------------------------------------
# trace context
# --------------------------------------------------------------------------


class TraceContext:
    """One hop's identity inside a trace: the durable ``trace_id`` plus
    this hop's ``span_id`` and its causal parent. Contexts are value
    objects — ``child()`` derives the next hop, ``to_wire()`` /
    ``from_wire()`` cross the subprocess JSON exchange."""

    __slots__ = ("trace_id", "span_id", "parent_span_id")

    def __init__(self, trace_id, span_id="root", parent_span_id=None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id

    def child(self, span_id):
        return TraceContext(self.trace_id, span_id,
                            parent_span_id=self.span_id)

    def to_wire(self):
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_span_id": self.parent_span_id}

    @classmethod
    def from_wire(cls, wire):
        if not wire or not wire.get("trace_id"):
            return None
        return cls(wire["trace_id"], wire.get("span_id", "root"),
                   wire.get("parent_span_id"))

    def __repr__(self):
        return (f"TraceContext({self.trace_id!r}, {self.span_id!r}, "
                f"parent={self.parent_span_id!r})")


def mint_run():
    """Short run id prefixing every trace id minted by one process
    (router or standalone engine) — collision-safe across restarts."""
    return uuid.uuid4().hex[:8]


# thread-local stack of active contexts: the Trainer (and tools)
# activate a context around a region so span completions link into it
_TLS = threading.local()


def current():
    """The innermost active TraceContext on this thread, else None."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


class activate:
    """``with trace.activate(ctx):`` — installs ``ctx`` as the thread's
    active trace context for the duration; nests."""

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        stack.append(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        _TLS.stack.pop()
        return False


def note_span(name, dt):
    """Link a completed host-side span into the active context by
    feeding the flight ring (a bounded deque append — no I/O). Called
    from ``spans.span()``'s exit path; returns fast when the flight
    recorder is off."""
    rec = flight.recorder()
    if rec is None:
        return
    ctx = current()
    rec.note_event("span", name=name, dt=dt,
                   trace=ctx.trace_id if ctx else None,
                   span=ctx.span_id if ctx else None)


# --------------------------------------------------------------------------
# clock anchors + the cross-replica merge
# --------------------------------------------------------------------------


def anchor_record(**tags):
    """One wall/monotonic clock pair taken back-to-back, tagged with
    the writing process — the per-RunLog record ``merge_fleet_trace``
    uses to rebase that log's monotonic event times onto the wall
    clock."""
    return dict(anchor=dict(wall=time.time(), mono=time.perf_counter()),
                pid=os.getpid(), **tags)


def write_anchor(run_log, **tags):
    """Write an anchor record to ``run_log`` (and mirror it into the
    flight ring when recording). Safe to call with run_log=None."""
    rec = anchor_record(**tags)
    if run_log is not None:
        run_log.write(rec)
    fl = flight.recorder()
    if fl is not None:
        fl.note_event("anchor", wall=rec["anchor"]["wall"],
                      mono=rec["anchor"]["mono"], pid=rec["pid"])
    return rec


def _anchor_offset(records):
    """wall - mono from the log's first anchor record, else None."""
    for rec in records:
        a = rec.get("anchor")
        if isinstance(a, dict) and "wall" in a and "mono" in a:
            return float(a["wall"]) - float(a["mono"])
    return None


def merge_fleet_trace(record_lists):
    """Merge per-replica RunLog record lists into one causally ordered
    timeline.

    ``record_lists`` maps a source name (e.g. ``"r0"``) to that log's
    records (as from ``runlog.read_records``). Each log's trace events
    (records with an ``event`` key) are rebased onto the wall clock via
    the log's anchor offset; a log without an anchor keeps raw times
    and is called out in the skew report rather than silently mixed in.

    Returns ``{"events": [...], "skew": {...}}`` where every event
    gains ``source`` (which log) and ``wall_t`` (corrected time), and
    ``skew`` reports each source's anchor offset plus the spread of
    wall-clock epochs ("skew_s" is relative to the earliest-anchored
    source — large values mean the logs disagree about when 'now' is).
    """
    offsets = {src: _anchor_offset(recs)
               for src, recs in record_lists.items()}
    anchored = {s: o for s, o in offsets.items() if o is not None}
    base = min(anchored.values()) if anchored else 0.0
    events = []
    for src, recs in record_lists.items():
        off = offsets[src]
        for rec in recs:
            if "event" not in rec or "t" not in rec:
                continue
            ev = dict(rec)
            ev["source"] = src
            ev["wall_t"] = (float(rec["t"]) + off if off is not None
                            else float(rec["t"]))
            events.append(ev)
    events.sort(key=lambda e: (e["wall_t"], e["source"]))
    skew = {src: dict(offset=off,
                      skew_s=(off - base if off is not None else None),
                      anchored=off is not None)
            for src, off in offsets.items()}
    return {"events": events, "skew": skew}


def group_by_trace(events):
    """{trace_id: [events...]} preserving merged order; events with no
    trace stamp group under None."""
    out = {}
    for ev in events:
        out.setdefault(ev.get("trace"), []).append(ev)
    return out
