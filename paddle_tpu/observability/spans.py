"""Trace spans — nestable host-side scopes on the device trace's clock.

Ref: /root/reference/paddle/fluid/platform/profiler.h:81 — the RAII
``RecordEvent`` the reference wrapped around every op run, feeding both
the sorted event tables (profiler.h:166) and the chrome-trace timeline
(tools/timeline.py). Here one ``span()`` feeds, always:

  * a `span.<path>` Histogram in the metrics registry (p50/p95 land in
    RunLog final snapshots, on /metrics, and in `span_report()`), and
  * the flight ring's ``span`` event;

and, WHILE A PROFILER SESSION IS ON (`TraceAnnotation.is_enabled()`,
read once at entry) and only then:

  * a `jax.profiler.TraceAnnotation`, so the scope shows up as a named
    range inside an XPlane trace next to the device ops it contains
    (``rid`` becomes a stat of that event), and
  * one record in the process-wide `SpanStore`:

    {"id", "name", "path", "start", "end", "parent", "rid", "counts"}

A ``phase()`` is a span for the inside of a hot loop (the serving
engine's round): it has the session's half alone, so with no session it
costs one `is_enabled()` and a push and pop of the thread's stack, and
the enclosing ``span()`` carries the histogram and the ring event.

`start`/`end` are `time.perf_counter` seconds, `id` comes from one
process-wide counter, `parent` is the id of the enclosing span on that
thread (None at the top, or where that span began before the session),
`rid` is the identifier that one request's spans and events share,
`counts` is what `s.count(...)` set.
`event()` puts an instant record (`start == end`, `path` None) into
the same store under the same rule. The store is bounded, counts what
falls out, and empties itself when a session begins: after a traced
window it holds that window's spans and nothing else, and `records()` /
`self_segments()` read it. With no session a span costs two clock
reads, one `is_enabled()`, one histogram observation and one ring
append, and keeps nothing.

Two kinds of record stand outside the span tree (`path` and `parent`
None, never on a thread's stack), each in a bounded list of its own:

  * `host.gc`: while a session is on, and only then, `gc.callbacks`
    holds `_on_gc` (installed when the store sees a session begin,
    removed when it sees one end), so that each collection of the
    interpreter is a `host.gc` annotation on the trace and one record,
    counts `generation`, `collected`, `uncollectable`, `thread`.
    Emptied with the spans when a session begins; `gc_records()`.
  * process records, one-off and kept across sessions
    (`process_records()`): `setup.import`, the body of
    `paddle_tpu/__init__.py`, and `setup.jit.<stage>` for every
    `jax.monitoring` duration of building a program (`JIT_STAGES`),
    stamped when it ended. `opened()` is the instant the store saw the
    newest session begin: what ended before it is set-up.

Nesting concatenates names with '/': a span("ingest") inside
span("step") has the path "step/ingest" (per-thread stacks — ingestion
threads and the device loop don't interleave each other's paths).

    from paddle_tpu import observability as obs

    with obs.span("serve.step") as s:
        with spans.phase("serve.prefill", rid=req.id):
            ...
        s.count(pages_in_use=3)
    print(obs.span_report())
"""

import collections
import gc
import itertools
import threading
import time

import jax

from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability import trace as _trace
from paddle_tpu.profiler import event_table

#: the most records the process-wide store keeps; the oldest fall out
MAX_RECORDS = 200_000
#: the most `host.gc` records it keeps (the oldest fall out), and the
#: most process records (the first stay: a program traced inside a
#: serving loop would otherwise push set-up out)
MAX_GC_RECORDS = 50_000
MAX_PROCESS_RECORDS = 16_384
GC_SPAN = "host.gc"
#: `jax.monitoring` duration events of building a program -> the
#: `<stage>` of its `setup.jit.<stage>` record. A fetch from the
#: persistent cache lies inside its `backend_compile`, and a nested
#: jit's trace inside its caller's
JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_TLS = threading.local()
_HISTOGRAMS = {}                # path -> its `span.<path>` Histogram
_IDS = itertools.count(1)       # next() is atomic under the GIL
_session_on = jax.profiler.TraceAnnotation.is_enabled


class SpanStore:
    """Bounded in-memory store of one profiler session's span and event
    records, its `host.gc` records, and the process's one-off records.
    `dropped` and `gc_dropped` count what fell out at the old end of
    theirs; the process list keeps its first records (set-up comes
    first) and `process_dropped` counts what came after it was full."""

    def __init__(self, max_records=MAX_RECORDS, max_gc=MAX_GC_RECORDS,
                 max_process=MAX_PROCESS_RECORDS):
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=max_records)
        self._gc = collections.deque(maxlen=max_gc)
        self._process, self._max_process = [], max_process
        self.dropped = self.gc_dropped = self.process_dropped = 0
        self.opened = None
        self._on = False    # read unlocked: a stale read costs one lock

    def session(self):
        """Is a profiler session on? Called at every span's entry; the
        call that first sees one begin empties the session's lists and
        hooks the collector, the one that sees it end unhooks it."""
        on = _session_on()
        if on != self._on:
            with self._lock:
                if on != self._on:
                    if on:
                        self._records.clear()
                        self._gc.clear()
                        self.dropped = self.gc_dropped = 0
                        self.opened = time.perf_counter()
                    _hook_gc(on)
                    self._on = on
        return on

    def add(self, record):
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(record)

    def add_gc(self, record):
        """Takes no lock: a collection may begin while its own thread
        holds it. Collections run one at a time, and a deque's append
        is atomic."""
        if len(self._gc) == self._gc.maxlen:
            self.gc_dropped += 1
        self._gc.append(record)

    def add_process(self, record):
        with self._lock:
            if len(self._process) < self._max_process:
                self._process.append(record)
            else:
                self.process_dropped += 1

    def records(self):
        with self._lock:
            return list(self._records)

    def gc_records(self):
        with self._lock:
            return list(self._gc)

    def process_records(self):
        with self._lock:
            return list(self._process)

    def __len__(self):
        with self._lock:
            return len(self._records)


_STORE = SpanStore()


def records():
    """The span and event records of the newest profiler session, oldest
    first (a span is appended when it ends, so a parent follows its
    children). Reading notices a session's end, as a span's entry
    does."""
    _STORE.session()
    return _STORE.records()


def gc_records():
    """The newest profiler session's `host.gc` records, oldest first;
    reading notices a session's end, as `records()` does."""
    _STORE.session()
    return _STORE.gc_records()


def process_records():
    """The process's one-off records (set-up), oldest first: a session
    does not empty them."""
    return _STORE.process_records()


def opened():
    """`perf_counter` instant at which the store saw the newest profiler
    session begin, or None."""
    return _STORE.opened


def note_process(name, start, end):
    """Keep one process record of ``name`` over ``[start, end]``
    (`time.perf_counter` seconds)."""
    _STORE.add_process({"id": next(_IDS), "name": name, "path": None,
                        "start": start, "end": end, "parent": None,
                        "rid": None, "counts": {}})


_gc_open = None     # (annotation, start) of the collection in progress


def _on_gc(phase, info):
    """`gc.callbacks` entry while a profiler session is on. Collections
    do not nest and run one at a time, so one slot holds the open one."""
    global _gc_open
    if phase == "start":
        if _session_on():
            ann = jax.profiler.TraceAnnotation(GC_SPAN)
            ann.__enter__()
            _gc_open = ann, time.perf_counter()
    elif _gc_open is not None:
        ann, t0 = _gc_open
        _gc_open = None
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        _STORE.add_gc({
            "id": next(_IDS), "name": GC_SPAN, "path": None, "start": t0,
            "end": t1, "parent": None, "rid": None,
            "counts": {"generation": info["generation"],
                       "collected": info["collected"],
                       "uncollectable": info["uncollectable"],
                       "thread": threading.current_thread().name}})


def _hook_gc(on):
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _on_duration(event, duration, **_):
    stage = JIT_STAGES.get(event)
    if stage is not None:
        end = time.perf_counter()
        note_process("setup.jit." + stage, end - duration, end)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _stack():
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


class phase:
    """A scope of a hot loop: annotated on the device trace and recorded
    while a profiler session is on, nothing kept and nothing fed
    otherwise. Nestable, no device sync. The object the ``with`` yields
    takes counts: ``s.count(tokens=128)``."""

    __slots__ = ("name", "rid", "path", "id", "parent", "counts", "_t0",
                 "_ann")

    def __init__(self, name, rid=None):
        self.name = str(name)
        self.rid = rid
        self.counts = None

    def count(self, **counts):
        """Counts taken at this span's boundary; kept with its record."""
        if self.counts is None:
            self.counts = counts
        else:
            self.counts.update(counts)

    def __enter__(self):
        stack = _stack()
        if stack:
            self.parent = stack[-1].id
            self.path = stack[-1].path + "/" + self.name
        else:
            self.parent = None
            self.path = self.name
        stack.append(self)
        if _STORE.session():
            self.id = next(_IDS)
            self._ann = (jax.profiler.TraceAnnotation(self.name)
                         if self.rid is None else
                         jax.profiler.TraceAnnotation(self.name,
                                                      rid=self.rid))
            self._t0 = time.perf_counter()
            self._ann.__enter__()
        else:
            self.id = None
        return self

    def __exit__(self, *exc):
        _stack().pop()
        if self.id is not None:
            self._ann.__exit__(*exc)
            _STORE.add({"id": self.id, "name": self.name,
                        "path": self.path, "start": self._t0,
                        "end": time.perf_counter(), "parent": self.parent,
                        "rid": self.rid, "counts": self.counts or {}})
        return False


class span(phase):
    """A phase that also, session or none, times the scope into its
    `span.<path>` histogram and the flight ring: for a loop's outermost
    scope and for anything that runs a few times a second."""

    __slots__ = ()

    def __enter__(self):
        phase.__enter__(self)
        if self.id is None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        phase.__exit__(self, *exc)
        hist = _HISTOGRAMS.get(self.path)
        if hist is None:    # registrations outlive reset_all(): keep it
            hist = _HISTOGRAMS[self.path] = _metrics.histogram(
                "span." + self.path)
        hist.observe(dt)
        _trace.note_span(self.path, dt)   # links into the active trace
        #                                   context via the flight ring
        return False


def event(name, rid=None):
    """An instant record (lifecycle point) in the span store, under the
    same rule as a span's: only while a profiler session is on. The
    store reads `time.perf_counter` itself."""
    if not _STORE.session():
        return
    stack = _stack()
    t = time.perf_counter()
    _STORE.add({"id": next(_IDS), "name": str(name), "path": None,
                "start": t, "end": t,
                "parent": stack[-1].id if stack else None, "rid": rid,
                "counts": {}})


def self_segments(recs):
    """{span id: [(start, end)]} — the parts of each span's interval
    that none of its child spans covers, in order; their lengths sum to
    the span's self time (its duration less its children's)."""
    kids = collections.defaultdict(list)
    for r in recs:
        if r["parent"] is not None and r["end"] > r["start"]:
            kids[r["parent"]].append((r["start"], r["end"]))
    out = {}
    for r in recs:
        if r["path"] is None:
            continue                    # an event has no self time
        own, cur = [], r["start"]
        for a, b in sorted(kids.get(r["id"], ())):
            if a > cur:
                own.append((cur, min(a, r["end"])))
            cur = max(cur, b)
        if r["end"] > cur:
            own.append((cur, r["end"]))
        out[r["id"]] = own
    return out


def _span_histograms():
    reg = _metrics.registry()
    for name in reg.names():
        if name.startswith("span."):
            yield name[len("span."):], reg.get(name)


def span_summary():
    """Structured rows of every span path seen since `reset_spans()`,
    from the `span.<path>` histograms, the largest total first."""
    rows = []
    for path, hist in _span_histograms():
        st = hist.stats()
        if not st:
            continue
        rows.append({
            "name": path, "calls": st["count"], "total_s": st["sum"],
            "avg_ms": 1e3 * st["mean"],
            "min_ms": 1e3 * st["min"], "max_ms": 1e3 * st["max"],
            "p50_ms": 1e3 * st["p50"], "p95_ms": 1e3 * st["p95"]})
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def span_report():
    """The sorted text table (ref: DisableProfiler's event table)."""
    return event_table(span_summary())


def reset_spans():
    """Clear the `span.<path>` histograms: `span_summary()` starts
    again from nothing."""
    for _, hist in _span_histograms():
        hist.reset()
