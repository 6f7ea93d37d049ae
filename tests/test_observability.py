"""Observability subsystem: metrics registry, RunLog, spans, promoted
profiler (ref: platform/profiler.h RecordEvent/EnableProfiler tables,
tools/timeline.py — see paddle_tpu/observability/__init__.py for the
full ancestry map)."""

import gzip
import json
import os

import pytest

from paddle_tpu.observability import metrics as M
from paddle_tpu.observability.runlog import RunLog, read_records


class TestMetrics:
    def test_counter_labels_and_total(self):
        c = M.Counter("t.c")
        c.inc()
        c.inc(2, op="x")
        c.inc(op="y")
        assert c.value() == 1
        assert c.value(op="x") == 2
        assert c.total() == 4
        assert c.snapshot() == {"": 1, "op=x": 2, "op=y": 1}

    def test_gauge_last_write_wins(self):
        g = M.Gauge("t.g")
        g.set(3)
        g.set(7)
        g.set(1, dev=0)
        assert g.value() == 7 and g.value(dev=0) == 1

    def test_histogram_stats_and_percentiles(self):
        h = M.Histogram("t.h")
        for i in range(1, 101):
            h.observe(i)
        st = h.stats()
        assert st["count"] == 100 and st["min"] == 1 and st["max"] == 100
        assert st["p50"] == pytest.approx(50.5)
        assert st["p95"] == pytest.approx(95.05)
        assert h.percentile(0.0) == 1

    def test_histogram_reservoir_bounds_memory_unbiased(self):
        """Satellite (PR 6): retention past max_samples is a UNIFORM
        reservoir, not keep-the-most-recent — percentiles of a ramp stay
        near the middle instead of collapsing onto the tail, and the
        observations not retained are reported as `dropped`."""
        h = M.Histogram("t.hw", max_samples=64)
        for i in range(10_000):
            h.observe(i)
        st = h.stats()
        assert st["count"] == 10_000    # exact totals survive sampling
        assert st["min"] == 0 and st["max"] == 9999
        assert st["dropped"] == 10_000 - 64
        assert len(h._series[""]["reservoir"]) == 64    # memory flat
        # uniform sample of 0..9999: p50 nowhere near the 99xx tail the
        # old recency window pinned it to
        assert 2000 < st["p50"] < 8000

    def test_histogram_reservoir_deterministic_and_exact_below_cap(self):
        """Identical observation sequences -> identical percentiles (the
        reservoir RNG is seeded from name+labels); under max_samples
        nothing drops and percentiles are exact."""
        a, b = (M.Histogram("t.det", max_samples=32) for _ in range(2))
        for i in range(500):
            a.observe(i)
            b.observe(i)
        assert a.stats() == b.stats()
        # different label set -> different seed -> (almost surely) a
        # different reservoir, but identical exact aggregates
        a.observe(0, op="x")
        small = M.Histogram("t.small", max_samples=32)
        for i in range(10):
            small.observe(i)
        st = small.stats()
        assert st["dropped"] == 0 and st["p50"] == 4.5

    def test_registry_snapshot_flattens_unlabeled(self):
        r = M.MetricsRegistry()
        r.counter("plain").inc(5)
        r.counter("labeled").inc(op="a")
        r.histogram("h").observe(1.0)
        snap = r.snapshot()
        assert snap["counters"]["plain"] == 5
        assert snap["counters"]["labeled"] == {"op=a": 1}
        assert snap["histograms"]["h"]["count"] == 1

    def test_registry_reset_keeps_registration(self):
        r = M.MetricsRegistry()
        c = r.counter("c")
        c.inc(3)
        r.reset()
        assert r.counter("c") is c and c.total() == 0

    def test_kind_conflict_raises(self):
        r = M.MetricsRegistry()
        r.counter("dual")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("dual")

    def test_thread_safety(self):
        import threading
        c = M.Counter("t.mt")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value() == 8000


class TestRunLog:
    def test_write_read_roundtrip(self, tmp_path):
        p = tmp_path / "r.jsonl"
        with RunLog(p) as log:
            for i in range(5):
                log.write({"i": i})
        assert [r["i"] for r in read_records(p)] == list(range(5))

    def test_rotation_preserves_order(self, tmp_path):
        p = tmp_path / "r.jsonl"
        log = RunLog(p, rotate_records=3, keep_rotated=2)
        for i in range(8):
            log.write({"i": i})
        log.close()
        assert os.path.exists(f"{p}.1") and os.path.exists(f"{p}.2")
        assert [r["i"] for r in read_records(p)] == list(range(8))

    def test_rotation_drops_beyond_keep(self, tmp_path):
        p = tmp_path / "r.jsonl"
        log = RunLog(p, rotate_records=3, keep_rotated=2)
        for i in range(12):
            log.write({"i": i})
        log.close()
        # three rotations: the 0..2 file fell off the keep window
        assert [r["i"] for r in read_records(p)] == list(range(3, 12))

    def test_torn_tail_tolerated(self, tmp_path):
        p = tmp_path / "r.jsonl"
        with RunLog(p) as log:
            log.write({"i": 0})
        with open(p, "a") as f:
            f.write('{"i": 1')      # writer killed mid-record
        assert [r["i"] for r in read_records(p)] == [0]


class TestSpans:
    def test_nesting_and_tables(self):
        from paddle_tpu.observability import (reset_spans, span,
                                              span_report, span_summary)
        reset_spans()
        with span("outer"):
            with span("inner"):
                pass
        names = {r["name"] for r in span_summary()}
        assert names == {"outer", "outer/inner"}
        rep = span_report()
        assert "outer/inner" in rep and "p95(ms)" in rep
        # registry-backed: the same spans land as histograms
        assert M.registry().get("span.outer/inner").count() >= 1
        reset_spans()
        assert span_summary() == []

    def test_span_survives_exception(self):
        from paddle_tpu.observability import reset_spans, span, span_summary
        reset_spans()
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
        assert [r["name"] for r in span_summary()] == ["boom"]
        reset_spans()


class TestSpanStore:
    def test_nothing_recorded_without_session(self, fresh_store):
        from paddle_tpu.observability import spans
        spans.reset_spans()
        for _ in range(3000):
            with spans.span("quiet") as s:
                s.count(n=1)
        spans.event("submitted", rid=1)
        assert spans.records() == [] and len(fresh_store) == 0
        # the only state a span leaves is its bounded histogram
        h = M.registry().get("span.quiet")
        assert h.count() == 3000
        assert h.stats()["dropped"] == 3000 - h.max_samples
        spans.reset_spans()

    def test_nested_pair_is_recorded(self, fresh_store,
                                     profiler_session):
        from paddle_tpu.observability import spans
        with profiler_session():
            with spans.span("outer", rid=7) as s:
                with spans.phase("inner"):
                    pass
                spans.event("first_token", rid=7)
                s.count(running=3)
                s.count(queued=1)
        inner, ev, outer = spans.records()
        assert (inner["name"], inner["path"]) == ("inner", "outer/inner")
        assert (outer["name"], outer["path"]) == ("outer", "outer")
        assert inner["parent"] == outer["id"] and outer["parent"] is None
        assert (outer["start"] <= inner["start"] <= inner["end"]
                <= ev["start"] == ev["end"] <= outer["end"])
        assert outer["rid"] == 7 and inner["rid"] is None
        assert outer["counts"] == {"running": 3, "queued": 1}
        assert inner["counts"] == {}
        assert (ev["name"], ev["rid"], ev["parent"], ev["path"]) == \
            ("first_token", 7, outer["id"], None)
        assert ev["counts"] == {}
        assert len({inner["id"], ev["id"], outer["id"]}) == 3

    def test_a_phase_feeds_the_session_alone(self, fresh_store,
                                             profiler_session):
        from paddle_tpu.observability import flight, spans
        spans.reset_spans()
        ring = flight.recorder()

        def nest():
            with spans.phase("hot") as p:
                p.count(n=1)
                with spans.span("cold"):
                    pass

        nest()                      # no session: the phase leaves nothing
        assert spans.records() == []
        with profiler_session():
            nest()
        # ... and with one, its record; never a histogram or a ring event
        cold, hot = spans.records()
        assert (hot["path"], hot["counts"]) == ("hot", {"n": 1})
        assert (cold["path"], cold["parent"]) == ("hot/cold", hot["id"])
        assert [r["name"] for r in spans.span_summary()] == ["hot/cold"]
        assert M.registry().get("span.hot/cold").count() == 2
        if ring is not None:
            assert not [e for e in ring.snapshot()
                        if e.get("event") == "span" and e["name"] == "hot"]
        spans.reset_spans()

    def test_exception_still_closes_the_span(self, fresh_store,
                                             profiler_session):
        from paddle_tpu.observability import spans
        with profiler_session():
            with pytest.raises(RuntimeError):
                with spans.span("boom"):
                    raise RuntimeError("x")
            with spans.span("after"):
                pass
        boom, after = spans.records()
        assert boom["name"] == "boom" and boom["end"] >= boom["start"]
        # the thread's stack was unwound: no path prefix, no parent
        assert after["path"] == "after" and after["parent"] is None

    def test_a_second_session_empties_the_store(
            self, fresh_store, profiler_session):
        from paddle_tpu.observability import spans
        with profiler_session():
            with spans.span("one"):
                pass
            with spans.span("two"):
                pass
        # kept after the session, for the readers ...
        assert [r["name"] for r in spans.records()] == ["one", "two"]
        with spans.span("between"):
            pass
        assert len(fresh_store) == 2
        # ... and dropped when the next one begins
        with profiler_session():
            with spans.span("three"):
                pass
        assert [r["name"] for r in spans.records()] == ["three"]

    def test_bounded_and_counts_what_it_dropped(
            self, monkeypatch, profiler_session):
        from paddle_tpu.observability import spans
        store = spans.SpanStore(max_records=3)
        monkeypatch.setattr(spans, "_STORE", store)
        with profiler_session():
            with spans.span("parent"):
                for i in range(4):
                    with spans.span(f"kid{i}"):
                        pass
        recs = spans.records()
        assert [r["name"] for r in recs] == ["kid2", "kid3", "parent"]
        assert store.dropped == 2 and len(store) == 3
        # parents are ids, not positions: they survive the drop
        assert recs[0]["parent"] == recs[2]["id"]

    def test_self_segments_on_a_hand_made_tree(self):
        from paddle_tpu.observability import spans

        def rec(i, start, end, parent=None, path="p"):
            return {"id": i, "name": "n", "path": path, "start": start,
                    "end": end, "parent": parent, "rid": None,
                    "counts": {}}
        recs = [rec(2, 1.0, 3.0, parent=1),      # child
                rec(4, 1.5, 2.0, parent=2),      # grandchild: not 1's
                rec(3, 2.5, 6.0, parent=1),      # overlaps its sibling
                rec(5, 9.0, 12.0, parent=1),     # runs past the parent
                rec(6, 7.0, 7.0, parent=1, path=None),     # an event
                rec(1, 0.0, 10.0)]
        own = spans.self_segments(recs)
        # children cover [1, 6] and [9, 10] of [0, 10]
        assert own[1] == [(0.0, 1.0), (6.0, 9.0)]
        assert own[2] == [(1.0, 1.5), (2.0, 3.0)]
        assert own[3] == [(2.5, 6.0)]            # a leaf: all of it
        assert own[4] == [(1.5, 2.0)]
        assert 6 not in own


class TestEventRecorder:
    def test_percentiles_and_reset(self):
        from paddle_tpu.profiler import EventRecorder
        r = EventRecorder()
        for v in [0.010] * 9 + [1.0]:
            r.add("op", v)
        row = r.summary()[0]
        assert row["calls"] == 10
        assert row["p50_ms"] == pytest.approx(10.0)
        assert 100.0 < row["p95_ms"] < 1000.0      # the tail outlier
        assert "p95(ms)" in r.report()
        r.reset()
        assert r.summary() == []

    def test_record_context_still_works(self):
        from paddle_tpu.profiler import EventRecorder
        r = EventRecorder()
        with r.record("ctx"):
            pass
        assert r.summary()[0]["name"] == "ctx"


class TestTraceOpTable:
    def _write_trace(self, tmp_path, events):
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)

    def test_metadata_without_args_and_missing_pid_lanes(self, tmp_path):
        """Satellite: a process_name metadata event with NO "args" dict
        used to KeyError; an X event whose pid has no lane must not
        crash either (it aggregates only under device_filter=None)."""
        from paddle_tpu.profiler import trace_op_table
        self._write_trace(tmp_path, [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "/device:TPU:0 (lane)"}},
            {"ph": "M", "name": "process_name", "pid": 2},  # args-less
            {"ph": "M", "name": "process_name"},            # pid-less
            {"ph": "X", "name": "fusion.1", "pid": 1, "dur": 10},
            {"ph": "X", "name": "fusion.1", "pid": 1, "dur": 30},
            {"ph": "X", "name": "copy.2", "pid": 3, "dur": 7},  # no lane
            {"ph": "X", "pid": 1, "dur": 5},                # name-less
        ])
        rows = trace_op_table(str(tmp_path), device_filter="TPU", steps=2)
        assert rows == [{"name": "fusion.1", "total_us": 40,
                         "per_step_us": 20.0, "count": 2}]

    def test_device_filter_none_includes_unnamed_lanes(self, tmp_path):
        from paddle_tpu.profiler import trace_op_table
        self._write_trace(tmp_path, [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "name": "fusion.1", "pid": 1, "dur": 10},
            {"ph": "X", "name": "copy.2", "pid": 3, "dur": 7},
        ])
        names = {r["name"]
                 for r in trace_op_table(str(tmp_path), device_filter=None)}
        assert names == {"fusion.1", "copy.2"}


class TestCounterWiring:
    """The degraded-path counters fire where the degradation happens."""

    def test_retry_attempts_and_giveups(self):
        from paddle_tpu.core.retry import RetryPolicy
        att = M.counter("retry.attempts")
        giv = M.counter("retry.giveups")
        a0, g0 = att.value(op="flaky"), giv.value(op="flaky")

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("transient")
            return 42

        p = RetryPolicy(max_attempts=5, backoff_base_s=0.0, jitter=0.0,
                        sleep=lambda s: None)
        assert p.call(flaky) == 42
        assert att.value(op="flaky") == a0 + 2
        assert giv.value(op="flaky") == g0

        def flaky_always():
            raise TimeoutError("down")

        g1 = giv.value(op="flaky_always")
        with pytest.raises(TimeoutError):
            RetryPolicy(max_attempts=3, backoff_base_s=0.0, jitter=0.0,
                        sleep=lambda s: None).call(flaky_always)
        assert giv.value(op="flaky_always") == g1 + 1

    def test_non_retryable_not_counted(self):
        from paddle_tpu.core.retry import RetryPolicy
        att = M.counter("retry.attempts")
        a0 = att.value(op="missing")

        def missing():
            raise FileNotFoundError("semantic miss, not a hiccup")

        with pytest.raises(FileNotFoundError):
            RetryPolicy(max_attempts=5, sleep=lambda s: None).call(missing)
        assert att.value(op="missing") == a0

    def test_pallas_fallback_counter(self):
        from paddle_tpu.ops import pallas
        c = M.counter("pallas.fallback")
        before = c.value(kernel="obs_test_kernel")
        # the log line is one-time per (kernel, reason); the counter is
        # the record and counts EVERY refusal
        pallas.log_fallback("obs_test_kernel", "reason A")
        pallas.log_fallback("obs_test_kernel", "reason A")
        assert c.value(kernel="obs_test_kernel") == before + 2

    def test_heartbeat_missed_counter(self):
        from paddle_tpu.parallel.heartbeat import (STALLED,
                                                   HeartBeatMonitor)
        now = [0.0]
        mon = HeartBeatMonitor(2, timeout_s=1.0, interval_s=0.1,
                               clock=lambda: now[0])
        mon.update(0)
        mon.update(1)
        c = M.counter("heartbeat.missed")
        before = c.value(worker=1)
        now[0] = 5.0
        mon.update(0)           # worker 0 stays live
        res = mon.check()
        assert res[1][0] == STALLED
        assert c.value(worker=1) == before + 1
        mon.check()             # stall latched: counted once
        assert c.value(worker=1) == before + 1

    def test_barrier_wait_counter(self, tmp_path):
        from paddle_tpu.parallel.heartbeat import barrier_with_timeout
        c = M.counter("heartbeat.barrier_wait_s")
        before = c.value(barrier="obs_b")
        # peer already arrived (its marker is on disk) -> no blocking
        (tmp_path / "obs_b.1").write_text("1")
        barrier_with_timeout(str(tmp_path), 0, 2, timeout_s=5.0,
                             tag="obs_b")
        assert c.value(barrier="obs_b") > before
