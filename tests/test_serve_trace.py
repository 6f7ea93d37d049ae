"""Serving lifecycle traces, SLO/goodput accounting, and the live
/metrics plane over a running engine.

Acceptance surface (ISSUE 6): scraping /metrics during a live
ServingEngine run returns valid Prometheus text carrying serve.goodput,
serve.ttft_s quantiles, and jit.retraces; a flush-spy test proves
request tracing adds no blocking device sync to the decode step; and
run_report --serve reconstructs a preempted-then-resumed request."""

import urllib.request

import numpy as np
import pytest

import jax

from paddle_tpu.observability import metrics as M
from paddle_tpu.observability.runlog import read_records


def _tiny_decoder(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    model = GPTDecoder(cfg)
    return model, model.init(jax.random.key(seed)), cfg


def _engine(model, v, run_log=None, **kw):
    from paddle_tpu.serving import ServeConfig, ServingEngine
    base = dict(num_slots=2, page_size=8, max_len=32, prefill_len=16,
                num_pages=10, run_log=run_log)
    base.update(kw)
    return ServingEngine(model, v, ServeConfig(**base))


def _events(path):
    return [r for r in read_records(path) if "event" in r]


class TestLifecycleTrace:
    def test_event_order_and_trace_ids(self, rng, tmp_path):
        model, v, cfg = _tiny_decoder()
        rl = str(tmp_path / "serve.jsonl")
        eng = _engine(model, v, run_log=rl)
        for L, mn in ((5, 4), (11, 3), (3, 5)):
            eng.submit(rng.randint(0, cfg.vocab_size, (L,))
                       .astype(np.int32), max_new=mn)
        done = eng.drain()
        eng.close()
        evs = _events(rl)
        by_req = {}
        for e in evs:
            by_req.setdefault(e["req"], []).append(e)
        assert set(by_req) == {0, 1, 2}
        for r, ev in by_req.items():
            names = [e["event"] for e in ev]
            assert names == ["submitted", "admitted", "prefill_done",
                             "first_token", "retired"], (r, names)
            ts = [e["t"] for e in ev]
            assert ts == sorted(ts)
            assert len({e["trace"] for e in ev}) == 1  # one trace id
        # trace ids are unique per request, shared per engine run
        ids = {ev[0]["trace"] for ev in by_req.values()}
        assert len(ids) == 3
        assert len({i.split("/")[0] for i in ids}) == 1
        # the retired event carries the attribution payload
        ret = [e for e in evs if e["event"] == "retired"]
        for e in ret:
            assert e["reason"] == "length" and e["slo_ok"] is True
            assert e["tokens"] == by_req[e["req"]][0]["max_new"]
        # the in-memory trace mirrors the RunLog
        for req in done:
            assert [t[0] for t in req.trace] == \
                [e["event"] for e in by_req[req.id]]

    def test_goodput_and_slo_violations(self, rng):
        model, v, cfg = _tiny_decoder()
        g0 = M.counter("serve.slo_violations").snapshot()
        # impossible TTFT target: every retirement violates
        eng = _engine(model, v, slo_ttft_s=1e-9)
        for _ in range(3):
            eng.submit(rng.randint(0, cfg.vocab_size, (4,))
                       .astype(np.int32), max_new=3)
        eng.drain()
        assert eng.goodput() == 0.0
        slo = eng.slo_stats()
        assert slo["goodput"] == 0.0 and slo["retired"] == 3
        assert slo["violations"]["ttft"] == 3
        assert M.gauge("serve.goodput").value() == 0.0
        eng.close()
        # generous targets: goodput 1.0, violation DELTA stays zero
        eng2 = _engine(model, v, slo_ttft_s=1e9,
                       slo_token_latency_s=1e9)
        for _ in range(2):
            eng2.submit(rng.randint(0, cfg.vocab_size, (4,))
                        .astype(np.int32), max_new=3)
        eng2.drain()
        assert eng2.goodput() == 1.0
        assert eng2.slo_stats()["violations"] == {"ttft": 0,
                                                  "token_latency": 0}
        assert M.gauge("serve.goodput").value() == 1.0
        eng2.close()

    def test_preempt_resume_trace(self, rng, tmp_path):
        """The page-starved two-request run (PR-5's recovery test) now
        leaves a full preempted-then-resumed lifecycle in the RunLog."""
        model, v, cfg = _tiny_decoder()
        rl = str(tmp_path / "preempt.jsonl")
        eng = _engine(model, v, run_log=rl, page_size=8, max_len=24,
                      prefill_len=8, num_pages=4)
        for _ in range(2):
            eng.submit(rng.randint(0, cfg.vocab_size, (7,))
                       .astype(np.int32), max_new=12)
        done = {r.id: r for r in eng.drain()}
        eng.close()
        victims = [r for r in done.values() if r.preemptions]
        assert victims, "page starvation should have preempted one"
        vic = victims[0]
        names = [t[0] for t in vic.trace]
        i_pre = names.index("preempted")
        assert "resumed" in names[i_pre:]
        assert names[-1] == "retired"
        evs = [e for e in _events(rl) if e["req"] == vic.id]
        assert [e["event"] for e in evs] == names
        ret = evs[-1]
        assert ret["preemptions"] == vic.preemptions >= 1

    def test_trace_adds_no_device_sync(self, rng, tmp_path, monkeypatch):
        """Flush-spy acceptance: with lifecycle tracing + RunLog on, a
        full submit/step/drain cycle performs ZERO block_until_ready-
        style syncs — tracing is host clocks + JSONL appends only."""
        model, v, cfg = _tiny_decoder()
        rl = str(tmp_path / "nosync.jsonl")
        eng = _engine(model, v, run_log=rl, slo_ttft_s=10.0)

        def no_sync(*a, **k):
            raise AssertionError(
                "block_until_ready during traced serving")

        monkeypatch.setattr(jax, "block_until_ready", no_sync)
        writes = []
        orig_write = type(eng._run_log).write

        def spy(self, rec):
            writes.append(rec)
            return orig_write(self, rec)

        monkeypatch.setattr(type(eng._run_log), "write", spy)
        for L in (3, 9, 5):
            eng.submit(rng.randint(0, cfg.vocab_size, (L,))
                       .astype(np.int32), max_new=4)
        eng.drain()
        eng.close()
        # tracing was live: lifecycle events actually flowed to the log
        assert sum(1 for r in writes if r.get("event") == "retired") == 3
        assert any(r.get("event") == "first_token" for r in writes)


class TestLiveScrape:
    def test_metrics_scrape_during_live_run(self, rng):
        """Acceptance: /metrics scraped MID-RUN (requests still decoding)
        is valid exposition containing serve.goodput, serve.ttft_s
        quantiles, and jit.retraces."""
        from test_exporter import assert_valid_exposition
        from paddle_tpu.observability.exporter import MetricsServer
        model, v, cfg = _tiny_decoder()
        eng = _engine(model, v)
        with MetricsServer(port=0) as srv:       # global registry
            eng.submit(rng.randint(0, cfg.vocab_size, (4,))
                       .astype(np.int32), max_new=2)
            eng.submit(rng.randint(0, cfg.vocab_size, (6,))
                       .astype(np.int32), max_new=20)
            while not eng.step():
                pass                 # run until the short request retires
            assert eng._running     # the long one is still live
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=10) as resp:
                assert resp.status == 200
                assert "version=0.0.4" in resp.headers["Content-Type"]
                body = resp.read().decode()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz",
                    timeout=10) as resp:
                assert resp.read() == b"ok\n"
        assert_valid_exposition(body)
        assert "\nserve_goodput 1" in body       # gauge live mid-run
        assert 'serve_ttft_s{quantile="0.5"}' in body
        assert 'serve_ttft_s{quantile="0.99"}' in body
        assert "serve_ttft_s_count" in body
        # jit.retraces is advertised (engine preregisters it) even while
        # its value is zero — dashboards see the name before an incident
        assert "# TYPE jit_retraces counter" in body
        assert "# HELP serve_goodput serve.goodput" in body
        eng.drain()
        eng.close()

    def test_serve_config_metrics_port_and_close(self, rng):
        """ServeConfig(metrics_port=0 via flag) -> no server;
        an explicit ephemeral port -> engine owns and stops it."""
        model, v, cfg = _tiny_decoder()
        eng = _engine(model, v)                  # flag default 0 = off
        assert eng._metrics_server is None
        eng.close()


class TestServeReport:
    def test_report_reconstructs_preempted_resumed_request(
            self, rng, tmp_path):
        """Acceptance: run_report --serve rebuilds the full lifecycle of
        a preempted-then-resumed request from the RunLog."""
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            from run_report import render_serve_report
        finally:
            sys.path.pop(0)
        model, v, cfg = _tiny_decoder()
        rl = str(tmp_path / "serve.jsonl")
        eng = _engine(model, v, run_log=rl, page_size=8, max_len=24,
                      prefill_len=8, num_pages=4, slo_ttft_s=100.0)
        for _ in range(2):
            eng.submit(rng.randint(0, cfg.vocab_size, (7,))
                       .astype(np.int32), max_new=12)
        done = {r.id: r for r in eng.drain()}
        eng.close()
        vic = [r for r in done.values() if r.preemptions][0]
        rep = render_serve_report(read_records(rl))
        assert "SERVE REPORT" in rep
        assert "2 submitted, 2 retired" in rep and "1 preempted" in rep
        assert "TTFT:" in rep and "token latency:" in rep
        assert "goodput:" in rep
        assert "slot timeline" in rep and "slot  0" in rep
        assert f"req {vic.id}: preempted at slot" in rep
        assert "resumed +" in rep
        # the lifecycle line shows the full arc for the victim
        line = [ln for ln in rep.splitlines()
                if ln.strip().startswith(f"req {vic.id} [")][0]
        for ev in ("submitted", "admitted", "preempted", "resumed",
                   "retired"):
            assert ev in line, (ev, line)


class TestEngineSpans:
    """ISSUE 25: the engine's spans under a real profiler session."""

    PROMPTS = ((5, 4), (20, 3), (3, 5))      # (prompt length, max_new)

    def _serve(self, model, v, cfg, after_step=None):
        """Three requests, the second two prefill chunks long, through a
        2-slot engine; returns (engine, {request id: tokens})."""
        rng = np.random.RandomState(5)
        eng = _engine(model, v, num_pages=12)
        for L, mn in self.PROMPTS:
            eng.submit(rng.randint(0, cfg.vocab_size, (L,))
                       .astype(np.int32), max_new=mn)
        steps = 0
        while eng._queue or eng._running:
            eng.step()
            steps += 1
            if after_step is not None:
                after_step(eng)
            assert steps < 100
        return eng, {r: list(q.tokens) for r, q in eng.requests.items()}

    def test_spans_events_and_counts(self, fresh_store, profiler_session):
        from paddle_tpu.observability import spans
        model, v, cfg = _tiny_decoder()

        def hand_count(eng):
            pinned = set()
            for req in eng._running.values():
                pinned.update(req.pages, req.shared_pages)
            assert eng.pages_in_use() == len(pinned)
            assert (eng.cfg.num_pages - len(eng._free_pages)
                    == len(pinned) + eng.pages_cached())

        # no session: nothing is kept, and the span state is one bounded
        # histogram for each of the two spans; the round's phases have
        # none
        _, plain = self._serve(model, v, cfg)
        assert spans.records() == []
        paths = {n for n in M.registry().names() if n.startswith("span.")}
        assert {"span.serve.step", "span.serve.submit"} <= paths
        assert not [n for n in paths if n.startswith("span.serve.step/")]
        for n in paths:
            h = M.registry().get(n)
            st = h.stats()
            assert st is None or \
                st["count"] - st["dropped"] <= h.max_samples
        with profiler_session():
            eng, traced = self._serve(model, v, cfg, after_step=hand_count)
        assert traced == plain                   # token for token
        assert {n for n in M.registry().names()
                if n.startswith("span.")} == paths
        # drained: nothing pinned, the 20-token prompt's two full pages
        # stay with the prefix cache
        assert eng.pages_in_use() == 0 and eng.pages_cached() == 2

        recs = spans.records()
        by_id = {r["id"]: r for r in recs}
        kids = {}
        for r in recs:
            if r["path"] is not None:            # spans, not events
                kids.setdefault(r["parent"], []).append(r)
        for group in kids.values():
            group.sort(key=lambda r: r["start"])
        steps = [r for r in recs if r["name"] == "serve.step"]
        decoded = 0
        for i, s in enumerate(steps):
            names = [k["name"] for k in kids[s["id"]]]
            assert names[:2] == ["serve.admit", "serve.grow"]
            # the round is LAUNCHED, then the round before it and this
            # step's admissions are waited for, then advanced
            rest = names[2:]
            launched = rest[:1] == ["serve.decode"]
            waits = rest[launched:-1]
            assert waits == sorted(waits, key=[
                "serve.fetch", "serve.prefill.fetch"].index)
            assert waits.count("serve.fetch") <= 1
            assert (rest[-1:] == ["serve.advance"]) == bool(waits)
            if launched and waits:
                # the launch has returned before any wait begins
                assert kids[s["id"]][2]["end"] <= kids[s["id"]][3]["start"]
            decoded += launched
            c = s["counts"]
            assert set(c) == {"pages_in_use", "pages_cached", "num_pages",
                              "sampled_rows", "overlapped", "late_rows"}
            assert c["sampled_rows"] == 0            # every request greedy
            assert c["late_rows"] == 0               # all end by max_new
            # every round but the first after an empty engine is
            # launched before the round before it is read
            assert c["overlapped"] == (launched and i > 0)
            assert ("serve.fetch" in waits) == (i > 0)
            assert c["num_pages"] == 12
            assert 0 <= c["pages_in_use"] <= 12 - c["pages_cached"]
        assert decoded >= 4
        assert eng.rounds_overlapped == decoded - 1
        assert steps[-1]["counts"]["pages_in_use"] == 0
        # a parent covers its children, at every level
        for r in recs:
            if r["path"] is None or r["id"] not in kids:
                continue
            inside = kids[r["id"]]
            assert sum(k["end"] - k["start"] for k in inside) <= \
                r["end"] - r["start"]
            assert all(r["start"] <= k["start"] and k["end"] <= r["end"]
                       for k in inside)
        own = spans.self_segments(recs)
        assert all(r["start"] <= a < b <= r["end"] for r in recs
                   if r["path"] is not None for a, b in own[r["id"]])

        # one request's spans and events share its id
        prefills = {r["rid"]: r for r in recs if r["name"] == "serve.prefill"}
        assert set(prefills) == {0, 1, 2}
        assert by_id[prefills[1]["parent"]]["name"] == "serve.admit"
        # one wait an admission, however many chunks it launched, at the
        # end of the step that admitted it
        fetches = [r for r in recs if r["name"] == "serve.prefill.fetch"]
        assert sorted(f["rid"] for f in fetches) == [0, 1, 2]
        assert all(f["parent"] == by_id[prefills[f["rid"]]["parent"]]
                   ["parent"] and f["start"] >= prefills[f["rid"]]["end"]
                   for f in fetches)
        assert {r["rid"] for r in recs if r["name"] == "serve.submit"} == \
            {0, 1, 2}
        for rid in (0, 1, 2):
            ev = {r["name"]: r for r in recs
                  if r["path"] is None and r["rid"] == rid}
            assert list(ev) == ["submitted", "admitted", "prefill_done",
                                "first_token", "retired"]
            assert (ev["submitted"]["start"] <= ev["admitted"]["start"]
                    <= ev["first_token"]["start"]
                    <= ev["retired"]["start"])
            # the lifecycle points lie inside the request's own spans
            p = prefills[rid]
            assert p["start"] <= ev["admitted"]["start"] <= p["end"]
            assert ev["admitted"]["parent"] == p["id"]
