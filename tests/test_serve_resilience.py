"""Serving resilience layer: chunked prefill, bounded admission
(deadlines / priorities / queue limit), crash-isolated step recovery,
client cancellation, and watchdog-driven load shedding.

The acceptance contract: degraded conditions produce degraded service,
never lost requests — every submitted request reaches a terminal status
(done | rejected | shed | cancelled | failed), and every COMPLETED
greedy request is token-exact vs a per-request generate() reference even
when injected `serve.step` / `serve.prefill` faults force the engine to
quarantine and rebuild its device state mid-stream."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.testing import chaos


@pytest.fixture
def flags_guard():
    saved = all_flags()
    yield
    set_flags(saved)


@pytest.fixture
def fast_retry(flags_guard):
    """Recovery backoff in microseconds, not the production schedule."""
    set_flags({"retry_backoff_base_s": 0.001, "retry_jitter": 0.0})


def _tiny_decoder(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    cfg.use_flash = False
    model = GPTDecoder(cfg)
    return model, model.init(jax.random.key(seed)), cfg


def _reference(model, variables, prompt, max_new):
    ref = model.apply(variables, jnp.asarray(prompt[None, :]),
                      method=lambda pr: model.generate(pr, max_new))
    return np.asarray(ref)[0]


def _engine(model, variables, **kw):
    from paddle_tpu.serving import ServeConfig, ServingEngine
    return ServingEngine(model, variables, ServeConfig(**kw))


class TestChunkedPrefill:
    def test_long_prompts_token_exact_and_traced_once(self):
        """Prompts past prefill_len admit as multiple fixed-shape calls
        of the ONE prefill trace; outputs stay token-exact and the
        allocator recycles fully."""
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=2, page_size=8,
                         max_len=48, prefill_len=8)
        rng = np.random.RandomState(3)
        specs = [(20, 6), (5, 4), (30, 8)]     # 20, 30 > prefill_len=8
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L, _ in specs]
        rids = [engine.submit(p, max_new=mn)
                for p, (_, mn) in zip(prompts, specs)]
        engine.drain()
        for rid, p, (_, mn) in zip(rids, prompts, specs):
            req = engine.requests[rid]
            assert req.status == "done"
            assert np.array_equal(req.output, _reference(
                model, variables, p, mn)), f"request {rid} diverged"
        assert engine.prefill_traces == 1 and engine.decode_traces == 1
        assert engine._pages_available() == engine.cfg.num_pages
        engine.close()

    def test_chunked_off_rejects_long_prompt_at_submit(self):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=1, page_size=8,
                         max_len=32, prefill_len=8, chunked_prefill=False)
        with pytest.raises(Exception,
                           match="serve_chunked_prefill is off"):
            engine.submit(np.ones((20,), np.int32), max_new=4)
        engine.close()


class TestStepRecovery:
    SPECS = [(5, 6), (11, 9), (3, 4), (18, 7)]   # 18 > prefill_len=8

    def _run(self, plan=None, step_retries=3, prepare=None):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=2, page_size=8,
                         max_len=32, prefill_len=8,
                         step_retries=step_retries)
        if prepare is not None:
            prepare(engine)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L, _ in self.SPECS]
        rids = [engine.submit(p, max_new=mn)
                for p, (_, mn) in zip(prompts, self.SPECS)]
        if plan is None:
            engine.drain()
        else:
            with chaos.active(plan):
                engine.drain()
        outs = {rid: engine.requests[rid].output for rid in rids}
        engine.close()
        return engine, outs

    def test_step_fault_recovers_token_exact(self, fast_retry):
        """An InjectedFault inside the jitted decode step mid-stream:
        the engine quarantines + rebuilds device state and every
        surviving greedy request still finishes token-exact vs the
        undisturbed run (host prompt + tokens are the durable state)."""
        _, clean = self._run()
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve\.step$", nth=3, times=1)
        engine, faulted = self._run(plan)
        assert plan.fired("fault_point") == 1
        assert engine.recoveries == 1
        assert all(r.status == "done" for r in engine.requests.values())
        assert any(r.recoveries for r in engine.requests.values())
        for rid in clean:
            assert np.array_equal(clean[rid], faulted[rid]), (
                f"request {rid} not token-exact after recovery")
        # the rebuilt pools have identical shapes: recovery never retraces
        assert engine.decode_traces == 1 and engine.prefill_traces == 1

    def test_a_failure_at_the_trailing_read_recovers_token_exact(
            self, fast_retry):
        """The third decode round is launched without complaint and
        fails where a device failure surfaces, at the read one step
        later, with the fourth round already launched behind it: the
        engine recovers at `serve.step`, both rounds are dropped, and
        every request replays from the tokens that were READ."""
        _, clean = self._run()
        launched = []

        class Lost:
            """A token array whose program failed on the device."""
            def __init__(self, real):
                self.real = real

            def copy_to_host_async(self):
                pass

            def __array__(self, *args, **kwargs):
                raise RuntimeError("the device lost this round")

        def prepare(engine):
            decode = engine._decode_jit

            def failing(params, caches, tokens, *rest):
                tokens = getattr(tokens, "real", tokens)
                toks, caches = decode(params, caches, tokens, *rest)
                launched.append(sum(not fl.first
                                    for fl in engine._inflight))
                return (Lost(toks) if len(launched) == 3 else toks), caches
            engine._decode_jit = failing

        before = _metrics.counter("serve.recoveries").snapshot().get(
            "where=serve.step", 0)
        engine, faulted = self._run(prepare=prepare)
        assert engine.recoveries == 1
        assert _metrics.counter("serve.recoveries").snapshot()[
            "where=serve.step"] == before + 1
        # rounds two to four were each launched with one round unread
        assert launched[:4] == [0, 1, 1, 1]
        assert all(r.status == "done" for r in engine.requests.values())
        assert any(r.recoveries for r in engine.requests.values())
        for rid in clean:
            assert np.array_equal(clean[rid], faulted[rid])
        assert engine.decode_traces == 1 and engine.prefill_traces == 1

    def test_prefill_fault_recovers_token_exact(self, fast_retry):
        _, clean = self._run()
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve\.prefill$", nth=2, times=1)
        engine, faulted = self._run(plan)
        assert plan.fired("fault_point") == 1
        assert engine.recoveries == 1
        for rid in clean:
            assert np.array_equal(clean[rid], faulted[rid])

    def test_retry_budget_exhaustion_fails_all_and_reraises(self,
                                                            fast_retry):
        """serve_step_retries consecutive decode failures: the engine
        retires every in-flight request as `failed` (no caller left
        waiting forever) and re-raises the fault."""
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve\.step$", nth=1, times=2)
        with pytest.raises(chaos.InjectedFault):
            self._run(plan, step_retries=1)   # budget = 2 consecutive


class TestRetryBudget:
    def test_counts_sleeps_and_reraises_at_budget(self):
        from paddle_tpu.core.retry import RetryBudget, RetryPolicy
        sleeps = []
        policy = RetryPolicy(max_attempts=3, backoff_base_s=0.5,
                             backoff_multiplier=2.0, jitter=0.0,
                             sleep=sleeps.append)
        b = RetryBudget(policy, "unit")
        exc = RuntimeError("boom")
        assert b.failure(exc) == 1
        assert b.failure(exc) == 2
        b.success()                       # streak resets
        assert b.failure(exc) == 1
        assert b.failure(exc) == 2
        with pytest.raises(RuntimeError, match="boom"):
            b.failure(exc)                # 3rd consecutive = max_attempts
        assert sleeps == [0.5, 1.0, 0.5, 1.0]


class TestBoundedAdmission:
    def test_queue_limit_and_infeasible_deadline_reject(self):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=1, page_size=8,
                         max_len=16, prefill_len=8, queue_limit=2)
        rng = np.random.RandomState(7)
        sub = lambda **kw: engine.submit(
            rng.randint(0, cfg.vocab_size, (3,), np.int32), max_new=3,
            **kw)
        r0, r1 = sub(), sub()
        r2 = sub()                          # queue already at limit
        r3 = sub(deadline_s=0.0)            # can never be met
        assert engine.requests[r2].status == "rejected"
        assert engine.requests[r2].retire_reason == "queue_full"
        assert engine.requests[r2].retriable
        assert engine.requests[r2].device_prompt is None
        assert engine.requests[r3].status == "rejected"
        assert engine.requests[r3].retire_reason == "infeasible_deadline"
        engine.drain()
        assert engine.requests[r0].status == "done"
        assert engine.requests[r1].status == "done"
        # rejections count as SLO-failed retirements: 2 ok of 4 retired
        assert engine.goodput() == 0.5
        engine.close()

    def test_expired_deadline_sheds_queued_request(self):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=1, page_size=8,
                         max_len=32, prefill_len=8)
        rng = np.random.RandomState(9)
        r0 = engine.submit(rng.randint(0, cfg.vocab_size, (5,), np.int32),
                           max_new=8)
        r1 = engine.submit(rng.randint(0, cfg.vocab_size, (4,), np.int32),
                           max_new=4, deadline_s=0.01)
        time.sleep(0.05)
        finished = engine.drain()
        assert engine.requests[r1].status == "shed"
        assert engine.requests[r1].retire_reason == "deadline_expired"
        assert engine.requests[r0].status == "done"
        assert {r.id for r in finished} == {r0, r1}
        engine.close()

    def test_preemption_victim_is_lowest_priority_not_youngest(self):
        """Pool deadlock with a high-priority younger request: the OLDER
        low-priority one is preempted (the pre-priority engine always
        evicted the youngest) and both still finish token-exact."""
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=2, page_size=8,
                         max_len=24, prefill_len=8, num_pages=4)
        rng = np.random.RandomState(11)
        p0 = rng.randint(0, cfg.vocab_size, (7,), np.int32)
        p1 = rng.randint(0, cfg.vocab_size, (7,), np.int32)
        r0 = engine.submit(p0, max_new=12, priority=0)   # older, low
        r1 = engine.submit(p1, max_new=12, priority=5)   # younger, high
        engine.drain()
        assert engine.requests[r0].preemptions >= 1
        assert engine.requests[r1].preemptions == 0
        assert np.array_equal(engine.requests[r0].output,
                              _reference(model, variables, p0, 12))
        assert np.array_equal(engine.requests[r1].output,
                              _reference(model, variables, p1, 12))
        engine.close()


class TestRoundInFlight:
    """Who reads tokens from outside step() while a round is launched
    and not read (engine.py, "the round")."""

    def _engine(self, n=2):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=2, page_size=8,
                         max_len=32, prefill_len=8)
        rng = np.random.RandomState(21)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (5, 9, 4)[:n]]
        return model, variables, engine, prompts

    def test_export_inflight_never_holds_a_token_that_was_not_read(self):
        """After every step the failover mirror is the tokens READ: one
        behind what the device has computed while a round is in flight,
        and complete when the request retires."""
        model, variables, engine, prompts = self._engine()
        rids = [engine.submit(p, max_new=7) for p in prompts]
        seen = []
        while engine._queue or engine._running:
            engine.step()
            mirror = {e["rid"]: e["tokens"]
                      for e in engine.export_inflight()}
            for slot, req in engine._running.items():
                assert mirror[req.id] == req.tokens
                launched = int(engine._gen_counts[slot])
                in_flight = sum(slot in fl.rows for fl in engine._inflight)
                assert len(req.tokens) == launched - in_flight
                seen.append(in_flight)
        assert 1 in seen                   # a round was in flight
        for rid, p in zip(rids, prompts):
            assert np.array_equal(engine.requests[rid].output,
                                  _reference(model, variables, p, 7))
        engine.close()

    def test_drain_returns_only_when_nothing_is_in_flight(self):
        """The step that launches a request's last round does not
        finish it: drain() takes the step that reads it too."""
        model, variables, engine, prompts = self._engine(1)
        rid = engine.submit(prompts[0], max_new=3)
        engine.step()                      # first token read, round 1 up
        engine.step()                      # round 2 up: all 3 launched
        req = engine.requests[rid]
        assert len(req.tokens) == 2 and req.status == "running"
        assert engine._inflight
        assert int(engine._gen_counts[req.slot]) == req.max_new
        (done,) = engine.drain()
        assert done is req and len(req.tokens) == 3
        assert not engine._inflight and not engine._running
        assert engine.drain() == []
        engine.close()

    def test_a_replica_adopts_what_was_read_and_recomputes_the_rest(self):
        """Failover with a round in flight: the mirror holds the tokens
        read, the adopting engine recomputes the round that was lost
        with the first engine, token-exact."""
        model, variables, engine, prompts = self._engine(1)
        engine.submit(prompts[0], max_new=8)
        for _ in range(4):
            engine.step()
        (entry,) = engine.export_inflight()
        assert engine._inflight and len(entry["tokens"]) == 4
        engine.close()                     # the round in flight is let go
        assert not engine._inflight
        _, _, other, _ = self._engine(0)
        rid = other.adopt(prompts[0], tokens=entry["tokens"], max_new=8)
        other.drain()
        assert np.array_equal(other.requests[rid].output,
                              _reference(model, variables, prompts[0], 8))
        other.close()


class TestCancel:
    def test_cancel_queued_and_running(self):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=1, page_size=8,
                         max_len=16, prefill_len=8)
        rng = np.random.RandomState(13)
        r0 = engine.submit(rng.randint(0, cfg.vocab_size, (4,), np.int32),
                           max_new=6)
        r1 = engine.submit(rng.randint(0, cfg.vocab_size, (4,), np.int32),
                           max_new=4)
        engine.step()                      # r0 running, r1 queued
        assert engine.requests[r0].status == "running"
        assert engine.cancel(r1)
        assert engine.requests[r1].status == "cancelled"
        assert all(r.id != r1 for r in engine._queue)
        assert engine.cancel(r0)
        assert engine.requests[r0].status == "cancelled"
        assert engine.requests[r0].retire_reason == "cancelled"
        assert not engine._running
        assert engine._pages_available() == engine.cfg.num_pages
        assert engine.cancel(r0) is False  # already terminal
        assert engine.cancel(9999) is False
        # cancellation is the client's choice, not an engine failure
        assert engine.goodput() == 1.0
        assert engine.drain() == []
        engine.close()


class TestWatchdogShedding:
    def test_goodput_collapse_sheds_only_lowest_priority_queued(self):
        """A forced goodput collapse (impossible TTFT SLO) fires the
        watchdog action exactly once (latched) and sheds exactly the
        lowest-priority queued request; everything else completes."""
        from paddle_tpu.observability.watchdog import WatchdogConfig
        model, variables, cfg = _tiny_decoder()
        engine = _engine(
            model, variables, num_slots=1, page_size=8, max_len=16,
            prefill_len=8, slo_ttft_s=1e-9,
            watchdog=WatchdogConfig(min_retired=2, goodput_min=0.5))
        rng = np.random.RandomState(17)
        shed_before = dict(_metrics.counter("serve.shed").snapshot())
        prios = [5, 5, 1, 5, 5]
        rids = [engine.submit(
            rng.randint(0, cfg.vocab_size, (3,), np.int32), max_new=3,
            priority=p) for p in prios]
        engine.drain()
        statuses = {rid: engine.requests[rid].status for rid in rids}
        low = rids[2]                      # the lone priority-1 request
        assert statuses[low] == "shed", statuses
        assert engine.requests[low].retire_reason == "goodput_collapse"
        assert all(statuses[r] == "done" for r in rids if r != low)
        assert any(a["anomaly"] == "goodput_collapse"
                   for a in engine._watchdog.anomalies)
        shed_after = dict(_metrics.counter("serve.shed").snapshot())
        key = "cause=goodput_collapse"
        assert shed_after.get(key, 0) - shed_before.get(key, 0) == 1
        engine.close()

    def test_shed_queued_prefers_expired_then_lowest_priority(self):
        model, variables, cfg = _tiny_decoder()
        engine = _engine(model, variables, num_slots=1, page_size=8,
                         max_len=16, prefill_len=8)
        rng = np.random.RandomState(19)
        r0 = engine.submit(rng.randint(0, cfg.vocab_size, (3,), np.int32),
                           max_new=3, priority=1)
        r1 = engine.submit(rng.randint(0, cfg.vocab_size, (3,), np.int32),
                           max_new=3, deadline_s=0.005)
        time.sleep(0.02)
        assert engine.shed_queued(cause="overload") == [r1]
        assert engine.requests[r1].retire_reason == "deadline_expired"
        assert [r.id for r in engine._queue] == [r0]
        assert engine.shed_queued(cause="overload") == [r0]
        assert engine.requests[r0].retire_reason == "overload"
        engine.close()


@pytest.mark.slow
def test_serve_chaos_drill_end_to_end():
    """The full tools/chaos_drill.py --serve scenario: mixed chunked
    traffic + 3 injected faults + overload + deadlines + a cancel."""
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chaos_drill", os.path.join(repo, "tools", "chaos_drill.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    summary = mod.run_serve_drill()
    assert summary["injected_faults"] == 3
    assert summary["recoveries"] == 3
    assert summary["statuses"].get("done") == 4
    # the shared-prefix wave: one degraded lookup (injected fault),
    # the rest hit, all token-exact
    assert summary["prefix_faults"] == 1
    assert summary["prefix_hits"] > 0
    assert summary["wave_token_exact"] == summary["prefix_wave"] == 3
