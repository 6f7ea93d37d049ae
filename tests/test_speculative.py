"""Speculative decoding + prefill/decode disaggregation.

The acceptance contract, both halves of the serving tentpole:

Speculation — a serve_draft engine (self-draft by default) is
token-exact BY CONSTRUCTION: the verify step emits the target model's
own draws, so greedy speculative output equals `generate()` bitwise and
seeded sampling equals a plain (draft-off) engine bitwise, including
across an injected step crash + recovery. The accounting that prices
the feature (spec_stats, per-request spec_tokens, serve.spec_*
counters) must stay consistent, and the draft/verify jits trace once.

Disaggregation — `fleet_prefill_replicas` carves the first N replicas
into a prefill role; a prefill-heavy request runs a max_new=1 leg
there, then hands off (adopt + seeded replay) to a decode replica.
The handoff is a pure routing optimization: token streams are
bit-identical to a mixed fleet (greedy AND sampled), a faulted or
role-dead handoff degrades to mixed routing rather than failing the
request, failover after a handoff keeps the role pin, and the
autoscaler never retires a role's last replica.
"""

import urllib.request

import numpy as np
import pytest

import jax

from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.testing import chaos

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture
def flags_guard():
    saved = all_flags()
    yield
    set_flags(saved)


@pytest.fixture
def fast_retry(flags_guard):
    """Recovery/respawn backoff in microseconds, not production pacing."""
    set_flags({"retry_backoff_base_s": 0.001, "retry_jitter": 0.0})


_MODEL_CACHE = {}


def _shared_decoder():
    if "m" not in _MODEL_CACHE:
        from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
        cfg = GPTConfig.tiny()
        cfg.dropout = 0.0
        cfg.use_flash = False
        model = GPTDecoder(cfg)
        _MODEL_CACHE["m"] = (model, model.init(jax.random.key(0)), cfg)
    return _MODEL_CACHE["m"]


def _serve_cfg(**kw):
    from paddle_tpu.serving import ServeConfig
    base = dict(num_slots=2, page_size=8, max_len=64, prefill_len=16,
                metrics_port=0)
    base.update(kw)
    return ServeConfig(**base)


def _engine(**kw):
    from paddle_tpu.serving import ServingEngine
    model, variables, cfg = _shared_decoder()
    return (ServingEngine(model, variables, _serve_cfg(**kw)),
            model, variables, cfg)


def _router(num_replicas=3, serve_kw=None, **fleet_kw):
    from paddle_tpu.serving import FleetConfig, FleetRouter
    model, variables, cfg = _shared_decoder()
    fleet_kw.setdefault("heartbeat_s", 5.0)
    fleet_kw.setdefault("metrics_port", 0)
    router = FleetRouter(
        model, variables,
        FleetConfig(num_replicas=num_replicas, **fleet_kw),
        serve_config=_serve_cfg(**(serve_kw or {})))
    return router, model, variables, cfg


def _generate_ref(model, variables, prompt, max_new):
    ref = model.apply(variables, jnp.asarray(prompt[None, :]),
                      method=lambda pr: model.generate(pr, max_new))
    return np.asarray(ref)[0]


# prompt lengths vs prefill_len=16: five prefill-heavy (> 16), three
# short — the mix every disaggregation test routes
_PROMPT_LENS = (24, 5, 30, 12, 40, 3, 20, 17)
_HEAVY = sum(1 for L in _PROMPT_LENS if L > 16)


def _disagg_prompts(cfg):
    rng = np.random.RandomState(11)
    return [rng.randint(0, cfg.vocab_size, (L,), np.int32)
            for L in _PROMPT_LENS]


@pytest.fixture(scope="module")
def disagg_refs():
    """Mixed-fleet (no roles) greedy + sampled token streams for the
    shared prompt set — the yardstick every disaggregation test
    compares against. Fleet request seeds pin by submission id, so the
    disaggregated fleets must submit in the same order."""
    router, model, variables, cfg = _router(num_replicas=3)
    prompts = _disagg_prompts(cfg)
    fids = [router.submit(p, max_new=8) for p in prompts]
    router.drain()
    tel = router.telemetry()
    assert tel["roles"] == [] and tel["handoffs"] == 0
    greedy = [list(router.requests[f].tokens) for f in fids]
    router.close()
    router2 = _router(num_replicas=3)[0]
    f2 = [router2.submit(p, max_new=8, temperature=0.9, top_k=20)
          for p in prompts]
    router2.drain()
    sampled = [list(router2.requests[f].tokens) for f in f2]
    router2.close()
    return prompts, greedy, sampled


# --------------------------------------------------------------------------
# speculative decoding: token-exact by construction
# --------------------------------------------------------------------------

class TestSpeculativeDecoding:

    def test_greedy_matches_generate_and_stats_price_the_win(self):
        """Greedy speculative output equals generate() bitwise (mixed
        short + chunked prompts); the accounting is self-consistent
        (proposed == accepted + rollbacks, tokens/target-step > 1.0)
        and lands on the serve.spec_* counters; draft + verify jits
        trace exactly once. A /metrics scrape exports the families."""
        from paddle_tpu.observability import metrics as _metrics
        from paddle_tpu.observability.exporter import MetricsServer
        base = {k: sum(_metrics.counter(k).snapshot().values())
                for k in ("serve.spec_proposed", "serve.spec_accepted",
                          "serve.spec_rollbacks")}
        eng, model, variables, cfg = _engine(draft=True, spec_k=4)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (5, 30, 11, 20)]
        ids = [eng.submit(p, max_new=8) for p in prompts]
        eng.drain()
        for rid, p in zip(ids, prompts):
            assert eng.requests[rid].status == "done"
            assert np.array_equal(eng.requests[rid].output,
                                  _generate_ref(model, variables, p, 8))
        stats = eng.spec_stats()
        assert stats["enabled"] and stats["spec_k"] == 4
        assert stats["rounds"] >= 1 and stats["proposed"] > 0
        assert stats["proposed"] == stats["accepted"] + stats["rollbacks"]
        assert stats["tokens_per_target_step"] > 1.0
        assert 0.0 < stats["acceptance_rate"] <= 1.0
        # per-request spec-vs-plain accounting: the bonus tokens are a
        # subset of the accepted proposals
        bonus = sum(eng.requests[r].spec_tokens for r in ids)
        assert 0 < bonus <= stats["accepted"]
        assert eng.draft_traces == 1 and eng.verify_traces == 1
        deltas = {k: sum(_metrics.counter(k).snapshot().values()) - v
                  for k, v in base.items()}
        assert deltas["serve.spec_proposed"] == stats["proposed"]
        assert deltas["serve.spec_accepted"] == stats["accepted"]
        with MetricsServer(port=0, host="127.0.0.1") as srv:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=5).read().decode()
        for family in ("serve_spec_proposed", "serve_spec_accepted",
                       "serve_spec_rollbacks"):
            assert family in body, family
        eng.close()

    def test_seeded_sampling_bit_exact_vs_plain_engine(self):
        """The same seeded sampled request through a draft engine and a
        plain engine emits bit-identical tokens — speculation never
        changes the sample law, only how many target steps it costs."""
        plain = _engine()[0]
        spec, model, variables, cfg = _engine(draft=True, spec_k=3)
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (7, 25, 12)]
        kw = dict(max_new=8, temperature=0.8, top_k=30)
        p_ids = [plain.submit(p, seed=1000 + i, **kw)
                 for i, p in enumerate(prompts)]
        plain.drain()
        s_ids = [spec.submit(p, seed=1000 + i, **kw)
                 for i, p in enumerate(prompts)]
        spec.drain()
        for pid, sid in zip(p_ids, s_ids):
            assert np.array_equal(plain.requests[pid].output,
                                  spec.requests[sid].output)
        assert spec.spec_stats()["rounds"] >= 1
        plain.close()
        spec.close()

    def test_one_trace_a_program_through_greedy_mixed_greedy(
            self, new_step_counts, profiler_session):
        """With speculation on, an all-greedy wave, a wave that mixes
        greedy and sampled rows and a greedy wave again (the sampler's
        short branch, its long one, its short one, as the ``serve.step``
        span's ``sampled_rows`` tells) run through ONE draft, ONE verify
        and ONE prefill program."""
        from paddle_tpu.observability import metrics as _metrics
        eng, model, variables, cfg = _engine(draft=True, spec_k=3)
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (6, 9, 20, 5)]
        retraces = _metrics.counter("jit.retraces").total()

        def wave():
            """Drain; the sampled rows of each round that ran."""
            eng.drain()
            return new_step_counts("sampled_rows")

        with profiler_session():
            first = eng.submit(prompts[0], max_new=8)
            assert set(wave()) == {0}
            eng.submit(prompts[1], max_new=8)
            eng.submit(prompts[2], max_new=8, temperature=0.8, top_k=30,
                       seed=11)
            assert 1 in wave()
            last = eng.submit(prompts[3], max_new=8)
            assert set(wave()) == {0}
        assert eng.draft_traces == 1 and eng.verify_traces == 1
        assert eng.prefill_traces == 1 and eng.draft_prefill_traces == 1
        assert eng.decode_traces == 0        # every round speculated
        assert _metrics.counter("jit.retraces").total() == retraces
        for rid, p in ((first, prompts[0]), (last, prompts[3])):
            assert np.array_equal(eng.requests[rid].output,
                                  _generate_ref(model, variables, p, 8))
        eng.close()

    def test_recovery_mid_speculation_token_exact(self, fast_retry):
        """An injected serve.step crash mid-stream on a speculative
        engine quarantines BOTH page pools (target + draft) and
        re-admits recompute-style: greedy completions stay token-exact
        and the engine counts exactly one recovery."""
        eng, model, variables, cfg = _engine(draft=True, spec_k=4,
                                             step_retries=4)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (6, 22, 10)]
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve\.step$", nth=2, times=1)
        with chaos.active(plan):
            ids = [eng.submit(p, max_new=8) for p in prompts]
            eng.drain()
        assert plan.fired("fault_point") == 1
        assert eng.recoveries == 1
        for rid, p in zip(ids, prompts):
            assert eng.requests[rid].status == "done"
            assert np.array_equal(eng.requests[rid].output,
                                  _generate_ref(model, variables, p, 8))
        eng.close()

    @pytest.mark.slow
    def test_failover_with_speculation_bit_exact(self, fast_retry):
        """Satellite: a replica death mid-stream on a speculative fleet
        re-routes the victims and the seeded replay on the adopting
        replica — itself speculating — finishes bit-identical to an
        undisturbed speculative fleet."""
        router, model, variables, cfg = _router(
            num_replicas=2, serve_kw=dict(draft=True, spec_k=3),
            respawn_budget=3)
        prompts = _disagg_prompts(cfg)[:4]
        ref = _router(num_replicas=1,
                      serve_kw=dict(draft=True, spec_k=3))[0]
        rids = [ref.submit(p, max_new=8, temperature=0.9, top_k=20)
                for p in prompts]
        ref.drain()
        ref_out = [list(ref.requests[f].tokens) for f in rids]
        ref.close()
        # note: a 1-replica and a 2-replica fleet draw the same request
        # seeds (pinned by id at submit), so the streams must agree
        fids = [router.submit(p, max_new=8, temperature=0.9, top_k=20)
                for p in prompts]
        for _ in range(50):
            router.step()
            busy = [i for i in range(2)
                    if router._replicas[i].alive()
                    and router._replicas[i].load() > 0]
            if busy and any(len(router.requests[f].tokens) >= 2
                            for f in fids):
                break
        assert busy, "no replica ever got busy"
        router.kill_replica(busy[-1])
        router.drain()
        assert router.failovers == 1
        assert any(router.requests[f].reroutes for f in fids)
        for f, want in zip(fids, ref_out):
            assert router.requests[f].status == "done"
            assert list(router.requests[f].tokens) == want
        router.close()


# --------------------------------------------------------------------------
# prefill/decode disaggregation: handoff == routing, never tokens
# --------------------------------------------------------------------------

class TestDisaggregation:

    def test_greedy_handoff_token_exact(self, fast_retry, disagg_refs):
        """Every prefill-heavy request runs its first token on the
        prefill replica and finishes on a decode replica with the SAME
        tokens a mixed fleet emits; short prompts never hand off. The
        handoff count lands in telemetry and on the fleet_handoffs
        metric a /metrics scrape exports."""
        from paddle_tpu.observability import metrics as _metrics
        from paddle_tpu.observability.exporter import MetricsServer
        prompts, greedy, _ = disagg_refs
        h0 = sum(_metrics.counter("fleet.handoffs").snapshot().values())
        router = _router(num_replicas=3, prefill_replicas=1)[0]
        fids = [router.submit(p, max_new=8) for p in prompts]
        router.drain()
        tel = router.telemetry()
        assert tel["roles"] == ["prefill", "decode", "decode"]
        assert tel["handoffs"] == _HEAVY
        for i, f in enumerate(fids):
            rec = router.requests[f]
            assert rec.status == "done", (i, rec.status)
            assert list(rec.tokens) == greedy[i], i
            if len(prompts[i]) > 16:
                assert rec.phase == "decode"
                assert rec.replica in (1, 2)   # finished on a decode role
            else:
                assert rec.phase is None
        assert sum(_metrics.counter("fleet.handoffs").snapshot()
                   .values()) - h0 == _HEAVY
        with MetricsServer(port=0, host="127.0.0.1") as srv:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=5).read().decode()
        assert "fleet_handoffs" in body
        router.close()

    def test_sampled_handoff_bit_exact(self, fast_retry, disagg_refs):
        """Seeded sampling replays bit-exact across the handoff: the
        decode replica adopts [t0] and continues the fold_in count
        sequence at 1, exactly like the mixed fleet did."""
        prompts, _, sampled = disagg_refs
        router = _router(num_replicas=3, prefill_replicas=1)[0]
        fids = [router.submit(p, max_new=8, temperature=0.9, top_k=20)
                for p in prompts]
        router.drain()
        assert router.telemetry()["handoffs"] == _HEAVY
        for i, f in enumerate(fids):
            assert list(router.requests[f].tokens) == sampled[i], i
        router.close()

    def test_handoff_fault_degrades_to_mixed(self, fast_retry,
                                             disagg_refs):
        """An injected fleet.handoff fault downgrades the request to
        mixed routing (phase cleared, no handoff counted) — it still
        finishes, token-exact."""
        prompts, greedy, _ = disagg_refs
        router = _router(num_replicas=3, prefill_replicas=1)[0]
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^fleet\.handoff$", times=1000)
        with chaos.active(plan):
            fids = [router.submit(p, max_new=8) for p in prompts]
            router.drain()
        assert plan.fired("fault_point") >= _HEAVY
        assert router.telemetry()["handoffs"] == 0
        for i, f in enumerate(fids):
            rec = router.requests[f]
            assert rec.status == "done" and rec.phase is None
            assert list(rec.tokens) == greedy[i], i
        router.close()

    def test_dead_prefill_role_degrades_to_mixed(self, fast_retry,
                                                 disagg_refs):
        """With the prefill role dead (respawn budget spent), fresh
        prefill-heavy requests are never classified — they run mixed on
        the surviving decode replicas, token-exact."""
        prompts, greedy, _ = disagg_refs
        router = _router(num_replicas=3, prefill_replicas=1,
                         respawn_budget=0)[0]
        router.kill_replica(0)
        router.step()
        fids = [router.submit(p, max_new=8) for p in prompts]
        router.drain()
        assert router.telemetry()["handoffs"] == 0
        for i, f in enumerate(fids):
            rec = router.requests[f]
            assert rec.status == "done", (i, rec.retire_reason)
            assert list(rec.tokens) == greedy[i], i
        router.close()

    def test_autoscale_respects_role_minimums(self, fast_retry):
        """The autoscaler never retires a role's last replica (an idle
        1-prefill/1-decode fleet stays at 2), and load-driven growth
        adds decode capacity (spawned replicas join the decode role)."""
        router, model, variables, cfg = _router(
            num_replicas=2, prefill_replicas=1, autoscale_min=1,
            autoscale_max=4, scale_cooldown_s=0.0)
        for _ in range(120):               # idle: must NOT scale down
            router.step()
        assert router._states == ["live", "live"]
        assert router.telemetry()["roles"] == ["prefill", "decode"]
        rng = np.random.RandomState(13)
        prompts = [rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(3, 15)),), np.int32)
                   for _ in range(12)]
        fids = [router.submit(p, max_new=4) for p in prompts]
        grew = 0
        for _ in range(300):
            router.step()
            grew = max(grew, len(router._replicas))
            if all(router.requests[f].status == "done" for f in fids):
                break
        assert all(router.requests[f].status == "done" for f in fids)
        assert grew > 2, "backlog never spawned a replica"
        roles = router.telemetry()["roles"]
        assert roles[:2] == ["prefill", "decode"]
        assert all(r == "decode" for r in roles[2:])
        router.close()

    @pytest.mark.slow
    def test_failover_after_handoff_stays_on_decode_role(
            self, fast_retry):
        """The e2e disaggregation drill: kill the decode replica serving
        a handed-off sampled request mid-stream — the re-route keeps the
        decode role pin and the completion is bit-identical to a mixed
        fleet serving only that request."""
        model, variables, cfg = _shared_decoder()
        heavy = _disagg_prompts(cfg)[4]          # length 40
        ref = _router(num_replicas=3)[0]
        rfid = ref.submit(heavy, max_new=8, temperature=0.9, top_k=20)
        ref.drain()
        want = list(ref.requests[rfid].tokens)
        ref.close()
        router = _router(num_replicas=3, prefill_replicas=1,
                         respawn_budget=3)[0]
        fid = router.submit(heavy, max_new=8, temperature=0.9, top_k=20)
        rec = router.requests[fid]
        for _ in range(200):
            router.step()
            if (rec.phase == "decode" and rec.status == "dispatched"
                    and len(rec.tokens) >= 3):
                break
        assert rec.phase == "decode" and rec.replica in (1, 2)
        router.kill_replica(rec.replica)
        router.drain()
        assert rec.status == "done", (rec.status, rec.retire_reason)
        assert rec.reroutes >= 1
        assert rec.replica != 0, "failover landed on the prefill role"
        assert list(rec.tokens) == want
        assert router.telemetry()["handoffs"] == 1
        router.close()
