"""A model with a per-slot recurrent state through ``ServingEngine``: the
same submit / step / drain, the same two step programs, and the cache
protocol's promises about the state (serving/engine.py).

Outputs are compared token for token with greedy decoding by the
model's plain forward over the whole sequence so far (float32 on the
CPU: the logits agree to 1e-6 and no argmax is that close here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
from paddle_tpu.models.hybrid import HybridConfig, HybridDecoder
from paddle_tpu.serving import ServeConfig, ServingEngine
from paddle_tpu.testing import chaos

CFG = HybridConfig.tiny()


@pytest.fixture
def fast_retry():
    """Recovery backoff in microseconds, not the production schedule."""
    from paddle_tpu.core.flags import get_flag, set_flags
    saved = {k: get_flag(k) for k in ("retry_backoff_base_s",
                                      "retry_jitter")}
    set_flags({"retry_backoff_base_s": 0.001, "retry_jitter": 0.0})
    yield
    set_flags(saved)


@pytest.fixture(scope="module")
def served():
    model = HybridDecoder(CFG)
    return model, model.init(jax.random.key(0))


def greedy(model, variables, prompt, n):
    """The n tokens greedy decoding gives, by the plain forward over the
    sequence so far (padded to one length: causal, so padding changes
    nothing before it, and one compiled program serves every call)."""
    ids = np.zeros(64, np.int32)
    ids[:len(prompt)] = prompt
    forward = _forward(model)
    for pos in range(len(prompt), len(prompt) + n):
        logits = forward(variables, jnp.asarray(ids)[None])
        ids[pos] = int(jnp.argmax(logits[0, pos - 1]))
    return ids[len(prompt):len(prompt) + n].tolist()


_FORWARDS = {}


def _forward(model):
    if id(model) not in _FORWARDS:
        _FORWARDS[id(model)] = jax.jit(lambda v, ids: model.apply(v, ids))
    return _FORWARDS[id(model)]


def engine_for(served, **kw):
    model, variables = served
    kw = {"num_slots": 2, "page_size": 8, "max_len": 64, "prefill_len": 8,
          "prefix_cache": False, **kw}
    return ServingEngine(model, variables, ServeConfig(**kw))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]


def test_slots_are_reused_from_zeros_and_programs_trace_once(served):
    """Five requests through two slots, prompts of one to three chunks:
    every reuse of a slot starts from zeros though nothing clears it on
    the host, and neither program retraces across admission waves."""
    eng = engine_for(served)
    ps = prompts((5, 13, 9, 21, 3))
    rids = [eng.submit(p, max_new=6) for p in ps]
    eng.drain()
    for p, rid in zip(ps, rids):
        assert eng.requests[rid].tokens == greedy(*served, p, 6)
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert eng.state_bytes() > 0 and eng._pages_available() == 16
    eng.close()


def test_a_preempted_request_finishes_token_exact(served):
    """Three pages for two requests that want three each: the pool
    deadlocks, one is preempted (its state is simply dropped: the
    replay's first chunk starts from zeros) and both finish exactly."""
    eng = engine_for(served, max_len=24, num_pages=3)
    ps = prompts((7, 7), seed=1)
    rids = [eng.submit(p, max_new=12) for p in ps]
    eng.drain()
    assert sum(eng.requests[r].preemptions for r in rids) >= 1
    for p, rid in zip(ps, rids):
        assert eng.requests[rid].tokens == greedy(*served, p, 12)
    eng.close()


@pytest.mark.parametrize("where,nth", [("serve.step", 3),
                                       ("serve.prefill", 2)])
def test_a_recovered_request_finishes_token_exact(served, where, nth,
                                                  fast_retry):
    """A fault inside either step program: pools AND state are rebuilt,
    every request in flight replays prompt + tokens from zeros."""
    eng = engine_for(served, max_len=32)
    ps = prompts((5, 11, 3, 18), seed=2)
    rids = [eng.submit(p, max_new=7) for p in ps]
    plan = chaos.FaultPlan(seed=0)
    plan.fail("fault_point", path=rf"^{where}$", nth=nth, times=1)
    with chaos.active(plan):
        eng.drain()
    assert eng.recoveries == 1
    for p, rid in zip(ps, rids):
        assert eng.requests[rid].tokens == greedy(*served, p, 7)
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    eng.close()


def test_a_failure_at_the_trailing_read_rebuilds_pools_and_state(
        served, fast_retry):
    """The engine launches round n+1 before it reads round n, so a
    round that failed on the device is found out one step late, with a
    round launched on its state behind it. Both are dropped, pools AND
    state are rebuilt, and every request replays from the tokens read
    (tests/test_serving.py::TestTrailingFetch holds the other cases of
    the trailing read for this model and for GPTDecoder alike)."""
    eng = engine_for(served, max_len=32)
    ps = prompts((5, 11, 3), seed=4)
    rids = [eng.submit(p, max_new=7) for p in ps]
    decode, launched = eng._decode_jit, []

    class Lost:
        def __init__(self, real):
            self.real = real

        def copy_to_host_async(self):
            pass

        def __array__(self, *args, **kwargs):
            raise RuntimeError("the device lost this round")

    def failing(params, caches, tokens, *rest):
        toks, caches = decode(params, caches,
                              getattr(tokens, "real", tokens), *rest)
        launched.append(1)
        return (Lost(toks) if len(launched) == 2 else toks), caches
    eng._decode_jit = failing
    eng.drain()
    assert eng.recoveries == 1
    assert all(eng.requests[r].recoveries == 1 for r in rids[:2])
    for p, rid in zip(ps, rids):
        assert eng.requests[rid].tokens == greedy(*served, p, 7)
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert not eng._inflight
    eng.close()


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "prefix hit skips"),
    ({"draft": True}, "roll"),
])
def test_what_the_state_cannot_serve_is_refused_with_the_reason(
        served, kw, reason):
    with pytest.raises(EnforceError, match=reason):
        engine_for(served, **kw)


def test_the_prefix_cache_flag_default_does_not_reach_a_stateful_model(
        served):
    model, variables = served
    eng = ServingEngine(model, variables, ServeConfig(
        num_slots=2, page_size=8, max_len=32, prefill_len=8))
    assert eng.cfg.prefix_cache is False and eng._prefix_cache is None
    eng.close()


def test_the_step_span_counts_the_state(served, fresh_store,
                                        profiler_session):
    eng = engine_for(served)
    per_slot = eng.state_bytes() // 2
    with profiler_session():
        eng.submit(prompts((5,))[0], max_new=3)
        eng.step()
    counts = [r["counts"] for r in fresh_store.records()
              if r["name"] == "serve.step"][0]
    assert counts["state_slots"] == 1
    assert counts["state_bytes"] == per_slot
    assert counts["state_bytes_reserved"] == 2 * per_slot
    eng.drain()
    eng.close()


def test_a_model_without_state_serves_as_before():
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    model = GPTDecoder(cfg)
    variables = model.init(jax.random.key(0))
    eng = ServingEngine(model, variables, ServeConfig(
        num_slots=2, page_size=8, max_len=32, prefill_len=8))
    assert eng._state == () and eng.state_bytes() == 0
    assert eng._prefix_cache is not None          # the flag's default
    p = prompts((6,), seed=3)[0] % cfg.vocab_size
    rid = eng.submit(p, max_new=5)
    eng.drain()
    want = model.apply(variables, jnp.asarray(p[None]),
                       method=lambda pr: model.generate(pr, 5))
    assert np.array_equal(eng.requests[rid].output, np.asarray(want)[0])
    assert eng._state == ()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    eng.close()
