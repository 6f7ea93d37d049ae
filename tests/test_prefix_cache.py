"""Prefix-cached paged KV: the PrefixCache index (chain hashing,
refcounts, LRU-by-refcount-zero eviction, collision verification) and
the engine integration — prefix hits skip prefill token-exact,
copy-on-write diverges shared pages before the first private write,
preemption / crash recovery degrade sharing without corruption, and
per-request sampling stays deterministic and traced-once through it
all. The oracle everywhere is the uncached path: per-request
generate() for greedy, a cache-off engine for seeded sampling."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.serving import PrefixCache, ServeConfig, ServingEngine
from paddle_tpu.serving import prefix_cache as pc_mod


@pytest.fixture
def flags_guard():
    saved = all_flags()
    yield
    set_flags(saved)


@pytest.fixture
def fast_retry(flags_guard):
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
    kw.setdefault("metrics_port", 0)
    return ServingEngine(model, variables, ServeConfig(**kw))


class TestPrefixCacheUnit:
    def test_match_insert_roundtrip_full_pages_only(self):
        pc = PrefixCache(page_size=4)
        toks = list(range(11))            # 2 full pages + 3 spare
        assert pc.match(toks, cap=10) == ([], 0)
        assert pc.misses == 2             # both full probe pages missed
        owned = pc.insert(toks, row_pages=[7, 3, 9])
        assert owned == [7, 3]            # the partial page is private
        pages, matched = pc.match(toks, cap=10)
        assert pages == [7, 3] and matched == 8
        assert pc.hits == 2
        # a diverging second page shares only the first
        other = toks[:4] + [99, 98, 97, 96]
        pages, matched = pc.match(other, cap=7)
        assert pages == [7] and matched == 4

    def test_match_cap_includes_partial_last_page_for_cow(self):
        pc = PrefixCache(page_size=4)
        toks = list(range(8))
        pc.insert(toks, row_pages=[5, 6])
        # cap=7 (total-1 for an exactly-2-page prompt): the second page
        # is still returned, matched clamped to the cap — the engine
        # copy-on-writes that page before reusing it
        pages, matched = pc.match(toks, cap=7)
        assert pages == [5, 6] and matched == 7

    def test_refcount_release_and_lru_eviction_order(self):
        pc = PrefixCache(page_size=2)
        a = [1, 2, 3, 4]
        b = [9, 8, 7, 6]
        pc.insert(a, row_pages=[0, 1])    # refs=1 each
        pc.insert(b, row_pages=[2, 3])
        assert pc.pages_shared() == 4 and pc.evictable() == 0
        assert pc.evict(4) == []          # nothing refcount-zero yet
        assert pc.release([0, 1]) == []   # idle, still cached
        assert pc.evictable() == 2 and pc.pages_shared() == 2
        pages, matched = pc.match(a, cap=3)
        assert pages == [0, 1] and matched == 3   # idle pages still hit
        pc.acquire(pages)
        assert pc.evictable() == 0        # re-acquired: protected again
        pc.release([0])
        pc.release([1])
        pc.release([2, 3])
        # LRU: page 0 went idle first, then 1, then 2 and 3
        assert pc.evict(1) == [0]
        assert pc.evict(2) == [1, 2]
        assert pc.evictions == 3

    def test_release_unknown_ids_returned_free(self):
        pc = PrefixCache(page_size=2)
        assert pc.release([5, 6]) == [5, 6]

    def test_max_idle_pages_trims_on_release(self):
        pc = PrefixCache(page_size=2, max_idle_pages=1)
        pc.insert([1, 2, 3, 4], row_pages=[0, 1])
        freed = pc.release([0, 1])
        # retention bound 1: the least-recently-idle page is trimmed
        assert freed == [0]
        assert pc.evictable() == 1 and len(pc) == 1

    def test_evictable_count_follows_every_mutation(self):
        # evictable() is a kept count (the engine reads it every round):
        # hold it to a walk of the entries through acquire, release
        # (also of an entry that is idle already), the trim, evict and
        # clear
        def walk(pc):
            return sum(1 for e in pc._entries.values() if e.refs == 0)

        pc = PrefixCache(page_size=2, max_idle_pages=3)
        pc.insert([1, 2, 3, 4, 5, 6], row_pages=[0, 1, 2])
        pc.insert([9, 8, 7, 6], row_pages=[3, 4])
        assert pc.evictable() == walk(pc) == 0
        pc.release([0, 1, 2])
        assert pc.evictable() == walk(pc) == 3
        pc.release([0])                   # idle already: counted once
        assert pc.evictable() == walk(pc) == 3
        pc.acquire([1])
        pc.acquire([1])                   # two slots map it
        assert pc.evictable() == walk(pc) == 2
        pc.release([1])
        assert pc.evictable() == walk(pc) == 2
        pc.release([1, 3, 4])             # 5 idle: trimmed to 3
        assert pc.evictable() == walk(pc) == 3
        pc.evict(1)
        assert pc.evictable() == walk(pc) == 2
        pc.clear()
        assert pc.evictable() == walk(pc) == 0

    def test_collision_verified_as_miss_never_corrupt(self, monkeypatch):
        pc = PrefixCache(page_size=2)
        pc.insert([1, 2], row_pages=[4])
        monkeypatch.setattr(pc_mod, "page_key",
                            lambda parent, tokens: b"same-key")
        pc2 = PrefixCache(page_size=2)
        pc2.insert([1, 2], row_pages=[4])
        # different content, same (forced) key: content check degrades
        # the probe to a miss instead of handing out page 4
        pages, matched = pc2.match([7, 8], cap=1)
        assert pages == [] and matched == 0
        assert pc2.collisions == 1

    def test_insert_stops_at_private_duplicate(self):
        pc = PrefixCache(page_size=2)
        pc.insert([1, 2, 3, 4], row_pages=[0, 1])
        # a row that re-prefilled page [1,2] privately into page 5 (a
        # degraded match or CoW divergence): insert must stop at the
        # duplicate so the SHARED run stays a contiguous row prefix
        owned = pc.insert([1, 2, 9, 9], row_pages=[5, 6])
        assert owned == []
        assert pc.lookup_depth([1, 2, 9, 9]) == 1   # only the old chain

    def test_lookup_depth_read_only(self):
        pc = PrefixCache(page_size=2)
        pc.insert([1, 2, 3, 4], row_pages=[0, 1])
        h, m = pc.hits, pc.misses
        assert pc.lookup_depth([1, 2, 3, 4]) == 2
        assert pc.lookup_depth([1, 2, 5, 6]) == 1
        assert pc.lookup_depth([5]) == 0
        assert (pc.hits, pc.misses) == (h, m)


class TestEnginePrefixCache:
    def test_hit_skips_prefill_and_stays_token_exact(self):
        """Second request sharing a 2-page prefix: its prefill skips the
        shared tokens entirely, both outputs match generate(), and the
        uncached engine agrees token-for-token."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(3)
        shared = rng.randint(0, cfg.vocab_size, (16,), np.int32)
        prompts = [np.concatenate([shared,
                                   rng.randint(0, cfg.vocab_size, (k,),
                                               np.int32)])
                   for k in (3, 5)]
        eng = _engine(model, v, num_slots=2, page_size=8, max_len=48,
                      prefill_len=16, num_pages=12)
        for p in prompts:
            eng.submit(p, max_new=6)
        done = {r.id: r for r in eng.drain()}
        pc = eng._prefix_cache
        assert pc.hits >= 2               # both shared pages re-used
        assert eng.prefill_tokens_skipped == 16
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        cold = _engine(model, v, num_slots=2, page_size=8, max_len=48,
                       prefill_len=16, num_pages=12, prefix_cache=False)
        for p in prompts:
            cold.submit(p, max_new=6)
        cold_done = {r.id: r for r in cold.drain()}
        assert cold._prefix_cache is None
        for i, p in enumerate(prompts):
            ref = _reference(model, v, p, 6)
            np.testing.assert_array_equal(done[i].output, ref)
            np.testing.assert_array_equal(cold_done[i].output, ref)
        eng.close()
        cold.close()

    def test_cow_divergence_page_aligned_greedy(self):
        """Identical exactly-page-aligned prompts: the follower maps the
        last shared page, copy-on-writes it before its first decode
        write, and both outputs stay bit-exact greedy."""
        model, v, cfg = _tiny_decoder(seed=1)
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, cfg.vocab_size, (16,), np.int32)
        eng = _engine(model, v, num_slots=2, page_size=8, max_len=32,
                      prefill_len=16, num_pages=10)
        cow0 = _metrics.counter("serve.cow_copies").total()
        eng.submit(prompt, max_new=7)
        eng.submit(prompt.copy(), max_new=7)
        done = {r.id: r for r in eng.drain()}
        assert _metrics.counter("serve.cow_copies").total() > cow0
        ref = _reference(model, v, prompt, 7)
        np.testing.assert_array_equal(done[0].output, ref)
        np.testing.assert_array_equal(done[1].output, ref)
        assert eng.decode_traces == 1
        eng.close()

    def test_cow_divergence_seeded_top_p_parity(self):
        """Same page-aligned CoW shape under seeded nucleus sampling:
        the cached engine's outputs must equal the cache-off engine's
        for the same per-request seeds (determinism survives sharing)."""
        model, v, cfg = _tiny_decoder(seed=2)
        rng = np.random.RandomState(6)
        prompt = rng.randint(0, cfg.vocab_size, (16,), np.int32)

        def run(prefix_cache):
            eng = _engine(model, v, num_slots=2, page_size=8,
                          max_len=32, prefill_len=16, num_pages=10,
                          prefix_cache=prefix_cache)
            for s in (11, 12):
                eng.submit(prompt.copy(), max_new=7, temperature=0.9,
                           top_p=0.8, seed=s)
            done = {r.id: r for r in eng.drain()}
            out = [list(done[i].output) for i in (0, 1)]
            eng.close()
            return out

        hot, cold = run(True), run(False)
        assert hot == cold

    def test_eviction_under_pressure_token_exact(self):
        """A pool too small to retain idle prefix pages: admissions
        evict refcount-zero entries instead of stalling, and every
        output stays exact."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, cfg.vocab_size, (9,), np.int32)
                   for _ in range(3)]
        eng = _engine(model, v, num_slots=1, page_size=8, max_len=24,
                      prefill_len=16, num_pages=3)
        for p in prompts:
            eng.submit(p, max_new=5)
        done = {r.id: r for r in eng.drain()}
        assert eng._prefix_cache.evictions > 0
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(done[i].output,
                                          _reference(model, v, p, 5))
        eng.close()

    def test_preemption_with_shared_pages_token_exact(self):
        """Pool deadlock between two requests sharing a prefix page:
        the low-priority one is preempted (its shared mapping released,
        refcounts keep the survivor's page intact), resumes via a fresh
        cache hit, and both finish token-exact."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(8)
        shared = rng.randint(0, cfg.vocab_size, (8,), np.int32)
        p0 = np.concatenate([shared,
                             rng.randint(0, cfg.vocab_size, (1,),
                                         np.int32)])
        p1 = np.concatenate([shared,
                             rng.randint(0, cfg.vocab_size, (1,),
                                         np.int32)])
        # pool of 3: one shared page + one private each fills it, so
        # BOTH slots stall at the same page boundary -> deadlock ->
        # priority preemption (the shared page itself is refcounted,
        # never evicted out from under the survivor)
        eng = _engine(model, v, num_slots=2, page_size=8, max_len=24,
                      prefill_len=8, num_pages=3)
        r0 = eng.submit(p0, max_new=12, priority=0)
        r1 = eng.submit(p1, max_new=12, priority=5)
        eng.drain()
        assert eng.requests[r0].preemptions >= 1
        np.testing.assert_array_equal(eng.requests[r0].output,
                                      _reference(model, v, p0, 12))
        np.testing.assert_array_equal(eng.requests[r1].output,
                                      _reference(model, v, p1, 12))
        eng.close()

    def test_recovery_clears_cache_and_replays_exact(self, fast_retry):
        """A decode-step crash mid-stream with shared pages mapped: the
        quarantine drops the pools AND the cache index (its ids point at
        zeroed K/V), and the replay still lands token-exact."""
        from paddle_tpu.testing import chaos
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(9)
        shared = rng.randint(0, cfg.vocab_size, (8,), np.int32)
        prompts = [np.concatenate([shared,
                                   rng.randint(0, cfg.vocab_size, (k,),
                                               np.int32)])
                   for k in (2, 3)]
        eng = _engine(model, v, num_slots=2, page_size=8, max_len=32,
                      prefill_len=8, num_pages=10, step_retries=3)
        for p in prompts:
            eng.submit(p, max_new=8)
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve\.step$", nth=3, times=1)
        with chaos.active(plan):
            done = {r.id: r for r in eng.drain()}
        assert eng.recoveries == 1
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(done[i].output,
                                          _reference(model, v, p, 8))
        eng.close()

    def test_prefix_fault_degrades_to_private_pages(self, fast_retry):
        """An injected serve.prefix_cache fault at admission: the match
        degrades to private pages (no hits for that request) and the
        output is unaffected."""
        from paddle_tpu.testing import chaos
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(10)
        shared = rng.randint(0, cfg.vocab_size, (16,), np.int32)
        prompts = [np.concatenate([shared,
                                   rng.randint(0, cfg.vocab_size, (k,),
                                               np.int32)])
                   for k in (3, 4)]
        eng = _engine(model, v, num_slots=1, page_size=8, max_len=48,
                      prefill_len=16, num_pages=12)
        plan = chaos.FaultPlan(seed=0)
        # nth=2: the SECOND admission's lookup (the one that would hit)
        plan.fail("fault_point", path=r"^serve\.prefix_cache$", nth=2,
                  times=1)
        with chaos.active(plan):
            for p in prompts:
                eng.submit(p, max_new=6)
            done = {r.id: r for r in eng.drain()}
        assert plan.fired("fault_point") == 1
        assert eng._prefix_cache.hits == 0        # degraded, no hit
        assert eng.prefill_tokens_skipped == 0
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(done[i].output,
                                          _reference(model, v, p, 6))
        eng.close()

    def test_sampling_mixed_batch_single_trace(self):
        """Greedy, temperature, top-k and top-p rows in ONE running
        batch: a single decode trace, greedy rows bit-exact with
        generate()."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (5, 7, 4, 6)]
        eng = _engine(model, v, num_slots=4, page_size=8, max_len=24,
                      prefill_len=8, num_pages=16)
        eng.submit(prompts[0], max_new=6)                 # greedy
        eng.submit(prompts[1], max_new=6, temperature=0.8)
        eng.submit(prompts[2], max_new=6, temperature=0.9, top_k=5)
        eng.submit(prompts[3], max_new=6, temperature=0.7, top_p=0.9)
        done = {r.id: r for r in eng.drain()}
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        np.testing.assert_array_equal(
            done[0].output, _reference(model, v, prompts[0], 6))
        eng.close()

    def test_top_k_one_equals_greedy(self):
        """top_k=1 with any temperature collapses the candidate set to
        the argmax — bit-exact with the temperature=0 greedy path."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, cfg.vocab_size, (6,), np.int32)
        eng = _engine(model, v, num_slots=2, page_size=8, max_len=24,
                      prefill_len=8, num_pages=10)
        g = eng.submit(prompt, max_new=8)
        k1 = eng.submit(prompt.copy(), max_new=8, temperature=1.3,
                        top_k=1, seed=77)
        eng.drain()
        np.testing.assert_array_equal(eng.requests[g].output,
                                      eng.requests[k1].output)
        eng.close()

    def test_seeded_sampling_deterministic_across_recovery(self,
                                                           fast_retry):
        """A seeded top-p request whose decode crashes mid-stream must
        replay to the SAME tokens: token i always draws with
        fold(seed, i), independent of batch composition or step
        number."""
        from paddle_tpu.testing import chaos
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(14)
        prompt = rng.randint(0, cfg.vocab_size, (6,), np.int32)

        def run(with_fault):
            eng = _engine(model, v, num_slots=1, page_size=8,
                          max_len=24, prefill_len=8, num_pages=6,
                          step_retries=3)
            rid = eng.submit(prompt, max_new=8, temperature=0.9,
                             top_p=0.85, seed=1234)
            if with_fault:
                plan = chaos.FaultPlan(seed=0)
                plan.fail("fault_point", path=r"^serve\.step$", nth=4,
                          times=1)
                with chaos.active(plan):
                    eng.drain()
                assert eng.recoveries == 1
            else:
                eng.drain()
            out = list(eng.requests[rid].output)
            eng.close()
            return out

        assert run(False) == run(True)
