"""Prefix-cached paged KV: the PrefixCache index (chain hashing,
refcounts, LRU-by-refcount-zero eviction, collision verification) and
the engine integration — prefix hits skip prefill token-exact,
copy-on-write diverges shared pages before the first private write,
preemption / crash recovery degrade sharing without corruption, and
per-request sampling stays deterministic and traced-once through it
all. The oracle everywhere is the uncached path: per-request
generate() for greedy, a cache-off engine for seeded sampling."""

import functools

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

    def test_sampling_mixed_batch_single_trace(self, new_step_counts,
                                               profiler_session):
        """Greedy, temperature, top-k and top-p rows in ONE running
        batch, between an all-greedy wave before it and one after it
        (the sampler's short branch, its long one, its short one
        again, as the ``serve.step`` span's ``sampled_rows`` tells): a
        single decode trace and a single prefill trace through every
        change of mix, greedy rows bit-exact with generate()."""
        model, v, cfg = _tiny_decoder()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, cfg.vocab_size, (L,), np.int32)
                   for L in (5, 7, 4, 6, 3, 6)]
        eng = _engine(model, v, num_slots=4, page_size=8, max_len=24,
                      prefill_len=8, num_pages=16)
        retraces = _metrics.counter("jit.retraces").total()
        done = {}

        def wave():
            """Drain; the sampled rows of each decode round it launched
            (its last step launches none: it reads the last round)."""
            done.update({r.id: r for r in eng.drain()})
            return new_step_counts("sampled_rows")

        with profiler_session():
            eng.submit(prompts[4], max_new=5)             # greedy wave
            assert wave() == [0] * 5
            eng.submit(prompts[0], max_new=6)             # greedy
            eng.submit(prompts[1], max_new=6, temperature=0.8)
            eng.submit(prompts[2], max_new=6, temperature=0.9, top_k=5)
            eng.submit(prompts[3], max_new=6, temperature=0.7, top_p=0.9)
            assert wave() == [3] * 5 + [0]
            eng.submit(prompts[5], max_new=5)             # greedy again
            assert wave() == [0] * 5
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        assert _metrics.counter("jit.retraces").total() == retraces
        for rid, p, mn in ((0, prompts[4], 5), (1, prompts[0], 6),
                           (5, prompts[5], 5)):
            np.testing.assert_array_equal(
                done[rid].output, _reference(model, v, p, mn))
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


# --- the sampler does only the work its rows ask for (ISSUE 29) ---

def _straight_line_law(base_key, logits, temps, top_ks, top_ps, seeds,
                       counts):
    """The sampler as it stood before ISSUE 29, kept here as the
    reference: one straight-line law for every mix of rows, which sorts,
    masks and draws for every row and selects on its last line."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    v = logits.shape[-1]
    scaled = (logits.astype(jnp.float32)
              / jnp.maximum(temps, 1e-6)[:, None])
    desc = -jnp.sort(-scaled, axis=-1)              # descending
    k_eff = jnp.where(top_ks > 0,
                      jnp.minimum(top_ks, v), v).astype(jnp.int32)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=1)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p_eff = jnp.where((top_ps > 0.0) & (top_ps < 1.0),
                      top_ps.astype(jnp.float32), 1.0)
    n_keep = jnp.maximum(
        jnp.sum((cum - probs) < p_eff[:, None], axis=-1), 1)
    pth = jnp.take_along_axis(desc, (n_keep - 1)[:, None], axis=1)
    masked = jnp.where((scaled >= kth) & (scaled >= pth),
                       scaled, -1e30)

    def row_key(s, c):
        return jax.random.fold_in(
            jax.random.fold_in(base_key, s), c)

    keys = jax.vmap(row_key)(seeds, counts)
    drawn = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps > 0.0, drawn.astype(jnp.int32), greedy)


_LAW_ROWS, _LAW_VOCAB = 6, 97      # a vocabulary that is no power of two

# (temperature, top_k, top_p) a row; None = a released slot's zeroed
# knobs (temperature, top-k, top-p, seed and count all 0)
_LAW_MIXES = {
    "all_greedy": [(0.0, 0, 0.0)] * 6,
    "all_greedy_knobs_left_set": [(0.0, 5, 0.9), (0.0, 0, 0.5),
                                  (0.0, 3, 0.0)] * 2,
    "one_sampled_among_greedy": [(0.0, 0, 0.0)] * 3 + [(0.8, 0, 0.0)]
    + [(0.0, 0, 0.0)] * 2,
    "all_sampled_no_cut": [(0.7, 0, 0.0), (1.0, 0, 1.0), (1.3, 0, 0.0)] * 2,
    "all_sampled_top_k": [(0.7, 1, 0.0), (1.0, 5, 0.0), (1.3, 40, 0.0),
                          (0.9, 97, 0.0), (0.9, 500, 0.0), (2.0, 2, 0.0)],
    "all_sampled_top_p": [(0.7, 0, 0.1), (1.0, 0, 0.5), (1.3, 0, 0.9),
                          (0.9, 0, 0.99), (0.5, 0, 1e-6), (2.0, 0, 0.3)],
    "all_sampled_top_k_and_top_p": [(0.7, 5, 0.9), (1.0, 40, 0.5),
                                    (1.3, 2, 0.99), (0.9, 10, 0.1),
                                    (0.5, 3, 0.7), (2.0, 20, 0.95)],
    "every_kind_in_one_round": [(0.0, 0, 0.0), (0.8, 0, 0.0),
                                (0.9, 5, 0.0), (0.7, 0, 0.9),
                                (1.1, 7, 0.8), (0.0, 4, 0.6)],
    "inactive_rows_zeroed_among_sampled": [None, (0.8, 5, 0.9), None,
                                           None, (1.2, 0, 0.7), None],
    "inactive_rows_zeroed_among_greedy": [None, (0.0, 0, 0.0), None,
                                          (0.0, 3, 0.5), None, None],
    "a_prefill_row_greedy": [(0.0, 0, 0.0)],
    "a_prefill_row_sampled": [(0.9, 8, 0.9)],
}


def _law_logits(rows, kind, seed):
    """[rows, 97] float32 logits. ``ties``: values on a grid of halves,
    so that every row holds its maximum several times and both cuts fall
    inside runs of equal logits; ``spread``: a smooth draw."""
    rng = np.random.RandomState(seed)
    if kind == "ties":
        x = rng.randint(-6, 7, (rows, _LAW_VOCAB)).astype(np.float32) / 2
        x[:, rng.randint(0, _LAW_VOCAB, 4)] = 3.0    # the maximum, tied
        return x
    return (rng.randn(rows, _LAW_VOCAB) * 3.0).astype(np.float32)


@pytest.fixture(scope="module")
def law_engine():
    model, v, _ = _tiny_decoder()
    eng = _engine(model, v, num_slots=2, page_size=8, max_len=24,
                  prefill_len=8, num_pages=6, seed=3)
    yield eng
    eng.close()


@pytest.mark.parametrize("kind", ["ties", "spread"])
@pytest.mark.parametrize("mix", sorted(_LAW_MIXES))
def test_sampler_answers_as_the_straight_line_law(law_engine, mix, kind):
    """Token for token, for every mix of rows: the engine's sampler,
    which chooses its work from the values of its knobs, against the
    straight-line law that does all of it for every row."""
    rows = _LAW_MIXES[mix]
    n = len(rows)
    live = np.array([r is not None for r in rows])
    knobs = [r or (0.0, 0, 0.0) for r in rows]
    temps = np.array([k[0] for k in knobs], np.float32)
    top_ks = np.array([k[1] for k in knobs], np.int32)
    top_ps = np.array([k[2] for k in knobs], np.float32)
    seeds = np.where(live, 1000 + 17 * np.arange(n), 0).astype(np.uint32)
    counts = np.where(live, 3 * np.arange(n) + 1, 0).astype(np.int32)
    logits = _law_logits(n, kind, seed=len(mix))
    if kind == "ties":
        assert ((logits == logits.max(-1, keepdims=True)).sum(-1) > 1).all()
    sample = jax.jit(law_engine._sample)
    law = jax.jit(functools.partial(_straight_line_law,
                                    law_engine._base_key))
    knobs = (logits, temps, top_ks, top_ps, seeds)
    got = np.asarray(sample(*knobs, counts))
    assert got.dtype == np.int32 and got.shape == (n,)
    np.testing.assert_array_equal(got, np.asarray(law(*knobs, counts)))
    greedy = temps == 0.0
    np.testing.assert_array_equal(got[greedy],
                                  logits.argmax(-1)[greedy])
    if not greedy.all():
        # the sampled rows are really drawn: another count, another key
        again = np.asarray(sample(*knobs, counts + 1))
        np.testing.assert_array_equal(again[greedy], got[greedy])
        np.testing.assert_array_equal(
            again, np.asarray(law(*knobs, counts + 1)))


# what only a sampled row needs: the sort of the vocabulary, the
# nucleus' cumulative sum, the keys and the draw's random bits
_SAMPLED_ONLY = {"sort", "cumsum", "cumlogsumexp", "random_bits",
                 "random_fold_in", "random_wrap", "random_unwrap",
                 "random_seed", "threefry2x32"}


def _equations(jaxpr, branch=()):
    """(primitive name, branch) of every equation under ``jaxpr``;
    ``branch`` is the chain of (cond equation, index) it lies under."""
    for eqn in jaxpr.eqns:
        yield eqn, branch
        if eqn.primitive.name == "cond":
            for i, br in enumerate(eqn.params["branches"]):
                yield from _equations(br.jaxpr, branch + ((id(eqn), i),))
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, branch)


def _step_args(eng, program):
    cfg = eng.cfg
    s, w = cfg.num_slots, cfg.spec_k + 1
    knobs = lambda n: (np.zeros(n, np.float32), np.zeros(n, np.int32),
                       np.zeros(n, np.float32), np.zeros(n, np.uint32),
                       np.zeros(n, np.int32))
    if program == "decode":
        return eng._decode_jit, (
            eng._params, (eng._caches, eng._state), np.zeros(s, np.int32),
            eng._page_table, np.zeros(s, np.int32), np.zeros(s, bool),
            *knobs(s))
    if program == "prefill":
        return eng._prefill_jit, (
            eng._params, (eng._caches, eng._state), np.zeros(s, np.int32),
            np.zeros((1, cfg.prefill_len), np.int32), np.zeros(1, np.int32),
            np.zeros(1, np.int32), eng._page_table[:1],
            np.zeros(1, np.int32), np.zeros(1, np.int32), *knobs(1))
    if program == "draft":
        return eng._draft_jit, (
            eng._draft_params, eng._draft_caches, np.zeros(s, np.int32),
            eng._page_table, np.zeros(s, np.int32), np.zeros(s, bool),
            *knobs(s))
    assert program == "verify"
    return eng._verify_jit, (
        eng._params, eng._caches, np.zeros((s, w), np.int32),
        np.zeros(s, np.int32), np.zeros(s, np.int32), eng._page_table,
        *knobs(s))


@pytest.mark.parametrize("program,samplers", [
    ("decode", 1), ("prefill", 1), ("draft", 1), ("verify", 4)])
def test_sampled_rows_work_stands_only_in_the_conds_taken_branch(
        program, samplers):
    """In each step program's jaxpr, the sort, the cumulative sum and
    the random bits stand nowhere but inside the taken branch of the
    sampler's lax.cond (one sampler a program, spec_k + 1 in verify);
    the other branch holds none of them, and the argmax stays outside."""
    model, v, _ = _tiny_decoder()
    eng = _engine(model, v, num_slots=2, page_size=8, max_len=24,
                  prefill_len=8, num_pages=6,
                  draft=program in ("draft", "verify"), spec_k=3)
    fn, args = _step_args(eng, program)
    eng._aot_trace = True          # a deliberate trace, as compiled_*()
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    finally:
        eng._aot_trace = False
    eng.close()
    eqns = list(_equations(jaxpr.jaxpr))
    names = {e.primitive.name for e, _ in eqns}
    assert {"sort", "cumsum", "cond", "argmax"} <= names
    assert names & {"random_bits", "threefry2x32"}
    conds = set()
    for eqn, branch in eqns:
        if eqn.primitive.name in _SAMPLED_ONLY:
            assert branch, f"{eqn.primitive.name} outside any cond"
            assert all(i == 1 for _, i in branch), (
                f"{eqn.primitive.name} in a branch not taken")
            conds.add(branch[0][0])
    assert len(conds) == samplers
    for eqn, branch in eqns:
        if id(eqn) in conds:
            assert not branch                # the sampler's cond: top level
            skipped, taken = eqn.params["branches"]
            assert len(skipped.jaxpr.eqns) <= 1      # hands greedy back
            assert [a.aval.dtype for a in eqn.outvars] == [jnp.int32]
            assert eqn.outvars[0].aval.shape == \
                taken.jaxpr.outvars[0].aval.shape
    # the greedy answer is taken outside the cond, once a sampler
    argmaxes = [b for e, b in eqns if e.primitive.name == "argmax"
                and not b]
    assert len(argmaxes) >= samplers
