"""Serving fast path: paged KV cache + decode attention parity, the
continuous-batching engine, and GPTDecoder.generate sampling coverage.

Parity chain (the acceptance contract): dense per-slot softmax (numpy
oracle) == XLA gather-and-mask fallback == Pallas decode kernel
(interpret mode) at <=1e-5 f32 across ragged lengths — then up the
stack: paged model decode == contiguous-cache decode == full forward,
and the engine's continuously-batched outputs == per-request
generate(), token-exact, through mid-stream slot reuse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import all_flags, set_flags


@pytest.fixture
def flags_guard():
    saved = all_flags()
    yield
    set_flags(saved)


def _tiny_decoder(seed=0, use_flash=False):
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    cfg.use_flash = use_flash
    model = GPTDecoder(cfg)
    return model, model.init(jax.random.key(seed)), cfg


def _ragged_pool(rng, lengths, h=4, hd=16, page_size=8, num_pages=16):
    """Build a paged pool holding per-slot K/V of the given ragged
    lengths; returns (pool, page_table, dense per-slot K/V dict)."""
    from paddle_tpu.ops.attention import init_page_pool, paged_write
    s = len(lengths)
    p_max = max(-(-max(lengths) // page_size), 1) + 1
    pool = init_page_pool(num_pages, h, page_size, hd)
    ptab = np.zeros((s, p_max), np.int32)
    free = list(range(num_pages))
    dense = {}
    for i, ln in enumerate(lengths):
        n = -(-ln // page_size)
        pages = [free.pop() for _ in range(n)]
        ptab[i, :n] = pages
        if not ln:
            continue
        k = rng.randn(ln, h, hd).astype(np.float32)
        v = rng.randn(ln, h, hd).astype(np.float32)
        dense[i] = (k, v)
        ids = np.asarray([ptab[i, t // page_size] for t in range(ln)],
                         np.int32)
        offs = np.arange(ln, dtype=np.int32) % page_size
        pool = paged_write(pool, jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(ids), jnp.asarray(offs))
    return pool, jnp.asarray(ptab), dense


def _dense_reference(q, dense, lengths):
    """Per-slot full-softmax attention oracle in numpy."""
    s, h, hd = q.shape
    out = np.zeros((s, h, hd), np.float32)
    for i, ln in enumerate(lengths):
        if not ln:
            continue
        k, v = dense[i]
        sc = np.einsum("hd,lhd->hl", np.asarray(q[i]), k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hl,lhd->hd", p, v)
    return out


class TestPagedDecodeAttention:
    LENGTHS = [13, 0, 37, 8]

    def test_xla_gather_matches_dense_ragged(self, rng):
        from paddle_tpu.ops.attention import _paged_attention_xla
        pool, ptab, dense = _ragged_pool(rng, self.LENGTHS)
        q = jnp.asarray(rng.randn(len(self.LENGTHS), 4, 16)
                        .astype(np.float32))
        out = _paged_attention_xla(q, pool["k"], pool["v"], ptab,
                                   jnp.asarray(self.LENGTHS), 1 / 4.0)
        ref = _dense_reference(q, dense, self.LENGTHS)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
        assert float(jnp.abs(out[1]).max()) == 0.0  # empty slot -> zeros

    def test_pallas_interpret_matches_xla_ragged(self, rng, flags_guard):
        from paddle_tpu.ops.attention import (_paged_attention_xla,
                                              paged_decode_attention)
        pool, ptab, dense = _ragged_pool(rng, self.LENGTHS)
        q = jnp.asarray(rng.randn(len(self.LENGTHS), 4, 16)
                        .astype(np.float32))
        lens = jnp.asarray(self.LENGTHS)
        ref = _paged_attention_xla(q, pool["k"], pool["v"], ptab, lens,
                                   1 / 4.0)
        set_flags({"pallas_interpret": True, "use_pallas_decode": True})
        out = paged_decode_attention(q, pool["k"], pool["v"], ptab, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_reference(q, dense,
                                                    self.LENGTHS),
                                   atol=1e-5)

    def test_unaligned_page_size_falls_back_with_counter(self, rng,
                                                         flags_guard):
        from paddle_tpu.observability import metrics as _metrics
        from paddle_tpu.ops.attention import paged_decode_attention
        pool, ptab, dense = _ragged_pool(rng, [5, 3], page_size=6,
                                         num_pages=8)
        q = jnp.asarray(rng.randn(2, 4, 16).astype(np.float32))
        set_flags({"pallas_interpret": True, "use_pallas_decode": True})
        before = _metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0)
        out = paged_decode_attention(q, pool["k"], pool["v"], ptab,
                                     jnp.asarray([5, 3]))
        after = _metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0)
        assert after == before + 1
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_reference(q, dense, [5, 3]),
                                   atol=1e-5)

    def test_paged_write_drops_out_of_range(self, rng):
        from paddle_tpu.ops.attention import init_page_pool, paged_write
        pool = init_page_pool(4, 2, 8, 16)
        vals = jnp.asarray(rng.randn(2, 2, 16).astype(np.float32))
        pool = paged_write(pool, vals, vals,
                           jnp.asarray([1, 4]),    # 4 == num_pages: drop
                           jnp.asarray([3, 0]))
        assert float(jnp.abs(pool["k"][1, 3]).max()) > 0.0
        assert float(jnp.abs(pool["k"][0]).max()) == 0.0
        assert float(jnp.abs(pool["k"][2:]).max()) == 0.0


class TestPagedModelDecode:
    def test_paged_matches_full_forward_ragged(self, rng):
        """Teacher-forced paged decoding of three ragged slots must
        reproduce the full forward's logits position by position."""
        model, v, cfg = _tiny_decoder()
        lens = [5, 3, 7]
        total = 12
        ids = rng.randint(0, cfg.vocab_size, (3, total)).astype(np.int32)
        full = np.asarray(model.apply(v, jnp.asarray(ids)))  # [3, T, V]

        def run(_):
            caches = model.init_paged_caches(num_pages=12, page_size=4)
            ptab = jnp.asarray(
                [[3 * s + i for i in range(3)] + [0]
                 for s in range(3)], jnp.int32)          # 3 pages/slot
            # ragged prefill in one padded batch
            lp = max(lens)
            prompt = jnp.asarray(ids[:, :lp])
            logits0, caches = model.paged_prefill(
                prompt, jnp.asarray(lens), caches, ptab)
            outs = {i: [] for i in range(3)}
            for i, ln in enumerate(lens):
                outs[i].append(logits0[i])
            # teacher-forced continuation to `total` tokens per slot
            lengths = jnp.asarray(lens)
            for step in range(total - min(lens)):
                cur = np.minimum(np.asarray(lengths), total - 1)
                toks = jnp.asarray(ids[np.arange(3), cur])
                active = jnp.asarray(np.asarray(lengths) < total - 1)
                logits, caches = model.paged_decode_step(
                    toks, caches, ptab, lengths, active)
                for i in range(3):
                    if bool(active[i]):
                        outs[i].append(logits[i])
                lengths = lengths + active.astype(lengths.dtype)
            return outs

        outs = model.apply(v, jnp.zeros((1,)), method=run)
        for i, ln in enumerate(lens):
            got = np.asarray(jnp.stack(outs[i]))      # logits at pos>=ln-1
            want = full[i, ln - 1:ln - 1 + got.shape[0]]
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_slot_reuse_after_release(self, rng):
        """A slot freed by one request and reused by another (different
        pages, different length) must decode the newcomer exactly as a
        fresh engine would — token-for-token vs generate()."""
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder(seed=2)
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=1, page_size=8, max_len=32, prefill_len=16,
            num_pages=4))
        p1 = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
        p2 = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
        eng.submit(p1, max_new=5)
        eng.submit(p2, max_new=7)       # queued until slot 0 frees
        done = {r.id: r for r in eng.drain()}
        assert eng.decode_traces == 1
        for rid, (p, mn) in enumerate([(p1, 5), (p2, 7)]):
            ref = model.apply(v, jnp.asarray(p[None, :]),
                              method=lambda pr: model.generate(pr, mn))
            np.testing.assert_array_equal(done[rid].output,
                                          np.asarray(ref)[0])


class TestServingEngine:
    def test_continuous_batching_matches_generate(self, rng):
        """Six mixed-length requests through two slots: every output
        token-exact vs the per-request generate() reference, one decode
        trace across all admissions, all pages/slots recycled."""
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=32, prefill_len=16,
            num_pages=10))
        specs = [(5, 6), (11, 9), (3, 4), (8, 7), (16, 5), (2, 8)]
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L, _ in specs]
        for p, (_, mn) in zip(prompts, specs):
            eng.submit(p, max_new=mn)
        done = {r.id: r for r in eng.drain()}
        assert len(done) == 6
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        for i, (p, (_, mn)) in enumerate(zip(prompts, specs)):
            ref = model.apply(v, jnp.asarray(p[None, :]),
                              method=lambda pr: model.generate(pr, mn))
            np.testing.assert_array_equal(done[i].output,
                                          np.asarray(ref)[0])
        # everything returned to the allocator (idle prefix-cache pages
        # count: they are reclaimable on demand)
        assert sorted(eng._free_slots) == [0, 1]
        assert eng._pages_available() == 10
        assert not eng._page_table.any() and not eng._lengths.any()

    def test_eos_terminates_early(self, rng):
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        ref = np.asarray(model.apply(
            v, jnp.asarray(prompt[None, :]),
            method=lambda pr: model.generate(pr, 8)))[0]
        gen = ref[6:]
        eos = int(gen[2])                # the third generated token
        expect_n = int(np.where(gen == eos)[0][0]) + 1  # first hit wins
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=1, page_size=8, max_len=32, prefill_len=8))
        eng.submit(prompt, max_new=8, eos_id=eos)
        (req,) = eng.drain()
        assert req.tokens[-1] == eos and len(req.tokens) == expect_n

    def test_temperature_sampling_deterministic_per_seed(self, rng):
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (4, 7)]

        def run(seed):
            eng = ServingEngine(model, v, ServeConfig(
                num_slots=2, page_size=8, max_len=24, prefill_len=8,
                temperature=1.0, seed=seed))
            for p in prompts:
                eng.submit(p, max_new=6)
            return {r.id: list(r.tokens) for r in eng.drain()}

        assert run(7) == run(7)          # same seed -> same samples
        assert all(t < cfg.vocab_size for ts in run(7).values()
                   for t in ts)

    def test_sampling_defaults_from_flags_and_per_request_override(
            self, rng, flags_guard):
        """ServeConfig top_k/top_p left as None resolve from the
        serve_top_k / serve_top_p flags; per-submit kwargs win over the
        config defaults; a missing seed derives deterministically from
        the engine seed and request id; and a per-request top_k=1
        override is bit-exact greedy even under a hot temperature."""
        from paddle_tpu.serving import ServeConfig, ServingEngine
        set_flags({"serve_top_k": 5, "serve_top_p": 0.9})
        model, v, cfg = _tiny_decoder()
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=24, prefill_len=8,
            temperature=0.8, seed=3))
        assert (eng.cfg.top_k, eng.cfg.top_p) == (5, 0.9)
        prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        rid_default = eng.submit(prompt, max_new=5)
        rid_override = eng.submit(prompt.copy(), max_new=5,
                                  temperature=1.3, top_k=1, top_p=0.0,
                                  seed=42)
        done = {r.id: r for r in eng.drain()}
        d = done[rid_default]
        assert (d.temperature, d.top_k, d.top_p) == (0.8, 5, 0.9)
        assert d.seed == (3 * 1_000_003 + rid_default) & 0xFFFFFFFF
        o = done[rid_override]
        assert (o.temperature, o.top_k, o.top_p, o.seed) == (
            1.3, 1, 0.0, 42)
        ref = model.apply(v, jnp.asarray(prompt[None, :]),
                          method=lambda m: model.generate(m, 5))
        np.testing.assert_array_equal(o.output, np.asarray(ref)[0])

    @pytest.mark.parametrize("sampled_new", [0, 4],
                             ids=["all_greedy", "mixed"])
    def test_step_span_counts_the_sampled_rows(
            self, rng, new_step_counts, profiler_session, sampled_new):
        """Which branch of the sampler each decode round took, read from
        the host's copy of the knobs: ``sampled_rows`` on the
        ``serve.step`` span (0 = the argmax alone), on the step that
        LAUNCHED the round. A greedy request of 9 tokens (8 decode
        rounds) runs beside a sampled one of ``sampled_new`` tokens
        (none, or 3 rounds of the 8, after which the sampled slot waits
        for its last token as a greedy row); the ninth step only reads
        the eighth round and the last one is idle."""
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=32, prefill_len=16,
            num_pages=8, metrics_port=0))
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 7)]
        with profiler_session():
            eng.submit(prompts[0], max_new=9)
            if sampled_new:
                eng.submit(prompts[1], max_new=sampled_new,
                           temperature=0.8, top_p=0.9, seed=5)
            eng.drain()
            eng.step()                       # an idle round: no decode
        sampled_rounds = max(sampled_new - 1, 0)
        assert new_step_counts("sampled_rows") == \
            [1] * sampled_rounds + [0] * (10 - sampled_rounds)
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        eng.close()

    def test_page_exhaustion_stalls_then_recovers(self, rng):
        """With a pool too small for both requests' full growth, a slot
        stalls (counter fires) but decoding still completes correctly
        once pages free up."""
        from paddle_tpu.observability import metrics as _metrics
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        # 2 slots x up to 24 tokens = 6 pages of 8 needed unconstrained;
        # give 4 so growth competes
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=24, prefill_len=8,
            num_pages=4))
        prompts = [rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
                   for _ in range(2)]
        for p in prompts:
            eng.submit(p, max_new=12)
        done = {r.id: r for r in eng.drain()}
        for i, p in enumerate(prompts):
            ref = model.apply(v, jnp.asarray(p[None, :]),
                              method=lambda pr: model.generate(pr, 12))
            np.testing.assert_array_equal(done[i].output,
                                          np.asarray(ref)[0])
        assert eng._pages_available() == 4


class TestServeExport:
    def test_export_decode_round_trips(self, rng, tmp_path):
        """The exported serve step (save_train_program state-feedback
        contract) must load back via load_program and reproduce the
        engine's greedy next-token choice on live pool state."""
        from paddle_tpu.io.inference import load_program
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=24, prefill_len=8))
        eng.submit(rng.randint(0, cfg.vocab_size, (5,))
                   .astype(np.int32), max_new=6)
        eng.step()                      # live pools + one running slot
        path = eng.export_decode(str(tmp_path / "serve"))
        prog = load_program(path)
        state_flat = jax.tree_util.tree_leaves(
            (eng._params, eng._caches))
        # the first step launched round 1 and left it in flight: the
        # pending tokens are the device's, the lengths the launch's
        out = prog(*state_flat, np.asarray(eng._tokens_dev),
                   eng._page_table.copy(), eng._lengths.copy(),
                   eng._active.copy())
        toks = np.asarray(out[0])
        assert toks.shape == (2,) and toks.dtype == np.int32
        # parity: the engine's own round 2 must pick the same token for
        # the running slot (launched by the next step, read by the one
        # after it)
        slot = next(iter(eng._running))
        req = eng._running[slot]
        eng.step()
        eng.step()
        assert req.tokens[2] == int(toks[slot])


class TestAdmissionStaging:
    def test_prompts_staged_at_submit_not_in_step(self, rng, monkeypatch):
        """Admission must never pay the host->device prompt transfer
        inside step(): staging runs (async) at submit() through the
        DataLoader placement path, and no block_until_ready-style sync
        happens while submitting (the PR-4 no-sync discipline)."""
        from paddle_tpu.serving import ServeConfig, ServingEngine
        model, v, cfg = _tiny_decoder()
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=24, prefill_len=8))
        phase = {"cur": "submit"}
        calls = []
        orig = eng._stager.place

        def spy(batch):
            calls.append(phase["cur"])
            return orig(batch)

        monkeypatch.setattr(eng._stager, "place", spy)

        orig_burt = jax.block_until_ready

        def no_sync(*a, **k):
            raise AssertionError("block_until_ready during submit "
                                 "(prompt staging must be async)")

        monkeypatch.setattr(jax, "block_until_ready", no_sync)
        for L in (3, 6, 5, 4):
            eng.submit(rng.randint(0, cfg.vocab_size, (L,))
                       .astype(np.int32), max_new=4)
        # prompts are device-committed jax arrays (one per prefill
        # chunk) before any step runs
        assert all(isinstance(c, jax.Array)
                   for r in eng._queue for c in r.device_prompt)
        monkeypatch.setattr(jax, "block_until_ready", orig_burt)
        phase["cur"] = "step"
        eng.drain()
        assert calls == ["submit"] * 4


class TestGenerateSampling:
    """GPTDecoder.generate sampling coverage (satellite): temperature
    path determinism/shape, bf16-vs-f32 greedy cache parity."""

    def test_temperature_sampling_shape_and_determinism(self, rng):
        model, v, cfg = _tiny_decoder()
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 5),
                                         dtype=np.int32))

        def gen(key):
            return np.asarray(model.apply(
                v, prompt, method=lambda pr: model.generate(
                    pr, 7, temperature=0.8, key=key)))

        a = gen(jax.random.key(3))
        b = gen(jax.random.key(3))
        assert a.shape == (2, 12)
        np.testing.assert_array_equal(a, b)      # fixed key -> fixed draw
        np.testing.assert_array_equal(a[:, :5], np.asarray(prompt))
        assert a.max() < cfg.vocab_size and a.min() >= 0

    def test_temperature_requires_key(self, rng):
        from paddle_tpu.core.enforce import EnforceError
        model, v, cfg = _tiny_decoder()
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 4),
                                         dtype=np.int32))
        with pytest.raises(EnforceError):
            model.apply(v, prompt,
                        method=lambda pr: model.generate(
                            pr, 3, temperature=1.0))

    def test_bf16_cache_greedy_parity(self, rng):
        """bf16 KV storage must agree with f32 on greedy argmax tokens
        for a short horizon (the serving default's quality contract)."""
        model, v, cfg = _tiny_decoder(seed=4)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 6),
                                         dtype=np.int32))
        o32 = np.asarray(model.apply(
            v, prompt, method=lambda pr: model.generate(
                pr, 8, cache_dtype=jnp.float32)))
        o16 = np.asarray(model.apply(
            v, prompt, method=lambda pr: model.generate(
                pr, 8, cache_dtype=jnp.bfloat16)))
        assert o16.shape == o32.shape == (2, 14)
        # identical prompts; generated tokens nearly always agree on a
        # tiny model — require the first step exact and >=90% overall
        np.testing.assert_array_equal(o16[:, 6], o32[:, 6])
        assert float(np.mean(o16 == o32)) >= 0.9


# ---------------------------------------------------------------------
# The round launched before the round before it is read (engine.py, "the
# round"): whatever the order of launch and read, the tokens are the
# unbatched reference's, for a model without per-slot state and for one
# with it.

_SERVED = {}


def _served(kind):
    """(model, variables, jitted plain forward), built once a kind."""
    if kind not in _SERVED:
        if kind == "gpt":
            model, variables, _ = _tiny_decoder(seed=4)
        else:
            from paddle_tpu.models.hybrid import HybridConfig, HybridDecoder
            model = HybridDecoder(HybridConfig.tiny())
            variables = model.init(jax.random.key(0))
        forward = jax.jit(lambda v, ids: model.apply(v, ids))
        _SERVED[kind] = model, variables, forward
    return _SERVED[kind]


def _lag_engine(kind, **kw):
    from paddle_tpu.serving import ServeConfig, ServingEngine
    model, variables, _ = _served(kind)
    kw = {"num_slots": 2, "page_size": 8, "max_len": 64, "prefill_len": 8,
          "prefix_cache": False, "metrics_port": 0, **kw}
    return ServingEngine(model, variables, ServeConfig(**kw))


def _unbatched(kind, prompt, n, draw=None):
    """The n tokens decoding gives one request alone, by the plain
    forward over the sequence so far (padded to one length: causal, so
    the padding changes nothing before it). ``draw(logits, i)`` picks
    token i; the default is greedy."""
    _, variables, forward = _served(kind)
    ids = np.zeros(64, np.int32)
    ids[:len(prompt)] = prompt
    for i, pos in enumerate(range(len(prompt), len(prompt) + n)):
        logits = forward(variables, jnp.asarray(ids)[None])[0, pos - 1]
        ids[pos] = int(jnp.argmax(logits)) if draw is None \
            else draw(logits, i)
    return ids[len(prompt):len(prompt) + n].tolist()


def _lag_prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).astype(np.int32) for n in lengths]


def _steps(eng, after=None, limit=200):
    """step() until nothing is queued or running; returns, for every
    request, the step that admitted it and the step that retired it."""
    admitted, retired = {}, {}
    n = 0
    while eng._queue or eng._running:
        for req in eng.step():
            retired[req.id] = n
        for req in eng._running.values():
            admitted.setdefault(req.id, n)
        if after is not None:
            after(eng, n)
        n += 1
        assert n < limit
    return admitted, retired


@pytest.mark.parametrize("kind", ["gpt", "hybrid"])
class TestTrailingFetch:
    def test_requests_ending_by_max_new(self, kind):
        """(a) Five requests through two slots, one to three chunks a
        prompt: exact tokens, every round but the first launched before
        the one before it was read, and no row computed in vain: a
        request that ends by count gets no surplus row."""
        eng = _lag_engine(kind)
        ps = _lag_prompts((5, 13, 9, 21, 3))
        rids = [eng.submit(p, max_new=mn)
                for p, mn in zip(ps, (6, 1, 4, 7, 2))]
        _steps(eng)
        for p, rid, mn in zip(ps, rids, (6, 1, 4, 7, 2)):
            assert eng.requests[rid].tokens == _unbatched(kind, p, mn)
            assert eng.requests[rid].retire_reason == "length"
        assert eng.late_rows == 0 and eng.rounds_overlapped >= 8
        assert eng.decode_traces == 1 and eng.prefill_traces == 1
        assert not eng._inflight and eng._pages_available() == 16
        assert not eng._lengths.any() and not eng._gen_counts.any()
        eng.close()

    def test_eos_mid_stream_is_learnt_a_round_late(self, kind):
        """(b) A request's eos_id arrives while the other slot runs on:
        the round already launched holds a row for it, whose token is
        discarded (``late_rows``); its neighbour is untouched, and the
        request admitted into the freed slot is exact."""
        ps = _lag_prompts((6, 9, 4), seed=3)
        ref = _unbatched(kind, ps[0], 10)
        eos = ref[3]
        want = ref[:ref.index(eos) + 1]
        assert 1 < len(want) < 10
        eng = _lag_engine(kind)
        a = eng.submit(ps[0], max_new=10, eos_id=eos)
        b = eng.submit(ps[1], max_new=12)
        c = eng.submit(ps[2], max_new=5)         # waits for a's slot
        admitted, retired = _steps(eng)
        assert eng.requests[a].tokens == want
        assert eng.requests[a].retire_reason == "eos"
        assert eng.requests[b].tokens == _unbatched(kind, ps[1], 12)
        assert eng.requests[c].tokens == _unbatched(kind, ps[2], 5)
        assert eng.requests[c].slot == eng.requests[a].slot
        assert admitted[c] == retired[a] + 1
        assert eng.late_rows == 1
        from paddle_tpu.observability import metrics as _metrics
        assert _metrics.counter("serve.late_rows").total() >= 1
        eng.close()

    def test_eos_as_the_first_token(self, kind):
        """(b') The first token is the eos_id: the request has a row in
        the round launched with its admission, and nothing else."""
        (p,) = _lag_prompts((7,), seed=4)
        first = _unbatched(kind, p, 1)
        eng = _lag_engine(kind)
        rid = eng.submit(p, max_new=6, eos_id=first[0])
        (req,) = eng.step()
        assert req.id == rid and req.tokens == first
        assert eng.late_rows == 1 and not eng._inflight
        assert not eng._running and eng._pages_available() == 16
        eng.close()

    def test_slot_and_pages_reused_the_step_after_a_release(self, kind):
        """(c) One slot and just the pages one request needs: the
        second request is admitted, into the same slot and the same
        pages, in the very step after the first one's EOS was read,
        behind the round that still holds the first one's surplus row;
        a third follows a request that ended by count."""
        ps = _lag_prompts((9, 11, 5), seed=5)
        ref = _unbatched(kind, ps[0], 8)
        eos = ref[2]
        want = ref[:ref.index(eos) + 1]
        eng = _lag_engine(kind, num_slots=1, max_len=24, num_pages=3)
        a = eng.submit(ps[0], max_new=8, eos_id=eos)
        b = eng.submit(ps[1], max_new=6)
        c = eng.submit(ps[2], max_new=7)
        admitted, retired = _steps(eng)
        assert eng.requests[a].tokens == want
        assert eng.requests[b].tokens == _unbatched(kind, ps[1], 6)
        assert eng.requests[c].tokens == _unbatched(kind, ps[2], 7)
        assert admitted[b] == retired[a] + 1
        assert admitted[c] == retired[b] + 1
        assert eng.late_rows == 1 and eng._pages_available() == 3
        eng.close()

    def test_a_stalled_slot_and_a_preemption_under_a_round_in_flight(
            self, kind):
        """(d) Three pages for two requests that want three each: a slot
        stalls for a page (its pending token waits on the device, its
        length does not move), the pool deadlocks, the engine reads what
        is in flight before it chooses a victim, and both requests
        finish exactly."""
        from paddle_tpu.observability import metrics as _metrics
        stalls = _metrics.counter("serve.page_stalls").total()
        eng = _lag_engine(kind, max_len=24, num_pages=3)
        ps = _lag_prompts((7, 7), seed=1)
        rids = [eng.submit(p, max_new=12) for p in ps]
        _steps(eng)
        assert _metrics.counter("serve.page_stalls").total() > stalls
        assert sum(eng.requests[r].preemptions for r in rids) >= 1
        for p, rid in zip(ps, rids):
            assert eng.requests[rid].tokens == _unbatched(kind, p, 12)
        assert eng.rounds_overlapped > 0 and not eng._inflight
        eng.close()

    def test_seeded_sampling_draws_with_the_hosts_count(self, kind):
        """(e) temperature > 0: token i of a request is drawn with
        fold(fold(base, seed), i), the count the host advances at the
        LAUNCH, beside a greedy request in the other slot: the same
        draws as the law gives one request alone."""
        eng = _lag_engine(kind, seed=11)
        ps = _lag_prompts((6, 10), seed=6)
        knobs = dict(temperature=0.9, top_k=40, top_p=0.95, seed=1234)

        def draw(logits, i):
            one = lambda x, dt: np.asarray([x], dt)
            return int(eng._sample(
                logits[None], one(knobs["temperature"], np.float32),
                one(knobs["top_k"], np.int32),
                one(knobs["top_p"], np.float32),
                one(knobs["seed"], np.uint32), one(i, np.int32))[0])

        s = eng.submit(ps[0], max_new=9, **knobs)
        g = eng.submit(ps[1], max_new=7)
        _steps(eng)
        want = _unbatched(kind, ps[0], 9, draw)
        assert eng.requests[s].tokens == want
        assert want != _unbatched(kind, ps[0], 9)     # it did sample
        assert eng.requests[g].tokens == _unbatched(kind, ps[1], 7)
        eng.close()

    def test_cancel_drops_the_rows_in_flight(self, kind):
        """cancel() between two steps: the cancelled request's row in
        the round in flight is dropped unread (no late row: it did not
        end at EOS), its neighbour is exact, the slot is reusable."""
        eng = _lag_engine(kind)
        ps = _lag_prompts((5, 8, 6), seed=7)
        a = eng.submit(ps[0], max_new=12)
        b = eng.submit(ps[1], max_new=9)
        for _ in range(3):
            eng.step()
        read = list(eng.requests[a].tokens)
        assert eng._inflight and a in {
            r.id for fl in eng._inflight for r in fl.rows.values()}
        assert eng.cancel(a)
        assert a not in {r.id for fl in eng._inflight
                         for r in fl.rows.values()}
        c = eng.submit(ps[2], max_new=4)
        _steps(eng)
        assert eng.requests[a].status == "cancelled"
        assert eng.requests[a].tokens == read
        assert eng.requests[b].tokens == _unbatched(kind, ps[1], 9)
        assert eng.requests[c].tokens == _unbatched(kind, ps[2], 4)
        assert eng.late_rows == 0
        eng.close()
