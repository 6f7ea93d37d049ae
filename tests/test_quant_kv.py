"""Int8 quantization hot paths (PR-18): paged-KV quantize/dequant units,
int8-vs-f32 decode parity on every read path (XLA gather, Pallas
interpret, the serving engine with prefix sharing and CoW), the chunked
quantized all-reduce vs exact psum, the planner's strategy choice (ICI
keeps f32, DCN picks int8), and the new metric family's scrape validity.

Parity contract: symmetric per-token-row absmax quantization bounds the
per-element error by scale/2 = absmax/254 per row, so decode outputs
(convex combinations of V rows) stay within ~1e-2 of f32 on randn-scale
data; the kernel and the XLA fallback dequantize the SAME gathered pages,
so kernel-vs-fallback parity is much tighter than int8-vs-f32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.ops.attention import (copy_pages, dequantize_pages,
                                      init_page_pool, paged_write,
                                      quantize_kv_rows, quantized_pool)


@pytest.fixture
def flags_guard():
    saved = all_flags()
    yield
    set_flags(saved)


# ------------------------------------------------- round-trip units


class TestKvRoundTrip:
    def test_round_trip_error_bounded_per_row(self, rng):
        x = jnp.asarray(rng.randn(17, 4, 16).astype(np.float32))
        q, scale = quantize_kv_rows(x)
        assert q.dtype == jnp.int8 and scale.shape == (17,)
        deq = q.astype(jnp.float32) * scale[:, None, None]
        err = np.abs(np.asarray(deq - x))
        # symmetric rounding: per-row error <= scale/2 (+ fp slack)
        bound = np.asarray(scale)[:, None, None] / 2 + 1e-6
        assert (err <= bound).all()
        assert err.max() < 0.02

    def test_all_zero_row_dequantizes_to_exact_zero(self):
        x = jnp.zeros((3, 4, 16), jnp.float32)
        q, scale = quantize_kv_rows(x)
        assert not np.asarray(scale).any()
        deq = q.astype(jnp.float32) * scale[:, None, None]
        assert not np.asarray(deq).any()

    def test_max_magnitude_hits_127_and_round_trips(self):
        x = np.zeros((2, 4, 16), np.float32)
        x[0, 1, 3] = 5.0
        x[1, 2, 7] = -3.0
        q, scale = quantize_kv_rows(jnp.asarray(x))
        assert int(q[0, 1, 3]) == 127 and int(q[1, 2, 7]) == -127
        deq = np.asarray(q.astype(jnp.float32) * scale[:, None, None])
        np.testing.assert_allclose(deq[0, 1, 3], 5.0, rtol=1e-6)
        np.testing.assert_allclose(deq[1, 2, 7], -3.0, rtol=1e-6)

    def test_pool_variants_and_rejection(self):
        plain = init_page_pool(4, 2, 8, 16)
        assert not quantized_pool(plain) and set(plain) == {"k", "v"}
        same = init_page_pool(4, 2, 8, 16, kv_dtype=jnp.float32)
        assert not quantized_pool(same)
        q = init_page_pool(4, 2, 8, 16, kv_dtype=jnp.int8)
        assert quantized_pool(q)
        assert q["k"].dtype == jnp.int8 and q["v"].dtype == jnp.int8
        assert q["k_scale"].shape == (4, 8)
        assert q["k_scale"].dtype == jnp.float32
        with pytest.raises(ValueError, match="kv_dtype"):
            init_page_pool(4, 2, 8, 16, kv_dtype=jnp.bfloat16)

    def test_paged_write_quantizes_and_drops_out_of_range(self, rng):
        pool = init_page_pool(4, 2, 8, 16, kv_dtype=jnp.int8)
        k = jnp.asarray(rng.randn(3, 2, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(3, 2, 16).astype(np.float32))
        # third row targets page id == num_pages: dropped, not written
        ids = jnp.asarray([1, 1, 4], jnp.int32)
        offs = jnp.asarray([0, 5, 2], jnp.int32)
        out = paged_write(pool, k, v, ids, offs)
        assert out["k"].dtype == jnp.int8
        kq, ks = quantize_kv_rows(k)
        np.testing.assert_array_equal(np.asarray(out["k"][1, 0]),
                                      np.asarray(kq[0]).reshape(-1))
        np.testing.assert_allclose(float(out["k_scale"][1, 5]),
                                   float(ks[1]))
        # rows not written (incl. the dropped one) stay zero
        assert not np.asarray(out["k"][2]).any()
        assert not np.asarray(out["k_scale"][2]).any()

    def test_copy_pages_moves_scales_bit_exact(self, rng):
        pool = init_page_pool(4, 2, 8, 16, kv_dtype=jnp.int8)
        k = jnp.asarray(rng.randn(8, 2, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(8, 2, 16).astype(np.float32))
        ids = jnp.zeros(8, jnp.int32)
        offs = jnp.arange(8, dtype=jnp.int32)
        pool = paged_write(pool, k, v, ids, offs)
        out = copy_pages(pool, jnp.asarray([0], jnp.int32),
                         jnp.asarray([3], jnp.int32))
        for name in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(out[name][3]),
                                          np.asarray(out[name][0]))

    def test_dequantize_pages_gather_shape(self, rng):
        pool = init_page_pool(6, 2, 8, 16, kv_dtype=jnp.int8)
        k = jnp.asarray(rng.randn(16, 2, 16).astype(np.float32))
        ids = jnp.repeat(jnp.asarray([2, 5], jnp.int32), 8)
        offs = jnp.tile(jnp.arange(8, dtype=jnp.int32), 2)
        pool = paged_write(pool, k, k, ids, offs)
        table = jnp.asarray([[2, 5]], jnp.int32)       # [S=1, Pmax=2]
        deq = dequantize_pages(pool["k"][table], pool["k_scale"][table])
        assert deq.shape == (1, 2, 8, 32) and deq.dtype == jnp.float32
        ref = np.asarray(k).reshape(2, 8, 32)
        np.testing.assert_allclose(np.asarray(deq[0]), ref, atol=0.03)


# ------------------------------------------------- decode read parity


def _ragged_pools(rng, lengths, h=4, hd=16, page_size=8, num_pages=16):
    """f32 and int8 pools holding the SAME per-slot ragged K/V, plus the
    shared page table — mirrors test_serving._ragged_pool."""
    s = len(lengths)
    p_max = max(-(-max(lengths) // page_size), 1) + 1
    pools = {"f32": init_page_pool(num_pages, h, page_size, hd),
             "int8": init_page_pool(num_pages, h, page_size, hd,
                                    kv_dtype=jnp.int8)}
    ptab = np.zeros((s, p_max), np.int32)
    free = list(range(num_pages))
    for i, ln in enumerate(lengths):
        n = -(-ln // page_size)
        pages = [free.pop() for _ in range(n)]
        ptab[i, :n] = pages
        if not ln:
            continue
        k = jnp.asarray(rng.randn(ln, h, hd).astype(np.float32))
        v = jnp.asarray(rng.randn(ln, h, hd).astype(np.float32))
        ids = jnp.asarray([ptab[i, t // page_size] for t in range(ln)],
                          jnp.int32)
        offs = jnp.arange(ln, dtype=jnp.int32) % page_size
        for key in pools:
            pools[key] = paged_write(pools[key], k, v, ids, offs)
    return pools, jnp.asarray(ptab)


def _decode(pool, ptab, lengths, q):
    from paddle_tpu.ops.attention import paged_decode_attention
    return paged_decode_attention(
        q, pool["k"], pool["v"], ptab, jnp.asarray(lengths, jnp.int32),
        k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"))


class TestInt8DecodeParity:
    LENGTHS = [13, 0, 37, 8]

    def test_xla_int8_close_to_f32(self, rng, flags_guard):
        set_flags({"use_pallas_decode": False})
        pools, ptab = _ragged_pools(rng, self.LENGTHS)
        q = jnp.asarray(rng.randn(4, 4, 16).astype(np.float32))
        out_f32 = _decode(pools["f32"], ptab, self.LENGTHS, q)
        out_i8 = _decode(pools["int8"], ptab, self.LENGTHS, q)
        np.testing.assert_allclose(np.asarray(out_i8),
                                   np.asarray(out_f32), atol=0.02)
        # the quantized path is genuinely lossy — not silently f32
        assert np.abs(np.asarray(out_i8 - out_f32)).max() > 0

    def test_pallas_interpret_matches_xla_int8(self, rng, flags_guard):
        pools, ptab = _ragged_pools(rng, self.LENGTHS)
        q = jnp.asarray(rng.randn(4, 4, 16).astype(np.float32))
        set_flags({"use_pallas_decode": False})
        ref = _decode(pools["int8"], ptab, self.LENGTHS, q)
        set_flags({"use_pallas_decode": True, "pallas_interpret": True})
        out = _decode(pools["int8"], ptab, self.LENGTHS, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_inactive_slot_exactly_zero(self, rng, flags_guard):
        set_flags({"use_pallas_decode": True, "pallas_interpret": True})
        pools, ptab = _ragged_pools(rng, self.LENGTHS)
        q = jnp.asarray(rng.randn(4, 4, 16).astype(np.float32))
        out = _decode(pools["int8"], ptab, self.LENGTHS, q)
        assert not np.asarray(out[1]).any()


# ------------------------------------------------- serving engine


def _tiny_decoder(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    model = GPTDecoder(cfg)
    return model, model.init(jax.random.key(seed)), cfg


def _serve(model, v, prompts, max_new=6, **cfg_kw):
    from paddle_tpu.serving import ServeConfig, ServingEngine
    base = dict(num_slots=2, page_size=8, max_len=48, prefill_len=16,
                num_pages=12)
    base.update(cfg_kw)
    eng = ServingEngine(model, v, ServeConfig(**base))
    for p in prompts:
        eng.submit(p, max_new=max_new)
    done = {r.id: r for r in eng.drain()}
    return eng, done


class TestInt8ServingEngine:
    def test_deterministic_one_trace_smaller_pool(self, rng):
        model, v, cfg = _tiny_decoder()
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (5, 11, 19)]
        eng_a, done_a = _serve(model, v, prompts, kv_dtype="int8")
        eng_b, done_b = _serve(model, v, prompts, kv_dtype="int8")
        eng_f, done_f = _serve(model, v, prompts)
        assert eng_a.decode_traces == 1 and eng_a.prefill_traces == 1
        assert eng_a.kv_dtype_name() == "int8"
        assert eng_f.kv_dtype_name() == "f32"
        # int8 pool: 2x1B payload + 2x scale rows vs 2x4B payload
        assert eng_a.kv_pool_bytes() < eng_f.kv_pool_bytes() / 2
        for rid in done_a:
            # quantization is deterministic: independent int8 engines
            # replay token-exact
            np.testing.assert_array_equal(done_a[rid].output,
                                          done_b[rid].output)
            assert len(done_a[rid].output) == len(done_f[rid].output)

    def test_prefix_hit_and_cow_token_exact(self, rng, flags_guard):
        """Shared quantized pages: a prefix-cache hit re-reads the SAME
        int8 rows + scales, so the repeat is token-exact vs the cold
        run; a diverging tail CoWs without perturbing the original."""
        set_flags({"serve_prefix_cache": True})
        model, v, cfg = _tiny_decoder()
        p = rng.randint(0, cfg.vocab_size, (19,)).astype(np.int32)
        div = p.copy()
        div[-1] = (div[-1] + 1) % cfg.vocab_size
        _, cold = _serve(model, v, [p], kv_dtype="int8")
        _, colddiv = _serve(model, v, [div], kv_dtype="int8")
        hits0 = _metrics.counter("serve.prefix_hits").total()
        eng, done = _serve(model, v, [p, p, div], kv_dtype="int8")
        assert _metrics.counter("serve.prefix_hits").total() > hits0
        np.testing.assert_array_equal(done[0].output, cold[0].output)
        np.testing.assert_array_equal(done[1].output, cold[0].output)
        np.testing.assert_array_equal(done[2].output, colddiv[0].output)

    def test_page_pressure_parity(self, rng, flags_guard):
        """A page-starved int8 engine (stall/requeue path) retires the
        same tokens as an ample one — quantized rewrites replay exact."""
        set_flags({"serve_prefix_cache": False})
        model, v, cfg = _tiny_decoder()
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in (9, 17, 13, 5)]
        _, ample = _serve(model, v, prompts, kv_dtype="int8",
                          num_pages=24)
        _, tight = _serve(model, v, prompts, kv_dtype="int8",
                          num_pages=7)
        assert len(tight) == len(ample) == 4
        for rid in ample:
            np.testing.assert_array_equal(tight[rid].output,
                                          ample[rid].output)

    def test_kv_quant_pages_gauge_tracks_pool_use(self, rng):
        model, v, cfg = _tiny_decoder()
        p = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
        from paddle_tpu.serving import ServeConfig, ServingEngine
        eng = ServingEngine(model, v, ServeConfig(
            num_slots=2, page_size=8, max_len=48, prefill_len=16,
            num_pages=12, kv_dtype="int8"))
        eng.submit(p, max_new=4)
        eng.step()
        assert _metrics.gauge("serve.kv_quant_pages").value() >= 1
        eng.drain()


# ------------------------------------------------- quantized all-reduce


class TestQuantizedAllReduce:
    def test_psum_parity_zero_clamps(self, rng):
        from paddle_tpu.parallel import communicator as C
        x = rng.randn(8, 100).astype(np.float32)
        out, clamps = jax.pmap(
            lambda v: C.quantized_psum(v, "dp", chunk=16),
            axis_name="dp")(x)
        ref = x.sum(0)
        assert not np.asarray(clamps).any()
        # every rank agrees (shared pmax scale -> exact integer sums)
        for i in range(8):
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(out[0]))
        err = np.abs(np.asarray(out[0]) - ref)
        assert err.max() / np.abs(ref).max() < 0.02

    def test_pmean_parity(self, rng):
        from paddle_tpu.parallel import communicator as C
        x = rng.randn(8, 64).astype(np.float32)
        out, _ = jax.pmap(
            lambda v: C.quantized_pmean(v, "dp", chunk=32),
            axis_name="dp")(x)
        np.testing.assert_allclose(np.asarray(out[0]), x.mean(0),
                                   atol=0.02)

    def test_wire_bytes_matches_costmodel(self):
        """quant_wire_bytes and costmodel.collective_bytes price the
        same layout — bench rows and the planner cannot drift."""
        from paddle_tpu.parallel import communicator as C
        from paddle_tpu.parallel.autoplan import costmodel as cm
        from paddle_tpu.parallel.autoplan import ModelSpec
        spec = ModelSpec(name="tiny", vocab=1024, hidden=64, layers=2,
                         heads=4, intermediate=128, seq=32, batch=64)
        elems = cm.dp_grad_elements(spec, tp=1, pp=1)
        chunk = 64
        priced = cm.collective_bytes(spec, dp=4, tp=1, pp=1,
                                     dp_collective="int8",
                                     quant_chunk=chunk)["dp"]
        assert C.quant_wire_bytes(elems, 4, chunk=chunk) == priced
        # and the manual expression, for one known case
        assert C.quant_wire_bytes(1000, 4, chunk=64) == pytest.approx(
            2 * 3 / 4 * (1000 + 16 * 4))

    def test_resolve_strategy(self, flags_guard):
        from paddle_tpu.parallel import communicator as C
        assert C.resolve_quant_allreduce("on") is True
        assert C.resolve_quant_allreduce("off") is False
        assert C.resolve_quant_allreduce(
            "auto", crosses_slices=True) is True
        assert C.resolve_quant_allreduce(
            "auto", crosses_slices=False) is False
        set_flags({"quant_allreduce": "on"})
        assert C.resolve_quant_allreduce() is True

    def test_publish_clamp_count_delta(self):
        from paddle_tpu.parallel import communicator as C
        before = _metrics.counter("quant.overflow_clamps").total()
        last = C.publish_clamp_count({"clamps": 5}, last=0)
        assert last == 5
        last = C.publish_clamp_count({"clamps": 7}, last=last)
        assert last == 7
        after = _metrics.counter("quant.overflow_clamps").total()
        assert after - before == 7


# ------------------------------------------------- planner choice


def _dcn_topology():
    from paddle_tpu.parallel.autoplan import Topology, get_topology
    ici = get_topology("cpu4")
    return ici, Topology(name="dcn2x2", num_chips=4,
                         hbm_bytes=ici.hbm_bytes,
                         peak_flops=ici.peak_flops,
                         intra_bw=ici.intra_bw, inter_bw=1e9,
                         num_slices=2)


def _spec():
    from paddle_tpu.parallel.autoplan import ModelSpec
    return ModelSpec(name="tiny", vocab=1024, hidden=64, layers=2,
                     heads=4, intermediate=128, seq=32, batch=64)


class TestPlannerQuantChoice:
    def test_ici_keeps_f32_with_reason(self):
        from paddle_tpu.parallel.autoplan import plan
        ici, _ = _dcn_topology()
        p = plan(_spec(), topology=ici, quant_allreduce="auto")
        assert p.dp > 1
        assert p.predicted["dp_collective"] == "f32"
        reason = p.predicted["dp_collective_reason"]
        assert "f32" in reason and "quantize" in reason
        s = p.summary()
        assert s["dp_collective"] == "f32" and s["dp_wire_bytes"] > 0

    def test_dcn_chooses_int8_and_saves_wire_bytes(self):
        from paddle_tpu.parallel.autoplan import plan
        from paddle_tpu.parallel.autoplan import costmodel as cm
        _, dcn = _dcn_topology()
        p = plan(_spec(), topology=dcn, quant_allreduce="auto")
        assert p.dp > 1
        assert p.predicted["dp_collective"] == "int8"
        assert "int8" in p.predicted["dp_collective_reason"]
        f32_bytes = cm.collective_bytes(
            _spec(), p.dp, p.tp, p.pp, dp_collective="f32")["dp"]
        assert p.summary()["dp_wire_bytes"] < f32_bytes / 2

    def test_forced_strategy_overrides_auto(self):
        from paddle_tpu.parallel.autoplan import plan
        ici, dcn = _dcn_topology()
        p_on = plan(_spec(), topology=ici, quant_allreduce="on")
        assert p_on.predicted["dp_collective"] == "int8"
        assert "forced" in p_on.predicted["dp_collective_reason"]
        p_off = plan(_spec(), topology=ici, quant_allreduce="off")
        assert p_off.predicted["dp_collective"] == "f32"
        assert "forced" in p_off.predicted["dp_collective_reason"]
        # with quantization forbidden, an f32 gradient exchange over the
        # 1 GB/s DCN prices out — the planner drops the dp axis entirely
        p_dcn = plan(_spec(), topology=dcn, quant_allreduce="off")
        assert p_dcn.dp == 1


# ------------------------------------------------- metric family scrape


class TestQuantMetricFamily:
    """The PR-18 quantization metric family: cataloged, preregisterable,
    and scrape-valid before any quantized traffic."""

    NAMES = ["collective.quant_bytes", "collective.quant_degraded",
             "quant.overflow_clamps", "serve.kv_quant_degraded",
             "serve.kv_quant_pages"]

    def test_family_cataloged(self):
        from paddle_tpu.observability import catalog
        for name in self.NAMES:
            assert name in catalog.CATALOG, name

    def test_family_scrapes_with_help_and_type(self):
        from paddle_tpu.observability import catalog
        from paddle_tpu.observability import exporter as E
        from paddle_tpu.observability import metrics as M
        r = M.MetricsRegistry()
        catalog.preregister(self.NAMES, registry=r)
        c = r.counter("collective.quant_bytes")
        c.inc(128, direction="send")
        c.inc(128, direction="recv")
        r.counter("quant.overflow_clamps").inc(2)
        r.gauge("serve.kv_quant_pages").set(3)
        text = E.render_prometheus(r)
        for name in ("collective_quant_bytes", "collective_quant_degraded",
                     "quant_overflow_clamps", "serve_kv_quant_degraded",
                     "serve_kv_quant_pages"):
            assert f"# HELP {name} " in text, name
            assert f"# TYPE {name} " in text, name
        assert 'collective_quant_bytes{direction="send"} 128' in text
        assert "quant_overflow_clamps 2" in text
        assert "serve_kv_quant_pages 3" in text
