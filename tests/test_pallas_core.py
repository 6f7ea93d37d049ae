"""Per-kernel parity for the shared Pallas primitive core.

Every kernel family built on ops/pallas/core.py runs its interpret-mode
Pallas path against its XLA fallback at awkward shapes — ragged lengths,
causal masks, padded tiles (totals that don't divide the block) — and
must agree to 1e-5 in value AND gradient. Plus the consolidated
kernel_mode/log_fallback refusal protocol: enable-flag off is silent,
unsupported shapes count `pallas.fallback{kernel}` on EVERY call but log
once per (kernel, reason), and the tiling/masking helpers hold their
contracts standalone.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas as pallas_pkg
from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import core


@pytest.fixture
def flags():
    saved = all_flags()
    yield set_flags
    set_flags(saved)


def _rs(seed=0):
    return np.random.RandomState(seed)


# --- flash attention --------------------------------------------------


def _flash_inputs(b=2, h=2, tq=24, tk=24, d=64, seed=0):
    rng = _rs(seed)
    mk = lambda *s: jnp.asarray(0.1 * rng.randn(*s).astype(np.float32))
    return mk(b, h, tq, d), mk(b, h, tk, d), mk(b, h, tk, d)


class TestFlashParity:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("lengths", [None, (24, 7)])
    def test_fwd_and_grad_vs_chunked(self, flags, causal, lengths):
        from paddle_tpu.ops.pallas.flash_attention import (
            chunked_attention, flash_attention)
        q, k, v = _flash_inputs()
        mask = (None if lengths is None else
                jnp.arange(24)[None, :] < jnp.asarray(lengths)[:, None])
        co = jnp.asarray(_rs(9).randn(*q.shape).astype(np.float32))

        def loss(fn):
            # block 16 against T=24: a padded 8-wide tail tile each axis
            def f(q, k, v):
                return jnp.sum(fn(q, k, v, causal=causal, kv_mask=mask,
                                  block_q=16, block_k=16) * co)
            return f

        flags({"pallas_interpret": True})
        o_p, g_p = jax.value_and_grad(loss(flash_attention),
                                      argnums=(0, 1, 2))(q, k, v)
        o_x, g_x = jax.value_and_grad(
            lambda q, k, v: jnp.sum(chunked_attention(
                q, k, v, causal=causal, kv_mask=mask) * co),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(o_p, o_x, atol=1e-4, rtol=1e-4)
        for a, b_ in zip(g_p, g_x):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_fully_masked_batch_row_is_exact_zero(self, flags):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = _flash_inputs()
        mask = jnp.arange(24)[None, :] < jnp.asarray([0, 24])[:, None]
        flags({"pallas_interpret": True})
        out = flash_attention(q, k, v, kv_mask=mask, block_q=16,
                              block_k=16)
        assert float(jnp.abs(out[0]).max()) == 0.0
        assert float(jnp.abs(out[1]).max()) > 0.0


# --- paged decode attention -------------------------------------------


class TestDecodeParity:
    def test_ragged_lengths_vs_dense_gather(self, flags):
        from paddle_tpu.ops.attention import (
            _paged_attention_xla, paged_decode_attention)
        rng = _rs(1)
        s, h, hd, n_pages, page, pmax = 3, 2, 16, 6, 8, 4
        q = jnp.asarray(0.2 * rng.randn(s, h, hd).astype(np.float32))
        kp = jnp.asarray(0.2 * rng.randn(n_pages, page, h * hd)
                         .astype(np.float32))
        vp = jnp.asarray(0.2 * rng.randn(n_pages, page, h * hd)
                         .astype(np.float32))
        table = jnp.asarray(
            rng.randint(0, n_pages, (s, pmax)).astype(np.int32))
        lengths = jnp.asarray([0, 5, 30], jnp.int32)  # 30 = ragged tail
        scale = 1.0 / hd ** 0.5
        flags({"pallas_interpret": True, "use_pallas_decode": True})
        out = paged_decode_attention(q, kp, vp, table, lengths)
        ref = _paged_attention_xla(q, kp, vp, table, lengths, scale)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        # inactive slot (length 0): exactly zero, not NaN/softmax-of-all
        assert float(jnp.abs(out[0]).max()) == 0.0

    @pytest.mark.parametrize("page", [16, 64, 128])
    @pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
    def test_token_major_pool_vs_dense_gather(self, flags, kv, page):
        """The kernel on THE pool layout ([N, ps, H*hd], written by
        paged_write) against the gather-and-mask oracle: an empty slot,
        a one-token slot, a slot whose table repeats page 0, a ragged
        tail; int8 pools carry their per-row scales."""
        from paddle_tpu.ops.attention import (
            _paged_attention_xla, init_page_pool, paged_decode_attention,
            paged_write)
        rng = _rs(page)
        s, h, hd, pmax = 4, 4, 16, 3
        n_pages = s * pmax
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                 "int8": jnp.float32}[kv]
        pool = init_page_pool(n_pages, h, page, hd, dtype,
                              kv_dtype=jnp.int8 if kv == "int8" else None)
        assert pool["k"].shape == (n_pages, page, h * hd)
        rows = n_pages * page
        pool = paged_write(
            pool, jnp.asarray(rng.randn(rows, h, hd).astype(np.float32)),
            jnp.asarray(rng.randn(rows, h, hd).astype(np.float32)),
            jnp.repeat(jnp.arange(n_pages), page),
            jnp.tile(jnp.arange(page), n_pages))
        table = rng.permutation(n_pages).reshape(s, pmax).astype(np.int32)
        table[2] = 0                               # page 0, three times
        table = jnp.asarray(table)
        lengths = jnp.asarray([0, 1, 3 * page, page + 3], jnp.int32)
        q = jnp.asarray(rng.randn(s, h, hd).astype(np.float32))
        scales = {n: pool[n] for n in ("k_scale", "v_scale") if n in pool}
        flags({"pallas_interpret": True, "use_pallas_decode": True})
        before = metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0)
        out = paged_decode_attention(q, pool["k"], pool["v"], table,
                                     lengths, **scales)
        assert metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0) == before  # the kernel ran
        ref = _paged_attention_xla(q, pool["k"], pool["v"], table,
                                   lengths, 1.0 / hd ** 0.5, **scales)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)
        assert float(jnp.abs(out[0]).max()) == 0.0
        assert float(jnp.abs(out[1:]).min(axis=(1, 2)).max()) > 0.0

    # (heads, K/V heads, head_dim, page_size, pages a slot, int8 pool):
    # the serving models' head layouts at small pools; a window layer's
    # ring is one page of 128 rows a slot
    LAYOUTS = {"mha": (16, 16, 64, 16, 3, False),
               "gqa": (64, 8, 128, 16, 3, False),
               "mqa": (20, 1, 128, 16, 3, False),
               "ring": (64, 8, 128, 128, 1, False),
               "int8": (16, 16, 64, 16, 3, True)}

    @staticmethod
    def _lengths(pattern, page, p_max):
        full = page * p_max
        return [min(n, full) for n in {
            # empty, one token, exactly a page, a page and one, full
            "edges": [0, 1, page, page + 1, full, 0],
            # only the last slot has pages: the first copy is started
            # before any slot runs
            "last_live": [0, 0, 0, 0, 0, full - 3],
            # live slots between idle ones: the next live slot's first
            # page is fetched across the idle steps
            "gaps": [0, page + 1, 0, 0, 2, 0],
        }[pattern]]

    def _paged_case(self, layout, lengths, seed):
        from paddle_tpu.ops.attention import init_page_pool, paged_write
        h, kvh, hd, page, p_max, int8 = self.LAYOUTS[layout]
        rng = _rs(seed)
        s = len(lengths)
        n_pages = s * p_max + 1
        pool = init_page_pool(n_pages, kvh, page, hd, jnp.float32,
                              kv_dtype=jnp.int8 if int8 else None)
        rows = n_pages * page
        pool = paged_write(
            pool, jnp.asarray(rng.randn(rows, kvh, hd).astype(np.float32)),
            jnp.asarray(rng.randn(rows, kvh, hd).astype(np.float32)),
            jnp.repeat(jnp.arange(n_pages), page),
            jnp.tile(jnp.arange(page), n_pages))
        table = (1 + rng.permutation(s * p_max)).reshape(s, p_max)
        q = jnp.asarray(rng.randn(s, h, hd).astype(np.float32))
        return pool, table.astype(np.int32), q

    @pytest.mark.parametrize("pattern", ["edges", "last_live", "gaps"])
    @pytest.mark.parametrize("layout", list(LAYOUTS))
    def test_live_pages_vs_dense_gather(self, flags, layout, pattern):
        """The kernel loops over each slot's live pages alone (one grid
        step a slot, the pages copied by hand, the next one in flight):
        against the gather-and-mask oracle at every head layout a served
        model has, the length edges and idle slots between live ones."""
        from paddle_tpu.ops.attention import (_paged_attention_xla,
                                              paged_decode_attention)
        h, kvh, hd, page, p_max, int8 = self.LAYOUTS[layout]
        lengths = jnp.asarray(self._lengths(pattern, page, p_max),
                              jnp.int32)
        pool, table, q = self._paged_case(layout, lengths, seed=p_max)
        scales = {n: pool[n] for n in ("k_scale", "v_scale") if n in pool}
        flags({"pallas_interpret": True, "use_pallas_decode": True})
        before = metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0)
        out = paged_decode_attention(q, pool["k"], pool["v"],
                                     jnp.asarray(table), lengths, **scales)
        assert metrics.counter("pallas.fallback").snapshot().get(
            "kernel=decode_attention", 0) == before  # the kernel ran
        ref = _paged_attention_xla(q, pool["k"], pool["v"],
                                   jnp.asarray(table), lengths,
                                   1.0 / hd ** 0.5, **scales)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        idle = np.asarray(lengths) == 0
        assert float(jnp.abs(out[idle]).max()) == 0.0
        assert float(jnp.abs(out[~idle]).min(axis=(1, 2)).max()) > 0.0

    @pytest.mark.parametrize("layout", ["mha", "gqa", "int8"])
    def test_pages_past_a_slot_length_are_never_read(self, flags, layout):
        """Table entries past a slot's length name a page of NaN (and,
        int8, NaN scales): the output equals, bit for bit, the same call
        with those entries 0. A loop that ran on past the live pages and
        masked them would carry the NaN into the sums (0 x NaN)."""
        from paddle_tpu.ops.attention import paged_decode_attention
        h, kvh, hd, page, p_max, int8 = self.LAYOUTS[layout]
        lengths = jnp.asarray([0, 5, page + 1, 0, 2 * page, 1], jnp.int32)
        pool, table, q = self._paged_case(layout, lengths, seed=7)
        poison = table.shape[0] * p_max          # the pool's last page
        table = np.where(table == poison, 0, table)    # no live entry
        pool = {n: x.at[poison].set(jnp.nan) if x.dtype == jnp.float32
                else x for n, x in pool.items()}
        if not int8:
            assert bool(jnp.isnan(pool["k"][poison]).all())
        dead = (np.arange(p_max)[None, :] * page
                >= np.asarray(lengths)[:, None])
        scales = {n: pool[n] for n in ("k_scale", "v_scale") if n in pool}
        flags({"pallas_interpret": True, "use_pallas_decode": True})
        outs = [paged_decode_attention(
                    q, pool["k"], pool["v"],
                    jnp.asarray(np.where(dead, fill, table)), lengths,
                    **scales)
                for fill in (poison, 0)]
        assert not bool(jnp.isnan(outs[0]).any())
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))


# --- fused (add+)layer norm -------------------------------------------


class TestLayerNormParity:
    def test_fwd_and_grad_ragged_rows(self, flags):
        from paddle_tpu.ops.pallas.layer_norm import layer_norm_fused
        rng = _rs(2)
        x = jnp.asarray(rng.randn(37, 24).astype(np.float32))
        g = jnp.asarray((rng.rand(24) + 0.5).astype(np.float32))
        b = jnp.asarray(rng.randn(24).astype(np.float32))
        co = jnp.asarray(rng.randn(37, 24).astype(np.float32))

        def loss(x, g, b):
            return jnp.sum(layer_norm_fused(x, g, b, begin_norm_axis=1)
                           * co)

        flags({"use_pallas_layer_norm": True, "pallas_interpret": True})
        o_p, g_p = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, g, b)
        flags({"pallas_interpret": False})
        o_x, g_x = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, g, b)
        np.testing.assert_allclose(o_p, o_x, atol=1e-4, rtol=1e-4)
        for a, b_ in zip(g_p, g_x):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_add_ln_fwd_and_grad(self, flags):
        from paddle_tpu.ops.pallas.layer_norm import add_layer_norm_fused
        rng = _rs(3)
        x = jnp.asarray(rng.randn(21, 16).astype(np.float32))
        h = jnp.asarray(rng.randn(21, 16).astype(np.float32))
        g = jnp.asarray((rng.rand(16) + 0.5).astype(np.float32))
        b = jnp.asarray(rng.randn(16).astype(np.float32))

        def loss(x, h, g, b):
            return jnp.sum(add_layer_norm_fused(x, h, g, b,
                                                begin_norm_axis=1) ** 2)

        flags({"use_pallas_layer_norm": True, "pallas_interpret": True})
        o_p, g_p = jax.value_and_grad(loss, argnums=(0, 1))(x, h, g, b)
        flags({"pallas_interpret": False})
        o_x, g_x = jax.value_and_grad(loss, argnums=(0, 1))(x, h, g, b)
        np.testing.assert_allclose(o_p, o_x, atol=1e-4, rtol=1e-4)
        for a, b_ in zip(g_p, g_x):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


# --- fused cross entropy (fwd stats + bwd kernels) --------------------


class TestXentParity:
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_loss_and_grads_vs_chunked_xla(self, flags, smoothing):
        from paddle_tpu.ops.fused import fused_xent
        rng = _rs(4)
        n, h, v = 19, 48, 133  # nothing divides the tiles
        hid = jnp.asarray(0.2 * rng.randn(n, h).astype(np.float32))
        w = jnp.asarray(0.2 * rng.randn(v, h).astype(np.float32))
        b = jnp.asarray(0.1 * rng.randn(v).astype(np.float32))
        lbl = jnp.asarray(rng.randint(0, v, n).astype(np.int32))

        def loss(hid, w, b):
            return jnp.mean(fused_xent(hid, w, lbl, bias=b,
                                       label_smoothing=smoothing))

        flags({"use_pallas_xent": True, "use_pallas_xent_bwd": True,
               "pallas_interpret": True})
        o_p, g_p = jax.value_and_grad(loss, argnums=(0, 1, 2))(hid, w, b)
        flags({"use_pallas_xent": False, "use_pallas_xent_bwd": False})
        o_x, g_x = jax.value_and_grad(loss, argnums=(0, 1, 2))(hid, w, b)
        np.testing.assert_allclose(o_p, o_x, atol=1e-5, rtol=1e-5)
        for a, b_ in zip(g_p, g_x):
            np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-5)


# --- fused GLU/MLP (the new kernel proving the layer) -----------------


class TestMLPParity:
    @pytest.mark.parametrize("act", ["gelu", "silu"])
    @pytest.mark.parametrize("gated", [False, True])
    def test_fwd_and_grad_vs_unfused(self, flags, act, gated):
        from paddle_tpu.ops.pallas.mlp import _mlp_unfused, fused_mlp
        rng = _rs(5)
        r, h, i = 37, 24, 56  # ragged against every tile heuristic
        mk = lambda *s: jnp.asarray(0.3 * rng.randn(*s)
                                    .astype(np.float32))
        x, w1, b1, w2, b2 = mk(r, h), mk(h, i), mk(i), mk(i, h), mk(h)
        wg, bg = (mk(h, i), mk(i)) if gated else (None, None)

        def loss_fused(*a):
            return jnp.sum(fused_mlp(*a, act=act) ** 2)

        def loss_ref(x, w1, b1, w2, b2, wg=None, bg=None):
            return jnp.sum(_mlp_unfused(x, w1, b1, w2, b2, wg, bg,
                                        act) ** 2)

        args = (x, w1, b1, w2, b2) + ((wg, bg) if gated else ())
        nargs = len(args)
        flags({"use_pallas_mlp": True, "pallas_interpret": True})
        o_p, g_p = jax.value_and_grad(loss_fused,
                                      argnums=tuple(range(nargs)))(*args)
        o_x, g_x = jax.value_and_grad(loss_ref,
                                      argnums=tuple(range(nargs)))(*args)
        np.testing.assert_allclose(o_p, o_x, atol=1e-4, rtol=1e-4)
        for a, b_ in zip(g_p, g_x):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_batched_leading_dims_and_flag_off(self, flags):
        from paddle_tpu.ops.pallas.mlp import fused_mlp
        rng = _rs(6)
        mk = lambda *s: jnp.asarray(0.3 * rng.randn(*s)
                                    .astype(np.float32))
        x, w1, b1, w2, b2 = (mk(2, 5, 16), mk(16, 32), mk(32),
                             mk(32, 16), mk(16))
        flags({"use_pallas_mlp": True, "pallas_interpret": True})
        out = fused_mlp(x, w1, b1, w2, b2)
        assert out.shape == x.shape
        flags({"use_pallas_mlp": False})
        ref = fused_mlp(x, w1, b1, w2, b2)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


# --- the refusal protocol: kernel_mode + log_fallback ------------------


class TestRefusalProtocol:
    def _counter(self, kernel):
        return metrics.counter("pallas.fallback").value(kernel=kernel)

    def test_unsupported_counts_every_call_logs_once(self, flags, caplog):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        pallas_pkg._fallback_logged.clear()
        flags({"pallas_interpret": True})
        q = jnp.zeros((1, 1, 16, 32), jnp.float32)  # D=32: not 64-lane
        before = self._counter("flash_attention")
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.pallas"):
            flash_attention(q, q, q)
            flash_attention(q, q, q)
        assert self._counter("flash_attention") == before + 2
        refusals = [r for r in caplog.records
                    if "flash_attention" in r.message
                    and "refused" in r.message]
        assert len(refusals) == 1  # latched per (kernel, reason)
        assert "D=32" in refusals[0].message
        # a DIFFERENT reason logs again
        q2 = jnp.zeros((1, 1, 12, 64), jnp.float32)  # T=12: not 8-aligned
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.pallas"):
            flash_attention(q2, q2, q2)
        refusals = [r for r in caplog.records
                    if "flash_attention" in r.message
                    and "refused" in r.message]
        assert len(refusals) == 2

    def test_enable_flag_off_is_silent(self, flags, caplog):
        from paddle_tpu.ops.pallas.layer_norm import layer_norm_fused
        flags({"use_pallas_layer_norm": False, "pallas_interpret": True})
        before = self._counter("layer_norm")
        x = jnp.ones((8, 16), jnp.float32)
        with caplog.at_level(logging.DEBUG, logger="paddle_tpu.pallas"):
            layer_norm_fused(x, begin_norm_axis=1)
        assert self._counter("layer_norm") == before  # no fallback noise
        assert not [r for r in caplog.records if "layer_norm" in r.message]

    def test_off_tpu_without_interpret_is_none(self, flags):
        flags({"pallas_interpret": False})
        assert core.kernel_mode("flash_attention") is None
        flags({"pallas_interpret": True})
        assert core.kernel_mode("flash_attention") in ("tpu", "interpret")

    def test_decode_page_size_refusal_counts(self, flags):
        from paddle_tpu.ops.attention import paged_decode_attention
        flags({"pallas_interpret": True, "use_pallas_decode": True})
        rng = _rs(7)
        q = jnp.asarray(rng.randn(1, 1, 16).astype(np.float32))
        kp = jnp.asarray(rng.randn(2, 6, 16).astype(np.float32))
        table = jnp.zeros((1, 2), jnp.int32)
        before = self._counter("decode_attention")
        out = paged_decode_attention(q, kp, kp, table,
                                     jnp.asarray([3], jnp.int32))
        assert self._counter("decode_attention") == before + 1
        assert out.shape == q.shape  # XLA fallback still answered


# --- the shared tiling/masking helpers --------------------------------


class TestCoreHelpers:
    def test_legal_block_lane_rounding(self):
        assert core.legal_block(96, 512, interpret=True) == 96
        # off-interpret Mosaic wants full 128 lanes when available
        assert core.legal_block(96, 512, interpret=False) == 128
        assert core.legal_block(512, 40, interpret=True) == 40

    def test_pick_block_rows_budget_and_cap(self):
        assert core.pick_block_rows(10_000, 64, 4) <= 256
        assert core.pick_block_rows(4, 64, 4) >= 1
        # a huge row never exceeds the VMEM budget
        br = core.pick_block_rows(10_000, 1 << 18, 4)
        assert br * (1 << 18) * 4 * 2 <= 2 * 2 ** 21

    def test_tail_valid_cols_masks_exact_tail(self):
        m = core.tail_valid_cols(1, 16, 24, (4, 16))  # tile 1: cols 16..31
        assert np.asarray(m).sum() == 4 * 8  # only 24-16=8 cols valid

    def test_softmax_finalize_zero_rows(self):
        l = jnp.zeros((4, 1), jnp.float32)
        acc = jnp.ones((4, 8), jnp.float32)
        out = core.softmax_finalize(l, acc, jnp.float32)
        assert float(jnp.abs(out).max()) == 0.0
