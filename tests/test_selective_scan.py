"""The scan kernel (``selective_scan`` / ``ssm_state_update``) through
the Pallas interpreter against the plain recurrence, and the paged
decode kernel with K/V heads of its own against dense attention.

Tolerance 1e-5: float32 on both sides, the same products summed in
another order over at most 128 positions (sound runs read under 1e-6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import mamba
from paddle_tpu.ops.attention import _paged_attention_xla
from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention_tpu
from paddle_tpu.ops.pallas.selective_scan import (pick_block_d,
                                                  selective_scan_tpu)

TOL = 1e-5
D, N, S = 256, 16, 5


def plain_recurrence(x, dt, b, c, z, a, d, h, length):
    """One sequence, position by position, in numpy float64."""
    x, dt, b, c, z, a, d, h = (np.asarray(v, np.float64)
                               for v in (x, dt, b, c, z, a, d, h))
    out = np.zeros_like(x)
    for t in range(length):
        h = np.exp(dt[t][None, :] * a) * h + b[t][:, None] * (dt[t] * x[t])
        y = (h * c[t][:, None]).sum(0) + d * x[t]
        out[t] = y * z[t] / (1.0 + np.exp(-z[t]))
    return out, h


def operands(batch, t, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    x = jax.random.normal(ks[0], (batch, t, D))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, t, D)) - 2.0)
    b = jax.random.normal(ks[2], (batch, t, N))
    c = jax.random.normal(ks[3], (batch, t, N))
    z = jax.random.normal(ks[4], (batch, t, D))
    a = -jnp.exp(0.5 * jax.random.normal(ks[5], (N, D)))
    d = jax.random.normal(ks[6], (D,))
    state = jax.random.normal(ks[7], (S, N, D))
    return x, dt, b, c, z, a, d, state


@pytest.mark.parametrize("t,lengths,name", [
    (1, (1, 0, 1), "ssm_state_update"),      # a decode round, one idle
    (7, (7, 4, 0), "selective_scan"),        # masked tails
    (128, (128, 77, 1), "selective_scan"),   # a whole prefill chunk
])
def test_kernel_matches_the_plain_recurrence(t, lengths, name):
    x, dt, b, c, z, a, d, state = operands(3, t)
    slots = jnp.asarray([3, 0, 4], jnp.int32)
    fresh = jnp.asarray([1, 0, 0], jnp.int32)
    out, new = selective_scan_tpu(
        x, dt, b, c, z, a, d, state, slots, jnp.asarray(lengths, jnp.int32),
        fresh, name=name, interpret=True)
    for row, (slot, n) in enumerate(zip((3, 0, 4), lengths)):
        h0 = np.zeros((N, D)) if row == 0 else state[slot]
        want, h = plain_recurrence(x[row], dt[row], b[row], c[row], z[row],
                                   a, d, h0, n)
        if n:
            assert np.max(np.abs(np.asarray(out[row, :n]) - want[:n])) < TOL
        # positions at or beyond the length leave h as it was
        assert np.max(np.abs(np.asarray(new[slot]) - h)) < TOL
    for slot in (1, 2):                      # slots not named: untouched
        assert np.array_equal(np.asarray(new[slot]), np.asarray(state[slot]))


def test_the_xla_fallback_is_the_same_recurrence():
    x, dt, b, c, z, a, d, state = operands(2, 9, seed=4)
    lengths = jnp.asarray([9, 3], jnp.int32)
    slots = jnp.asarray([1, 2], jnp.int32)
    fresh = jnp.asarray([False, True])
    got = mamba._selective_scan_xla(x, dt, b, c, z, a, d, state, slots,
                                    lengths, fresh)
    want = selective_scan_tpu(x, dt, b, c, z, a, d, state, slots, lengths,
                              fresh.astype(jnp.int32), name="selective_scan",
                              interpret=True)
    mask = (np.arange(9)[None, :] < np.asarray(lengths)[:, None])[..., None]
    assert np.max(np.abs(np.where(mask, got[0] - want[0], 0))) < TOL
    assert np.max(np.abs(got[1] - want[1])) < TOL


def test_conv_window_skips_padding_and_idle_rows():
    """The carried window is the last K-1 REAL inputs."""
    k, d, s = 4, 8, 3
    x = jnp.arange(2 * 6 * d, dtype=jnp.float32).reshape(2, 6, d)
    w = jnp.ones((k, d))
    state = -jnp.ones((k - 1, s, d))
    y, new = mamba.causal_conv1d(
        x, w, jnp.zeros(d), state, jnp.asarray([2, 0]),
        jnp.asarray([2, 0]), jnp.asarray([False, False]))
    # row 0 is 2 real positions into slot 2: window = [old last, x0, x1]
    assert np.array_equal(new[:, 2], np.stack([state[2, 2], x[0, 0], x[0, 1]]))
    assert np.array_equal(new[:, 0], state[:, 0])      # length 0: kept
    assert np.array_equal(new[:, 1], state[:, 1])      # not named
    assert np.allclose(y[0, 0], x[0, 0] - 3.0)         # three old taps of -1
    _, fresh = mamba.causal_conv1d(
        x, w, jnp.zeros(d), state, jnp.asarray([2, 0]),
        jnp.asarray([1, 6]), jnp.asarray([True, True]))
    assert np.array_equal(fresh[:, 2], np.stack(
        [np.zeros(d), np.zeros(d), x[0, 0]]))


def test_block_width_is_lane_aligned_and_fits():
    assert pick_block_d(128, 5120) == 1280 and 5120 % 1280 == 0
    assert pick_block_d(1, 5120) == 5120
    assert pick_block_d(7, 200) == 200       # no lane-aligned divisor


def dense_attention(q, k_pages, v_pages, table, lengths, scale, kv_heads):
    """Per slot, plain softmax attention over its cached rows, K/V heads
    repeated for their query heads: the meaning of the kernel."""
    s, h, hd = q.shape
    out = np.zeros((s, h, hd))
    k = np.asarray(k_pages, np.float64)[np.asarray(table)]
    v = np.asarray(v_pages, np.float64)[np.asarray(table)]
    for i in range(s):
        n = int(lengths[i])
        if not n:
            continue
        ki = k[i].reshape(-1, kv_heads, hd)[:n].repeat(h // kv_heads, 1)
        vi = v[i].reshape(-1, kv_heads, hd)[:n].repeat(h // kv_heads, 1)
        sc = np.einsum("hd,thd->ht", np.asarray(q[i], np.float64), ki) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[i] = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), vi)
    return out


@pytest.mark.parametrize("heads,kv_heads,hd", [
    (20, 1, 128),     # the multi-query layer of the hybrid decoder
    (4, 1, 16), (4, 2, 16),
    (4, 4, 16),       # every head its own K/V: the kernel as it was
])
def test_paged_decode_with_its_own_kv_head_count(heads, kv_heads, hd):
    s, n_pages, ps, p_max = 3, 12, 8, 4
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (s, heads, hd))
    k = jax.random.normal(ks[1], (n_pages, ps, kv_heads * hd))
    v = jax.random.normal(ks[2], (n_pages, ps, kv_heads * hd))
    table = jax.random.randint(ks[3], (s, p_max), 0, n_pages)
    lengths = jnp.asarray([5, 0, 29], jnp.int32)
    got = paged_decode_attention_tpu(q, k, v, table, lengths, 0.3,
                                     interpret=True)
    want = dense_attention(q, k, v, table, lengths, 0.3, kv_heads)
    assert np.max(np.abs(np.asarray(got) - want)) < TOL
    oracle = _paged_attention_xla(q, k, v, table, lengths, 0.3)
    assert np.max(np.abs(np.asarray(got) - np.asarray(oracle))) < TOL
