"""``nn.HeldExperts`` (a sigmoid-routed, dropless expert layer that is
told which experts it holds), its grouped kernel
(ops/pallas/moe_mlp.py) and what ``GroupedQueryAttention`` gained for
the same model: rotary positions, a norm on q and k, a window.

The layer's oracle is written here in float64 numpy, expert by expert
and row by row, from the equations of ISSUE 35 (and of
``benchmark/reference/exaone_moe.py``, which the serving tests hold the
whole model to).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.core.flags import get_flag, set_flags
from paddle_tpu.nn.layers import rotate_half
from paddle_tpu.nn.moe import sigmoid_top_k
from paddle_tpu.ops.pallas import moe_mlp

DIM, HIDDEN, EXPERTS, K = 32, 48, 8, 4


@pytest.fixture
def interpret():
    was = get_flag("pallas_interpret")
    set_flags({"pallas_interpret": True})
    yield
    set_flags({"pallas_interpret": was})


def layer(held=None, shared=None, seed=0):
    m = nn.HeldExperts(DIM, HIDDEN, EXPERTS, K, held=held, scale=2.5,
                       shared_hidden=shared)
    v = m.init(jax.random.key(seed))
    # a selection bias that matters: the initializer leaves it at zero
    v["params"]["bias"] = 0.3 * jax.random.normal(jax.random.key(9),
                                                  (EXPERTS,))
    return m, v


def silu(x):
    return x / (1.0 + np.exp(-x))


def whole_layer(p, x):
    """Every expert's gated part, by hand in float64: [T, E, dim], the
    gates [T, E] (0 where not chosen)."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    s = 1.0 / (1.0 + np.exp(-(x @ p["router"].T)))
    order = np.argsort(-(s + p["bias"]), axis=-1, kind="stable")[:, :K]
    gates = np.zeros_like(s)
    for t, chosen in enumerate(order):
        gates[t, chosen] = 2.5 * s[t, chosen] / (s[t, chosen].sum() + 1e-20)
    return p, x, gates


def test_the_router_bias_moves_the_choice_and_not_the_gate():
    s = jnp.asarray([[0.9, 0.8, 0.7, 0.6, 0.5, 0.1]])
    chosen, gates = sigmoid_top_k(s, jnp.zeros(6), 3, 2.5)
    assert sorted(chosen[0].tolist()) == [0, 1, 2]
    np.testing.assert_allclose(float(gates.sum()), 2.5, rtol=1e-6)
    # a bias lifts expert 5 over expert 2; its gate is its OWN score's
    bias = jnp.asarray([0, 0, 0, 0, 0, 0.65])
    chosen, gates = sigmoid_top_k(s, bias, 3, 2.5)
    assert sorted(chosen[0].tolist()) == [0, 1, 5]
    g = dict(zip(chosen[0].tolist(), gates[0].tolist()))
    np.testing.assert_allclose(g[5], 2.5 * 0.1 / (0.9 + 0.8 + 0.1),
                               rtol=1e-6)
    np.testing.assert_allclose(sum(g.values()), 2.5, rtol=1e-6)


def test_no_row_is_dropped_when_every_row_picks_one_expert():
    """GShard's capacity would keep ceil(k T / E x 1.25) rows of an
    expert and drop the rest: here all T rows go through expert 3."""
    m, v = layer()
    v["params"]["bias"] = jnp.zeros(EXPERTS).at[3].set(10.0)
    x = jax.random.normal(jax.random.key(1), (40, DIM))
    y, rows = m.apply(v, x)
    assert int(rows[3]) == 40 and int(rows.sum()) == 40 * K
    p, x64, gates = whole_layer(v["params"], x)
    assert (gates[:, 3] > 0).all()
    want = sum(gates[:, e:e + 1] * (
        (silu(x64 @ p["w_gate"][e]) * (x64 @ p["w_up"][e])) @ p["w_down"][e])
        for e in range(EXPERTS))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)


def test_the_shares_add_up_to_the_whole_layer():
    """model-configs guide, section 4: over all shares, the routed parts
    summed and the shared expert counted once equal the uncut layer."""
    whole, v = layer(shared=24)
    x = jax.random.normal(jax.random.key(2), (23, DIM))
    want, rows_all = whole.apply(v, x)
    assert int(rows_all.sum()) == 23 * K
    parts, held_rows = [], []
    for first in (0, 2, 4, 6):
        m = nn.HeldExperts(DIM, HIDDEN, EXPERTS, K, held=(first, 2),
                           scale=2.5, shared_hidden=24)
        p = dict(v["params"])
        for name in ("w_gate", "w_up", "w_down"):
            p[name] = v["params"][name][first:first + 2]
        y, rows = m.apply({"params": p, "state": {}}, x,
                          method=lambda x: m.routed(x))
        parts.append(y)
        held_rows.append(rows)
    shared = whole.apply(v, x, method=lambda x: whole.shared(x))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(held_rows),
                                  np.asarray(rows_all))
    # and the hand-written float64 layer agrees with the whole
    p, x64, gates = whole_layer(v["params"], x)
    by_hand = (silu(x64 @ p["shared_gate"]) * (x64 @ p["shared_up"])
               ) @ p["shared_down"]
    for e in range(EXPERTS):
        by_hand += gates[:, e:e + 1] * (
            (silu(x64 @ p["w_gate"][e]) * (x64 @ p["w_up"][e]))
            @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(want), by_hand, atol=2e-5)


def test_rows_that_are_not_live_are_routed_nowhere():
    m, v = layer(held=(2, 4))
    x = jax.random.normal(jax.random.key(3), (16, DIM))
    live = jnp.arange(16) < 9
    y, rows = m.apply(v, x, live)
    y9, rows9 = m.apply(v, x[:9])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows9))
    np.testing.assert_allclose(np.asarray(y[:9]), np.asarray(y9), atol=1e-6)
    assert not np.asarray(y[9:]).any()


@pytest.mark.parametrize("sizes,m", [
    ([10, 0, 13, 5], 40), ([0, 0, 0, 0], 40), ([40, 0, 0, 0], 40),
    ([0, 0, 0, 40], 40), ([1, 1, 1, 1], 40), ([7, 9, 8, 8], 40),
    ([0, 3, 0, 0], 40),
    # most items dead: 2 live of 80 / 16 + 4 - 1 = 8 (tile_m 16)
    ([0, 5, 0, 2], 80)])
def test_the_grouped_kernel_against_ragged_dot(sizes, m):
    """Under the Pallas interpreter: runs that cross a row tile, two runs
    in one tile, EMPTY groups (no work item, no weight read), no row at
    all, a grid whose items are nearly all dead. Rows behind the last
    run are undefined and not compared."""
    g, d, f = 4, 64, 256
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (m, d))
    wg, wu = (0.1 * jax.random.normal(k, (g, d, f)) for k in ks[1:3])
    wd = 0.1 * jax.random.normal(ks[3], (g, f, d))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = moe_mlp.expert_mlp_xla(x, wg, wu, wd, sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    got = moe_mlp.expert_mlp_tpu(x, wg, wu, wd, offsets, interpret=True)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               atol=1e-5)
    gid, tile, live = moe_mlp.work_items(offsets, m, 8)
    assert gid.shape == tile.shape == (m // 8 + g - 1,)
    # one item a (run, row tile) pair that shares a row
    items = sum(len({r // 8 for r in range(int(a), int(b))})
                for a, b in zip(offsets[:-1], offsets[1:]))
    assert int(live[0]) == items


def block_fetches(index, gid, tile, n, off, nf):
    """Fetches a weight stream's pipeline issues over the kernel's whole
    grid: the first step's, then one at every step whose block index
    differs from the step before."""
    fetches, last = 0, None
    for w in range(gid.shape[0]):
        for j in range(nf):
            at = tuple(int(i) for i in index(w, j, gid, tile, n, off))
            fetches += at != last
            last = at
    return fetches


def routing_of(sizes, m, tile_m):
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    gid, tile, n = (np.asarray(a) for a in moe_mlp.work_items(
        jnp.asarray(offsets), m, tile_m))
    return gid, tile, n, offsets


@pytest.mark.parametrize("sizes,m,tile_m,f,tile_f", [
    ([10, 0, 13, 5], 40, 8, 512, 128),
    ([0, 5, 0, 2], 80, 8, 512, 128),
    ([0, 0, 0, 40], 40, 8, 512, 128),
    ([1, 1, 1, 1], 40, 8, 512, 128),
    # the published decode call: 256 x 8 pairs on 16 held experts, tiles
    # 64 x 128 of a hidden dim of 2048, some 100 rows routed here
    ("seeded", 2048, 64, 2048, 128)])
def test_a_dead_work_item_streams_no_weight(sizes, m, tile_m, f, tile_f):
    """The pipeline fetches a block where its index changes from one
    step to the next (``pl.when`` skips the products, not the fetches).
    With the dead items pinned to the last live step's indices, each
    weight stream fetches ``live items x f / tile_f`` blocks; the maps
    before PR 36, ``(expert, 0, j)`` and ``(expert, j, 0)``, fetched an
    expert for every item of the static grid."""
    if sizes == "seeded":
        rng = np.random.default_rng(2147493711)
        sizes = rng.multinomial(100, rng.dirichlet(np.full(16, 0.3)))
    gid, tile, n, off = routing_of(sizes, m, tile_m)
    nf = f // tile_f
    live = int(n[0])
    assert live == moe_mlp.live_items(sizes, tile_m) > 0
    assert gid.shape[0] == m // tile_m + len(sizes) - 1 > live
    for index in (moe_mlp.weight_cols_index, moe_mlp.weight_rows_index):
        pinned = functools.partial(index, nf=nf)
        assert block_fetches(pinned, gid, tile, n, off, nf) == live * nf
    # the control: the parent's maps walk j from 0 again on a dead item
    parent_cols = lambda w, j, gi, *_: (gi[w], 0, j)  # noqa: E731
    parent_down = lambda w, j, gi, *_: (gi[w], j, 0)  # noqa: E731
    for index in (parent_cols, parent_down):
        assert block_fetches(index, gid, tile, n, off, nf) == \
            gid.shape[0] * nf
    # the x rows and the output tile: one fetch a row tile the items use
    assert block_fetches(moe_mlp.rows_index, gid, tile, n, off, nf) == \
        1 + int(np.count_nonzero(np.diff(tile[:live])))


def test_a_grid_without_a_live_item_fetches_each_block_once():
    gid, tile, n, off = routing_of([0, 0, 0, 0], 40, 8)
    assert int(n[0]) == 0
    for index in (moe_mlp.weight_cols_index, moe_mlp.weight_rows_index):
        assert block_fetches(functools.partial(index, nf=4), gid, tile, n,
                             off, 4) == 1


@pytest.mark.parametrize("seed", range(6))
def test_the_hosts_item_count_is_the_kernels(seed):
    """``live_items`` (the engine's count, on the host) against
    ``work_items``' live count on the device, on random group sizes at
    the published call's tiles and at a tiny one's; the sizes are
    summed over a leading axis as the engine sums its layers."""
    rng = np.random.default_rng(seed)
    for m, g, tile_m in ((2048, 16, 64), (1024, 16, 64), (40, 4, 8)):
        layers = rng.integers(1, 5)
        sizes = np.stack([rng.multinomial(
            rng.integers(0, m + 1), rng.dirichlet(np.full(g, 0.5)))
            for _ in range(layers)])
        want = sum(int(routing_of(s, m, tile_m)[2][0]) for s in sizes)
        assert moe_mlp.live_items(sizes, tile_m) == want


def test_the_layer_takes_the_kernel_under_the_interpreter(interpret):
    m, v = layer(held=(0, 4), shared=24)
    x = jax.random.normal(jax.random.key(4), (19, DIM))
    y, rows = m.apply(v, x)
    set_flags({"pallas_interpret": False})
    want, rows_xla = m.apply(v, x)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_xla))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)


def test_tiles_fit_the_default_scoped_vmem_at_the_published_widths():
    """K-EXAONE's experts: 6144 x 2048 in bfloat16, a decode round's
    256 x 8 pairs and a chunk's 128 x 8. No raised limit (a kernel that
    asked for one hung a whole step program: PERF.md section 6, PR 28),
    so the blocks stay under 15 MiB."""
    for m in (2048, 1024):
        tm, tf = moe_mlp.pick_tiles(m, 6144, 2048, 2)
        assert (tm, tf) == (64, 128)
        assert 2 * 3 * 6144 * tf * 2 + 2 * tm * 6144 * (2 + 4) <= 15 * 2 ** 20
    assert moe_mlp.pick_tiles(16, 64, 96, 4) == (16, 96)


def test_rotary_and_qk_norm_against_a_float64_case():
    """Two positions, two heads of four dims, theta 100: the angles of
    lane i and i + 2 are p x theta^(-i/2), and [x1, x2] turns into
    [x1 cos - x2 sin, x2 cos + x1 sin]."""
    x = np.asarray([[[1.0, 2.0, 3.0, 4.0], [0.5, -1.0, 0.0, 2.0]],
                    [[-2.0, 0.0, 1.0, 1.0], [3.0, 1.0, -1.0, 0.5]]])
    pos = np.asarray([3, 10])
    got = rotate_half(jnp.asarray(x, jnp.float32), jnp.asarray(pos), 100.0)
    want = np.empty_like(x)
    for t, p in enumerate(pos):
        for i, freq in enumerate((1.0, 100.0 ** -0.5)):
            c, s = np.cos(p * freq), np.sin(p * freq)
            want[t, :, i] = x[t, :, i] * c - x[t, :, i + 2] * s
            want[t, :, i + 2] = x[t, :, i + 2] * c + x[t, :, i] * s
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    # the norm runs over each head's dims, before the rotation, and a
    # rotation by position keeps q.k a function of the distance alone
    att = nn.GroupedQueryAttention(8, 2, 1, 4, qk_norm=True,
                                   rope_theta=100.0, window=4)
    v = att.init(jax.random.key(0))
    v["params"]["q_norm"]["scale"] = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    u = jax.random.normal(jax.random.key(1), (1, 6, 8))
    q, k = att.apply(v, u, method=lambda u: att._qk(
        u, jnp.arange(6)[None]))
    raw = np.asarray(u[0] @ v["params"]["wq"], np.float64).reshape(6, 2, 4)
    normed = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray([1.0, 2.0, 0.5, 1.5])
    want = np.asarray(rotate_half(jnp.asarray(normed, jnp.float32),
                                  jnp.arange(6), 100.0))
    np.testing.assert_allclose(np.asarray(q[0]).reshape(6, 2, 4), want,
                               atol=1e-5)
    q5, k5 = att.apply(v, u, method=lambda u: att._qk(
        u, jnp.arange(6)[None] + 5))
    np.testing.assert_allclose(
        np.asarray(q[0, 3].reshape(2, 4) @ k[0, 1].reshape(4)),
        np.asarray(q5[0, 3].reshape(2, 4) @ k5[0, 1].reshape(4)), atol=1e-4)


def test_a_window_layer_sees_exactly_its_window():
    """Changing the key at position p - window leaves position p's
    output alone; changing p - window + 1 does not."""
    att = nn.GroupedQueryAttention(16, 2, 1, 8, window=3)
    v = att.init(jax.random.key(0))
    u = jax.random.normal(jax.random.key(1), (1, 8, 16))
    out = att.apply(v, u)
    moved = att.apply(v, u.at[0, 2].add(1.0))
    assert np.allclose(np.asarray(out[0, 5:]), np.asarray(moved[0, 5:]),
                       atol=1e-6)
    assert not np.allclose(np.asarray(out[0, 4]), np.asarray(moved[0, 4]),
                           atol=1e-4)
