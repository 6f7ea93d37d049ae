"""The K/V page write's Pallas kernel against XLA's scatter.

``ops/attention.py paged_write`` hands a pool of 2- or 4-byte values
with 128-lane rows to one Pallas call a layer
(``ops/pallas/paged_write.py``) and everything else to
``pool.at[page_ids, offsets].set(rows, mode="drop")``. The kernel has to
write the same bytes to the same places: these cases compare the two
bit for bit through the Pallas interpreter, at the widths of the three
served configurations, with rows routed out of range as the engine
routes its idle slots and a chunk's pad positions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import all_flags, set_flags
from paddle_tpu.ops.attention import init_page_pool, paged_write


@pytest.fixture
def interpret():
    saved = all_flags()
    set_flags({"pallas_interpret": True})
    yield
    set_flags(saved)


def _scatter(pool, k, v, ids, offs):
    """XLA's write, as paged_write ran it before the kernel."""
    return {n: pool[n].at[ids, offs].set(
        x.reshape(x.shape[0], -1).astype(pool[n].dtype), mode="drop")
        for n, x in (("k", k), ("v", v))}


def _bits(x):
    return np.asarray(x).view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _assert_same_bits(got, want):
    for n in ("k", "v"):
        np.testing.assert_array_equal(_bits(got[n]), _bits(want[n]))


def _calls(fn, *args):
    """The Pallas calls of fn's program, by name."""
    text = str(jax.make_jaxpr(fn)(*args))
    return [line for line in text.splitlines() if "pallas_call" in line]


def _pool(rng, shape, dtype):
    """A pool whose every row is already written (random bits), so a
    row the kernel sets wrongly or a neighbour it disturbs shows."""
    bits = {2: jnp.uint16, 4: jnp.uint32}[jnp.dtype(dtype).itemsize]
    keys = jax.random.split(jax.random.key(int(rng.integers(2 ** 31))))
    return {n: jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, bits), dtype)
        for n, key in zip(("k", "v"), keys)}


# (pool, rows, live rows, where a dropped row is routed): a decode round
# of each served configuration
SERVED = {
    # 24 layers of 16 heads of 64; 64 slots, 13 running
    "gpt2_medium": ((1024, 64, 1024), 64, 13, "pages"),
    # one K/V head of 128; 128 slots
    "jamba2_3b": ((4096, 64, 128), 128, 40, "pages"),
    # a window layer's ring: one page of 128 rows a slot, 8 K/V heads of
    # 128, an idle slot routed to num_slots
    "k_exaone_236b_ring": ((256, 128, 1024), 256, 90, "slots"),
}


@pytest.mark.parametrize("cell", list(SERVED))
def test_decode_round_is_the_scatter_bit_for_bit(interpret, cell):
    (n_pages, page, width), rows, live, routed = SERVED[cell]
    rng = np.random.default_rng(rows)
    pool = _pool(rng, (n_pages, page, width), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
    running = rng.choice(rows, live, replace=False)
    if routed == "slots":            # a ring: slot s owns page s
        ids = np.full(rows, n_pages)
        ids[running] = running
    else:                            # each running slot its own page
        ids = np.full(rows, n_pages)
        ids[running] = rng.choice(n_pages, live, replace=False)
    offs = rng.integers(0, page, rows)
    ids, offs = jnp.asarray(ids, jnp.int32), jnp.asarray(offs, jnp.int32)
    want = _scatter(pool, k, v, ids, offs)
    assert len(_calls(paged_write, pool, k, v, ids, offs)) == 1
    _assert_same_bits(paged_write(pool, k, v, ids, offs), want)


@pytest.mark.parametrize("start", [40, 0, 7])
def test_prefill_chunk_crossing_a_page(interpret, start):
    """A chunk of 128 positions from ``start``: 90 real tokens over
    three pages (two of them partly), 38 pad positions dropped. Rows
    that share an 8-row tile are set in one read and one write."""
    rng = np.random.default_rng(start)
    page, width, rows, real = 64, 1024, 128, 90
    pool = _pool(rng, (16, page, width), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
    pos = start + np.arange(rows)
    pages = np.array([9, 3, 12, 5])[pos // page]
    ids = jnp.asarray(np.where(np.arange(rows) < real, pages, 16), jnp.int32)
    offs = jnp.asarray(pos % page, jnp.int32)
    want = _scatter(pool, k, -k, ids, offs)
    _assert_same_bits(paged_write(pool, k, -k, ids, offs), want)


def test_float32_pool_over_two_blocks(interpret):
    """More rows than one grid step takes: each block's writes land
    before the next block reads, so a tile split between two blocks
    keeps both halves."""
    from paddle_tpu.ops.pallas.paged_write import block_rows
    rng = np.random.default_rng(3)
    n_pages, page, width, rows = 8, 64, 1024, 300
    assert block_rows(rows, width, 4) < rows
    pool = _pool(rng, (n_pages, page, width), jnp.float32)
    k = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
    pos = 3 + np.arange(rows)
    ids = jnp.asarray(np.array([4, 1, 6, 0, 2])[pos // page], jnp.int32)
    offs = jnp.asarray(pos % page, jnp.int32)
    want = _scatter(pool, k, k * 2, ids, offs)
    _assert_same_bits(paged_write(pool, k, k * 2, ids, offs), want)


def test_a_row_routed_out_of_range_touches_nothing(interpret):
    """NaN rows routed past the pool (page id == num_pages, one past it,
    a negative one, an offset past the page or below it) leave both
    pools bit for bit as they were; the live row beside them lands. The
    kernel and XLA's path agree on a negative id: both drop it (where
    ``.at[]`` alone would count it from the end)."""
    rng = np.random.default_rng(5)
    pool = _pool(rng, (32, 64, 1024), jnp.bfloat16)
    k = jnp.full((7, 1024), jnp.nan, jnp.float32)
    k = k.at[2].set(1.5)
    ids = jnp.asarray([32, 33, 7, -1, 5, 32, 4], jnp.int32)
    offs = jnp.asarray([0, 9, 9, 3, 64, 63, -2], jnp.int32)
    got = paged_write(pool, k, k, ids, offs)
    want = {n: pool[n].at[7, 9].set(1.5) for n in ("k", "v")}
    _assert_same_bits(got, want)
    set_flags({"pallas_interpret": False})
    assert _calls(paged_write, pool, k, k, ids, offs) == []
    _assert_same_bits(paged_write(pool, k, k, ids, offs), want)


def test_rows_of_a_tile_apart_raise_in_the_interpreter(interpret):
    """The kernel's contract: rows that land in one 8-row tile are
    adjacent among the rows that land (dropped rows between them do not
    matter). A call that breaks it raises under the interpreter rather
    than losing a row as the chip would."""
    rng = np.random.default_rng(9)
    pool = _pool(rng, (8, 64, 128), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    kept = jnp.asarray([3, 8, 3, 5], jnp.int32), jnp.asarray([1, 0, 6, 9])
    _assert_same_bits(paged_write(pool, k, k, *kept),
                      _scatter(pool, k, k, *kept))
    apart = jnp.asarray([3, 5, 3, 8], jnp.int32), jnp.asarray([1, 9, 6, 0])
    with pytest.raises(Exception, match="not adjacent"):
        jax.block_until_ready(paged_write(pool, k, k, *apart))


def test_int8_pool_keeps_the_scatter(interpret):
    """An int8 pool's per-row scales are [N, ps], narrower than any
    slice Mosaic copies: the write stays XLA's scatter, quantizing."""
    rng = np.random.default_rng(7)
    pool = init_page_pool(16, 16, 64, 64, jnp.float32, kv_dtype=jnp.int8)
    k = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)
    ids = jnp.asarray([3, 16, 5, 16, 0, 1, 16, 9], jnp.int32)
    offs = jnp.asarray(rng.integers(0, 64, 8), jnp.int32)
    assert _calls(paged_write, pool, k, k, ids, offs) == []
    out = paged_write(pool, k, k, ids, offs)
    assert set(out) == {"k", "v", "k_scale", "v_scale"}
    assert out["k"].dtype == jnp.int8


def test_narrow_rows_keep_the_scatter(interpret):
    """A token row narrower than 128 lanes (the tiny test models) is
    written by XLA's scatter, as before."""
    pool = init_page_pool(8, 4, 8, 16, jnp.float32)
    k = jnp.ones((3, 64), jnp.float32)
    ids, offs = jnp.asarray([1, 8, 2]), jnp.asarray([0, 0, 7])
    assert _calls(paged_write, pool, k, k, ids, offs) == []
    _assert_same_bits(paged_write(pool, k, k, ids, offs),
                      _scatter(pool, k, k, ids, offs))


@pytest.mark.parametrize("window", [16, 64])
def test_ring_prefill_writes_in_ring_order(interpret, window):
    """A window layer's chunk of 40 from starts that are not multiples
    of the window: longer than a 16-row ring, and shorter than a 64-row
    ring that it wraps round. Each slot's last ``min(window, real)``
    positions land on their rows (``position % window``), every other
    row stays as it was. They are handed to the kernel rotated so that
    the rows of a tile are adjacent (the 16-row ring's first and last
    rows share a tile), and the same ring comes out of XLA's scatter."""
    from paddle_tpu.nn.layers import GroupedQueryAttention, matmul
    layer = GroupedQueryAttention(256, 4, num_kv_heads=2, head_dim=64,
                                  window=window)
    variables = layer.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    ring = {n: jnp.asarray(rng.standard_normal((3, window, 128)),
                           jnp.float32) for n in ("k", "v")}
    x = jnp.asarray(rng.standard_normal((2, 40, 256)), jnp.float32)
    slots = jnp.asarray([2, 0], jnp.int32)
    starts = jnp.asarray([2 * window - 14, 3 + window], jnp.int32)
    lengths = jnp.asarray([37, 9], jnp.int32)

    def run():
        return layer.apply(variables, x, ring, slots, starts, lengths,
                           method="ring_prefill_chunk")

    out, got = run()
    pos = starts[:, None] + jnp.arange(40)[None, :]
    _, k = layer.apply(variables, x, pos, method="_qk")
    v = layer.apply(variables, x, method=lambda y: matmul(y, layer.p("wv")))
    # slot 1 is untouched
    want = {n: np.asarray(ring[n]).copy() for n in ("k", "v")}
    for b, slot in enumerate((2, 0)):
        s, real = int(starts[b]), int(lengths[b])
        for p in range(s + max(0, real - window), s + real):
            want["k"][slot, p % window] = np.asarray(k[b, p - s])
            want["v"][slot, p % window] = np.asarray(v[b, p - s])
    _assert_same_bits(got, want)
    set_flags({"pallas_interpret": False})
    want_out, want = run()
    _assert_same_bits(got, want)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
