"""Attention ops.

Ref: the reference has no attention *op* — transformer attention appears as a
fused IR pass (/root/reference/paddle/fluid/framework/ir/
multihead_matmul_fuse_pass.h) over matmul/softmax subgraphs, plus
layers/nn.py scaled_dot_product_attention. Here attention is a first-class
op with an XLA path and a Pallas flash-attention path for long sequences
(ops/pallas/flash_attention.py).
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op

NEG_INF = -1e30


@register_op("scaled_dot_product_attention")
def scaled_dot_product_attention(q, k, v, mask=None, scale=None,
                                 causal=False, dropout_rate=0.0,
                                 dropout_key=None):
    """q,k,v: [B, H, T, D] (or [B, T, D]). mask: broadcastable to
    [B, H, Tq, Tk], True/1 = keep.

    XLA path: materializes the [Tq, Tk] score matrix — fine up to ~4k tokens;
    beyond that use `flash_attention` (Pallas, O(T) memory).
    """
    scale = scale if scale is not None else 1.0 / jnp.sqrt(q.shape[-1])
    scores = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    keep = None
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        keep = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
    if mask is not None:
        keep = mask.astype(bool) if keep is None else keep & mask.astype(bool)
    if keep is not None:
        scores = jnp.where(keep, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    if keep is not None:
        # fully-masked rows are defined as exactly zero output — the same
        # semantics as the flash/chunked paths (a plain softmax would emit
        # the uniform mean-of-v artifact instead)
        any_keep = jnp.any(jnp.broadcast_to(keep, scores.shape), -1,
                           keepdims=True)
        probs = jnp.where(any_keep, probs, 0.0)
    if dropout_rate > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_rate,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("...qk,...kd->...qd", probs, v)


def _as_key_padding_mask(mask, batch, tk):
    """Reduce a broadcastable attention mask to key-padding form [B, Tk]
    when its per-head and per-query dims are 1 (the padded-batch case the
    reference feeds through the fused path's eltwise-add bias input).
    Returns None for masks that genuinely vary per query/head."""
    if mask is None:
        return None
    m = mask
    if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1:
        m = m[:, 0, 0, :]
    elif m.ndim == 3 and m.shape[0] == 1 and m.shape[1] == 1:
        # a 3D mask's leading dim broadcasts against the HEAD axis in the
        # dense path, so only the fully-degenerate [1,1,Tk] is unambiguous
        m = m[:, 0, :]
    elif m.ndim == 2 and m.shape[0] == 1:
        # [1, Tk] broadcasts identically under both interpretations; a
        # [B, Tk] 2D mask would broadcast as [Tq, Tk] per-query in the
        # dense path, so it must NOT be reduced to key-padding form
        pass
    else:
        return None
    if m.shape[-1] != tk:
        return None
    if m.shape[0] == 1 and batch > 1:
        m = jnp.broadcast_to(m, (batch, tk))
    elif m.shape[0] != batch:
        return None
    return m.astype(bool)


# --- paged KV cache (serving fast path) -----------------------------------
#
# The per-request contiguous [B, H, Tmax, hd] decode cache streams the whole
# padded buffer every generated token and welds requests into one fixed
# lockstep batch. The paged layout replaces it with a slot/page-pool scheme:
# one pool of fixed-size pages per layer plus a per-slot page table
# ([slots, Pmax] int32) and token counts ([slots] int32). Memory scales with
# tokens actually held, mixed-length requests share one batch, and a
# finished request frees its pages without reshaping anything — the jitted
# serve step's shapes never change across admissions (paddle_tpu/serving/
# owns the host-side allocator).
#
# THE pool layout is token-major and lane-dense: [num_pages, page_size,
# H*hd], one row per cached token with its heads side by side. All three
# users take it as it lies: paged_write sets whole rows on the two leading
# dims (in place on the donated buffer), the decode kernel's page
# block is one contiguous (page_size, H*hd) tile, and the chunked prefill's
# gather pool[page_rows] is a reshape away from [B, T, H, hd]. There is no
# second layout. A head-major pool ([N, H, ps, hd]) cost three copies of
# every whole pool in every serve step on the v5e — the compiler held the
# parameter page-minor, relaid it token-major for the scatter, back for
# the aliased output and once more row-major for the Mosaic call: 92% of
# the device's busy time (PERF.md section 6, PR 27;
# tests/test_mosaic_compile.py::test_pool_layout_no_relayout holds the
# compiled step to none).
#
# Quantized pools (kv_dtype=int8) add {"k_scale","v_scale"} f32
# [num_pages, page_size] beside the int8 value tensors: one symmetric
# absmax scale per (page, token-row), shared across heads and head_dim.
# Row granularity makes the incremental decode write exact (each new token
# sets its own int8 row + one scale scalar; existing rows are untouched),
# and keying scales by page id means prefix-cache sharing, copy-on-write
# and recovery-rebuild all carry scales for free — they only ever move
# whole pages.


def quantized_pool(pool):
    """True iff `pool` is an int8 pool carrying per-row scales."""
    return "k_scale" in pool


def pool_dims(pool):
    """(num_pages, page_size) of one layer's pool — the one place that
    unpacks the pool's shape."""
    num_pages, page_size, _ = pool["k"].shape
    return num_pages, page_size


def quantize_kv_rows(x):
    """Symmetric per-token-row int8 quantization. x: [T, ...] (a row is
    everything behind the leading dim: H*hd, or [H, hd]) -> (q int8 of
    x.shape, scale f32 [T]) with scale = absmax/127. An all-zero row
    stores scale 0 and dequantizes to exactly zero."""
    x = x.astype(jnp.float32)
    row = (-1,) + (1,) * (x.ndim - 1)
    scale = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim))) / 127.0
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-30).reshape(row)),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_pages(pages, scales):
    """Dequantize gathered pages. pages: [..., ps, H*hd] int8 with
    leading gather dims; scales: [..., ps] f32 aligned on those dims.
    -> f32 of pages.shape."""
    return pages.astype(jnp.float32) * scales[..., None]


def gather_pages(pages, page_table, num_heads, scales=None):
    """Every table page of every row, densely: [B, Pmax*ps, H, hd], a
    reshape of pages[page_table] (the pool is token-major; ``num_heads``
    is the POOL's head count, the K/V heads of a grouped layer). scales
    ([N, ps]) dequantize an int8 pool to f32 on the gathered pages.
    Admission-rate and fallback work; the decode hot path reads live
    pages in the kernel."""
    b, p_max = page_table.shape
    g = pages[page_table]                       # [B, Pmax, ps, H*hd]
    if scales is not None:
        g = dequantize_pages(g, scales[page_table])
    return g.reshape(b, p_max * pages.shape[1], num_heads, -1)


def init_page_pool(num_pages, num_heads, page_size, head_dim,
                   dtype=jnp.float32, kv_dtype=None):
    """One layer's KV page pool: {"k","v"} [num_pages, page_size, H*hd].
    kv_dtype=int8 adds {"k_scale","v_scale"} f32 [num_pages, page_size]
    (per-row symmetric scales) and stores values as int8."""
    shape = (num_pages, page_size, num_heads * head_dim)
    if kv_dtype is None or jnp.dtype(kv_dtype) == jnp.dtype(dtype):
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(kv_dtype) != jnp.dtype(jnp.int8):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                         "(supported: int8, or None for the pool dtype)")
    sshape = (num_pages, page_size)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def paged_write(pool, k_t, v_t, page_ids, offsets):
    """Scatter per-token K/V rows into pool pages. k_t/v_t: [T, H, hd]
    (or [T, H*hd]); page_ids/offsets: [T] int32 index the pool's two
    leading dims, so each token is one contiguous H*hd row. A page id or
    offset out of range, a negative one too, DROPS the write — the
    engine routes inactive slots and pad positions to page id ==
    num_pages on purpose. On a quantized pool each row is quantized on
    the way in and its scale written beside it.

    On TPU (or under pallas_interpret), a pool of 2- or 4-byte values
    whose token row (H*hd) is a multiple of 128 lanes and whose pages a
    multiple of 8 rows is written by one Pallas call for K and V
    (ops/pallas/paged_write.py), which moves only the rows that land.
    Its contract: rows that land in one 8-row tile of a page are
    adjacent among the rows that land (one row a page in a decode
    round, a chunk's consecutive positions); the interpreter raises on
    a call that breaks it. Int8 pools (their [N, ps] scales are
    narrower than a copyable slice), narrow rows and other backends
    take XLA's scatter."""
    _, page_size, width = pool["k"].shape
    if (not quantized_pool(pool) and width % 128 == 0
            and page_size % 8 == 0 and pool["k"].dtype.itemsize in (2, 4)):
        from paddle_tpu.ops.pallas.core import INTERPRET, kernel_mode
        mode = kernel_mode("paged_write")
        if mode is not None:
            from paddle_tpu.ops.pallas.paged_write import paged_write_tpu

            # rounded to the pool dtype here, widened for the kernel's
            # row loads: it narrows back exactly
            def rows(x):
                return x.reshape(x.shape[0], -1).astype(
                    pool["k"].dtype).astype(jnp.float32)

            k, v = paged_write_tpu(
                pool["k"], pool["v"], rows(k_t), rows(v_t), page_ids,
                offsets, interpret=mode == INTERPRET)
            return {"k": k, "v": v}
    # ``.at[]`` counts a negative index from the end: route it past the
    # pool, where mode="drop" drops it as the kernel does
    page_ids = jnp.where((page_ids < 0) | (offsets < 0), pool["k"].shape[0],
                         page_ids)
    new = {}
    for name, x in (("k", k_t), ("v", v_t)):
        x = x.reshape(x.shape[0], -1)
        if quantized_pool(pool):
            x, scale = quantize_kv_rows(x)
            new[name + "_scale"] = pool[name + "_scale"].at[
                page_ids, offsets].set(scale, mode="drop")
        new[name] = pool[name].at[page_ids, offsets].set(
            x.astype(pool[name].dtype), mode="drop")
    return new


def copy_pages(pool, src_ids, dst_ids):
    """Copy whole pages src->dst within one layer's pool — the serving
    engine's copy-on-write primitive: a slot about to write into a
    prefix-cache-shared page first duplicates it to a private page.
    src_ids/dst_ids: [M] int32. An out-of-range dst DROPS the copy
    (mode="drop"), matching paged_write's inactive-slot convention.
    Generic over the pool's entries, so a quantized pool's per-row
    scales travel with their int8 pages (already-quantized content is
    copied bit-exact — no requantization error on CoW)."""
    return {name: arr.at[dst_ids].set(arr[src_ids], mode="drop")
            for name, arr in pool.items()}


def _paged_attention_xla(q, k_pages, v_pages, page_table, lengths, scale,
                         k_scale=None, v_scale=None):
    """Gather-and-mask reference: pull every table page densely and mask by
    length. Materializes [S, H, Pmax*ps]-scale score temporaries — the
    parity oracle for the Pallas kernel and the CPU fallback, never the
    serving hot path (compile_smoke's serve probe asserts the kernel path
    holds no such temporary, with this path as the positive control).
    k_scale/v_scale [N, ps] dequantize int8 pools on the same gathered
    pages the kernel reads."""
    h, hd = q.shape[1:]
    kvh = k_pages.shape[2] // hd
    k = gather_pages(k_pages, page_table, kvh, k_scale)  # [S, T, KVH, hd]
    v = gather_pages(v_pages, page_table, kvh, v_scale)
    if kvh != h:
        # grouped K/V heads: each is shared by H/KVH query heads (the
        # oracle may repeat them; the kernel never does)
        k, v = (jnp.repeat(x, h // kvh, axis=2) for x in (k, v))
    scores = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = (jnp.arange(k.shape[1])[None, :] < lengths[:, None])[:, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    # mask p, not just scores: a fully-masked slot (length 0) keeps m at
    # the NEG_INF sentinel where exp(s - m) would be 1
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("sht,sthd->shd", p, v.astype(jnp.float32))
    out = jnp.where(l > 0, out / jnp.maximum(l, 1e-30), 0.0)
    return out.astype(q.dtype)


@register_op("paged_decode_attention")
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None, k_scale=None, v_scale=None):
    """Single-query attention over a paged KV cache (the serving decode
    read). q: [S, H, hd] — one query token per slot; k_pages/v_pages:
    [N, page_size, KVH*hd] (the pool's own head count KVH divides H: 1 is
    multi-query attention, H the ordinary case); page_table: [S, Pmax]
    int32, IN-RANGE below each slot's length (the kernel copies no page
    past it; the XLA path gathers every entry, so 0 for unallocated);
    lengths: [S] int32 valid token counts (0 = inactive slot ->
    exactly-zero output).
    k_scale/v_scale: [N, page_size] f32 per-row scales when the pool is
    int8 (init_page_pool(kv_dtype=int8)); both paths dequantize the same
    gathered pages, so kernel-vs-fallback parity holds for quantized
    pools too.

    On TPU (or under pallas_interpret): the Pallas kernel, one grid step
    a slot, copies that slot's live pages alone through the page table
    and runs flash-style online softmax over them. Elsewhere, or with
    use_pallas_decode=False: the XLA gather-and-mask formulation (same
    semantics, dense temporaries)."""
    from paddle_tpu.core.flags import get_flag
    from paddle_tpu.ops.pallas.core import kernel_mode
    scale = (float(scale) if scale is not None
             else 1.0 / (q.shape[-1] ** 0.5))
    page_size, width = k_pages.shape[1:]
    interpret = get_flag("pallas_interpret")
    shape_ok = page_size % 8 == 0 and (interpret or width % 128 == 0)
    mode = kernel_mode(
        "decode_attention", enable_flag="use_pallas_decode",
        unsupported=None if shape_ok else (
            f"page_size={page_size} not a multiple of 8 or a token row "
            f"of H*hd={width} not a multiple of 128 "
            "(supported: page_size%8==0, H*hd%128==0 on silicon)"))
    heads, hd = q.shape[1:]
    if width % hd or heads % (width // hd):
        raise ValueError(
            f"paged_decode_attention: a token row of {width} does not "
            f"hold a divisor of {heads} heads of {hd}")
    if mode is not None:
        from paddle_tpu.ops.pallas.decode_attention import (
            paged_decode_attention_tpu)
        return paged_decode_attention_tpu(
            q, k_pages, v_pages, page_table, lengths, scale,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                                scale, k_scale=k_scale, v_scale=v_scale)


@register_op("multihead_attention")
def multihead_attention(x, wq, wk, wv, wo, bq=None, bk=None, bv=None, bo=None,
                        num_heads=8, mask=None, causal=False, kv=None,
                        dropout_rate=0.0, dropout_key=None, use_flash=False,
                        seq_axis=None):
    """Full fused MHA forward (ref: ir/multihead_matmul_fuse_pass.h — the
    reference *fuses* q/k/v matmuls post-hoc; we write it fused from the
    start). x: [B, T, E]; w*: [E, E]."""
    b, t, e = x.shape
    hd = e // num_heads
    kv = kv if kv is not None else x

    def proj(inp, w, bias):
        out = inp @ w
        if bias is not None:
            out = out + bias
        return out.reshape(b, -1, num_heads, hd).transpose(0, 2, 1, 3)

    q = proj(x, wq, bq)
    k = proj(kv, wk, bk)
    v = proj(kv, wv, bv)
    no_dropout = dropout_rate == 0.0 or dropout_key is None
    if seq_axis is not None:
        # sequence sharded over a mesh axis: ring attention (flash-backed
        # on TPU). Per-device positions are contiguous so block-granular
        # causality is exact. Masks/dropout are not supported here.
        from paddle_tpu.core.enforce import enforce
        enforce(mask is None and no_dropout,
                "seq_axis attention supports no mask/attention-dropout")
        from paddle_tpu.parallel.ring_attention import ring_flash_attention
        ctx = ring_flash_attention(q, k, v, seq_axis, causal=causal)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, e)
        out = ctx @ wo
        return out + bo if bo is not None else out
    # flash path handles key-padding masks ([B,1,1,Tk]-style) natively;
    # only an arbitrary per-query mask or attention dropout falls back to
    # the XLA path
    kv_mask = _as_key_padding_mask(mask, b, k.shape[2])
    if use_flash and (mask is None or kv_mask is not None) and no_dropout:
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        ctx = flash_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    else:
        ctx = scaled_dot_product_attention(q, k, v, mask=mask, causal=causal,
                                           dropout_rate=dropout_rate,
                                           dropout_key=dropout_key)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, e)
    out = ctx @ wo
    if bo is not None:
        out = out + bo
    return out
