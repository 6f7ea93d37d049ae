"""Compile the main path's kernels for the real chip, without the chip.

Every other kernel test runs the Pallas interpreter, which cannot see a
tile the Mosaic compiler refuses or a kernel that wants too much VMEM.
The TPU compiler is installed beside jax and compiles for a chip that is
described, not attached (``on-chip-measurement`` guide, section 2), so
these cases lower each kernel family at the widths chip_smoke.py
runs — BERT-base / GPT-small: hidden 768, 12 heads of 64, FFN
3072, vocab 30522 / 32000 — and assert the compiled module really holds
the Mosaic kernel. Each would have caught a refusal this tree once had:
erfc inside the fused MLP, a row-less batched dot and (1, page) scale
blocks in paged decode, the dW/db tiles of fused xent overflowing the
16 MB scoped VMEM limit inside the GPT-small step.

The topology is described inside a module-scoped fixture (only one
process may load the TPU library: never at import, never in conftest),
everything compiles in this process, and ``on_tpu`` is steered by
monkeypatch, not by an option of the program.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
H, HEADS, HD, FFN = 768, 12, 64, 3072


@pytest.fixture(scope="module")
def one_chip():
    """A described (unattached) v5e chip's sharding; the persistent
    compile cache is off around the module — a described-device compile
    is written to it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """kernel_mode sees a TPU: the dispatchers take their Mosaic path
    while jax itself stays on the CPU backend."""
    from paddle_tpu.ops.pallas import core
    monkeypatch.setattr(core, "on_tpu", lambda: True)


def _compile(one_chip, fn, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn, donate_argnums=donate).lower(
        *args).compile().as_text()


def _kernels(hlo):
    """{kernel name: scoped VMEM bytes} of the module's Mosaic calls."""
    out = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
        size = re.search(r'"used_scoped_memory_configs":\[\{[^}]*?'
                         r'"size":"(\d+)"', line)
        out[name.group(1) if name else "?"] = (
            int(size.group(1)) if size else 0)
    return out


# ------------------------------------------------------ flash attention

FLASH = [("causal", True, False), ("full", False, False),
         ("masked", False, True)]


def _flash(causal, masked):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def fn(q, k, v, *mask):
        return flash_attention(q, k, v, causal=causal,
                               kv_mask=mask[0] if masked else None)
    return fn


def _flash_shapes(masked, b=64, t=512):
    qkv = [((b, HEADS, t, HD), BF16)] * 3
    return qkv + ([((b, t), F32)] if masked else [])


@pytest.mark.parametrize("name,causal,masked", FLASH,
                         ids=[c[0] for c in FLASH])
def test_flash_forward(one_chip, as_tpu, name, causal, masked):
    hlo = _compile(one_chip, _flash(causal, masked),
                   *_flash_shapes(masked))
    assert set(_kernels(hlo)) == {"flash_attention"}


@pytest.mark.parametrize("name,causal,masked", FLASH,
                         ids=[c[0] for c in FLASH])
def test_flash_forward_backward(one_chip, as_tpu, name, causal, masked):
    fwd = _flash(causal, masked)

    def grads(q, k, v, *mask):
        return jax.grad(lambda q, k, v: jnp.sum(
            fwd(q, k, v, *mask).astype(F32) ** 2), argnums=(0, 1, 2))(
                q, k, v)

    hlo = _compile(one_chip, grads, *_flash_shapes(masked))
    assert set(_kernels(hlo)) == {"flash_attention",
                                  "flash_attention_bwd_dq",
                                  "flash_attention_bwd_dkv"}


# ------------------------------------------------- layer norm and MLP

def test_layer_norm(one_chip, as_tpu):
    from paddle_tpu.ops.pallas.layer_norm import layer_norm_fused
    hlo = _compile(one_chip, layer_norm_fused, ((32768, H), BF16),
                   ((H,), BF16), ((H,), BF16))
    assert set(_kernels(hlo)) == {"layer_norm"}


def test_add_layer_norm(one_chip, as_tpu):
    from paddle_tpu.ops.pallas.layer_norm import add_layer_norm_fused
    hlo = _compile(one_chip, add_layer_norm_fused, ((32768, H), BF16),
                   ((32768, H), BF16), ((H,), BF16), ((H,), BF16))
    assert set(_kernels(hlo)) == {"add_layer_norm"}


@pytest.mark.parametrize("gated", [False, True], ids=["gelu", "wg_gate"])
def test_fused_mlp_forward(one_chip, as_tpu, gated):
    """gelu: the exact-erf activation must lower (Mosaic has no erf or
    erfc). wg_gate: the GLU operands, biases riding as [1, n] rows."""
    from paddle_tpu.ops.pallas.mlp import fused_mlp
    shapes = [((32768, H), BF16), ((H, FFN), BF16), ((FFN,), BF16),
              ((FFN, H), BF16), ((H,), BF16)]
    if gated:
        shapes += [((H, FFN), BF16), ((FFN,), BF16)]
    hlo = _compile(
        one_chip, lambda *a: fused_mlp(*a, act="silu" if gated else "gelu"),
        *shapes)
    assert set(_kernels(hlo)) == {"mlp"}


# ------------------------------------------------------------ fused xent

# rows x vocab of the two train steps: BERT-base's masked rows
# (64 x int(0.15 * 512) = 4864, padded here to the issue's 5120) and
# GPT-small's 16 x 511 shifted rows
XENT = [(5120, 30522), (8176, 32000)]


def _headroom(kernels):
    """Compiled inside a whole train step the same kernel was allocated
    up to 3 MB more than alone (core.RV_VMEM_BUDGET's comment), so alone
    it must leave that much of the 16 MB limit free."""
    from paddle_tpu.ops.pallas.core import VMEM_LIMIT_BYTES
    return {k: v for k, v in kernels.items()
            if v > VMEM_LIMIT_BYTES - 3 * 2 ** 20}


@pytest.mark.parametrize("n,v", XENT, ids=[f"{n}x{v}" for n, v in XENT])
def test_xent_stats(one_chip, as_tpu, n, v):
    from paddle_tpu.ops.pallas.xent import xent_stats_pallas
    kernels = _kernels(_compile(
        one_chip, xent_stats_pallas, ((n, H), BF16), ((v, H), BF16),
        ((v,), F32), ((n,), I32)))
    assert set(kernels) == {"xent_stats"}
    assert not _headroom(kernels), kernels


@pytest.mark.parametrize("n,v", XENT, ids=[f"{n}x{v}" for n, v in XENT])
def test_xent_backward(one_chip, as_tpu, n, v):
    """dh and dW/db size their own tiles: dW/db holds a [bv, H] f32
    accumulator the shared pair once overflowed VMEM with."""
    from paddle_tpu.ops.pallas.xent import xent_bwd_pallas
    kernels = _kernels(_compile(
        one_chip,
        lambda h, w, b, lbl, logz, g: xent_bwd_pallas(
            h, w, b, lbl, logz, g, 0.0, 1.0),
        ((n, H), BF16), ((v, H), BF16), ((v,), F32), ((n,), I32),
        ((n,), F32), ((n,), F32)))
    assert set(kernels) == {"xent_bwd_dh", "xent_bwd_dwb"}
    assert not _headroom(kernels), kernels


def test_gpt_small_train_step_whole(one_chip, as_tpu):
    """The whole jitted GPT-small b16 x s512 ``opt.minimize`` step, every
    kernel flag at its default: where the xent dW/db kernel overflowed
    (alone it compiled), and where any later refusal would show first."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    from paddle_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig.small()
    cfg.dropout, cfg.use_flash, cfg.scan_layers = 0.0, True, True
    model, opt = GPT(cfg), chip_smoke._amp_optimizer()
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0))["params"])
    state = {"params": params, "opt": jax.eval_shape(opt.init, params)}
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), state)
    ids = jax.ShapeDtypeStruct((16, 512), I32, sharding=one_chip)
    step = jax.jit(chip_smoke.make_train_step(
        opt, chip_smoke.gpt_loss_fn(model)), donate_argnums=(0,))
    kernels = _kernels(step.lower(state, ids).compile().as_text())
    assert set(chip_smoke.TRAIN_KERNELS) <= set(kernels), kernels


# ----------------------------------------------------------- paged decode

DECODE = [(dt, ps) for dt in ("f32", "bf16", "int8") for ps in (16, 64, 128)]


@pytest.mark.parametrize("kv,page", DECODE,
                         ids=[f"{d}_page{p}" for d, p in DECODE])
def test_paged_decode(one_chip, as_tpu, kv, page):
    """8 slots x 12 heads of 64 over a 1024-token page table: a page is
    one lane-dense (page, 768) block of the token-major pool, and int8
    scales a block whose last two dims are legal."""
    from paddle_tpu.ops.attention import paged_decode_attention
    slots, p_max = 8, 1024 // page
    n_pages = slots * p_max + 1
    pool = {"f32": F32, "bf16": BF16, "int8": I8}[kv]
    q_dt = F32 if kv == "int8" else pool
    shapes = [((slots, HEADS, HD), q_dt),
              ((n_pages, page, HEADS * HD), pool),
              ((n_pages, page, HEADS * HD), pool),
              ((slots, p_max), I32), ((slots,), I32)]
    if kv == "int8":
        shapes += [((n_pages, page), F32)] * 2

    def fn(q, k, v, table, lengths, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return paged_decode_attention(q, k, v, table, lengths, **kw)

    assert set(_kernels(_compile(one_chip, fn, *shapes))) == {
        "decode_attention"}


# ------------------------------------- the K/V page pool is never relaid
#
# gpt2_medium.chat's shapes (benchmark/configs/gpt2_medium.json and the
# cell's engine): bf16 pools of 1024 pages of 64 tokens, 16 heads of 64,
# 64 slots, 16 pages a slot, prefill chunks of 128. One layer of each
# serve program, pools donated as the engine donates them.

POOL_PAGES, POOL_PAGE, POOL_HEADS, POOL_SLOTS, POOL_PMAX, CHUNK = (
    1024, 64, 16, 64, 16, 128)
POOL = (POOL_PAGES, POOL_PAGE, POOL_HEADS * HD)
HEAD_MAJOR_POOL = (POOL_PAGES, POOL_HEADS, POOL_PAGE, HD)


def _decode_layer(pk, pv, q, k_t, v_t, table, lengths, pages, offsets):
    from paddle_tpu.ops.attention import (paged_decode_attention,
                                          paged_write)
    pool = paged_write({"k": pk, "v": pv}, k_t, v_t, pages, offsets)
    return (paged_decode_attention(q, pool["k"], pool["v"], table,
                                   lengths), pool["k"], pool["v"])


def _prefill_layer(pk, pv, k_t, v_t, pages, offsets, rows):
    from paddle_tpu.ops.attention import gather_pages, paged_write
    pool = paged_write({"k": pk, "v": pv}, k_t, v_t, pages, offsets)
    return (gather_pages(pool["k"], rows, POOL_HEADS),
            gather_pages(pool["v"], rows, POOL_HEADS),
            pool["k"], pool["v"])


def _head_major_prefill_layer(pk, pv, k_t, v_t, pages, offsets, rows):
    """The positive control: the pool as it was declared before PR 27,
    [N, H, ps, hd], written by that tree's scatter."""
    pk = pk.at[pages, :, offsets, :].set(k_t, mode="drop")
    pv = pv.at[pages, :, offsets, :].set(v_t, mode="drop")
    return pk[rows], pv[rows], pk, pv


def _rows(n):
    """K and V rows of n tokens, their page ids and offsets."""
    return [((n, POOL_HEADS, HD), BF16)] * 2 + [((n,), I32)] * 2


POOL_LAYERS = {
    "decode": (_decode_layer, POOL,
               [((POOL_SLOTS, POOL_HEADS, HD), F32),
                ((POOL_SLOTS, POOL_HEADS, HD), F32),
                ((POOL_SLOTS, POOL_HEADS, HD), F32),
                ((POOL_SLOTS, POOL_PMAX), I32), ((POOL_SLOTS,), I32),
                ((POOL_SLOTS,), I32), ((POOL_SLOTS,), I32)]),
    "prefill": (_prefill_layer, POOL,
                _rows(CHUNK) + [((1, POOL_PMAX), I32)]),
    "head_major": (_head_major_prefill_layer, HEAD_MAJOR_POOL,
                   _rows(CHUNK) + [((1, POOL_PMAX), I32)]),
}


def _pool_relayouts(hlo, pool_shape):
    """(copies, writes, aliased): the instructions of the compiled
    module that write a whole pool anew (a `copy`, a `transpose`, any
    fusion but the page write), its in-place page writes (the scatter
    fusions), and the parameters the module's outputs alias."""
    shape = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    copies, writes = [], 0
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = " + re.escape(shape)
                     + r"\{[^}]*\} (\w[\w-]*)\(", line)
        if not m or m.group(1) in ("parameter", "scatter",
                                   "get-tuple-element", "bitcast"):
            continue
        if m.group(1) == "fusion" and "/scatter" in line:
            writes += 1
            continue
        copies.append(line.strip()[:120])
    header = next(l for l in hlo.splitlines() if l.startswith("HloModule"))
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    return copies, writes, aliased


def _compile_pool_layer(one_chip, name):
    fn, pool_shape, rest = POOL_LAYERS[name]
    hlo = _compile(one_chip, fn, *[(pool_shape, BF16)] * 2 + rest,
                   donate=(0, 1))
    return hlo, pool_shape


@pytest.mark.parametrize("layer", ["decode", "prefill"])
def test_pool_layout_no_relayout(one_chip, as_tpu, layer):
    """The writer, the decode kernel and the prefill gather take the
    pool as it lies: one layer of `_decode_jit` (write + kernel) and of
    `_prefill_jit` (write + pool[page_rows] gather) holds no copy of a
    pool, and the donated pools come back in place. The head-major pool
    paid two such copies a pool in a prefill chunk and three in a decode
    round: 92% of gpt2_medium.chat's device time (PERF.md, PR 27)."""
    hlo, pool_shape = _compile_pool_layer(one_chip, layer)
    copies, writes, aliased = _pool_relayouts(hlo, pool_shape)
    assert not copies, copies
    assert writes == 2 and aliased == {0, 1}
    if layer == "decode":
        assert set(_kernels(hlo)) == {"decode_attention"}


def test_pool_layout_control_trips(one_chip):
    """The detector sees what it is for: a head-major pool under the
    scatter on dims 0 and 2 is relaid before the write and back after
    it, for each of the two pools."""
    hlo, pool_shape = _compile_pool_layer(one_chip, "head_major")
    copies, writes, _ = _pool_relayouts(hlo, pool_shape)
    assert writes == 2 and len(copies) >= 4, copies
    assert all(" copy(" in c for c in copies)


# ------------------------------------------ the hybrid decoder's kernels
#
# jamba2_3b.chat_1k's shapes (benchmark/configs/jamba2_3b.json): d_inner
# 5120, 16 states, 128 slots, prefill chunks of 128; 20 query heads of
# 128 over ONE K/V head in pools of 4096 pages of 64; a gated MLP of
# 2560 x 8192 in bf16.

SCAN_D, SCAN_N, SCAN_SLOTS = 5120, 16, 128


@pytest.mark.parametrize("batch,t,name", [
    (1, 128, "selective_scan"), (SCAN_SLOTS, 1, "ssm_state_update")])
def test_selective_scan_updates_the_state_in_place(one_chip, as_tpu, batch,
                                                   t, name):
    """Both lengths of the scan kernel compile, the donated state is the
    output's buffer, and nothing of the state's shape is copied."""
    from paddle_tpu.ops.mamba import selective_scan

    def fn(state, x, dt, b, c, z, a_log, d, slots, lengths):
        y, new = selective_scan(x, dt, b, c, z, a_log, d, state, slots,
                                lengths, lengths > 0, name=name)
        return new, y

    seq = ((batch, t, SCAN_D), F32)
    bc = ((batch, t, SCAN_N), F32)
    state = (SCAN_SLOTS, SCAN_N, SCAN_D)
    hlo = _compile(one_chip, fn, (state, F32), seq, seq, bc, bc, seq,
                   ((SCAN_D, SCAN_N), BF16), ((SCAN_D,), BF16),
                   ((batch,), I32), ((batch,), I32), donate=(0,))
    assert set(_kernels(hlo)) == {name}
    copies, _, aliased = _pool_relayouts(hlo, state)
    header = next(l for l in hlo.splitlines() if l.startswith("HloModule"))
    assert "{0}: (0, {}, may-alias)" in header or 0 in aliased, header[:300]
    shape = "f32[" + ",".join(map(str, state)) + "]"
    assert not [l for l in hlo.splitlines()
                if re.search(re.escape(shape) + r"\{[^}]*\} (copy|transpose)\(",
                             l)]


def test_paged_decode_with_one_kv_head(one_chip, as_tpu):
    from paddle_tpu.ops.attention import paged_decode_attention
    hlo = _compile(
        one_chip, paged_decode_attention, ((128, 20, 128), F32),
        ((4096, 64, 128), BF16), ((4096, 64, 128), BF16),
        ((128, 32), I32), ((128,), I32))
    assert set(_kernels(hlo)) == {"decode_attention"}


@pytest.mark.parametrize("pairs", [2048, 1024])
def test_grouped_expert_mlp_at_the_published_widths(one_chip, pairs):
    """K-EXAONE's held experts: 16 of 6144 x 2048 in bfloat16, a decode
    round's 256 x 8 (row, choice) pairs and a prefill chunk's 128 x 8.
    The kernel asks for NO raised scoped-VMEM limit (one that did hung a
    whole step program on the chip: PERF.md section 6, PR 28), so its
    blocks have to fit the default 16 MiB."""
    from paddle_tpu.ops.pallas import moe_mlp
    w = ((16, 6144, 2048), BF16)
    hlo = _compile(one_chip, moe_mlp.expert_mlp_tpu, ((pairs, 6144), BF16),
                   w, w, ((16, 2048, 6144), BF16), ((17,), I32))
    assert 0 < _kernels(hlo)["moe_expert_mlp"] <= 16 * 2 ** 20
    assert "vmem_limit" not in hlo


def test_ring_decode_with_eight_kv_heads_under_sixty_four(one_chip, as_tpu):
    """A window layer's ring as the decode kernel takes it: one page of
    128 rows a slot, 8 K/V heads of 128 under 64 query heads."""
    from paddle_tpu.ops.attention import paged_decode_attention
    hlo = _compile(
        one_chip, paged_decode_attention, ((256, 64, 128), F32),
        ((256, 128, 1024), BF16), ((256, 128, 1024), BF16),
        ((256, 1), I32), ((256,), I32))
    assert set(_kernels(hlo)) == {"decode_attention"}


# The decode kernel at the serving cells' own shapes (jamba2_3b's and
# k_exaone_236b's ring are the two cases above): (slots, heads, head_dim,
# pool, pages a slot, pool dtype). The pools stay in HBM and the kernel
# copies each live page by hand, so the scoped VMEM holds two pages of K
# and of V, the carry and (int8) two slots' tables of scale rows: what it
# needs does not grow with the pool (256 slots at 4k context hold 16384
# pages, whose two whole scale arrays alone would fill 16 MiB).
SERVED_DECODE = {
    "gpt2_medium": (64, 16, 64, (1024, 64, 1024), 16, BF16),
    "k_exaone_236b_full": (256, 64, 128, (8192, 64, 1024), 32, BF16),
    "gpt2_medium_int8": (64, 16, 64, (1024, 64, 1024), 16, I8),
    "k_exaone_236b_full_int8": (256, 64, 128, (8192, 64, 1024), 32, I8),
    "gpt2_medium_int8_4k_context": (256, 16, 64, (16384, 64, 1024), 64,
                                    I8),
}


@pytest.mark.parametrize("cell", list(SERVED_DECODE))
def test_paged_decode_at_the_served_shapes(one_chip, as_tpu, cell):
    from paddle_tpu.ops.attention import paged_decode_attention
    slots, heads, hd, pool, p_max, dtype = SERVED_DECODE[cell]
    shapes = [((slots, heads, hd), F32), (pool, dtype), (pool, dtype),
              ((slots, p_max), I32), ((slots,), I32)]
    if dtype == I8:
        shapes += [(pool[:2], F32)] * 2

    def fn(q, k, v, table, lengths, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return paged_decode_attention(q, k, v, table, lengths, **kw)

    kernels = _kernels(_compile(one_chip, fn, *shapes))
    assert set(kernels) == {"decode_attention"}
    assert 0 < kernels["decode_attention"] <= 16 * 2 ** 20


def test_fused_mlp_at_six_thousand_wide_reads_its_weights_once(one_chip,
                                                               as_tpu):
    """K-EXAONE's dense FFN (6144 x 18432) and shared expert (6144 x
    2048) at a decode round's 256 rows and a chunk's 128: the scoped
    VMEM leaves the kernel row tiles of 40, seven reads of every weight
    (9.2 ms a round on the chip where the bytes are worth 1.2: PERF.md
    section 6, PR 35), so ``fused_mlp`` hands such a call (fewer than
    512 rows, more than one row tile) to XLA's own products, which read
    each matrix once. The 2560-wide layers keep the kernel
    (test_fused_mlp_wide_gated_fits_the_default_vmem)."""
    from paddle_tpu.ops.pallas import mlp

    def fn(x, w1, w2, wg):
        return mlp.fused_mlp(x, w1, None, w2, None, wg=wg, act="silu")
    for rows, inter in ((256, 18432), (128, 2048)):
        shapes = [((rows, 6144), F32), ((6144, inter), BF16),
                  ((inter, 6144), BF16), ((6144, inter), BF16)]
        x, w1, w2, _ = (jax.ShapeDtypeStruct(s, d) for s, d in shapes)
        assert mlp._default_mlp_blocks(x, w1, w2, False, True)[0] < rows
        hlo = _compile(one_chip, fn, *shapes)
        assert "mlp" not in _kernels(hlo)
        # bfloat16 operands, float32 accumulation, no f32 copy of a weight
        assert not re.search(rf"f32\[6144,{inter}\]", hlo)


def test_fused_mlp_wide_gated_fits_the_default_vmem(one_chip, as_tpu):
    """2560 x 8192 with a gate: 512-wide tiles of three bf16 matrices
    pass the 16 MiB scoped VMEM limit, so the intermediate tile is
    halved until the blocks fit (a RAISED limit compiled too, and the
    28-layer prefill program then hung on the chip: PERF.md section 6,
    PR 28); the accepted cells' shapes keep their tiles
    (test_fused_mlp_forward)."""
    from paddle_tpu.ops.pallas import mlp

    def fn(x, w1, w2, wg):
        return mlp.fused_mlp(x, w1, None, w2, None, wg=wg, act="silu")
    shapes = [((128, 2560), F32), ((2560, 8192), BF16),
              ((8192, 2560), BF16), ((2560, 8192), BF16)]
    hlo = _compile(one_chip, fn, *shapes)
    assert 0 < _kernels(hlo)["mlp"] <= 16 * 2 ** 20
    x, w1, w2, _ = (jax.ShapeDtypeStruct(s, d) for s, d in shapes)
    assert mlp._default_mlp_blocks(x, w1, w2, False, True) == (128, 256)
    # gpt2_medium's and bert_large's layers: as before
    for rows, dt in ((64, F32), (128, F32), (8192, BF16)):
        x, w1, w2 = (jax.ShapeDtypeStruct(s, dt) for s in (
            (rows, 1024), (1024, 4096), (4096, 1024)))
        assert mlp._default_mlp_blocks(x, w1, w2, False)[1] == 512


def test_tile_plan_fits_budget():
    """pick_rv_blocks is the plan the compiles above check: at every
    width it is asked for, what it plans fits its own budget."""
    from paddle_tpu.ops.pallas.core import (RV_VMEM_BUDGET, pick_rv_blocks,
                                            rv_vmem_bytes)
    for ib in (2, 4):
        for n, v, h in [(8176, 32000, 768), (4864, 30522, 768),
                        (8192, 128256, 4096), (19, 133, 48)]:
            for resident, rows, out in (("rows", 5, None),
                                        ("rows", 3, "rows"),
                                        ("vocab", 3, "vocab")):
                bn, bv = pick_rv_blocks(n, v, h, ib, resident, rows, out)
                assert rv_vmem_bytes(bn, bv, h, ib, rows,
                                     out) <= RV_VMEM_BUDGET
                assert bn == n or bn % 8 == 0
                assert bv == v or bv % 128 == 0
    # the resident axis is maximized first
    assert pick_rv_blocks(8176, 32000, 768, 2, "vocab", 3, "vocab")[1] \
        == 1024
    assert pick_rv_blocks(8176, 32000, 768, 2, "rows", 3, "rows")[0] == 512
    assert np.prod(pick_rv_blocks(19, 133, 48, 4)) == 19 * 133


# ------------------------------------------------------------ the sampler

@pytest.mark.parametrize("rows,vocab", [(64, 50257), (128, 65536)])
def test_sampler_keeps_its_conditional_on_the_chip(one_chip, rows, vocab):
    """The chip's compiler leaves the sampler's lax.cond a conditional
    (it does not flatten it into a select that runs both sides): at the
    serving cells' shapes the sort of the vocabulary stands in a branch
    computation and not in the entry computation, so a round whose rows
    are all greedy does not run it (PERF.md section 6, PR 29)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.serving import ServeConfig, ServingEngine
    cfg = GPTConfig.tiny()
    model = GPTDecoder(cfg)
    eng = ServingEngine(model, model.init(jax.random.key(0)), ServeConfig(
        num_slots=2, page_size=8, max_len=24, prefill_len=8, num_pages=6,
        metrics_port=0))
    hlo = _compile(one_chip, eng._sample, ((rows, vocab), F32),
                   ((rows,), F32), ((rows,), I32), ((rows,), F32),
                   ((rows,), jnp.uint32), ((rows,), I32))
    eng.close()
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert " conditional(" in entry and 'op_name="jit(_sample)/cond"' in entry
    assert " sort(" in hlo and " sort(" not in entry
    assert "rng-bit-generator" not in entry and "cumsum" not in entry


# ------------------------------------ the serving engine's step programs
#
# Both whole programs as ServingEngine builds them, at gpt2_medium.chat's
# widths and two layers: the slots' pending tokens go in and come out as
# a device array (the engine launches round n+1 before it reads round n,
# serving/engine.py "the round"), the donated pools still come back in
# place with nothing relaid, and the sampler is still a branch.

@pytest.fixture(scope="module")
def serve_programs(one_chip):
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.ops.pallas import core
    from paddle_tpu.serving import ServeConfig, ServingEngine
    cfg = GPTConfig(vocab_size=50257, hidden_size=1024, num_layers=2,
                    num_heads=16, intermediate_size=4096,
                    max_position=1024, dropout=0.0, use_flash=True)
    model = GPTDecoder(cfg)
    # weights and pools as shapes alone (the cell's 1024 pages a pool: a
    # small pool the compiler would move to faster memory and back): the
    # engine only hands them to its programs
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    real_pools = model.init_paged_caches
    model.init_paged_caches = lambda *a, **kw: jax.eval_shape(
        lambda: real_pools(*a, **kw))
    eng = ServingEngine(model, variables, ServeConfig(
        num_slots=POOL_SLOTS, page_size=POOL_PAGE, max_len=1024,
        prefill_len=CHUNK, num_pages=POOL_PAGES, cache_dtype=BF16,
        prefix_cache=False, metrics_port=0))
    s = POOL_SLOTS

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def knobs(n):
        return [jax.ShapeDtypeStruct((n,), d, sharding=one_chip)
                for d in (F32, I32, F32, jnp.uint32, I32)]

    def vec(n, dt=I32):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    state = on_chip((eng._params, (eng._caches, eng._state)))
    table = jax.ShapeDtypeStruct((s, POOL_PMAX), I32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((1, POOL_PMAX), I32, sharding=one_chip)
    chunk = jax.ShapeDtypeStruct((1, CHUNK), I32, sharding=one_chip)
    was = core.on_tpu
    core.on_tpu = lambda: True
    eng._aot_trace = True         # deliberate traces, as compiled_*()
    try:
        decode = eng._decode_jit.lower(
            *state, vec(s), table, vec(s), vec(s, jnp.bool_),
            *knobs(s)).compile().as_text()
        prefill = eng._prefill_jit.lower(
            *state, vec(s), chunk, vec(1), vec(1), row, vec(1), vec(1),
            *knobs(1)).compile().as_text()
    finally:
        core.on_tpu = was
        eng._aot_trace = False
        eng.close()
    pools = sum(len(jax.tree_util.tree_leaves(c)) for c in eng._caches)
    return {"decode": decode, "prefill": prefill, "pools": pools}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serve_step_programs_keep_the_tokens_on_the_device(
        serve_programs, program):
    hlo = serve_programs[program]
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    # the pending tokens: a [slots] int32 parameter in, the same out
    slots = re.escape(f"s32[{POOL_SLOTS}]") + r"\{[^}]*\}"
    assert re.search(slots + r' parameter\(\d+\)[^\n]*op_name="tokens"',
                     entry)
    header = next(l for l in hlo.splitlines() if l.startswith("HloModule"))
    assert re.search(r"\)->\(" + slots + ", bf16", header)
    # every pool is written in place, twice a layer, and nothing of a
    # pool's shape is copied or relaid
    copies, writes, aliased = _pool_relayouts(hlo, POOL)
    assert not copies, copies
    assert writes == serve_programs["pools"] == len(aliased) == 4
    # the sampler keeps its conditional: no sort in the entry computation
    assert " conditional(" in entry and " sort(" in hlo
    assert " sort(" not in entry and "rng-bit-generator" not in entry
    want = {"decode": {"decode_attention", "mlp"},
            "prefill": {"flash_attention", "mlp"}}[program]
    assert want <= set(_kernels(hlo)), _kernels(hlo)
