"""K-EXAONE's layers through ``HybridDecoder`` and ``ServingEngine`` at
the tiny size of ``tests/benchmark/fixtures/tiny/k_exaone_236b.json``
(a dense layer and a whole period window window full window, 4 of 8
experts held, a window of 8), against the plain reference
``benchmark/reference/exaone_moe.py``, which imports nothing of the
program. Weights are float32 here, so that the program and the reference
differ by summation order alone; the bfloat16 path is the benchmark's
(``tests/benchmark``).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import weights, work_exaone_moe as work  # noqa: E402
from benchmark.reference import exaone_moe as reference  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.enforce import EnforceError  # noqa: E402
from paddle_tpu.models.hybrid import HybridConfig, HybridDecoder  # noqa: E402
from paddle_tpu.serving import ServeConfig, ServingEngine  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402


def read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


REAL = read("benchmark", "configs", "k_exaone_236b.json")
TINY = read("tests", "benchmark", "fixtures", "tiny", "k_exaone_236b.json")
KWARGS = {**REAL["constructor"]["kwargs"], **TINY["kwargs"]}
SHAPES = TINY["shapes"]
WINDOW = SHAPES["sliding_window"]

#: float32 weights on the CPU: the program sums in another order than
#: the reference (grouped products, a ring read out of order, chunks),
#: and logits of about 1.4 then agree to a few 1e-6; 5e-5 is ten times
#: that and a twelfth of what a router rounded to bfloat16 moves (below)
LOGIT_TOL = 5e-5


@pytest.fixture(scope="module")
def served():
    model = HybridDecoder(HybridConfig(**KWARGS))
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["params"]
    return model, {"params": weights.fill_blocks(shapes, 7, jnp.float32),
                   "state": {}}


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SHAPES["vocab_size"], n).astype(np.int32)


def reference_logits(variables, ids, first, n_out, pad_to=64):
    padded = np.zeros(pad_to, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(reference.logits_at(
        variables["params"], jnp.asarray(padded), first, shapes=SHAPES,
        n_out=n_out))


def through_the_caches(model, variables, ids, prompt_len, chunk=16,
                       slot=1, slots=3, page=8, pages=24):
    """Teacher-forced logits by the cache protocol alone: the prompt in
    chunks, then one decode round a token, in slot ``slot`` of ``slots``
    (the others idle). -> logits at positions prompt_len - 1 .. end."""
    def run(_):
        pools = model.init_paged_caches(pages, page)
        state = model.init_slot_state(slots)
        table = np.zeros((slots, 8), np.int32)
        table[slot] = np.arange(8) + 3
        out = []
        for start in range(0, prompt_len, chunk):
            n = min(chunk, prompt_len - start)
            piece = np.zeros((1, chunk), np.int32)
            piece[0, :n] = ids[start:start + n]
            logits, pools, state, routed = model.paged_prefill_chunk(
                jnp.asarray(piece), jnp.asarray([start]), jnp.asarray([n]),
                pools, jnp.asarray(table[slot:slot + 1]),
                state=state, slots=jnp.asarray([slot]))
            assert routed.shape == (4, SHAPES["held_experts"][1])
        out.append(logits[0])
        active = np.zeros(slots, bool)
        active[slot] = True
        for pos in range(prompt_len, len(ids)):
            tokens = np.zeros(slots, np.int32)
            tokens[slot] = ids[pos]
            lengths = np.zeros(slots, np.int32)
            lengths[slot] = pos
            logits, pools, state, routed = model.paged_decode_step(
                jnp.asarray(tokens), pools, jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(active), state)
            out.append(logits[slot])
        return jnp.stack(out)
    return np.asarray(model.apply(variables, None, method=run))


def test_whole_sequences_agree_with_the_reference(served):
    model, variables = served
    ids = ids_of(40)
    got = np.asarray(model.apply(variables, jnp.asarray(ids[None])))[0]
    want = reference_logits(variables, ids, 0, 40)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("prompt_len", [5, 21, 37])
def test_prefill_then_decode_through_the_caches_agrees_with_the_reference(
        served, prompt_len):
    """Prompts of one to three chunks of 16 (twice the window: a chunk's
    first queries read the ring, its last ones their own chunk), then
    decode to position 58, past TWICE the window of 8 from any start: a
    ring that wrapped seven times. Logits, position by position."""
    model, variables = served
    ids = ids_of(58, seed=prompt_len)
    got = through_the_caches(model, variables, ids, prompt_len)
    want = reference_logits(variables, ids, prompt_len - 1,
                            58 - prompt_len + 1)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


def test_a_router_product_rounded_to_bfloat16_fails_the_same_comparison(
        served, monkeypatch):
    """The float32 router is no nicety: with the product W_r h rounded
    to bfloat16 (and nothing else changed) the gates move in their third
    digit and the k-th expert can swap with the next, and the comparison
    above catches it: the logits then differ by 6e-4 here, twelve times
    the tolerance."""
    model, variables = served

    def rounded(self, x):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.bfloat16), self.p("router").astype(jnp.bfloat16).T,
            preferred_element_type=jnp.bfloat16).astype(jnp.float32))
        return nn.moe.sigmoid_top_k(
            scores, self.p("bias").astype(jnp.float32), self.k, self.scale)
    monkeypatch.setattr(nn.HeldExperts, "route", rounded)
    ids = ids_of(58, seed=21)
    got = through_the_caches(model, variables, ids, 21)
    want = reference_logits(variables, ids, 20, 38)
    assert np.abs(got - want).max() > 5 * LOGIT_TOL


def engine_for(served, **kw):
    model, variables = served
    kw = {"num_slots": 3, "page_size": 8, "max_len": 64, "prefill_len": 16,
          "prefix_cache": False, **kw}
    return ServingEngine(model, variables, ServeConfig(**kw))


def assert_served_tokens_are_the_references(served, eng, rids):
    for rid in rids:
        r = eng.requests[rid]
        n = len(r.tokens)
        logits = reference_logits(served[1], r.output, len(r.prompt) - 1, n)
        # greedy: every served token is the reference's best, and no
        # logit is within the tolerance of it
        assert np.asarray(logits.argmax(-1)).tolist() == r.tokens
        top2 = np.sort(logits, -1)[:, -2:]
        assert (top2[:, 1] - top2[:, 0]).min() > 2 * LOGIT_TOL


def test_the_engine_serves_the_references_tokens_and_counts_the_rows(
        served, fresh_store, profiler_session, monkeypatch):
    """Four requests through three slots (a slot is reused: its ring is
    never cleared), prompts of one to three chunks, 24 tokens each. Both
    programs trace once. Under a profiler session every ``serve.step``
    that read a program counts the routed rows; and the counts ride the
    trailing fetch: ONE device_get a flight, no block_until_ready."""
    eng = engine_for(served)
    ids = ids_of(40)
    gets = []
    real_get = jax.device_get

    def spy(x):
        gets.append(x)
        return real_get(x)

    def no_sync(*a, **k):
        raise AssertionError("block_until_ready in the serving loop")
    monkeypatch.setattr(jax, "device_get", spy)
    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    with profiler_session():
        rids = [eng.submit(ids[:n], max_new=24) for n in (5, 21, 13, 35)]
        eng.drain()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert len(gets) == eng.target_steps + len(rids)
    assert_served_tokens_are_the_references(served, eng, rids)

    counts = [r["counts"] for r in fresh_store.records()
              if r["name"] == "serve.step"]
    routed = [c for c in counts if "moe_calls" in c]
    assert routed and all(c["moe_rows"] <= c["moe_calls"] * 4 * 4 * 48
                          for c in routed)
    held, k, experts = 4, 4, 8
    # every real row of every program brings k choices, half of them
    # (by expectation) on the four held experts, in each of four layers
    prompt_rows = 5 + 21 + 13 + 35
    decode_rows = 4 * 23
    total = sum(c["moe_rows"] for c in routed)
    assert 0.35 < total / (4 * k * (prompt_rows + decode_rows)) < 0.65
    assert sum(c["moe_calls"] for c in routed) == (
        eng.target_steps + sum(-(-n // 16) for n in (5, 21, 13, 35)))
    for c in routed:
        assert 0 < c["moe_experts_hit"] <= held
        assert c["moe_rows_max"] * c["moe_calls"] * 4 * held >= c["moe_rows"]
        # the grouped kernel's grid: 3 slots x 4 choices are 16 rows a
        # decode call, a chunk's 16 x 4 are 64: one row tile each
        # (float32 weights), 1 + 4 - 1 items a layer; a live item is an
        # expert with a row, at least, and at most one a held expert
        assert c["moe_item_slots"] == c["moe_calls"] * 4 * 4
        assert c["moe_experts_hit"] * c["moe_calls"] * 4 == \
            pytest.approx(c["moe_items"])
    assert eng._moe_grid == {3: (16, 4), 16: (64, 4)}
    assert experts == SHAPES["num_experts"]
    # the window layers' state: counted as a model with state counts it
    per_slot = 4 * WINDOW * 2 * (2 * 64) * 4     # layers x rows x k,v
    assert eng.state_bytes() == 3 * per_slot
    assert counts[0]["state_bytes_reserved"] == 3 * per_slot
    eng.close()


def test_a_window_layer_reserves_a_window_and_never_a_context(served):
    model, _ = served
    state = model.init_slot_state(3, jnp.bfloat16)
    pools = model.init_paged_caches(16, 8, jnp.bfloat16)
    assert len(pools) == 1 and len(state) == 4
    for ring in state:
        assert ring["k"].shape == ring["v"].shape == (3, WINDOW, 2 * 64)
    # at the published sizes: slots x window x 4096 bytes a layer
    real = HybridDecoder(HybridConfig(**REAL["constructor"]["kwargs"]))
    rings = jax.eval_shape(lambda: real.init_slot_state(256, jnp.bfloat16))
    assert [sum(x.size * 2 for x in ring.values()) for ring in rings] == \
        [256 * 128 * 4096] * 4


def test_a_preempted_request_finishes_with_the_references_tokens(served):
    eng = engine_for(served, max_len=24, num_pages=3)
    ids = ids_of(14, seed=3)
    rids = [eng.submit(ids[:7], max_new=12), eng.submit(ids[7:], max_new=12)]
    eng.drain()
    assert sum(eng.requests[r].preemptions for r in rids) >= 1
    assert_served_tokens_are_the_references(served, eng, rids)
    eng.close()


def test_a_recovered_request_finishes_with_the_references_tokens(served):
    from paddle_tpu.core.flags import get_flag, set_flags
    saved = {k: get_flag(k) for k in ("retry_backoff_base_s",
                                      "retry_jitter")}
    set_flags({"retry_backoff_base_s": 0.001, "retry_jitter": 0.0})
    try:
        eng = engine_for(served)
        ids = ids_of(40, seed=5)
        rids = [eng.submit(ids[:n], max_new=12) for n in (5, 19, 11)]
        plan = chaos.FaultPlan(seed=0)
        plan.fail("fault_point", path=r"^serve.step$", nth=4, times=1)
        with chaos.active(plan):
            eng.drain()
        assert eng.recoveries == 1
        assert_served_tokens_are_the_references(served, eng, rids)
        eng.close()
    finally:
        set_flags(saved)


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "prefix hit skips"), ({"draft": True}, "roll")])
def test_what_the_rings_cannot_serve_is_refused(served, kw, reason):
    with pytest.raises(EnforceError, match=reason):
        engine_for(served, **kw)


def test_the_hybrid_models_tiny_logits_are_unchanged_bit_for_bit():
    """``jamba2_3b``'s class gained layer kinds, a norm placement and an
    untied head: with its own arguments it computes what it computed at
    the parent commit (``tests/fixtures/hybrid_tiny_golden.npz``: the
    tiny model's logits and served tokens, written by the parent)."""
    gold = np.load(os.path.join(ROOT, "tests", "fixtures",
                                "hybrid_tiny_golden.npz"))
    cfg = HybridConfig.tiny()
    model = HybridDecoder(cfg)
    variables = model.init(jax.random.key(0))
    logits = np.asarray(jax.jit(lambda v, i: model.apply(v, i))(
        variables, jnp.asarray(gold["ids"])))
    assert np.array_equal(logits[:, -8:], gold["logits"])
    eng = ServingEngine(model, variables, ServeConfig(
        num_slots=2, page_size=8, max_len=64, prefill_len=8,
        prefix_cache=False))
    rids = [eng.submit(gold["ids"][i, :n], max_new=10)
            for i, n in ((0, 13), (1, 5))]
    eng.drain()
    assert [eng.requests[r].tokens for r in rids] == gold["tokens"].tolist()
    eng.close()


# ------------------------------------------------- the counts, by hand

def test_the_published_count_and_the_held_count_worked_by_hand():
    cfg = REAL["shapes"]
    assert work.layer_counts(cfg) == (4, 1)
    attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144     # 113.2M
    expert = 3 * 6144 * 2048                                    # 37.7M
    assert work.attention_params(cfg) == attention == 113246208
    assert work.expert_params(cfg) == expert == 37748736
    norms = 2 * 6144 + 2 * 128
    dense = attention + 3 * 6144 * 18432 + norms
    sparse = attention + expert + 128 * 6144 + 128 + 16 * expert + norms
    held = dense + 4 * sparse + 2 * 19200 * 6144 + 6144
    assert work.parameters(cfg) == held == REAL["assumed"]["parameters"]
    assert round(2 * held / 1e9, 2) == 7.42                      # GB, bf16
    published = (47 * (sparse + 112 * expert) + dense
                 + 2 * 153600 * 6144 + 6144)
    assert published == REAL["assumed"]["published"]["parameters"]
    assert round(published / 1e9, 1) == 236.6


def test_forward_operations_and_the_kernels_work_worked_by_hand():
    cfg = REAL["shapes"]
    assert work.pairs_per_row(cfg) == 1.0          # 8 x 16 / 128
    matmuls = 2 * (5 * 113246208 + 3 * 6144 * 18432
                   + 4 * (128 * 6144 + 37748736 + 37748736))
    # four window layers see min(ctx, 128) keys, the full layer all
    at_300 = matmuls + 4 * (4 * 128 + 300) * 64 * 128
    assert work.forward_flops(cfg, 300, False) == at_300
    assert work.forward_flops(cfg, 300, True) == at_300 + 2 * 19200 * 6144
    at_100 = matmuls + 4 * (5 * 100) * 64 * 128
    assert work.forward_flops(cfg, 100, False) == at_100
    assert work.prefill_flops(cfg, 0, 3) == 3 * matmuls + 4 * 5 * (
        1 + 2 + 3) * 64 * 128
    # the kernel's work is made of what was counted: 15000 pairs and
    # 100 calls x 4 layers x 10 of 16 held experts that got a row
    ops, nbytes = work.expert_mlp(cfg, 15000, 4000)
    assert ops == 2 * 15000 * 37748736
    assert nbytes == 4000 * 2 * 37748736 + 15000 * 6144 * 6
