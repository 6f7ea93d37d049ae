"""Tier-1 jit-compilability smoke for the fused train step (no chip).

Drives ``python bench.py --compile-only --model gpt --tiny`` through
tools/compile_smoke.py: the chunked fused cross-entropy (custom VJP), the
scan-over-layers + remat GPT encoder, and the fused LN path must lower AND
compile inside one jitted train step on the CPU backend — a trace-time
regression in the step-fusion layer fails here, not in the next chip
run (what the chip's own compiler accepts is tests/test_mosaic_compile.py's
business).
"""

import pytest


@pytest.mark.perf
def test_bench_gpt_compile_only_tiny():
    import tools.compile_smoke as cs
    row = cs.run(model="gpt", tiny=True, timeout=420)
    assert row["metric"] == "gpt_compile_only"
    assert row["value"] == 1.0 and row["unit"] == "compiled"


@pytest.mark.perf
def test_bench_gpt_compile_only_tiny_remat():
    """The remat-enabled scan step must also compile (dots_saveable is
    the policy the silicon runs will flip on first)."""
    import tools.compile_smoke as cs
    row = cs.run(model="gpt", tiny=True, timeout=420,
                 extra_env={"PT_BENCH_REMAT": "dots_saveable"})
    assert row["metric"] == "gpt_compile_only"


@pytest.mark.perf
def test_bench_gpt_sharded_dp_tp_hlo_contract():
    """The dp2,tp2 GSPMD train step (4 fake CPU devices, vocab-sharded
    tied embedding) must compile AND its per-device HLO must contain no
    [rows, V]-scale temporary and no all-gather of the vocab-sharded
    weight; the PT_FUSED_XENT=0 reference step must TRIP the detector
    (positive control — proves the grep sees full-vocab logits).

    The row also carries cost-model-priced budgets and the blessed
    train.gpt@dp2,tp2 snapshot: the compiled flops/bytes must stay
    under costmodel.predict() x tolerance (with a tolerance=0 control
    proving the budget detector trips on a real compile) and the op
    histogram must match the blessed record."""
    import tools.compile_smoke as cs
    out = cs.sharded_vocab_check(model="gpt", timeout=420)
    assert out["clean"], out["violations"]
    assert out["positive_control_trips"]
    assert out["cost"] and out["cost"]["flops"] > 0, out["cost"]
    assert out["budget_control_trips"]
    assert out["row"]["mesh"] == {"dp": 2, "tp": 2}


@pytest.mark.perf
def test_serve_step_traced_once_and_paged_hlo_contract():
    """Serving fast path (in-process, CPU): mixed-length admission waves
    must leave the jitted serve step traced exactly once, and the
    paged + Pallas(interpret) decode HLO must hold no [rows, Tmax]-dense
    gathered-K/V or score temporary — the XLA gather-and-mask fallback
    (use_pallas_decode=0) is the positive control that proves the
    detector sees dense decode attention. The wave includes a
    40-token prompt admitted through prefill_len=16 chunked prefill.

    The decode row also prices the step against
    costmodel.predict_decode() budgets (tolerance=0 control included)
    and gates the op histogram on the blessed serve.decode snapshot."""
    import tools.compile_smoke as cs
    out = cs.serve_smoke()
    assert out["decode_traces"] == 1 and out["prefill_traces"] == 1, out
    assert out["clean"], out["violations"]
    assert out["positive_control_trips"]
    assert out["cost"] and out["cost"]["flops"] > 0, out["cost"]
    assert out["budget_control_trips"]
    assert out["finished"] == 7


@pytest.mark.perf
def test_fused_mlp_hlo_contract():
    """Fused GLU/MLP (in-process, CPU): the compiled forward of both the
    plain and gated variants must hold no [rows, 4H] activation
    temporary — the kernel streams I-axis tiles through a
    [block_rows, H] accumulator. The unfused composition
    (use_pallas_mlp=0) is the positive control that proves the detector
    sees the materialized activation."""
    import tools.compile_smoke as cs
    out = cs.mlp_smoke()
    assert out["clean"], (out["mlp_temporaries"], out["glu_temporaries"])
    assert out["positive_control_trips"]


@pytest.mark.perf
def test_bench_bert_sharded_dp_tp_hlo_contract():
    """Same contract for the BERT-pretrain step (masked-position MLM head
    over the vocab-sharded table + tp-sharded mlm_bias). Detector
    validity is already proven by the GPT positive control; skipping the
    extra reference compile keeps the tier-1 budget."""
    import tools.compile_smoke as cs
    out = cs.sharded_vocab_check(model="bert", timeout=420,
                                 positive_control=False)
    assert out["clean"], (out["vocab_temporaries"],
                          out["weight_all_gathers"])
