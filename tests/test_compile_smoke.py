"""Tier-1 jit-compilability smoke for the fused train step (no chip).

``tools/compile_smoke.train_program`` builds the tiny train step from the
builders chip_smoke.py runs on the chip and compiles it here, in the
test's own process: the chunked fused cross-entropy (custom VJP), the
scan-over-layers + remat GPT encoder, and the fused LN path must lower AND
compile inside one jitted train step on the CPU backend — a trace-time
regression in the step-fusion layer fails here, not in the next chip
run (what the chip's own compiler accepts is tests/test_mosaic_compile.py's
business).
"""

import pytest


@pytest.fixture(scope="module")
def plain_gpt():
    import tools.compile_smoke as cs
    return cs.train_program("gpt")


@pytest.mark.perf
def test_bench_gpt_compile_only_tiny(plain_gpt):
    assert plain_gpt["mesh"] is None and plain_gpt["plan"] is None
    assert "ENTRY" in plain_gpt["hlo"]
    assert plain_gpt["cost"]["flops"] > 0, plain_gpt["cost"]


@pytest.mark.perf
def test_bench_gpt_compile_only_tiny_remat(plain_gpt):
    """The remat-enabled scan step must also compile (dots_saveable is
    the policy the silicon runs will flip on first), and it is another
    program: what the policy recomputes in the backward pass is counted."""
    import tools.compile_smoke as cs
    remat = cs.train_program("gpt", remat="dots_saveable")
    assert remat["cost"]["flops"] > plain_gpt["cost"]["flops"]


@pytest.mark.perf
@pytest.mark.parametrize("model", ["gpt", "bert", "transformer_big"])
def test_sharded_dp_tp_hlo_contract(model):
    """The dp2,tp2 GSPMD train step (4 of the virtual CPU devices, the
    vocab dimension of the tied embedding / output projection over tp)
    must compile AND its per-device HLO must contain no [rows, V]-scale
    temporary and no all-gather of the vocab-sharded weight.

    gpt carries the controls and the priced row: the ``fused_xent=False``
    reference step must TRIP the detector (proves the grep sees
    full-vocab logits; the other two skip that extra compile), the
    compiled flops/bytes must stay under costmodel.predict() x tolerance
    (with a tolerance=0 control proving the budget detector trips on a
    real compile), and the op histogram must match the blessed
    train.gpt@dp2,tp2 snapshot."""
    import tools.compile_smoke as cs
    out = cs.sharded_vocab_check(model=model,
                                 positive_control=model == "gpt")
    assert out["clean"], out["violations"]
    assert out["mesh"] == {"dp": 2, "tp": 2}
    assert out["cost"] and out["cost"]["flops"] > 0, out["cost"]
    if model == "gpt":
        assert out["positive_control_trips"]
        assert out["budget_control_trips"]


@pytest.mark.perf
def test_mesh_the_case_does_not_divide_over_raises():
    """dp3 divides no batch in the contract table: the build says so,
    with the axes, where a child process used to print a failed row."""
    import tools.compile_smoke as cs
    with pytest.raises(ValueError, match=r"'dp': 3.*batch 16"):
        cs.train_program("gpt", mesh="dp3")


@pytest.mark.perf
def test_mesh_larger_than_the_process_names_the_xla_flag():
    """dp4,tp4 wants sixteen devices and the tests' process has eight:
    no devices are fabricated behind the caller's back."""
    import tools.compile_smoke as cs
    with pytest.raises(
            RuntimeError,
            match="xla_force_host_platform_device_count=16"):
        cs.train_program("gpt", mesh="dp4,tp4")


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
