"""The cell ``k_exaone_236b.chat_1k`` (PR 35): the per-layer metrics it
brought, each on what it cannot read and its two rooflines against
numbers worked by hand. The grouped kernel's roofline takes its work
from what the program COUNTED (pairs on held experts, experts that got a
row), not from an expectation of the routing; the decode kernel's counts
K/V heads, the full layer's live pages and the window layers' rings.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import benchmark_roots as roots  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import work_exaone_moe as work  # noqa: E402

CELL = "k_exaone_236b.chat_1k"
OWN = ["moe_expert_mlp_roofline", "decode_attention_gqa_roofline",
       "serve.moe_rows_per_expert", "serve.moe_load_max_over_mean"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000


def trace_of(*ops):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "XLA Modules", "events": [
                ["jit_decode(1)", 1 * MS, 2 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 100 * MS], ["bench.step", 0, 3 * MS]]}]}]}


def spec_of(metric):
    return roots.read(os.path.join(ROOT, "benchmark", "metrics",
                                   metric + ".json"))


@pytest.fixture(scope="module")
def shapes():
    return bench_run.find_cell(ROOT, CELL)[2]["shapes"]


@pytest.mark.parametrize("metric", OWN)
def test_a_metric_of_the_cell_is_its_models_own_and_reads_nothing_from_nothing(
        metric, shapes):
    bench = roots.read(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] in roots.reported_by(bench, CELL)
    spec = spec_of(metric)
    assert roots.is_a_models_own(spec)
    reader = bench_run.load_reader(spec)
    # no trace (a rehearsal); a trace with neither the kernel's events
    # nor a store with the counts (the parent commit)
    other = trace_of(["%mlp.2 = bf16[8,8] custom-call(...)", 1 * MS, 2 * MS])
    for trace in (None, other):
        run = {"trace": trace, "facts": {}, "config": shapes, "traffic": {},
               "peaks": PEAKS}
        assert reader(spec, run) is None


def test_the_grouped_kernels_roofline_is_made_of_what_was_counted(shapes):
    """Two steps read three programs over four expert layers: 300 pairs
    on held experts; 10 and 9.5 of 16 held experts got a row a layer's
    call. The kernel ran 12 times, 24 ms in all."""
    spec = spec_of("moe_expert_mlp_roofline")
    counts = [{"moe_rows": 200, "moe_rows_max": 40, "moe_experts_hit": 10.0,
               "moe_calls": 2},
              {"moe_rows": 100, "moe_rows_max": 30, "moe_experts_hit": 9.5,
               "moe_calls": 1}]
    events = [[f"moe_expert_mlp.{i}" if i else "moe_expert_mlp",
               (1 + 3 * i) * MS, 2 * MS] for i in range(12)]
    run = {"trace": trace_of(*events), "facts": {}, "config": shapes,
           "traffic": {}, "peaks": PEAKS, "moe_rounds": (counts, 4 * 16)}
    expert = 3 * 6144 * 2048
    reads = 4 * (10.0 * 2 + 9.5 * 1)                  # 118 experts read
    ops, nbytes = work.expert_mlp(shapes, 300, reads)
    assert ops == 2 * 300 * expert
    assert nbytes == 2 * reads * expert + 300 * 6144 * 6
    got = bench_run.load_reader(spec)(spec, run)
    assert got == pytest.approx(100.0 * (nbytes / 819e9) / 24e-3)
    # all sixteen read in every call would be 1.6 times the bytes: the
    # expectation of an even routing is not what the kernel streamed
    assert 4 * 3 * 16 / reads == pytest.approx(1.627, abs=1e-3)
    # the kernel's events without the counts: nothing
    assert bench_run.load_reader(spec)(spec, dict(run, moe_rounds=None)) \
        is None


def test_the_decode_kernels_roofline_counts_kv_heads_pages_and_rings(shapes):
    """1000 positions decoded whose contexts fill 320 000 rows of whole
    pages (320 a position: past the window): the full layer reads them
    all, each of the four window layers its ring of 128 rows a
    position; K and V of 8 heads of 128 in bfloat16."""
    ops, nbytes = work.decode_attention(shapes, 320_000, 1000)
    rows = 320_000 + 4 * 128 * 1000
    assert nbytes == rows * 8 * 128 * 2 * 2
    assert ops == 4 * rows * 64 * 128
    # contexts of one page: a ring holds no more than the context
    ops, nbytes = work.decode_attention(shapes, 64_000, 1000)
    assert nbytes == 5 * 64_000 * 8 * 128 * 2 * 2
    spec = spec_of("decode_attention_gqa_roofline")
    events = [["decode_attention.7", 1 * MS, 2 * MS],
              ["decode_attention", 4 * MS, 3 * MS]]
    run = {"trace": trace_of(*events), "config": shapes, "traffic": {},
           "peaks": PEAKS,
           "facts": {"decode_live_rows": 320_000, "decode_slot_steps": 1000}}
    got = bench_run.load_reader(spec)(spec, run)
    assert got == pytest.approx(
        100.0 * (rows * 4096 / 819e9) / 5e-3)
    # the other model's file would count 64 heads and five pooled layers
    assert 5 * 320_000 * 64 / (rows * 8) == pytest.approx(15.4, abs=0.1)
