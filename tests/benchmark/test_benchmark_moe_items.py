"""The metric ``serve.moe_items_live_share`` (PR 36) of the cell
``k_exaone_236b.chat_1k``: the share of the grouped expert kernel's grid
items that stream an expert's weights, read from ``serve.step``'s
``moe_items`` / ``moe_item_slots`` counts. It is the cell's model's own,
reads nothing where nothing was counted (the parent of PR 36), and reads
hand counts right.
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

CELL = "k_exaone_236b.chat_1k"
METRIC = "serve.moe_items_live_share"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000


@pytest.fixture(scope="module")
def shapes():
    return bench_run.find_cell(ROOT, CELL)[2]["shapes"]


@pytest.fixture(scope="module")
def spec():
    return roots.read(os.path.join(ROOT, "benchmark", "metrics",
                                   METRIC + ".json"))


def run_of(shapes, records=None, trace=None):
    run = {"trace": trace, "facts": {}, "config": shapes, "traffic": {},
           "peaks": PEAKS}
    if records is not None:
        run["engine_spans"] = (records, 0.0)
    return run


def test_the_live_share_is_the_cells_models_own(spec):
    bench = roots.read(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[METRIC]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] in roots.reported_by(bench, CELL)
    assert (entry["unit"], entry["source"], entry["layer"]) == (
        "%", "program_counter", "serve engine")
    assert roots.is_a_models_own(spec)


@pytest.mark.parametrize("records", [
    None,
    # a store of the parent's: the rows counted, no items
    [{"name": "serve.step", "counts": {
        "moe_calls": 1, "moe_rows": 800, "moe_rows_max": 60,
        "moe_experts_hit": 9.5}}],
    # a model without experts
    [{"name": "serve.step", "counts": {"pages_in_use": 3}},
     {"name": "serve.admit", "counts": {}}],
], ids=["no_store", "parent_store", "no_experts"])
def test_the_live_share_reads_nothing_where_nothing_was_counted(
        records, spec, shapes):
    read = bench_run.load_reader(spec)
    assert read(spec, run_of(shapes, records)) is None


def test_the_live_share_is_made_of_what_was_counted(spec, shapes):
    """Three rounds read: a decode round (4 layers x 47 items of which
    40 live), a round with two chunks besides (4 x 47 + 2 x 4 x 31
    items, 90 live) and one that read no program."""
    records = [
        {"name": "serve.step", "counts": {
            "moe_calls": 1, "moe_items": 40, "moe_item_slots": 188}},
        {"name": "serve.admit", "counts": {}},
        {"name": "serve.step", "counts": {
            "moe_calls": 3, "moe_items": 90, "moe_item_slots": 436}},
        {"name": "serve.step", "counts": {"pages_in_use": 3}}]
    read = bench_run.load_reader(spec)
    assert read(spec, run_of(shapes, records)) == pytest.approx(
        100.0 * 130 / 624)
