"""The readers of the host's own time (``benchmark/readers/host``) on the
hand-made scene of ``test_benchmark_engine_spans`` (a window of 100 ms,
two rounds, the device busy 12-19, 24-34 and 53-73 ms: idle 63 ms), with
the program's real span store filled by hand: no chip, no engine.

Collections, in ms on the trace's clock: 20-22 (all of it device idle),
30-31 (under the device's work), 70-76 (idle from 73), 99-103 (across
the window's close: 1 ms inside, idle) and 120-125 (the drain's, outside).
Set-up, in s: the import 0-23.5; a trace 101.0-101.5 with a nested one
101.1-101.3, a lowering 101.5-101.7, a compile 101.7-103.0 with a fetch
from the cache 102.0-102.9 inside it, all before the session opened at
110, and a compile 120-121 after it.
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import engine_spans as es  # noqa: E402
from test_benchmark_engine_spans import (OFFSET, hand_records,  # noqa: E402
                                         hand_trace, rec)

NEW = ["host.gc_share.train", "host.gc_share.serve",
       "host.gc_longest_ms.serve", "host.gc_idle_share.serve",
       "setup.import_s", "setup.programs_s"]
PAUSES = [(20, 22), (30, 31), (70, 76), (99, 103), (120, 125)]


def pause(i, a_ms, b_ms, generation=0):
    r = rec(100 + i, "host.gc", a_ms, b_ms, generation=generation,
            collected=3, uncollectable=0, thread="MainThread")
    r["path"] = None
    return r


def process(name, a_s, b_s):
    return {"id": None, "name": name, "path": None, "start": a_s,
            "end": b_s, "parent": None, "rid": None, "counts": {}}


@pytest.fixture
def spans(monkeypatch):
    """The program's spans module over a store of this test's own."""
    from paddle_tpu.observability import spans
    store = spans.SpanStore()
    monkeypatch.setattr(spans, "_STORE", store)
    return spans


def fill(store, pauses=True, setup=True):
    for r in hand_records():
        store.add(r)
    if pauses:
        for i, (a, b) in enumerate(PAUSES):
            store.add_gc(pause(i, a, b, generation=2 if b - a > 4 else 0))
    if setup:
        for name, a, b in [("setup.import", 0.0, 23.5),
                           ("setup.jit.trace", 101.1, 101.3),
                           ("setup.jit.trace", 101.0, 101.5),
                           ("setup.jit.lower", 101.5, 101.7),
                           ("setup.jit.cache_load", 102.0, 102.9),
                           ("setup.jit.compile", 101.7, 103.0),
                           ("setup.jit.compile", 120.0, 121.0)]:
            store.add_process(process(name, a, b))
        store.opened = 110.0


def run_of(trace, **facts):
    return {"trace": trace, "facts": facts, "config": {}, "traffic": {},
            "peaks": None}


def read(cell, run, names=NEW):
    bench = bench_run.read_json(ROOT, "BENCHMARK.json")
    got = bench_run.read_per_layer(ROOT, {"per_layer": [
        m for m in bench["per_layer"] if m["name"] in names]}, cell, run)
    return {k: v["value"] for k, v in got.items()}


def test_each_serving_reader_reads_its_hand_computed_value(spans, capsys):
    fill(spans._STORE)
    got = read("gpt2_medium.chat", run_of(hand_trace()))
    assert got == pytest.approx({
        "host.gc_share.serve": (2 + 1 + 6 + 1) / 100 * 100,
        "host.gc_longest_ms.serve": 6.0,
        # 20-22, 73-76 and 99-100 of the 63 ms idle
        "host.gc_idle_share.serve": 100 * 6 / 63}, abs=1e-9)
    err = capsys.readouterr().err
    assert "4 collection(s) in the window of 0.100 s" in err
    assert "6.000 ms of it under a collection" in err


def test_the_training_cells_readers_read_the_windows_facts_and_setup(
        spans, capsys):
    fill(spans._STORE)
    run = run_of(hand_trace(), t_open=OFFSET + 0.010, t_close=OFFSET + 0.080)
    got = read("bert_large.pretrain", run)
    assert got == pytest.approx({
        # 20-22, 30-31 and 70-76 inside [10, 80)
        "host.gc_share.train": 100 * 9 / 70,
        "setup.import_s": 23.5,
        # 101.0-103.0: the nested trace and the fetch lie inside others,
        # the compile after the session's opening is not set-up
        "setup.programs_s": 2.0}, abs=1e-9)
    assert "built 1 program(s), 1 of them from the persistent cache" in \
        capsys.readouterr().err


def test_each_cell_reads_the_new_metrics_it_lists(spans):
    fill(spans._STORE)
    bench = bench_run.read_json(ROOT, "BENCHMARK.json")
    for cell in [c["name"] for c in bench["workloads"]]:
        listed = {m["name"] for m in bench_run.metrics_of(
            bench, cell, "per_layer") if m["name"] in NEW}
        run = run_of(hand_trace(), t_open=OFFSET, t_close=OFFSET + 0.1)
        assert set(read(cell, run)) == listed, cell
    assert {m["layer"] for m in bench["per_layer"]
            if m["name"] in NEW} == {"host", "set-up"}


def test_with_the_hook_and_no_collection_the_shares_read_zero(spans):
    fill(spans._STORE, pauses=False)
    got = read("gpt2_medium.chat", run_of(hand_trace()))
    assert got["host.gc_share.serve"] == 0.0
    assert got["host.gc_longest_ms.serve"] == 0.0
    assert got["host.gc_idle_share.serve"] == 0.0
    run = run_of(hand_trace(), t_open=OFFSET, t_close=OFFSET + 0.1)
    assert read("bert_large.pretrain", run)["host.gc_share.train"] == 0.0


def test_nothing_to_read_gives_none(spans, monkeypatch):
    fill(spans._STORE)
    # the CPU rehearsal: no device trace
    assert read("gpt2_medium.chat", run_of(None)) == {}
    assert read("bert_large.pretrain",
                run_of(None, t_open=OFFSET, t_close=OFFSET + 0.1)) == {}
    # a program with a span store and without these lists (the parent)
    real = es.program_spans
    parent = types.SimpleNamespace(records=spans.records,
                                   self_segments=spans.self_segments)
    monkeypatch.setattr(es, "program_spans", lambda: parent)
    assert read("gpt2_medium.chat", run_of(hand_trace())) == {}
    assert read("bert_large.pretrain", run_of(
        hand_trace(), t_open=OFFSET, t_close=OFFSET + 0.1)) == {}
    # records that cannot be paired with the window's rounds: the serving
    # readers read nothing, the training cell's need no pairing
    monkeypatch.setattr(es, "program_spans", real)
    spans._STORE._records.clear()
    assert read("gpt2_medium.chat", run_of(hand_trace())) == {}
    assert set(read("bert_large.pretrain", run_of(
        hand_trace(), t_open=OFFSET, t_close=OFFSET + 0.1))) == {
        "host.gc_share.train", "setup.import_s", "setup.programs_s"}


def test_every_accepted_metric_reads_the_same_with_collections(spans):
    """The collections stand outside the span tree: no reader of the
    tree, and no other accepted metric, moves when the store holds them
    (nor when they are handed to ``self_segments`` with the tree)."""
    bench = bench_run.read_json(ROOT, "BENCHMARK.json")
    accepted = [m["name"] for m in bench["per_layer"] if m["name"] not in NEW]
    readings = []
    for pauses in (False, True):
        spans._STORE._records.clear()
        spans._STORE._gc.clear()
        fill(spans._STORE, pauses=pauses, setup=False)
        got = {}
        for cell in [c["name"] for c in bench["workloads"]]:
            run = run_of(hand_trace(), t_open=OFFSET, t_close=OFFSET + 0.1)
            got[cell] = read(cell, run, accepted)
        readings.append(got)
    assert readings[0] == readings[1]
    assert set(readings[0]["gpt2_medium.chat"]) >= {
        "serve.admit_wait_ms_p50", "serve.round_host_ms_p50",
        "serve.idle_ms_per_round.outside", "device.idle_share.serve"}
    tree = hand_records()
    own = spans.self_segments(tree)
    assert spans.self_segments(
        tree + [pause(i, a, b) for i, (a, b) in enumerate(PAUSES)]) == own
    assert es.idle_by_span(spans.records(), OFFSET, hand_trace()) == \
        es.idle_by_span(tree, OFFSET, hand_trace())
