"""The readers of the engine's spans (``benchmark/readers/engine_spans``)
on a hand-made neutral trace and hand-made span records whose answers
can be worked out on paper: no chip, no engine.

The scene, in ms on the trace's clock (the records' ``perf_counter``
runs ``OFFSET`` seconds ahead). A window of 100 ms holds two rounds:

    serve.submit 4.8-5.4 (request 5, under no annotation)
    bench.step 10-40   serve.step 10-39: admit 10-22 (prefill 11-21 of
                       request 5, its fetch 15-20), grow 22-23, decode
                       23-25, fetch 25-35, advance 35-38, self 38-39
    bench.account 40-42, bench.submit 43-44 (serve.submit 43.2-43.8 of
                       request 6, which the session never sees admitted)
    bench.step 50-80   serve.step 50-79: admit 50-51 (request 4, queued
                       before the session, is admitted at 50.5), grow
                       51-52, decode 52-54, fetch 54-74, advance 74-78
    bench.account 80-82

and the device runs 12-19 (the prefill), 24-34 and 53-73 (the decode
rounds): busy 37 ms, idle 63 ms in the gaps 0-12, 19-24, 34-53, 73-100.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import readers  # noqa: E402
from benchmark.readers import engine_spans as es  # noqa: E402

MS = 1_000_000
OFFSET = 1234.5
IDLE = ["serve.idle_ms_per_round." + phase for phase in
        ("admit", "decode", "fetch", "advance", "step", "outside")]
NAMES = ["serve.admit_wait_ms_p50", "serve.prefill_ms_p50",
         "serve.round_host_ms_p50", "serve.kv_pool_in_use"] + IDLE


def rec(i, name, a_ms, b_ms, parent=None, rid=None, event=False, **counts):
    return {"id": i, "name": name, "path": None if event else name,
            "start": OFFSET + a_ms / 1e3, "end": OFFSET + b_ms / 1e3,
            "parent": parent, "rid": rid, "counts": counts}


def hand_records():
    """In the order the store keeps them: a span is appended at its end."""
    return [
        rec(20, "submitted", 5, 5, parent=19, rid=5, event=True),
        rec(19, "serve.submit", 4.8, 5.4, rid=5),
        rec(21, "admitted", 11, 11, parent=3, rid=5, event=True),
        rec(4, "serve.prefill.fetch", 15, 20, parent=3, rid=5),
        rec(22, "first_token", 20.5, 20.5, parent=3, rid=5, event=True),
        rec(3, "serve.prefill", 11, 21, parent=2, rid=5),
        rec(2, "serve.admit", 10, 22, parent=1),
        rec(5, "serve.grow", 22, 23, parent=1),
        rec(6, "serve.decode", 23, 25, parent=1),
        rec(7, "serve.fetch", 25, 35, parent=1),
        rec(8, "serve.advance", 35, 38, parent=1),
        rec(1, "serve.step", 10, 39, pages_in_use=100, pages_cached=300,
            num_pages=1000),
        rec(23, "submitted", 43.5, 43.5, parent=9, rid=6, event=True),
        rec(9, "serve.submit", 43.2, 43.8, rid=6),
        rec(24, "admitted", 50.5, 50.5, parent=11, rid=4, event=True),
        rec(11, "serve.admit", 50, 51, parent=10),
        rec(12, "serve.grow", 51, 52, parent=10),
        rec(13, "serve.decode", 52, 54, parent=10),
        rec(14, "serve.fetch", 54, 74, parent=10),
        rec(15, "serve.advance", 74, 78, parent=10),
        rec(10, "serve.step", 50, 79, pages_in_use=140, pages_cached=300,
            num_pages=1000),
    ]


def hand_trace():
    ops = [["%copy.1 = bf16[8] copy(...)", 12 * MS, 7 * MS],
           ["%fusion.2 = f32[8] fusion(...)", 24 * MS, 10 * MS],
           ["%fusion.2 = f32[8] fusion(...)", 53 * MS, 20 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.step", 10 * MS, 30 * MS],
            ["bench.account", 40 * MS, 2 * MS],
            ["bench.submit", 43 * MS, 1 * MS],
            ["bench.step", 50 * MS, 30 * MS],
            ["bench.account", 80 * MS, 2 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


@pytest.fixture
def store(monkeypatch):
    """The program's span store, as the readers see it."""
    held = {"records": hand_records()}
    monkeypatch.setattr(es, "program_records", lambda: held["records"])
    return held


def run_of(trace):
    return {"trace": trace, "facts": {}, "config": {}, "traffic": {},
            "peaks": None}


def read_all(trace):
    bench = bench_run.read_json(ROOT, "BENCHMARK.json")
    got = bench_run.read_per_layer(ROOT, {"per_layer": [
        m for m in bench["per_layer"] if m["name"] in NAMES]},
        "gpt2_medium.chat", run_of(trace))
    return {k: v["value"] for k, v in got.items()}


def test_the_offset_is_recovered_from_shifted_clocks(store, capsys):
    assert es.pair_clocks(store["records"], hand_trace()) == \
        pytest.approx(OFFSET, abs=1e-9)
    # the second round's step entered 30 us later than the first's: the
    # offset is the median, and both pairs lie within the tolerance
    late = hand_records()
    late[-1]["start"] += 30e-6
    assert es.pair_clocks(late, hand_trace()) == \
        pytest.approx(OFFSET + 15e-6, abs=1e-9)
    assert "widest departure 15.0 us" in capsys.readouterr().err


def test_records_of_another_window_are_refused(store, capsys):
    # a bench.step is missing: the counts differ
    short = hand_trace()
    del short["planes"][1]["lines"][0]["events"][4]
    assert es.pair_clocks(store["records"], short) is None
    assert "2 serve.step record(s) against 1 bench.step" in \
        capsys.readouterr().err
    # a bench.step outside bench.window is no round of the window
    outside = hand_trace()
    outside["planes"][1]["lines"][0]["events"][0] = \
        ["bench.window", 45 * MS, 55 * MS]
    assert es.pair_clocks(store["records"], outside) is None
    # a pair 1 ms out: over the 0.2 ms that a pair may depart (with two
    # pairs the median lies half way, so each departs by 0.5 ms)
    out = hand_records()
    out[-1]["start"] += 1e-3
    assert es.pair_clocks(out, hand_trace()) is None
    assert "0.500 ms from the median offset" in capsys.readouterr().err
    store["records"] = out
    assert read_all(hand_trace()) == {}


def drain_records():
    """A third round after the window's close at 100 ms, as the store
    holds it since the profiler stops after the drain (PR 31): a step
    105-130 with its phases, and request 6 admitted in it."""
    return [
        rec(31, "admitted", 105.5, 105.5, parent=32, rid=6, event=True),
        rec(32, "serve.admit", 105, 106, parent=30),
        rec(33, "serve.decode", 106, 108, parent=30),
        rec(34, "serve.fetch", 108, 126, parent=30),
        rec(35, "serve.advance", 126, 129, parent=30),
        rec(30, "serve.step", 105, 130, pages_in_use=900, pages_cached=0,
            num_pages=1000),
    ]


def test_the_drains_records_after_the_close_are_left_out(store, capsys):
    """The session outlives the window: what the store holds of the
    drain changes no reading (the trace's own events are cut at
    ``bench.window`` by ``trace_reduce``)."""
    sound = read_all(hand_trace())
    assert set(sound) == set(NAMES)
    trace = hand_trace()
    trace["planes"][1]["lines"][0]["events"].append(
        ["bench.step", 105 * MS, 26 * MS])
    trace["planes"][0]["lines"][0]["events"].append(
        ["%fusion.2 = f32[8] fusion(...)", 107 * MS, 18 * MS])
    store["records"] = hand_records() + drain_records()
    capsys.readouterr()
    assert read_all(trace) == pytest.approx(sound)
    assert "6 record(s) after the window's close" in capsys.readouterr().err
    # a surplus step INSIDE the window is no drain: the records are not
    # this window's rounds
    inside = hand_records() + [rec(40, "serve.step", 85, 95)]
    assert es.pair_clocks(inside, hand_trace()) is None
    assert "3 serve.step record(s) against 2 bench.step" in \
        capsys.readouterr().err


def test_idle_time_goes_to_the_innermost_span_that_covers_it(store):
    table = es.idle_by_span(store["records"], OFFSET, hand_trace())
    ms = {k: 1e3 * v for k, v in table.items()}
    assert ms == pytest.approx({
        # 19-20 of the gap 19-24 lies under the fetch inside the prefill
        "serve.prefill.fetch": 1.0,
        # 11-12 and 20-21: the prefill's own time, its fetch taken out
        "serve.prefill": 2.0,
        "serve.admit": 3.0,          # 10-11, 21-22, 50-51
        "serve.grow": 2.0, "serve.decode": 2.0, "serve.fetch": 2.0,
        "serve.advance": 7.0,        # 35-38, 74-78
        "serve.step": 2.0,           # its self time: 38-39, 78-79
        "serve.submit": 1.2,         # 4.8-5.4, 43.2-43.8
        # between two steps: bench.step's own edge 39-40 and 79-80,
        # bench.account 40-42 and 80-82, bench.submit round serve.submit
        es.OUTSIDE: 6.4,
        # 0-4.8, 5.4-10, 42-43, 44-50, 82-100: under no annotation
        es.UNATTRIBUTED: 34.4}, abs=1e-6)
    assert sum(ms.values()) == pytest.approx(63.0)


def test_each_metric_reads_its_hand_computed_value(store, capsys):
    trace = hand_trace()
    got = read_all(trace)
    assert got == pytest.approx({
        # request 5: in the queue at 5.4, admitted at 11; request 6: in
        # the queue at 43.8 and still there when the session closes at
        # 79, at least 35.2; request 4: queued before the session opened
        # at 4.8, admitted at 50.5, at least 45.7
        "serve.admit_wait_ms_p50": 35.2,
        "serve.prefill_ms_p50": 10.0,
        # 29 - 10 - 5 and 29 - 20
        "serve.round_host_ms_p50": 11.5,
        "serve.kv_pool_in_use": 12.0,
        # the table of the test above, over two rounds
        "serve.idle_ms_per_round.admit": (3.0 + 2.0 + 1.0) / 2,
        "serve.idle_ms_per_round.decode": 1.0,
        "serve.idle_ms_per_round.fetch": 1.0,
        "serve.idle_ms_per_round.advance": 3.5,
        "serve.idle_ms_per_round.step": (2.0 + 2.0) / 2,
        "serve.idle_ms_per_round.outside": (1.2 + 6.4 + 34.4) / 2},
        abs=1e-6)
    # the six parts are all of the idle time of device.idle_share.serve
    share = readers.idle_share({}, run_of(trace))
    assert sum(got[name] for name in IDLE) * 2 == pytest.approx(
        share / 100 * 100.0)
    err = capsys.readouterr().err
    assert err.count("paired with bench.step") == 1      # once a run
    assert err.count("by what the host was in") == 1
    assert "serve.advance" in err and "a round" in err
    assert "median of 3 waits, 2 of them lower bounds" in err


def test_the_idle_metrics_files_leave_no_row_out():
    specs = {name: bench_run.read_json(ROOT, "benchmark", "metrics",
                                       name + ".json") for name in IDLE}
    listed = [row for name in IDLE[:-1] for row in specs[name]["under"]]
    assert len(listed) == len(set(listed))
    # what no other file lists falls to `outside`, a row of a span that
    # a later PR adds too
    assert sorted(specs[IDLE[-1]]["not_under"]) == sorted(listed)
    run = {"engine_spans": ([], 0.0),
           "engine_spans_idle": ({"serve.spec": 0.004, "serve.fetch": 0.002,
                                  es.OUTSIDE: 0.001}, 2)}
    assert es.idle_ms_per_round(specs[IDLE[-1]], run) == \
        pytest.approx(2.5)
    assert es.idle_ms_per_round(specs[IDLE[2]], run) == pytest.approx(1.0)
    assert es.idle_ms_per_round(specs[IDLE[1]], run) == 0.0


def test_nothing_to_read_gives_none_for_every_metric(store, monkeypatch,
                                                     capsys):
    # a run with no device trace (a rehearsal on the CPU)
    assert read_all(None) == {}
    # an empty store: no session was on
    store["records"] = []
    assert read_all(hand_trace()) == {}
    assert "span store is empty" in capsys.readouterr().err
    # a program that keeps no records (the parent of PR 25)
    monkeypatch.undo()
    from paddle_tpu.observability import spans
    monkeypatch.delattr(spans, "records")
    assert es.program_spans() is None and es.program_records() == []
    assert read_all(hand_trace()) == {}
