"""BENCHMARK.json against the contract's letter, and the harness's
promise that a cell, a configuration, a traffic mix and a per-layer
metric are found by name, as files."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, bench):
    return metric.get("workloads", [c["name"] for c in bench["workloads"]])


def test_top_level_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells fits the day
    assert ((2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)
    assert bench["command"][1].startswith(tuple(bench["paths"]))
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "bound" not in m and "\n" not in m["layer"]
    for c in bench["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
        assert NAME.match(c["traffic"]) and NAME.match(c["config"])
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for c in bench["workloads"]:
        e2e = [m["name"] for m in bench_run.metrics_of(
            bench, c["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, c["name"]
        assert bench_run.metrics_of(bench, c["name"], "per_layer"), c["name"]


def test_moves_names_an_end_to_end_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        target = e2e[m["moves"]]
        for cell in cells_of(m, bench):
            assert cell in cells_of(target, bench), (m["name"], cell)
    # a share of a roofline or of the peak is named as the contract
    # says, and the whole step's share stands beside the kernels'
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert re.fullmatch(r"[A-Za-z0-9_]+_roofline", m["name"])
            assert m["unit"] == "%"
            beside = [o for o in bench["per_layer"]
                      if "mfu" in re.split(r"[._]", o["name"])
                      and o["moves"] == m["moves"]
                      and set(cells_of(m, bench)) <= set(cells_of(o, bench))]
            assert beside, m["name"]


def test_every_cells_files_exist_and_are_found_by_name(bench):
    used = set()
    for c in bench["workloads"]:
        _, cell, config, traffic, own = bench_run.find_cell(ROOT, c["name"])
        used.add(c["config"])
        assert config["name"] == c["config"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "harness", traffic["kind"] + "_window.py"))
        assert set(own["limits"]), c["name"]
        # what is one configuration's alone is in the cell's file, so
        # that a mix is reusable data: no rate and no limits in a mix
        assert not {"rate_per_s", "limits"} & set(traffic), c["traffic"]
        if traffic["kind"] == "serve":
            assert own["rate_per_s"] > 0 and traffic["source"]
        for m in bench_run.metrics_of(bench, c["name"], "per_layer"):
            spec = os.path.join(ROOT, "benchmark", "metrics",
                                m["name"] + ".json")
            assert os.path.exists(spec), spec
            with open(spec) as f:
                assert callable(bench_run.load_reader(json.load(f)))
    for entry in bench["configs"]:
        assert entry["name"] in used
        assert entry["file"].startswith(tuple(bench["paths"]))
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
        assert cfg["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            assert not re.search(r"(_dim|_rank|_size|n_embd|head)", key), key
    files = [e["file"] for e in bench["configs"]]
    assert len(files) == len(set(files))


def test_nothing_under_benchmark_imports_the_old_harnesses():
    bad = re.compile(r"^\s*(import|from)\s+(bench|chip_smoke)\b|"
                     r"observability\.perf|observability import perf")
    for top in ("benchmark",):
        for d, _, fs in os.walk(os.path.join(ROOT, top)):
            for name in fs:
                if name.endswith(".py"):
                    with open(os.path.join(d, name)) as f:
                        for line in f:
                            assert not bad.search(line), (name, line)
    # and the references import nothing of the program
    for name in os.listdir(os.path.join(ROOT, "benchmark", "reference")):
        if name.endswith(".py"):
            with open(os.path.join(ROOT, "benchmark", "reference",
                                   name)) as f:
                assert "paddle_tpu" not in f.read(), name


def test_a_new_cell_is_files_and_entries_only(tiny_root, tmp_path):
    """A later PR's cell: a new traffic file, the cell's own file, a new
    metric file of an existing reader, new BENCHMARK.json entries, and
    not one line of the harness: ``find_cell`` and the metric discovery
    find them."""
    import shutil
    root = str(tmp_path / "grown")
    shutil.copytree(tiny_root, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", "chat.json")) as f:
        mix = json.load(f)
    mix["shared_prefix"] = {"share": 0.8, "length": 16, "groups": 2}
    with open(os.path.join(root, "benchmark", "traffic",
                           "sessions.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(root, "benchmark", "cells",
                             "gpt2_medium.chat.json"),
                os.path.join(root, "benchmark", "cells",
                             "gpt2_medium.sessions.json"))
    bench["workloads"].append(
        {"name": "gpt2_medium.sessions", "config": "gpt2_medium",
         "traffic": "sessions", "chips": 1, "why": "a later PR's cell"})
    bench["per_layer"].append(
        {"name": "serve.late_ms_max", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "serve engine",
         "moves": "ttft_p90_ms", "workloads": ["gpt2_medium.sessions"]})
    with open(os.path.join(root, "benchmark", "metrics",
                           "serve.late_ms_max.json"), "w") as f:
        json.dump({"reader": "benchmark.harness.readers:fact",
                   "fact": "generator_late_ms_max"}, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    _, cell, config, traffic, own = bench_run.find_cell(
        root, "gpt2_medium.sessions")
    assert traffic["shared_prefix"]["groups"] == 2 and own["rate_per_s"]
    run = {"trace": None, "facts": {"generator_late_ms_max": 1.5},
           "config": config["shapes"], "traffic": traffic, "peaks": None}
    got = bench_run.read_per_layer(root, bench, "gpt2_medium.sessions", run)
    assert got["serve.late_ms_max"] == {"value": 1.5, "unit": "ms"}
    # readers that find nothing to read are left out, never reported as 0
    assert "decode_attention_roofline" not in got
