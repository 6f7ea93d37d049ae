"""A tiny copy of the benchmark for the CPU tests: the repo's own
BENCHMARK.json, metric files, traffic files and cell files, with the
configurations and the traffic cut to sizes a test run can hold. The tiny presets live
here, in the tests, and never in a cell."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_BERT = {"vocab_size": 1024, "hidden_size": 64, "num_layers": 2,
             "num_heads": 4, "intermediate_size": 128, "max_position": 128}
TINY_GPT = {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
            "num_heads": 4, "intermediate_size": 128, "max_position": 128}
#: limits for the tiny sizes on the CPU, set as the real ones are: above
#: what sound runs read on six seeds (loss 5e-4, gradient 0.017, change
#: 0.030; served gap 0) and below what the control and the faults read
#: (int8 control: change 0.43; half batch: gradient 0.63; unchanged
#: state: change 1; fp8 control over 400 served tokens: gap 0.011 to
#: 0.027 on seeds 1 to 4; an altered token: gap over 0.009)
TINY_LIMITS = {"train": {"loss_gap": 5e-3, "grad_gap": 0.2,
                         "update_gap": 0.15, "last_loss_finite": 0},
               "serve": {"served_gap": 0.004, "never_answered": 0}}


def read(path):
    with open(path) as f:
        return json.load(f)


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tiny_root(root):
    """Write the tiny benchmark under ``root`` and return it."""
    bench = read(os.path.join(ROOT, "BENCHMARK.json"))
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(root, "benchmark", "metrics"))
    for entry in bench["configs"]:
        cfg = read(os.path.join(ROOT, entry["file"]))
        tiny = TINY_BERT if "bert" in entry["name"] else TINY_GPT
        cfg["constructor"]["kwargs"].update(tiny, use_flash=False)
        cfg["shapes"] = {**tiny, "head_dim": 16}
        if "engine" in cfg:
            cfg["engine"].update(num_slots=4, page_size=16, max_len=128,
                                 prefill_len=32, num_pages=32)
        entry["file"] = f"benchmark/configs/{entry['name']}_tiny.json"
        write(os.path.join(root, entry["file"]), cfg)
    for cell in bench["workloads"]:
        t = read(os.path.join(ROOT, "benchmark", "traffic",
                              cell["traffic"] + ".json"))
        if t["kind"] == "train":
            t.update(batch=4, seq=128, masked_per_row=19, length_min=64,
                     length_max=128, trace_seconds=1, reference_block_rows=2)
        else:
            t.update(trace_seconds=1, sample_tokens=400, sample_requests=40,
                     prompt={"mean": 24, "min": 4, "max": 96},
                     answer={"mean": 10, "min": 2, "max": 32})
        write(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json"), t)
        own = read(os.path.join(ROOT, "benchmark", "cells",
                                cell["name"] + ".json"))
        own["limits"] = TINY_LIMITS[t["kind"]]
        if t["kind"] == "serve":
            own.update(rate_per_s=20.0, warmup_seconds=0.5)
        write(os.path.join(root, "benchmark", "cells",
                           cell["name"] + ".json"), own)
    write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny_benchmark")))


@pytest.fixture(autouse=True)
def _leave_jax_as_found():
    """A run turns JAX's persistent compile cache on (in the tiny root);
    the other tests of this worker must not inherit it."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    if any(getattr(jax.config, k) != v for k, v in saved.items()):
        from jax.experimental.compilation_cache import compilation_cache
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
