"""The cell ``jamba2_3b.chat_1k`` (PR 28): its files against what the
issue and the catalog fix, ``work_jamba``'s counts against numbers
worked by hand, the new readers on records they cannot read, and a
rehearsal of the whole run on the CPU at a tiny size with the
lower-precision control and one planted fault.

The tiny fixture (a copy of the shared tiny root with this
configuration's files written anew) keeps what the real one has: the
period of the layer kinds, one K/V head, the three inner norms, bfloat16
weights, pools and conv state. Its limit is set as the real one is,
from readings on seeds 1 to 4 and the driver-sized seed at this size
(hidden 256: at 64 the harness's 0.02-wide weights leave the layers so
little to say that neither the control nor the fault moves an argmax):
the program's ``served_gap`` read 0 to 0.0029 (bf16 operands against the
float32 reference), the fp8 control 0.056 to 0.116, a recurrent state
that admission does not zero 0.054 to 0.307: the limit 0.012 is four
times the program's largest and under a quarter of the others'
smallest.
"""

import importlib
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import compare, flops, work_jamba  # noqa: E402
from benchmark.readers import state_space  # noqa: E402

CELL = "jamba2_3b.chat_1k"
SEED = 2 ** 31 + 1

#: the catalog's copy of the source's config.json (model-configs guide,
#: architectures.jsonl, AI21-Jamba2-3B): every key, unchanged
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    return bench_run.find_cell(ROOT, CELL)


# ------------------------------------------------------------ the files

def test_the_configuration_holds_the_catalogs_keys_and_cuts_nothing(real):
    _, cell, config, _, _ = real
    assert {k: config[k] for k in CATALOG} == CATALOG
    assert config["reduced"] == [] and cell["chips"] == 1
    shapes, kw = config["shapes"], config["constructor"]["kwargs"]
    assert shapes["num_layers"] == kw["num_layers"] == 28
    assert shapes["vocab_size"] == kw["vocab_size"] == 65536
    assert shapes["d_inner"] == CATALOG["mamba_expand"] * 2560 == 5120
    assert (kw["hidden_size"], kw["intermediate_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["mamba_d_state"],
            kw["mamba_d_conv"], kw["mamba_dt_rank"]) == (
        2560, 8192, 20, 1, 128, 16, 4, 160)
    assert config["assumed"]["parameters"] == work_jamba.parameters(shapes)
    assert config["engine"] == {
        "num_slots": 128, "page_size": 64, "max_len": 2048,
        "prefill_len": 128, "num_pages": 4096, "cache_dtype": "bfloat16",
        "prefix_cache": False}


def test_the_program_builds_that_configuration_with_one_kv_head(real):
    import jax
    from benchmark.harness import weights
    ctor = real[2]["constructor"]
    model = weights.load_object(ctor["model"])(
        weights.load_object(ctor["config"])(**ctor["kwargs"]))
    assert [i for i, b in enumerate(model.blocks) if b.attention] == [7, 21]
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == \
        3029337472
    pools = jax.eval_shape(lambda: model.init_paged_caches(4096, 64))
    assert len(pools) == 2 and pools[0]["k"].shape == (4096, 64, 128)
    state = jax.eval_shape(lambda: model.init_slot_state(128))
    assert len(state) == 26 and state[0]["ssm"].shape == (128, 16, 5120)


def test_the_mix_is_chat_with_the_named_keys_changed_and_no_others(real):
    chat, mine = read("benchmark", "traffic", "chat.json"), real[3]
    changed = {k for k in chat if chat[k] != mine[k]}
    # since PR 31 ``chat`` too lets its answers run to 1024
    assert changed == {"kind", "cut", "base_seed"}
    assert mine["kind"] == "serve_model" and mine["base_seed"] == 20261002
    assert mine["answer"] == chat["answer"] and mine["answer"]["max"] == 1024
    assert set(mine) == set(chat)


@pytest.mark.parametrize("cell", ["gpt2_medium.chat", CELL])
def test_the_cells_own_file_states_its_rate_and_limits_with_their_origin(
        cell):
    """Both serving cells are held to the same: a rate inside 0.7-0.8 of a
    knee that was swept, and every number with where it comes from."""
    own = read("benchmark", "cells", cell + ".json")
    assert 0.7 * own["knee_per_s"] <= own["rate_per_s"] <= \
        0.8 * own["knee_per_s"] + 1e-9
    assert own["drain_limit_s"] == 60 and own["warmup_seconds"] > 0
    assert own["limits"]["never_answered"] == 0
    assert own["limits"]["served_gap"] > 0
    for key in ("knee_from", "rate_from", "warmup_from", "limits_from"):
        assert len(own[key]) > 40, key
    assert "NOT" in own["knee_from"]       # bracketed from above


@pytest.mark.parametrize("metric", [
    "selective_scan_roofline", "ssm_state_update_roofline",
    "serve.state_pool_in_use", "mfu.serve.rate"])
def test_a_new_metric_lists_the_new_cell_alone_and_reads_nothing_from_nothing(
        metric):
    bench = read("BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert entry["workloads"] == [CELL]
    spec = read("benchmark", "metrics", metric + ".json")
    reader = bench_run.load_reader(spec)
    # no trace (a rehearsal), and a trace with no such event or count
    # (the parent commit): nothing, and no exception
    ms = 1_000_000
    other = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%mlp.2 = bf16[8,8] custom-call(...)", 1 * ms, 2 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_decode(1)", 1 * ms, 2 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 10 * ms], ["bench.step", 0, 3 * ms]]}]}]}
    for trace in (None, other):
        run = {"trace": trace, "facts": {}, "config": {}, "traffic": {},
               "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
        assert reader(spec, run) is None


def test_the_accepted_serving_metrics_list_the_new_cell_where_it_can_report():
    """The cell reports ``serve_tokens_per_s``, ``ttft_p90_ms`` and
    ``setup_s``, and NOT ``gap_p90_ms``: the driver's check refused the
    cell on it (two sets of six runs of one code read medians of 47.75
    and 46.99 ms under a bound of 1%; PERF.md sections 2 and 7 say where
    the spread comes from). So every accepted serving metric lists the
    cell but the gap's tail, the per-layer metrics that move it, and the
    decode kernel's roofline (its file multiplies by num_heads and
    num_layers, here 1 K/V head and 2 layers). The two scan rooflines
    move the rate, and ``mfu.serve.rate`` is ``mfu.serve``'s quantity
    split by what it moves, as the contract splits ``dispatch_ms``: the
    accepted contract test wants the whole step's share beside every
    roofline under the same end-to-end metric."""
    bench = read("BENCHMARK.json")
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "ttft_p90_ms", "setup_s"}
    for m in bench["per_layer"]:
        cells = m["workloads"]
        if "gpt2_medium.chat" not in cells:
            continue
        assert (CELL in cells) == (
            m["moves"] in e2e
            and m["name"] != "decode_attention_roofline"), m["name"]
    mine = {m["name"]: m["moves"] for m in bench["per_layer"]
            if m["workloads"] == [CELL]}
    assert mine == {"selective_scan_roofline": "serve_tokens_per_s",
                    "ssm_state_update_roofline": "serve_tokens_per_s",
                    "serve.state_pool_in_use": "serve_tokens_per_s",
                    "mfu.serve.rate": "serve_tokens_per_s"}
    assert read("benchmark", "metrics", "mfu.serve.rate.json")["reader"] == \
        read("benchmark", "metrics", "mfu.serve.json")["reader"]
    assert [c["chips"] for c in bench["workloads"]] == [1, 1, 1]


# ------------------------------------------------- the counts, by hand

SHAPES = {"hidden_size": 2560, "num_layers": 28, "num_heads": 20,
          "num_kv_heads": 1, "head_dim": 128, "intermediate_size": 8192,
          "vocab_size": 65536, "d_inner": 5120, "d_state": 16, "d_conv": 4,
          "dt_rank": 160, "attn_layer_period": 14, "attn_layer_offset": 7}


def test_parameters_worked_by_hand():
    assert work_jamba.layer_counts(SHAPES) == (26, 2)
    # in_proj 2560 x 10240, x_proj 5120 x 192, dt_proj 160 x 5120,
    # out_proj 5120 x 2560
    assert work_jamba.mamba_matmul_params(SHAPES) == (
        26214400 + 983040 + 819200 + 13107200)
    # q and o 2560 x 2560, k and v 2560 x 128
    assert work_jamba.attention_matmul_params(SHAPES) == (
        2 * 6553600 + 2 * 327680)
    assert work_jamba.mlp_params(SHAPES) == 3 * 20971520
    # conv 20480 + 5120, dt bias 5120, A_log 81920, D 5120, norms 192
    small = 20480 + 5120 + 5120 + 81920 + 5120 + 192
    total = (26 * (41123840 + small) + 2 * 13762560 + 28 * 62914560
             + 28 * 2 * 2560 + 2560 + 65536 * 2560)
    assert work_jamba.parameters(SHAPES) == total == 3029337472   # 3.03B


def test_forward_operations_worked_by_hand():
    matmuls = 2 * (26 * 41123840 + 2 * 13762560 + 28 * 62914560)
    scan = 26 * (5120 * (6 * 16 + 5) + 2 * 4 * 5120)
    at_100 = matmuls + 4 * 2 * 100 * 2560 + scan
    assert work_jamba.forward_flops(SHAPES, 100, False) == at_100
    assert work_jamba.forward_flops(SHAPES, 100, True) == \
        at_100 + 2 * 65536 * 2560
    # positions 0..2 attend 1, 2, 3 keys
    assert work_jamba.prefill_flops(SHAPES, 0, 3) == \
        3 * (matmuls + scan) + 4 * 2 * (1 + 2 + 3) * 2560


def test_kernel_work_worked_by_hand():
    per_token = 4 * (4 * 5120 + 2 * 16)          # x, dt, z, y; B, C
    state = 2 * 4 * 5120 * 16                    # h read and written
    params = 4 * 5120 * 17                       # A and D
    ops, nbytes = work_jamba.selective_scan(SHAPES, 200, 3)
    assert ops == 200 * 5120 * 101
    assert nbytes == 200 * per_token + 3 * (state + params)
    ops, nbytes = work_jamba.ssm_state_update(SHAPES, 1000, 10)
    assert ops == 1000 * 5120 * 101
    assert nbytes == 1000 * (per_token + state) + 10 * params
    # bandwidth bounds both by a wide margin on the v5e
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(ops, nbytes, peaks)[1] == "bandwidth"


def test_the_state_counts_reader_reads_the_step_spans_counts(monkeypatch):
    records = [{"name": "serve.step", "counts": {
        "state_slots": s, "state_bytes": 10 * s,
        "state_bytes_reserved": 40}} for s in (1, 3)]
    monkeypatch.setattr(state_space.engine_spans, "session",
                        lambda run: (records, 0.0))
    assert state_space.state_pool_in_use({}, {}) == pytest.approx(50.0)
    # a program that counts no state (the parent, a GPTDecoder)
    for r in records:
        r["counts"] = {"num_pages": 8}
    assert state_space.state_pool_in_use({}, {}) is None


# -------------------------------------- the whole run, tiny, on the CPU

TINY_KW = {"vocab_size": 512, "hidden_size": 256, "num_layers": 4,
           "num_heads": 4, "num_kv_heads": 1, "head_dim": 64,
           "intermediate_size": 512, "attn_layer_period": 2,
           "attn_layer_offset": 1, "mamba_expand": 2, "mamba_d_state": 16,
           "mamba_d_conv": 4, "mamba_dt_rank": 16, "rms_norm_eps": 1e-06}
TINY_SHAPES = {"hidden_size": 256, "num_layers": 4, "num_heads": 4,
               "num_kv_heads": 1, "head_dim": 64, "intermediate_size": 512,
               "vocab_size": 512, "d_inner": 512, "d_state": 16, "d_conv": 4,
               "dt_rank": 16, "attn_layer_period": 2, "attn_layer_offset": 1}
TINY_LIMITS = {"served_gap": 0.012, "never_answered": 0}


@pytest.fixture(scope="module")
def tiny_jamba_root(tiny_root, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_jamba") / "root")
    shutil.copytree(tiny_root, root)

    def rewrite(path, change):
        with open(os.path.join(root, *path)) as f:
            obj = json.load(f)
        change(obj)
        with open(os.path.join(root, *path), "w") as f:
            json.dump(obj, f, indent=1)

    def tiny_config(cfg):
        real_cfg = read("benchmark", "configs", "jamba2_3b.json")
        cfg.clear()
        cfg.update(real_cfg)
        cfg["constructor"] = dict(real_cfg["constructor"], kwargs=TINY_KW)
        cfg["shapes"] = TINY_SHAPES
        cfg["engine"] = dict(real_cfg["engine"], num_slots=4, page_size=16,
                             max_len=128, prefill_len=32, num_pages=32)
    rewrite(("benchmark", "configs", "jamba2_3b_tiny.json"), tiny_config)
    # short prompts: under the harness's weight rule a Mamba channel
    # forgets by half a step, so a state left over from the slot's last
    # request shows in the served tokens only behind a short prompt
    rewrite(("benchmark", "traffic", "chat_1k.json"), lambda t: t.update(
        prompt={"mean": 6, "min": 2, "max": 96}))
    rewrite(("benchmark", "cells", CELL + ".json"), lambda own: own.update(
        limits=TINY_LIMITS, rate_per_s=20.0, warmup_seconds=0.5))
    return root


def run_tiny(root, trace=0, seconds=1.5):
    return bench_run.run_cell(CELL, SEED, seconds, trace, root=root,
                              need_chip=False)


def test_the_window_through_the_real_engine_times_and_compares(
        tiny_jamba_root):
    line = run_tiny(tiny_jamba_root)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                    "setup_s"}
    assert line["compared"]["served_gap"]["value"] <= \
        line["compared"]["served_gap"]["limit"]
    json.dumps(line)


def test_a_traced_rehearsal_reports_what_needs_no_device(tiny_jamba_root):
    line = run_tiny(tiny_jamba_root, trace=1)
    assert line["correct"] is True
    # counted by the window itself; everything read from a device trace
    # is left out on the CPU, never reported as 0
    assert set(line["metrics"]) == {
        "serve.slot_occupancy", "serve.kv_pool_live",
        "serve.queue_wait_ms_p50"}


def test_the_window_counts_what_the_rooflines_divide(tiny_jamba_root):
    from benchmark.harness import serve_model_window as window

    class C:
        def __init__(self, prompt, admitted_at, times):
            import numpy as np
            self.prompt = np.zeros(prompt, np.int32)
            self.admitted_at, self.token_times = admitted_at, times
    clients = [C(40, 1.0, [1.1, 1.2, 1.3]),     # two chunks of 32
               C(5, 0.5, [0.6, 1.5, 9.0]),      # admitted before the window
               C(7, None, [])]
    rounds = [(1.0, 1.1, 1, 1, 3, 1), (1.2, 1.3, 2, 2, 4, 0),
              (8.0, 9.0, 1, 1, 1, 0)]
    facts = window.window_facts(rounds, clients, work_jamba, TINY_SHAPES,
                                32, 0.9, 2.0)
    assert (facts["scan_tokens"], facts["scan_chunks"]) == (40, 2)
    assert facts["decode_slot_steps"] == 3      # 1.2, 1.3 and 1.5
    assert facts["decode_rounds"] == facts["rounds"] == 2
    want = (work_jamba.prefill_flops(TINY_SHAPES, 0, 40)
            + work_jamba.head_flops(TINY_SHAPES)
            + sum(work_jamba.forward_flops(TINY_SHAPES, n, True)
                  for n in (41, 42, 6)))
    assert facts["model_ops"] == want


def state_not_zeroed(monkeypatch):
    """The planted fault: admission leaves the slot's recurrent state as
    the last request left it (the mixer is told no sequence is fresh)."""
    from paddle_tpu.nn import mamba
    sound = mamba.MambaMixer.forward

    def forward(self, u, state, slots, lengths, fresh, name="selective_scan"):
        return sound(self, u, state, slots, lengths, None, name)
    monkeypatch.setattr(mamba.MambaMixer, "forward", forward)


def test_a_state_not_zeroed_on_admission_comes_out_as_not_correct(
        tiny_jamba_root, monkeypatch):
    state_not_zeroed(monkeypatch)
    line = run_tiny(tiny_jamba_root)
    assert line["correct"] is False
    row = line["compared"]["served_gap"]
    assert row["value"] > row["limit"]


def test_the_fp8_control_comes_out_as_not_correct(tiny_jamba_root):
    import jax
    _, _, config, traffic, own = bench_run.find_cell(tiny_jamba_root, CELL)
    window = importlib.import_module(
        f"benchmark.harness.{traffic['kind']}_window")
    ctx = bench_run.make_ctx(tiny_jamba_root, CELL, config, traffic, own,
                             jax.devices()[:1], 1, 1.5,
                             control_precision="fp8")
    out = window.control(ctx)
    _, program_ok = compare.judge(out["program"], own["limits"])
    rows, control_ok = compare.judge(out["control"], own["limits"])
    assert program_ok and not control_ok
    assert [n for n, _, _, ok in rows if not ok] == ["served_gap"]
