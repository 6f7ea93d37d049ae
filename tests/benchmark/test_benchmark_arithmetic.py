"""The benchmark's own arithmetic: operation counts against hand-worked
numbers, percentiles and rates, the traffic generator's repeatability,
the peaks table."""

import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import flops, peaks, stats, traffic  # noqa: E402


def shapes(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["shapes"]


def test_bert_large_train_flops_per_token_hand_worked():
    # 6 x 24 x (4*1024^2 + 2*1024*4096)            = 1,811,939,328
    # 12 x 24 x 512 x 1024                          =   150,994,944
    # 6 x (1024^2 + 30522*1024) x 76/512            =    28,769,952
    # 6 x (1024^2 + 2*1024) / 512                   =        12,312
    got = flops.bert_train_flops_per_token(shapes("bert_large"), 512, 76)
    assert got == pytest.approx(1_991_716_536, rel=1e-9)
    assert round(got / 1e9, 2) == 1.99


def test_gpt2_medium_forward_flops_hand_worked():
    cfg = shapes("gpt2_medium")
    # one decoded token at context 256, with the head
    assert flops.gpt_forward_flops(cfg, 256, True) == (
        603_979_776 + 25_165_824 + 102_926_336)
    # prefill of positions [0, 128): sum of (pos + 1) is 8256
    assert flops.gpt_prefill_flops(cfg, 0, 128) == (
        77_309_411_328 + 4 * 24 * 1024 * 8256)
    # two chunks add up to the whole
    assert (flops.gpt_prefill_flops(cfg, 0, 128)
            + flops.gpt_prefill_flops(cfg, 128, 192)
            == flops.gpt_prefill_flops(cfg, 0, 192))


def test_kernel_work_hand_worked():
    assert flops.mlp_forward(8192, 1024, 4096) == (137_438_953_472,
                                                   50_341_888)
    fwd = flops.flash_forward(16, 16, 512, 512, 64)
    assert fwd == (17_179_869_184, 67_108_864)
    bwd = flops.flash_backward(16, 16, 512, 512, 64)
    assert bwd[0] == 42_949_672_960
    parts = [flops.flash_backward_part(s, batch=16, heads=16, seq_q=512,
                                       seq_k=512, head_dim=64)
             for s in (0.4, 0.6)]
    assert sum(p[0] for p in parts) == pytest.approx(bwd[0])
    ops, nbytes = flops.decode_attention(64 * 256, 16, 64)
    # K and V, 2 bytes each: 4 bytes a row element; 4 operations too
    assert nbytes == 2 * 64 * 256 * 16 * 64 * 2 == 67_108_864 and ops == nbytes


def test_roofline_says_which_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    t, bound = flops.least_seconds(*flops.mlp_forward(8192, 1024, 4096), v5e)
    assert bound == "compute" and t == pytest.approx(137_438_953_472 / 197e12)
    t, bound = flops.least_seconds(*flops.decode_attention(16384, 16, 64), v5e)
    assert bound == "bandwidth"


def test_peaks_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_percentile_rate_and_a_failed_request_as_the_worst():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert stats.rate(3000, 20.0) == 150.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    # 19 answered in 0.1 s, one never: it counts as the worst, and the
    # 95th percentile feels it
    due = [0.0] * 20
    first = [0.1] * 19 + [None]
    t = stats.ttft_ms(due, first, worst=30.0)
    assert max(t) == 30_000.0
    assert stats.percentile(t, 95) > 100.0
    assert stats.percentile([1.0] * 9 + [math.inf], 95) == math.inf
    assert stats.gaps_ms([[0.0, 0.01, 0.03], [1.0], []]) == pytest.approx(
        [10.0, 20.0])


MIX = {"base_seed": 7,
       "prompt": {"mean": 69.5, "min": 4, "max": 768},
       "answer": {"mean": 214.5, "min": 4, "max": 256}}


def test_schedule_repeats_for_a_seed_and_differs_across_seeds():
    big = 2 ** 31 + 5
    a = traffic.serve_schedule(MIX, 10.0, 50257, 1024, big, 20.0)
    b = traffic.serve_schedule(MIX, 10.0, 50257, 1024, big, 20.0)
    c = traffic.serve_schedule(MIX, 10.0, 50257, 1024, big + 1, 20.0)
    assert 150 < len(a) < 250
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    # the mix fixes the instants and the sizes, the seed the token ids:
    # every seed does the same work at the same times
    shape = lambda s: [(x["due"], x["prompt"].size, x["max_new"]) for x in s]
    assert shape(a) == shape(b) == shape(c)
    assert not all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, c))
    # another base_seed is another schedule
    other = traffic.serve_schedule(dict(MIX, base_seed=8), 10.0, 50257, 1024,
                                   big, 20.0)
    assert shape(other) != shape(a)
    # a shorter horizon is a prefix, and a rate only rescales the clock
    short = traffic.serve_schedule(MIX, 10.0, 50257, 1024, big, 5.0)
    assert shape(short) == shape(a)[:len(short)]
    fast = traffic.serve_schedule(MIX, 20.0, 50257, 1024, big, 10.0)
    assert [x["due"] * 2 for x in fast] == pytest.approx(
        [x["due"] for x in a][:len(fast)])
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 20.0
    assert len({x["prompt"].size for x in a}) > 50
    assert all(4 <= x["prompt"].size <= 768 and 4 <= x["max_new"] <= 256
               and x["prompt"].size + x["max_new"] <= 1024 for x in a)
    assert all(x["prompt"].dtype == np.int32 and x["prompt"].max() < 50257
               for x in a)
    # the lengths are exponential with the source's means: 69.5 a prompt,
    # and 214.5 an answer before the cap at 256, 149.5 after it
    prompts = traffic.lengths(np.random.default_rng(1), MIX["prompt"], 50000)
    answers = traffic.lengths(np.random.default_rng(2), MIX["answer"], 50000)
    assert prompts.mean() == pytest.approx(70.0, rel=0.02)
    assert answers.mean() == pytest.approx(149.5, rel=0.02)
    assert (answers == 256).mean() == pytest.approx(0.303, abs=0.01)


def test_answers_capped_by_the_context_alone_never_pass_it():
    """The ``chat`` mix since PR 31: the answers' cap is the 1024
    positions of the engine's ``max_len`` itself, so the schedule's clip
    (prompt + answer <= ``max_len``) is what ends the longest ones."""
    mix = dict(MIX, answer=dict(MIX["answer"], max=1024))
    s = traffic.serve_schedule(mix, 200.0, 50257, 1024, 2 ** 31 + 5, 100.0)
    assert len(s) > 15000
    assert all(4 <= x["max_new"] and x["prompt"].size + x["max_new"] <= 1024
               for x in s)
    touched = [x for x in s if x["prompt"].size + x["max_new"] == 1024]
    assert len(touched) / len(s) == pytest.approx(0.0126, abs=0.004)
    assert max(x["max_new"] for x in s) > 900
    # by the law: 0.85% reach 1024 and the mean is 213.3; the clip by the
    # prompt takes it to 212.4
    answers = traffic.lengths(np.random.default_rng(2), mix["answer"], 200000)
    assert (answers == 1024).mean() == pytest.approx(0.0085, abs=0.002)
    assert answers.mean() == pytest.approx(213.3, rel=0.02)
    assert np.mean([x["max_new"] for x in s]) == pytest.approx(212.4,
                                                               rel=0.04)


def test_shared_prefix_is_data_not_code():
    mix = dict(MIX, shared_prefix={"share": 1.0, "length": 64, "groups": 1})
    s = [x for x in traffic.serve_schedule(mix, 10.0, 50257, 1024, 3, 5.0)
         if x["prompt"].size > 32]
    heads = {tuple(x["prompt"][:32]) for x in s}
    assert len(s) > 5 and len(heads) == 1
