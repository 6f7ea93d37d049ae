"""What ``serve_tokens_per_s`` counts, and why (PR 26): the output
tokens OF THE REQUESTS DUE IN THE WINDOW that appeared inside it. A model
of the engine (``submit`` / ``step`` / ``requests`` / ``cfg``; a step
costs ``chunks x (chunk + host) + (round + host)`` on a stepped clock,
its tokens seen at its end) is driven by the real ``drive()`` over the
cell's own schedule (``benchmark/traffic/chat.json`` at the rate and the
warm-up of ``benchmark/cells/gpt2_medium.chat.json``, a window of
BENCHMARK.json's ``run_seconds``). No JAX, no chip: the readings are a
replay of the schedule, never a device number."""

import functools
import os
import re
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import serve_window, traffic  # noqa: E402

CELL = "gpt2_medium.chat"

#: (decode round, prefill chunk) in ms, from today's (ledger, PR 25:
#: 117.1 / 71.1) down to what PERF.md section 7 forecasts for the engine
#: without its pool copies; the host's launch and sync cost 3.5 ms
STEP_TIMES_MS = [(117.1, 71.1), (100, 60), (80, 48), (60, 36), (25, 8),
                 (12, 1.5)]
HOST_MS = 3.5


class SteppedClock:
    """Time moves only when the engine works or the loop sleeps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def work(self, seconds):
        self.t += seconds

    def sleep(self, seconds):
        self.t += max(seconds, 1e-4)


class Request:
    def __init__(self, prompt, max_new):
        self.prompt, self.max_new = prompt, max_new
        self.tokens, self.status = [], "queued"


class ModelEngine:
    """``ServingEngine.step`` as the benchmark sees it: every queued
    request is admitted into a free slot (a prefill of ``chunks`` chunks,
    which gives its first token), then one decode round gives every
    running request one token. ``wait`` spends the step's cost: on the
    stepped clock, or by sleeping."""

    def __init__(self, wait, round_s, chunk_s, host_s, slots=64,
                 prefill_len=128, page_size=64, num_pages=1024):
        self.wait = wait
        self.round_s, self.chunk_s, self.host_s = round_s, chunk_s, host_s
        self.cfg = SimpleNamespace(num_slots=slots, prefill_len=prefill_len,
                                   page_size=page_size, num_pages=num_pages)
        self.requests, self.queue, self.running = {}, [], []

    def submit(self, prompt, max_new):
        rid = len(self.requests)
        self.requests[rid] = Request(prompt, max_new)
        self.queue.append(self.requests[rid])
        return rid

    def _emit(self, reqs):
        for r in reqs:
            r.tokens.append(0)
            if len(r.tokens) >= r.max_new:
                r.status = "done"
        self.running = [r for r in self.running if r.status == "running"]

    def step(self):
        while self.queue and len(self.running) < self.cfg.num_slots:
            r = self.queue.pop(0)
            chunks = -(-r.prompt.size // self.cfg.prefill_len)
            self.wait(chunks * (self.chunk_s + self.host_s))
            r.status = "running"
            self.running.append(r)
            self._emit([r])
        if self.running:
            self.wait(self.round_s + self.host_s)
            self._emit(self.running)


def tokens_of_all_clients(clients, t_open, t_close):
    """The count before PR 26: the warm-up's tokens too."""
    return sum(1 for c in clients for t in c.token_times
               if t_open <= t < t_close)


@functools.lru_cache(maxsize=None)
def replay(round_ms, chunk_ms):
    """(the new count, the count before PR 26) in tokens/s over the
    cell's own schedule and window."""
    bench, _, config, mix, own = bench_run.find_cell(ROOT, CELL)
    warm, seconds = own["warmup_seconds"], bench["run_seconds"]
    items = traffic.serve_schedule(
        mix, own["rate_per_s"], config["shapes"]["vocab_size"],
        config["engine"]["max_len"], 1, warm + seconds)
    schedule = [serve_window.Client(it, measured=it["due"] >= warm)
                for it in items]
    clock = SteppedClock()
    engine = ModelEngine(clock.work, round_ms / 1e3, chunk_ms / 1e3,
                         HOST_MS / 1e3)
    t_open, t_close = float(warm), float(warm + seconds)
    serve_window.drive(engine, schedule, 0.0, t_open, t_close,
                       own["drain_limit_s"], lambda msg: None, clock=clock,
                       sleep=clock.sleep)
    assert all(len(c.token_times) == c.max_new for c in schedule
               if c.measured)
    return (serve_window.tokens_in_window(schedule, t_open, t_close)
            / seconds,
            tokens_of_all_clients(schedule, t_open, t_close) / seconds)


@pytest.mark.parametrize("slower, faster",
                         list(zip(STEP_TIMES_MS, STEP_TIMES_MS[1:])))
def test_a_faster_engine_never_reads_lower(slower, faster):
    assert replay(*faster)[0] >= replay(*slower)[0]


@pytest.mark.parametrize("step_times, reads", [(STEP_TIMES_MS[0], 206.7),
                                               (STEP_TIMES_MS[-1], 265.6)])
def test_the_replay_reads_what_issue_26_foretold(step_times, reads):
    """Today's step times and those forecast without the pool copies;
    the ceiling is the demand, 14 164 answer tokens due in 51 s."""
    new, _ = replay(*step_times)
    assert new == pytest.approx(reads, abs=1.0)
    assert new <= 14164 / 51


def test_the_count_of_all_clients_falls_when_the_engine_gets_faster():
    """The planted control, and why the definition changed: the count
    before PR 26 (every client's tokens, the warm-up's too) falls by more
    than the metric's 1% bound between the same two points on this very
    schedule, where every token of every request comes sooner."""
    _, old_today = replay(*STEP_TIMES_MS[0])
    _, old_fast = replay(*STEP_TIMES_MS[-1])
    assert old_today > 14164 / 51          # above the demand: spill-in
    assert old_fast < 0.99 * old_today


def client(due, token_times, measured):
    c = serve_window.Client({"due": due, "prompt": np.zeros(4, np.int32),
                             "max_new": len(token_times)}, measured)
    c.token_times = list(token_times)
    return c


def test_three_clients_by_hand():
    t_open, t_close = 30.0, 81.0
    clients = [
        # warm-up traffic whose answer falls in the window: not counted
        client(25.0, [29.9, 30.0, 30.5, 31.0], measured=False),
        # due in the window, straddles its close: counted up to the close,
        # and a token AT the close is out
        client(79.0, [80.0, 80.5, 81.0, 81.5], measured=True),
        # due in the window and wholly inside it
        client(30.0, [30.0, 30.2, 40.0], measured=True),
    ]
    assert serve_window.tokens_in_window(clients, t_open, t_close) == 2 + 3
    assert tokens_of_all_clients(clients, t_open, t_close) == 3 + 2 + 3


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_token_later_means_no_lower_count(seed):
    """The property the metric is there for, on the pure function: when
    every token of every request comes no later (and none before its
    request is due), the count does not fall."""
    rng = np.random.default_rng(seed)
    t_open, t_close = 10.0, 20.0
    before, after = [], []
    for _ in range(50):
        due = float(rng.uniform(0.0, t_close))
        times = due + np.cumsum(rng.exponential(0.5, int(rng.integers(1, 40))))
        sooner = due + (times - due) * rng.uniform(0.0, 1.0)
        before.append(client(due, times, measured=due >= t_open))
        after.append(client(due, np.maximum.accumulate(sooner),
                            measured=due >= t_open))
    count = serve_window.tokens_in_window
    assert count(after, t_open, t_close) >= count(before, t_open, t_close)
    assert count(before, t_open, t_close) > 0


def test_measure_reports_the_new_count_and_logs_the_old():
    """``measure()`` (and with it ``run.py``, ``sweep.py`` and
    ``control.py``) reports the count of the window's own requests; the
    count of all clients is on the log line beside it and nowhere else."""
    lines = []
    _, _, config, mix, _ = bench_run.find_cell(ROOT, CELL)
    mix = dict(mix, prompt={"mean": 24, "min": 4, "max": 96},
               answer={"mean": 10, "min": 2, "max": 32})
    ctx = {"config": config, "traffic": mix, "log": lines.append, "seed": 1,
           "trace": False, "tracer": None,
           "compiles": SimpleNamespace(compiles=0)}
    cell = {"rate_per_s": 200.0, "warmup_seconds": 0.1, "drain_limit_s": 5}
    engine = ModelEngine(time.sleep, 2e-3, 1e-3, 0.0)
    out = serve_window.measure(engine, ctx, cell, 0.4)
    found = re.search(r"(\d+) tokens in the window of the requests due in "
                      r"it \((\d+) of all clients", "\n".join(lines))
    own_tokens, all_tokens = int(found.group(1)), int(found.group(2))
    assert out["failed"] == 0 and out["attempted"] > 20
    assert out["e2e"]["serve_tokens_per_s"] == pytest.approx(own_tokens / 0.4)
    assert 0 < own_tokens < all_tokens
