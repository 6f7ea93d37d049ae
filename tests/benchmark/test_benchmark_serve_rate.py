"""What ``serve_tokens_per_s`` counts, and why (PR 26): the output
tokens OF THE REQUESTS DUE IN THE WINDOW that appeared inside it. A model
of the engine (``submit`` / ``step`` / ``requests`` / ``cfg``; a step
costs ``chunks x (chunk + host) + (round + host)`` on a stepped clock,
its tokens seen at its end) is driven by the real ``drive()`` over the
schedule that PR 26 reasoned about, kept here as constants (the ``chat``
mix with its answers capped at 256, 1.84 requests/s, 30 s of warm-up, a
window of 51 s: the cell has moved on since, PR 31, and what these cases
prove is the definition of the count, not the cell). No JAX, no chip: the
readings are a replay of the schedule, never a device number.

Also here, on the same model engine and stepped clock: a traced run's
window closes without holding the loop (PR 31)."""

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
from benchmark.harness import (serve_model_window,  # noqa: E402
                               serve_window, traffic)

CELL = "gpt2_medium.chat"

#: the schedule of PR 26: ``benchmark/traffic/chat.json`` and
#: ``benchmark/cells/gpt2_medium.chat.json`` as they stood then
PR26_MIX = {"kind": "serve", "base_seed": 20260941,
            "prompt": {"mean": 69.5, "min": 4, "max": 768},
            "answer": {"mean": 214.5, "min": 4, "max": 256}}
PR26_CELL = {"rate_per_s": 1.84, "warmup_seconds": 30, "drain_limit_s": 60}
PR26_SECONDS, VOCAB, MAX_LEN = 51, 50257, 1024

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
    """(the new count, the count before PR 26) in tokens/s over PR 26's
    schedule and window."""
    mix, own = PR26_MIX, PR26_CELL
    warm, seconds = own["warmup_seconds"], PR26_SECONDS
    items = traffic.serve_schedule(mix, own["rate_per_s"], VOCAB, MAX_LEN,
                                   1, warm + seconds)
    assert sum(it["max_new"] for it in items if it["due"] >= warm) == 14164
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


class SlowTracer:
    """A profiler whose stop takes 3 s of the stepped clock, as
    ``jax.profiler.stop_trace`` takes seconds of the real one.
    ``holds_the_loop`` is the harness before PR 31: the whole stop at the
    window's close, inside the loop."""

    def __init__(self, clock, holds_the_loop=False):
        self.clock, self.holds_the_loop = clock, holds_the_loop
        self.calls = []

    def start(self):
        self.calls.append(("start", self.clock()))

    def close_window(self):
        self.calls.append(("close_window", self.clock()))
        if self.holds_the_loop:
            self.clock.work(3.0)

    def stop(self):
        self.calls.append(("stop", self.clock()))
        if not self.holds_the_loop:
            self.clock.work(3.0)


def traced_window(window, cell_name, holds_the_loop):
    """``measure()`` of ``window`` in a traced run on the stepped clock:
    rounds of 2 ms, chunks of 1 ms, 200 requests/s. Returns (the run, the
    tracer, the drain's end on the clock)."""
    _, _, config, mix, _ = bench_run.find_cell(ROOT, cell_name)
    mix = dict(mix, prompt={"mean": 24, "min": 4, "max": 96},
               answer={"mean": 10, "min": 2, "max": 32})
    clock = SteppedClock()
    tracer = SlowTracer(clock, holds_the_loop)
    ctx = {"config": config, "traffic": mix, "log": lambda msg: None,
           "seed": 1, "trace": True, "tracer": tracer,
           "compiles": SimpleNamespace(compiles=0)}
    cell = {"rate_per_s": 200.0, "warmup_seconds": 0.1, "drain_limit_s": 5}
    engine = ModelEngine(clock.work, 2e-3, 1e-3, 0.0)
    out = window.measure(engine, ctx, cell, 0.4, clock=clock,
                         sleep=clock.sleep)
    return out, tracer


@pytest.mark.parametrize("window, cell_name", [
    (serve_window, "gpt2_medium.chat"),
    (serve_model_window, "jamba2_3b.chat_1k")])
def test_a_traced_window_closes_without_holding_the_loop(window, cell_name):
    """The profiler's stop (3 s here) comes after the drain: no request
    running at the window's close waits it out, so a traced run's mean gap
    stays a round's (2 ms, and 1 ms a chunk admitted before it). The
    control is the harness before PR 31, whose stop held the loop: every
    request then running got one gap of 3 s."""
    out, tracer = traced_window(window, cell_name, holds_the_loop=False)
    assert out["failed"] == 0 and out["attempted"] > 20
    assert [c[0] for c in tracer.calls] == ["start", "close_window", "stop"]
    assert out["facts"]["gap_mean_ms"] < 2 * 2.0 + 1.0
    # the facts' window is what the loop saw: 0.4 s, not 3.4
    assert out["facts"]["window_s"] == pytest.approx(0.4, abs=0.01)
    # the drain was over when the profiler stopped: the stop is the last
    # thing on the clock
    assert tracer.calls[2][1] > tracer.calls[1][1]
    held, _ = traced_window(window, cell_name, holds_the_loop=True)
    assert held["failed"] == 0
    assert held["facts"]["gap_mean_ms"] > 4 * out["facts"]["gap_mean_ms"]


def test_the_tracer_closes_its_window_apart_from_the_profilers_stop(
        tmp_path, monkeypatch):
    """``Tracer.close_window`` ends ``bench.window`` and touches no
    profiler; ``stop`` closes it if it is still open (the training window
    calls ``stop`` alone) and stops the profiler once."""
    import jax
    from benchmark.harness import tracing
    events = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: events.append("start_trace"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: events.append("stop_trace"))

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append("open " + self.name)

        def __exit__(self, *exc):
            events.append("close " + self.name)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    t = tracing.Tracer(str(tmp_path), "cell")
    t.start()
    t.close_window()
    assert events == ["start_trace", "open bench.window",
                      "close bench.window"]
    t.stop()
    assert events[3:] == ["stop_trace"]
    del events[:]
    t.start()
    t.stop()
    assert events == ["start_trace", "open bench.window",
                      "close bench.window", "stop_trace"]
