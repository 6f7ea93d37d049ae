"""The serving window: an open loop over ``ServingEngine.submit`` and
``ServingEngine.step``, driven by the benchmark in one thread.

Every request is timed from when it was DUE, on the benchmark's clock
(the engine's own ``serve.ttft_s`` counts from ``submit``, and its
``serve.token_latency_s`` is one scheduling round, not a gap a client
sees). Warm-up traffic from the same schedule runs before the window,
for as long as the cell's file says (about one request's lifetime), so
that the slots are as full when it opens as when it closes; it counts
as set-up. Requests due in the window and still running at its close
are stepped to completion for at most ``drain_limit_s`` and count as
failed beyond it. What belongs to the cell and not to the mix (the rate,
the warm-up, the drain limit, the limits of ``correct``) is read from
``benchmark/cells/<cell>.json``.

After the window has closed, the memory peak has been read and the
engine is freed, the plain reference runs once over a sample of the
finished requests (drawn from the seed, the longest among them), prompt
and served tokens together, and the comparison reads the widest gap by
which a served token's logit lies below the reference's best.
"""

import gc
import importlib
import math
import time

import numpy as np

from benchmark.harness import flops, stats, traffic as traffic_gen, weights
from benchmark.harness.tracing import annotate


class Client:
    """What the benchmark knows of one request."""
    __slots__ = ("due", "prompt", "max_new", "rid", "req", "seen",
                 "token_times", "admitted_at", "measured", "late")

    def __init__(self, item, measured):
        self.due, self.prompt = item["due"], item["prompt"]
        self.max_new, self.measured = item["max_new"], measured
        self.rid = self.req = self.admitted_at = None
        self.seen, self.token_times, self.late = 0, [], 0.0


def build(ctx):
    """Model, weights from the seed on the device, the engine."""
    import jax.numpy as jnp

    from paddle_tpu.serving import ServeConfig, ServingEngine

    config = ctx["config"]
    model, params = weights.model_and_params(config, ctx["seed"])
    e = dict(config["engine"])
    e["cache_dtype"] = jnp.dtype(e["cache_dtype"]).type
    engine = ServingEngine(model, {"params": params, "state": {}},
                           ServeConfig(**e))
    ctx["log"]("weights on the device, engine built")
    return engine


def prewarm(engine, vocab, seed):
    """Build both step programs before the schedule's clock starts: one
    short prompt and one longer than a prefill chunk, two tokens each."""
    rng = np.random.default_rng([int(seed), 2])
    lp = engine.cfg.prefill_len
    for n in (lp // 2, lp + lp // 2):
        engine.submit(rng.integers(0, vocab, n).astype(np.int32), max_new=2)
    engine.drain()


def drive(engine, schedule, t0, t_open, t_close, drain_limit_s, log,
          on_open=None, on_close=None, clock=time.perf_counter,
          sleep=time.sleep):
    """The loop: submit what is due, step, note every new token's time.
    ``schedule`` holds Clients sorted by due time (relative to ``t0``).
    ``clock`` and ``sleep`` are the tests' way in (a stepped clock).
    Returns the per-round records ``(start, end, running requests, new
    tokens, K/V pages that hold their context, requests admitted)``."""
    live, rounds = {}, []
    page = engine.cfg.page_size
    nxt, n = 0, len(schedule)
    opened = closed = False
    while True:
        now = clock()
        if not opened and now >= t_open:
            opened = True
            if on_open is not None:
                on_open()
            now = clock()
        if not closed and now >= t_close:
            closed = True
            if on_close is not None:
                on_close()
        if not closed:
            with annotate("bench.submit"):
                while nxt < n and t0 + schedule[nxt].due <= now:
                    c = schedule[nxt]
                    nxt += 1
                    c.late = now - (t0 + c.due)
                    c.rid = engine.submit(c.prompt, max_new=c.max_new)
                    c.req = engine.requests[c.rid]
                    live[c.rid] = c
        elif not any(c.measured for c in live.values()):
            break
        elif now > t_close + drain_limit_s:
            log(f"drain limit of {drain_limit_s} s reached with "
                f"{sum(c.measured for c in live.values())} measured "
                f"request(s) unfinished")
            break
        if not live:
            if closed:
                break
            nxt_due = t0 + schedule[nxt].due if nxt < n else t_close
            with annotate("bench.sleep"):
                sleep(max(0.0, min(nxt_due - now, t_close - now, 0.002)))
            continue
        t_step = clock()
        with annotate("bench.step"):
            engine.step()
        t_done = clock()
        with annotate("bench.account"):
            running = new_tokens = live_pages = admitted = 0
            for rid in list(live):
                c = live[rid]
                req = c.req
                got = len(req.tokens)
                if req.status == "running" or got:
                    if c.admitted_at is None:
                        c.admitted_at = t_step
                        admitted += 1
                    if req.status == "running":
                        running += 1
                        live_pages += -(-(c.prompt.size + got) // page)
                if got > c.seen:
                    c.token_times.extend([t_done] * (got - c.seen))
                    new_tokens += got - c.seen
                    c.seen = got
                if req.status not in ("queued", "running"):
                    del live[rid]
            rounds.append((t_step, t_done, running, new_tokens, live_pages,
                           admitted))
    return rounds


def served_gap(ctx, sample, precision=None):
    """The comparison with the plain reference over ``sample`` (Clients
    that finished). Returns (widest gap of a served token's logit below
    the reference's best, served tokens compared, and, with
    ``precision``, the widest gap of the token that the reference at that
    lower precision puts first: the control)."""
    import jax
    import jax.numpy as jnp

    config, traffic = ctx["config"], ctx["traffic"]
    ref = importlib.import_module(config["reference"])
    _, params = weights.model_and_params(config, ctx["seed"])
    max_len = config["engine"]["max_len"]
    # the reference scores ``n_out`` positions from a start that must
    # leave them inside ``max_len`` (a dynamic slice would clamp it in
    # silence): where the longest answer allowed is as long as the
    # engine's ``max_len`` it starts before the prompt's end, and the
    # served tokens' rows begin ``skip`` rows down
    n_out = min(traffic["answer"]["max"], max_len)
    heads = config["shapes"]["num_heads"]

    @jax.jit
    def gaps(logits, toks):
        best = jnp.max(logits, -1)
        return best - jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]

    worst = worst_control = 0.0
    compared = 0
    for c in sample:
        toks = np.asarray(c.req.tokens, np.int32)
        ids = np.zeros(max_len, np.int32)
        ids[:c.prompt.size] = c.prompt
        ids[c.prompt.size:c.prompt.size + toks.size] = toks
        first = np.int32(min(c.prompt.size - 1, max_len - n_out))
        skip = c.prompt.size - 1 - int(first)
        rows = slice(skip, skip + toks.size)
        logits = ref.logits_at(params, jnp.asarray(ids), first,
                               num_heads=heads, n_out=n_out)
        padded = np.zeros(n_out, np.int32)
        padded[rows] = toks
        g = np.asarray(gaps(logits, jnp.asarray(padded)))[rows]
        worst = max(worst, float(g.max()))
        compared += toks.size
        if precision:
            low = ref.logits_at(params, jnp.asarray(ids), first,
                                num_heads=heads, n_out=n_out,
                                precision=precision)
            g = np.asarray(gaps(logits, jnp.argmax(low, -1).astype(
                jnp.int32)))[rows]
            worst_control = max(worst_control, float(g.max()))
    return worst, compared, worst_control


def pick_sample(done, seed, want_tokens, most):
    """A sample of the finished requests drawn from the seed, the longest
    (prompt + answer) first, until ``want_tokens`` served tokens or
    ``most`` requests."""
    if not done:
        return []
    longest = max(done, key=lambda c: c.prompt.size + len(c.req.tokens))
    rest = [c for c in done if c is not longest]
    rng = np.random.default_rng([int(seed), 3])
    rng.shuffle(rest)
    sample, tokens = [longest], len(longest.req.tokens)
    for c in rest:
        if tokens >= want_tokens or len(sample) >= most:
            break
        sample.append(c)
        tokens += len(c.req.tokens)
    return sample


def tokens_in_window(clients, t_open, t_close):
    """Output tokens OF THE REQUESTS DUE IN THE WINDOW (``measured``, the
    population of the two tails) that appeared in [t_open, t_close):
    what ``serve_tokens_per_s`` counts. At a fixed open-loop rate it is
    how much of what the window asked for was delivered inside it: its
    ceiling is the demand, and serving every token no later than before
    never lowers it. (Before PR 26 the metric counted every client's
    tokens, the warm-up's too: the demand plus the warm-up's backlog
    spilling in, minus the window's spilling out, so a faster server
    read LOWER.)"""
    return sum(1 for c in clients if c.measured
               for t in c.token_times if t_open <= t < t_close)


def window_facts(rounds, clients, cfg_shapes, page, lo, hi):
    """What the per-layer readers need, counted over the rounds that lie
    in [lo, hi]: decode rounds, the live K/V rows they read (in whole
    pages), slot occupancy, the K/V pages that held live context, and
    the forward operations the model needed (prefill of every prompt
    admitted there, one head per prompt, every decoded token at its
    context length)."""
    inside = [r for r in rounds if r[0] >= lo and r[1] <= hi]
    decode_rounds = sum(1 for r in inside if r[2] and r[3])
    occupancy = [r[2] for r in inside]
    pages = [r[4] for r in inside]
    ops = 0.0
    live_rows = 0
    for c in clients:
        if c.admitted_at is not None and lo <= c.admitted_at <= hi:
            ops += flops.gpt_prefill_flops(cfg_shapes, 0, c.prompt.size)
            ops += flops.gpt_head_flops(cfg_shapes)
        # token k (k >= 1) comes from a decode round at context prompt + k
        for k, t in enumerate(c.token_times):
            if k >= 1 and lo <= t <= hi:
                ctx_len = c.prompt.size + k
                ops += flops.gpt_forward_flops(cfg_shapes, ctx_len, True)
                live_rows += -(-ctx_len // page) * page
    return {"decode_rounds": decode_rounds, "decode_live_rows": live_rows,
            "slot_occupancy_mean": (sum(occupancy) / len(occupancy)
                                    if occupancy else None),
            "kv_pages_live_mean": (sum(pages) / len(pages)
                                   if pages else None),
            "kv_pages_live_max": max(pages, default=None),
            "model_ops": ops, "rounds": len(inside)}


def by_quarter(rounds, lo, hi, col):
    """The mean of one column of the round records in each quarter of
    [lo, hi]: says whether the window opened in a ramp-up."""
    out = []
    for q in range(4):
        a, b = lo + (hi - lo) * q / 4, lo + (hi - lo) * (q + 1) / 4
        xs = [r[col] for r in rounds if a <= r[0] < b]
        out.append(sum(xs) / len(xs) if xs else 0.0)
    return out


def measure(engine, ctx, cell, seconds, clock=time.perf_counter,
            sleep=time.sleep):
    """Warm-up traffic, then one window of ``seconds`` at the cell's fixed
    rate on ``engine``, then the drain. ``cell`` is the cell's own file
    (``rate_per_s``, ``warmup_seconds``, ``drain_limit_s``). Returns the
    end-to-end numbers, the facts for the per-layer readers and the
    finished requests.
    ``clock`` and ``sleep`` are the tests' way in, as in ``drive``."""
    config, traffic, log = ctx["config"], ctx["traffic"], ctx["log"]
    shapes = config["shapes"]
    warm, rate = cell["warmup_seconds"], cell["rate_per_s"]
    items = traffic_gen.serve_schedule(
        traffic, rate, shapes["vocab_size"], config["engine"]["max_len"],
        ctx["seed"], warm + seconds)
    schedule = [Client(it, measured=it["due"] >= warm) for it in items]
    built, seen = [], []          # programs built; the clock, at open/close
    tracer = ctx["tracer"]

    def on_open():
        built.append(ctx["compiles"].compiles)
        if ctx["trace"]:
            tracer.start()
        seen.append(clock())

    def on_close():
        seen.append(clock())
        built.append(ctx["compiles"].compiles)
        if ctx["trace"]:
            tracer.close_window()

    t0 = clock() + 0.05
    t_open, t_close = t0 + warm, t0 + warm + seconds
    rounds = drive(engine, schedule, t0, t_open, t_close,
                   cell["drain_limit_s"], log, on_open=on_open,
                   on_close=on_close, clock=clock, sleep=sleep)
    t_end = clock()
    if ctx["trace"]:
        # the profiler's stop takes seconds: only now, with no request
        # running. The trace holds the drain too; the readers cut at
        # the window's close
        tracer.stop()
        log(f"profiler stopped in {clock() - t_end:.2f} s")

    measured = [c for c in schedule if c.measured and c.rid is not None]
    done = [c for c in measured if c.req.status == "done"
            and len(c.req.tokens) == c.max_new]
    ttft = stats.ttft_ms(
        [t0 + c.due for c in measured],
        [c.token_times[0] if c.token_times else None for c in measured],
        worst=t_end - t_open)
    gaps = stats.gaps_ms([c.token_times for c in measured])
    own_tokens = tokens_in_window(schedule, t_open, t_close)
    # the count before PR 26, logged so that older runs compare by eye
    all_tokens = sum(1 for c in schedule for t in c.token_times
                     if t_open <= t < t_close)
    late = [c.late for c in measured] or [0.0]
    lives = [c.token_times[-1] - (t0 + c.due) for c in done] or [0.0]
    log(f"rate {rate}/s, window {seconds:.1f} s after {warm} s of "
        f"warm-up: {len(measured)} requests due, {len(done)} finished, "
        f"{own_tokens} tokens in the window of the requests due in it "
        f"({all_tokens} of all clients, the warm-up's too: the count "
        f"before PR 26, {stats.rate(all_tokens, seconds):.1f}/s), drain "
        f"{t_end - t_close:.2f} s; a request lives p50 "
        f"{stats.percentile(lives, 50):.1f} s, max {max(lives):.1f} s; "
        f"generator late by p50 {1e3 * stats.percentile(late, 50):.2f} ms, "
        f"max {1e3 * max(late):.2f} ms; programs built inside: "
        f"{built[1] - built[0]}")

    # where a stall of the host sits, if the run held one (PERF.md
    # section 6, PR 25 and PR 26): inside ``engine.step()`` or in this
    # loop between two steps
    inside = [r for r in rounds if t_open <= r[0] < t_close]
    if len(inside) > 1:
        longest = max(inside, key=lambda r: r[1] - r[0])
        quiet = [r[1] - r[0] for r in inside if not r[5]]
        # the host's level is what moves gap_p90_ms between runs (PERF.md
        # section 2): by quarter, to tell a slow run from a slow stretch
        quiet_by_quarter = [
            [r[1] - r[0] for r in inside if not r[5]
             and k <= 4 * (r[0] - t_open) / seconds < k + 1]
            for k in range(4)]
        pause, at = max((b[0] - a[1], a[1])
                        for a, b in zip(inside, inside[1:]))
        log(f"longest engine step {1e3 * (longest[1] - longest[0]):.1f} ms "
            f"({longest[5]} admitted in it), {longest[0] - t_open:.1f} s "
            f"into the window, the median "
            f"{1e3 * stats.percentile([r[1] - r[0] for r in inside], 50):.1f}"
            f" (of the steps that admitted nothing "
            f"{1e3 * stats.percentile(quiet or [0.0], 50):.3f}, by quarter "
            + "/".join(f"{1e3 * stats.percentile(q or [0.0], 50):.2f}"
                       for q in quiet_by_quarter) + ")"
            f"; longest pause of the loop between two steps "
            f"{1e3 * pause:.1f} ms, {at - t_open:.1f} s into the window")

    # the end-to-end metrics, over every request due in the window and
    # every gap between its tokens. Each tail is the highest percentile
    # of the ladder logged below that repeats from run to run (PERF.md
    # section 2); the other percentiles are logged and are no metrics
    def tail(xs, q):
        return stats.percentile(xs, q) if xs else math.inf
    e2e = {"serve_tokens_per_s": stats.rate(own_tokens, seconds),
           "ttft_p90_ms": tail(ttft, 90), "gap_p90_ms": tail(gaps, 90)}
    gap_mean = sum(gaps) / len(gaps) if gaps else None
    log("; ".join(f"{k} {v:.1f}" for k, v in e2e.items())
        + f"; over {len(ttft)} requests, {len(gaps)} gaps")
    for name, xs in (("ttft", ttft), ("gap", gaps)):
        log(f"{name} ms: mean {sum(xs) / max(len(xs), 1):.1f}; " + ", ".join(
            f"p{q:g} {tail(xs, q):.2f}"
            for q in (50, 75, 80, 85, 87.5, 90, 91.25, 92.5, 95, 97.5, 99)))
    slots, pages = engine.cfg.num_slots, engine.cfg.num_pages
    # the loop learns of the open and the close between two engine steps:
    # the facts (and the trace) cover the window as the loop saw it
    facts = window_facts(rounds, schedule, shapes, engine.cfg.page_size,
                         seen[0], seen[1])
    waits = [c.admitted_at - (t0 + c.due) for c in measured
             if c.admitted_at is not None]

    def share(x, of):
        return None if x is None else 100.0 * x / of
    facts.update(
        window_s=seen[1] - seen[0], slots=slots,
        queue_wait_ms_p50=(1e3 * stats.percentile(waits, 50)
                           if waits else None),
        gap_mean_ms=gap_mean,
        slot_occupancy_pct=share(facts["slot_occupancy_mean"], slots),
        kv_pool_live_pct=share(facts["kv_pages_live_mean"], pages),
        generator_late_ms_max=1e3 * max(late))
    if facts["rounds"]:
        log("by quarter of the window: running requests "
            + "/".join(f"{x:.1f}" for x in by_quarter(
                rounds, seen[0], seen[1], 2))
            + f" of {slots} slots; K/V pages that hold live context "
            + "/".join(f"{x:.0f}" for x in by_quarter(
                rounds, seen[0], seen[1], 4))
            + f", at the most {facts['kv_pages_live_max']}, of {pages} "
            f"reserved")
    return {
        "t_open": t_open, "attempted": len(measured),
        "failed": len(measured) - len(done), "done": done,
        "window_s": seconds, "ttft_ms": ttft,
        "programs_built_in_window": built[1] - built[0],
        "e2e": e2e,
        "facts": facts,
    }


def run(ctx):
    traffic, log = ctx["traffic"], ctx["log"]
    engine = build(ctx)
    prewarm(engine, ctx["config"]["shapes"]["vocab_size"], ctx["seed"])
    log("both step programs built")
    seconds = ctx["seconds"]
    if ctx["trace"]:
        seconds = min(seconds, traffic["trace_seconds"])
    out = measure(engine, ctx, ctx["cell"], seconds)
    # the window opened at t_open by the clock; set-up is everything
    # before it, the warm-up traffic included
    ctx["setup_s"] = out.pop("t_open") - ctx["t_process"]
    out["device"] = ctx["describe"]()
    done = out.pop("done")
    sample = pick_sample(done, ctx["seed"], traffic["sample_tokens"],
                         traffic["sample_requests"])
    # free the engine (weights, pools) before the reference runs
    engine.close()
    del engine
    gc.collect()

    t_ref = time.perf_counter()
    gap, compared, control_gap = served_gap(ctx, sample,
                                            ctx.get("control_precision"))
    log(f"reference over {len(sample)} requests, {compared} served tokens, "
        f"in {time.perf_counter() - t_ref:.2f} s")
    out["numbers"] = {"served_gap": gap if sample else math.inf,
                      "never_answered": float(out["failed"])}
    if ctx.get("control_precision"):
        out["numbers"]["control_gap"] = control_gap
    return out


def control(ctx):
    """One seed's readings at the cell's own load: the served tokens'
    widest gap, and the widest gap of the tokens that the reference in
    the lower precision (the CONTROL) puts first at the same positions."""
    out = run(ctx)
    return {"program": {"served_gap": out["numbers"]["served_gap"]},
            "control": {"served_gap": out["numbers"]["control_gap"]},
            "attempted": out["attempted"], "failed": out["failed"],
            "e2e": out["e2e"]}
