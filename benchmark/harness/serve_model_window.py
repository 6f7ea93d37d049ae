"""The serving window for a configuration that names its own ``work``
module (traffic ``kind: serve_model``): the same open loop over
``ServingEngine.submit`` / ``step`` as ``serve_window``, for a model
whose operations ``flops.py``'s transformer formulas do not count and
whose weights are served in the dtype the configuration states.

``Client``, ``drive``, ``prewarm``, ``pick_sample``, ``tokens_in_window``
and ``by_quarter`` are ``serve_window``'s own, imported, so the
``bench.*`` annotations that ``readers/engine_spans.py`` pairs with are
the same. What differs:

  * ``build`` fills the parameter tree in ``constructor.weights_dtype``
    (bfloat16: 3 billion float32 parameters do not stand on one chip);
  * ``window_facts`` counts ``model_ops`` with the configuration's
    ``work`` module and adds what its kernels' rooflines divide:
    ``scan_tokens`` / ``scan_chunks`` (real prompt tokens prefilled in
    the window, and the prefill calls they took) and
    ``decode_slot_steps`` (positions decoded: one per running slot per
    round);
  * ``served_gap`` hands the reference the SAME values the program was
    given (the served dtype's, made again from the seed); the reference
    upcasts them.

A DEBT, named in PERF.md section 7: ``measure`` below is a copy of
``serve_window.measure`` with the counting taken out to the ``work``
module (``serve_window.measure`` calls ``flops.gpt_*`` itself and could
not be reused); a later ``benchmark`` PR folds the two windows into one
that takes its counting from the configuration.
"""

import gc
import importlib
import math
import time

import numpy as np

from benchmark.harness import stats, traffic as traffic_gen, weights
from benchmark.harness.serve_window import (Client, by_quarter,  # noqa: F401
                                            drive, pick_sample, prewarm,
                                            tokens_in_window)


#: layer i of seed s is filled under the seed ``LAYER_SEEDS * s + i + 1``
#: (``weights.seed_key`` takes whole numbers up to 2**63)
LAYER_SEEDS = 4096


def model_and_params(config, seed):
    """The program's model and its parameter tree filled from ``seed`` in
    the dtype the configuration serves its weights in. The tree is
    filled block by block (``weights.make_params`` on each layer's
    subtree, under a seed of the layer's own): layers of one kind have
    one shape tree, so their fill is ONE compiled program that the
    compile cache hands back for every later layer, where the whole tree
    in one program is 425 distinct leaves and two and a half minutes of
    compiling (my chip run, PR 28). A leaf's value follows from the
    seed, its layer and its path inside the layer."""
    import jax
    import jax.numpy as jnp
    ctor = config["constructor"]
    model = weights.load_object(ctor["model"])(
        weights.load_object(ctor["config"])(**ctor["kwargs"]))
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))["params"]
    dtype = jnp.dtype(ctor["weights_dtype"])
    blocks = shapes.pop("blocks")
    params = weights.make_params(shapes, seed, dtype)
    params["blocks"] = {
        i: weights.make_params(blocks[i], LAYER_SEEDS * int(seed) + int(i) + 1,
                               dtype)
        for i in sorted(blocks, key=int)}
    return model, params


def build(ctx):
    """Model, weights from the seed on the device, the engine."""
    import jax.numpy as jnp

    from paddle_tpu.serving import ServeConfig, ServingEngine

    config = ctx["config"]
    model, params = model_and_params(config, ctx["seed"])
    e = dict(config["engine"])
    e["cache_dtype"] = jnp.dtype(e["cache_dtype"]).type
    engine = ServingEngine(model, {"params": params, "state": {}},
                           ServeConfig(**e))
    ctx["log"]("weights on the device, engine built")
    return engine


def served_gap(ctx, sample, precision=None):
    """As ``serve_window.served_gap``: the widest gap by which a served
    token's logit lies below the reference's best over ``sample``, the
    served tokens compared and, with ``precision``, the widest gap of the
    token the reference at that lower precision puts first."""
    import jax
    import jax.numpy as jnp

    config, traffic = ctx["config"], ctx["traffic"]
    ref = importlib.import_module(config["reference"])
    _, params = model_and_params(config, ctx["seed"])
    max_len = config["engine"]["max_len"]
    n_out = traffic["answer"]["max"]
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
        first = np.int32(c.prompt.size - 1)
        logits = ref.logits_at(params, jnp.asarray(ids), first,
                               num_heads=heads, n_out=n_out)
        padded = np.zeros(n_out, np.int32)
        padded[:toks.size] = toks
        g = np.asarray(gaps(logits, jnp.asarray(padded)))[:toks.size]
        worst = max(worst, float(g.max()))
        compared += toks.size
        if precision:
            low = ref.logits_at(params, jnp.asarray(ids), first,
                                num_heads=heads, n_out=n_out,
                                precision=precision)
            g = np.asarray(gaps(logits, jnp.argmax(low, -1).astype(
                jnp.int32)))[:toks.size]
            worst_control = max(worst_control, float(g.max()))
    return worst, compared, worst_control


def window_facts(rounds, clients, work, cfg_shapes, chunk, lo, hi):
    """What the per-layer readers need, counted over the rounds that lie
    in [lo, hi], the operations by the configuration's ``work`` module:
    decode rounds, slot occupancy, the K/V pages that held live context,
    the forward operations the model needed (prefill of every prompt
    admitted there, one head per prompt, every decoded token at its
    context length), the real prompt tokens prefilled and the calls of
    ``chunk`` positions they took, and the positions decoded."""
    inside = [r for r in rounds if r[0] >= lo and r[1] <= hi]
    occupancy = [r[2] for r in inside]
    pages = [r[4] for r in inside]
    ops = 0.0
    scan_tokens = scan_chunks = slot_steps = 0
    for c in clients:
        if c.admitted_at is not None and lo <= c.admitted_at <= hi:
            ops += work.prefill_flops(cfg_shapes, 0, c.prompt.size)
            ops += work.head_flops(cfg_shapes)
            scan_tokens += c.prompt.size
            scan_chunks += -(-c.prompt.size // chunk)
        # token k (k >= 1) comes from a decode round at context prompt + k
        for k, t in enumerate(c.token_times):
            if k >= 1 and lo <= t <= hi:
                ops += work.forward_flops(cfg_shapes, c.prompt.size + k,
                                          True)
                slot_steps += 1
    return {"decode_rounds": sum(1 for r in inside if r[2] and r[3]),
            "slot_occupancy_mean": (sum(occupancy) / len(occupancy)
                                    if occupancy else None),
            "kv_pages_live_mean": (sum(pages) / len(pages)
                                   if pages else None),
            "kv_pages_live_max": max(pages, default=None),
            "model_ops": ops, "rounds": len(inside),
            "scan_tokens": scan_tokens, "scan_chunks": scan_chunks,
            "decode_slot_steps": slot_steps}


def measure(engine, ctx, cell, seconds, clock=time.perf_counter,
            sleep=time.sleep):
    """Warm-up traffic, then one window of ``seconds`` at the cell's fixed
    rate on ``engine``, then the drain: ``serve_window.measure`` with the
    counting handed to ``window_facts`` above (the module's docstring
    says why it is a copy). Returns the end-to-end numbers, the facts
    for the per-layer readers and the finished requests.
    ``clock`` and ``sleep`` are the tests' way in, as in ``drive``."""
    config, traffic, log = ctx["config"], ctx["traffic"], ctx["log"]
    shapes = config["shapes"]
    work = importlib.import_module(config["work"])
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
    late = [c.late for c in measured] or [0.0]
    lives = [c.token_times[-1] - (t0 + c.due) for c in done] or [0.0]
    log(f"rate {rate}/s, window {seconds:.1f} s after {warm} s of "
        f"warm-up: {len(measured)} requests due, {len(done)} finished, "
        f"{own_tokens} tokens in the window of the requests due in it, "
        f"drain {t_end - t_close:.2f} s; a request lives p50 "
        f"{stats.percentile(lives, 50):.1f} s, max {max(lives):.1f} s; "
        f"generator late by p50 {1e3 * stats.percentile(late, 50):.2f} ms, "
        f"max {1e3 * max(late):.2f} ms; programs built inside: "
        f"{built[1] - built[0]}")

    # where a stall of the host sits, if the run held one
    inside = [r for r in rounds if t_open <= r[0] < t_close]
    if len(inside) > 1:
        longest = max(inside, key=lambda r: r[1] - r[0])
        quiet = [r[1] - r[0] for r in inside if not r[5]]
        pause, at = max((b[0] - a[1], a[1])
                        for a, b in zip(inside, inside[1:]))
        log(f"longest engine step {1e3 * (longest[1] - longest[0]):.1f} ms "
            f"({longest[5]} admitted in it), {longest[0] - t_open:.1f} s "
            f"into the window, the median "
            f"{1e3 * stats.percentile([r[1] - r[0] for r in inside], 50):.1f}"
            f" (of the steps that admitted nothing "
            f"{1e3 * stats.percentile(quiet or [0.0], 50):.3f})"
            f"; longest pause of the loop between two steps "
            f"{1e3 * pause:.1f} ms, {at - t_open:.1f} s into the window")

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
    facts = window_facts(rounds, schedule, work, shapes,
                         engine.cfg.prefill_len, seen[0], seen[1])
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
            f"reserved; {facts['scan_tokens']} prompt tokens prefilled in "
            f"{facts['scan_chunks']} chunks, {facts['decode_slot_steps']} "
            f"positions decoded in {facts['decode_rounds']} rounds")
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
    ctx["setup_s"] = out.pop("t_open") - ctx["t_process"]
    out["device"] = ctx["describe"]()
    done = out.pop("done")
    sample = pick_sample(done, ctx["seed"], traffic["sample_tokens"],
                         traffic["sample_requests"])
    # free the engine (weights, pools, state) before the reference runs
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
    """One seed's readings at the cell's own load, as
    ``serve_window.control``."""
    out = run(ctx)
    return {"program": {"served_gap": out["numbers"]["served_gap"]},
            "control": {"served_gap": out["numbers"]["control_gap"]},
            "attempted": out["attempted"], "failed": out["failed"],
            "e2e": out["e2e"]}
