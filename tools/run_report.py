#!/usr/bin/env python
"""Run report — join a telemetry RunLog with an optional XPlane trace.

The CLI successor of the reference's EnableProfiler/DisableProfiler
sorted event tables (platform/profiler.h:166) + tools/timeline.py: one
command turns a training run's artifacts into the human-readable story —

  * step-time percentiles (p50/p90/p95/p99) over the per-step records,
  * the MFU curve (bucketed ASCII sparkline) + tokens/s,
  * loss trajectory and device-memory peaks,
  * counter deltas (retries, Pallas fallbacks, torn-checkpoint skips,
    missed heartbeats, preemptions) from the final snapshot record,
  * the span table (Trainer ingest/stage/step phases), and
  * top-K device ops when given a jax.profiler trace dir
    (profiler.trace_op_table).

`--serve` renders the serving view instead: per-request lifecycles
reconstructed from the engine's trace events (submitted/admitted/
prefill_done/first_token/preempted/resumed/retired), an ASCII per-slot
Gantt of slot occupancy, TTFT + token-latency percentiles, goodput
against the configured SLOs, preemption attribution, the KV pool
footprint (kv_dtype + pool bytes, plus quantized-page / overflow-clamp
/ degraded-admission counters for serve_kv_dtype=int8 runs), and — for
serve_draft runs — the speculation story: the per-round acceptance-rate
trajectory (spec_proposed/spec_accepted step fields), tokens per target
step, and per-request speculative-vs-plain accounting (the spec_tokens
field each retirement carries).

`--fleet` renders the fleet live-ops view: the deploy/scale/canary
timeline from FleetRouter ops events (raw records or a dumped telemetry
snapshot's `ops_log`), the per-version goodput table, and, from a
record with a `curve` list, the goodput-vs-offered-load curve (nothing
in the tree writes such a record now: ROADMAP D11 decides the view).

`--fleet-trace` takes SEVERAL RunLogs (one per replica) and renders the
distributed-tracing view: the logs merge into one causally ordered
timeline via their wall/monotonic anchor records (clock-skew
corrected), shown as a cross-replica per-request Gantt — a failover
re-route appears as the SAME trace id continuing on another replica,
and a disaggregated request's prefill -> decode handoff appears as a
'P' row handing to an 'H' row — plus the critical-path breakdown
(queue -> prefill -> first token -> decode) and a skew report.

`--train-health` renders the resilience view: guardian non-finite
skips, loss-spike episodes and mitigation-ladder actions, rollbacks
with their restore targets, watchdog anomalies, checkpoint-integrity
outcomes (corrupt leaves / fallbacks), ingest reader deaths, and the
AMP loss-scale trail.

Usage:
  python tools/run_report.py /runs/exp1/run.jsonl
  python tools/run_report.py run.jsonl --trace /tmp/prof --top 20
  python tools/run_report.py serve.jsonl --serve
  python tools/run_report.py fleet.jsonl --fleet
  python tools/run_report.py serve.jsonl.r0 serve.jsonl.r1 --fleet-trace
  python tools/run_report.py run.jsonl --train-health
  python tools/run_report.py --selftest      # tier-1 smoke: tiny GPT
                                             # through the Trainer with
                                             # telemetry on, then render
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = (len(sorted_vals) - 1) * q
    lo, hi = int(idx), min(int(idx) + 1, len(sorted_vals) - 1)
    frac = idx - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


def _flatten_counters(counters):
    """{'a': 3, 'b': {'op=x': 2}} -> {'a': 3, 'b{op=x}': 2}."""
    out = {}
    for name, v in (counters or {}).items():
        if isinstance(v, dict):
            for label, val in v.items():
                out[f"{name}{{{label}}}"] = val
        else:
            out[name] = v
    return out


def _bars(values, width=40):
    """One-line ASCII bar chart (the MFU curve): scaled to the max."""
    if not values:
        return "(no data)"
    blocks = " .:-=+*#%@"
    top = max(values) or 1.0
    return "".join(
        blocks[min(len(blocks) - 1,
                   int(round(v / top * (len(blocks) - 1))))]
        for v in values)


def _bucket(values, n_buckets=40):
    """Average `values` into at most n_buckets buckets, in order."""
    if len(values) <= n_buckets:
        return list(values)
    out = []
    per = len(values) / n_buckets
    for b in range(n_buckets):
        lo, hi = int(b * per), max(int((b + 1) * per), int(b * per) + 1)
        chunk = values[lo:hi]
        out.append(sum(chunk) / len(chunk))
    return out


def render_report(records, trace_dir=None, top=20, device_filter="TPU"):
    """The full text report from RunLog records (+ optional trace dir)."""
    steps = [r for r in records if "step" in r and not r.get("final")]
    finals = [r for r in records if r.get("final")]
    lines = ["=" * 72, "RUN REPORT", "=" * 72]

    # -- step-time percentiles --------------------------------------------
    walls = sorted(r["wall_s"] for r in steps
                   if isinstance(r.get("wall_s"), (int, float)))
    lines.append(f"\nstep records: {len(steps)}"
                 + (f"  (steps {steps[0]['step']}..{steps[-1]['step']})"
                    if steps else ""))
    if walls:
        lines.append("step time:   "
                     + "  ".join(
                         f"p{int(q * 100)}={_percentile(walls, q) * 1e3:.2f}ms"
                         for q in (0.50, 0.90, 0.95, 0.99))
                     + f"  mean={sum(walls) / len(walls) * 1e3:.2f}ms"
                     + f"  max={walls[-1] * 1e3:.2f}ms")
    tps = [r["tokens_per_s"] for r in steps
           if isinstance(r.get("tokens_per_s"), (int, float))]
    if tps:
        s_tps = sorted(tps)
        lines.append(f"tokens/s:    p50={_percentile(s_tps, 0.5):,.0f}  "
                     f"mean={sum(tps) / len(tps):,.0f}  "
                     f"max={s_tps[-1]:,.0f}")

    # -- MFU curve --------------------------------------------------------
    mfus = [r["mfu"] for r in steps
            if isinstance(r.get("mfu"), (int, float))]
    if mfus:
        lines.append(f"MFU:         min={min(mfus):.4f}  "
                     f"mean={sum(mfus) / len(mfus):.4f}  "
                     f"max={max(mfus):.4f}")
        lines.append(f"MFU curve:   [{_bars(_bucket(mfus))}]")

    # -- loss / memory ----------------------------------------------------
    losses = [(r["step"], r["loss"]) for r in steps
              if isinstance(r.get("loss"), (int, float))]
    if losses:
        lines.append(f"loss:        first={losses[0][1]:.6f} "
                     f"(step {losses[0][0]})  last={losses[-1][1]:.6f} "
                     f"(step {losses[-1][0]})  "
                     f"min={min(v for _, v in losses):.6f}")
    peaks = [r["memory"].get("peak_bytes_in_use") or
             r["memory"].get("bytes_in_use") for r in steps
             if isinstance(r.get("memory"), dict)]
    peaks = [p for p in peaks if p]
    lines.append(f"memory peak: {max(peaks) / 2 ** 20:.1f} MiB"
                 if peaks else
                 "memory peak: n/a (backend reports no allocator stats)")

    # -- counters (deltas when the log holds >1 snapshot) -----------------
    if finals:
        last = _flatten_counters(finals[-1].get("counters"))
        first = (_flatten_counters(finals[0].get("counters"))
                 if len(finals) > 1 else {})
        lines.append("\ncounters" + (" (delta since first snapshot)"
                                     if first else "") + ":")
        if not last:
            lines.append("  (none fired)")
        for name in sorted(last):
            delta = last[name] - first.get(name, 0)
            val = (f"{last[name]:.4f}" if isinstance(last[name], float)
                   else f"{last[name]}")
            suffix = (f"   (+{delta:g})" if first else "")
            lines.append(f"  {name:<52} {val:>12}{suffix}")

        spans = finals[-1].get("spans") or []
        if spans:
            lines.append("\nspans:")
            lines.append(f"  {'span':<28}{'calls':>8}{'total_s':>10}"
                         f"{'p50_ms':>10}{'p95_ms':>10}")
            for s in spans[:top]:
                lines.append(
                    f"  {s['name']:<28}{s['calls']:>8}"
                    f"{s['total_s']:>10.3f}{s.get('p50_ms', 0):>10.3f}"
                    f"{s.get('p95_ms', 0):>10.3f}")

    # -- device ops from the XPlane trace ---------------------------------
    if trace_dir:
        lines.append(f"\ntop device ops ({trace_dir}):")
        try:
            from paddle_tpu.profiler import trace_op_table
            n_steps = max(len(steps), 1)
            rows = trace_op_table(trace_dir, device_filter=device_filter,
                                  top=top, steps=n_steps)
            if not rows and device_filter not in (None, "CPU"):
                rows = trace_op_table(trace_dir, device_filter="CPU",
                                      top=top, steps=n_steps)
            if not rows:
                rows = trace_op_table(trace_dir, device_filter=None,
                                      top=top, steps=n_steps)
            width = max((len(r["name"]) for r in rows), default=10)
            width = min(width, 80)
            lines.append(f"  {'op':<{width}}  {'total_us':>12}  "
                         f"{'per_step':>10}  {'count':>6}")
            for r in rows:
                lines.append(f"  {r['name'][:width]:<{width}}  "
                             f"{r['total_us']:>12.0f}  "
                             f"{r['per_step_us']:>10.1f}  "
                             f"{r['count']:>6d}")
        except Exception as e:
            lines.append(f"  (trace unreadable: {e})")

    lines.append("=" * 72)
    return "\n".join(lines)


# -- training-health view -------------------------------------------------

def render_train_health(records):
    """The resilience story of a training run: guardian events (non-finite
    skip-applies, loss-spike episodes, mitigation-ladder actions,
    rollbacks), watchdog anomalies, checkpoint-integrity outcomes, ingest
    failures, and the AMP loss-scale trail — everything static/guardian.py
    and io/checkpoint.py wrote into the RunLog and the final metrics
    snapshot."""
    guardian = [r for r in records if "guardian" in r]
    anomalies = [r for r in records if "anomaly" in r]
    finals = [r for r in records if r.get("final")]
    counters = _flatten_counters(finals[-1].get("counters")) if finals else {}
    gauges = (finals[-1].get("gauges") or {}) if finals else {}
    lines = ["=" * 72, "TRAIN HEALTH", "=" * 72]

    def ctr(name):
        return sum(v for k, v in counters.items()
                   if k == name or k.startswith(name + "{"))

    # -- guardian ladder ---------------------------------------------------
    kinds = {}
    actions = {}
    for r in guardian:
        kinds[r["guardian"]] = kinds.get(r["guardian"], 0) + 1
        if r.get("action"):
            actions[r["action"]] = actions.get(r["action"], 0) + 1
    lines.append(f"\nguardian events: {len(guardian)}"
                 + (f"  ({', '.join(f'{k} {v}' for k, v in sorted(kinds.items()))})"
                    if kinds else "  (clean run)"))
    lines.append(f"non-finite skips:   {ctr('trainer.nonfinite_skips')}")
    lines.append(f"loss-spike episodes: {ctr('trainer.loss_spikes')}")
    if actions:
        lines.append("ladder actions:     "
                     + "  ".join(f"{a}={actions[a]}" for a in
                                 ("skip", "reread", "rollback")
                                 if a in actions))
    rb = [r for r in guardian if r["guardian"] == "rollback"]
    done = [r for r in guardian if r["guardian"] == "rollback_done"]
    lines.append(f"rollbacks:          {ctr('trainer.rollbacks')}")
    for r, d in zip(rb, done + [None] * len(rb)):
        lines.append(f"  at step {r.get('step')}"
                     + (f" -> restored step {d['restored_step']}"
                        if d else " (restore unrecorded)"))

    # -- watchdog anomalies ------------------------------------------------
    if anomalies:
        by_kind = {}
        for r in anomalies:
            by_kind.setdefault(r["anomaly"], []).append(r.get("step"))
        lines.append("\nwatchdog anomalies:")
        for k in sorted(by_kind):
            steps_s = ", ".join(str(s) for s in by_kind[k][:8])
            more = len(by_kind[k]) - 8
            lines.append(f"  {k:<18} x{len(by_kind[k])}  (steps {steps_s}"
                         + (f", +{more} more)" if more > 0 else ")"))
    else:
        lines.append("\nwatchdog anomalies: none")

    # -- checkpoint integrity / ingest / amp -------------------------------
    lines.append("\ncheckpoint integrity:")
    for name, label in (("checkpoint.saves", "saves"),
                        ("checkpoint.restores", "restores"),
                        ("checkpoint.corrupt_leaves", "corrupt leaves"),
                        ("checkpoint.integrity_fallbacks",
                         "integrity fallbacks"),
                        ("checkpoint.torn_skips", "torn-mirror skips")):
        lines.append(f"  {label:<20} {ctr(name)}")
    ingest = {k: v for k, v in counters.items()
              if k.startswith("trainer.ingest_errors")}
    lines.append("ingest reader deaths: "
                 + (", ".join(f"{k.split('{', 1)[-1].rstrip('}')} x{v}"
                              for k, v in sorted(ingest.items()))
                    if ingest else "0"))
    if "amp.loss_scale" in gauges or ctr("amp.skipped_steps"):
        lines.append(f"amp: loss_scale={gauges.get('amp.loss_scale')}  "
                     f"skipped_steps={ctr('amp.skipped_steps')}")

    # -- loss trajectory around the incidents ------------------------------
    steps = [r for r in records if "step" in r and not r.get("final")
             and "guardian" not in r and "anomaly" not in r]
    losses = [(r["step"], r["loss"]) for r in steps
              if isinstance(r.get("loss"), (int, float))]
    if losses:
        worst = max(losses, key=lambda sv: sv[1])
        lines.append(f"\nloss: first={losses[0][1]:.6g} "
                     f"last={losses[-1][1]:.6g} "
                     f"worst={worst[1]:.6g} (step {worst[0]})")
    verdict = ("DEGRADED (rollback budget was drawn on)" if rb
               else "contained" if guardian or anomalies else "clean")
    lines.append(f"verdict: {verdict}")
    lines.append("=" * 72)
    return "\n".join(lines)


# -- serving view ---------------------------------------------------------

_GANTT_CHARS = "0123456789abcdefghijklmnopqrstuvwxyz"


def _pctl_line(label, vals_s):
    vals = sorted(vals_s)
    if not vals:
        return f"{label} (no data)"
    return (label + "  ".join(
        f"p{int(q * 100)}={_percentile(vals, q) * 1e3:.1f}ms"
        for q in (0.50, 0.90, 0.99)) + f"  n={len(vals)}")


def _slot_gantt(events, width=64):
    """ASCII per-slot occupancy: each request renders as its id's base-36
    digit from admission (or resume) to preemption/retirement."""
    slotted = [e for e in events
               if "slot" in e and e["event"] in
               ("admitted", "resumed", "preempted", "retired")]
    if not slotted:
        return ["(no slot events)"]
    t0 = min(e["t"] for e in slotted)
    t1 = max(e["t"] for e in slotted)
    span = max(t1 - t0, 1e-9)

    def col(t):
        return min(int((t - t0) / span * (width - 1)), width - 1)

    slots = sorted({e["slot"] for e in slotted})
    rows = {s: [" "] * width for s in slots}
    open_at = {}                      # slot -> (req, start col)
    for e in sorted(slotted, key=lambda e: e["t"]):
        s = e["slot"]
        if e["event"] in ("admitted", "resumed"):
            open_at[s] = (e["req"], col(e["t"]))
        else:
            req, c0 = open_at.pop(s, (e["req"], col(e["t"])))
            c1 = col(e["t"])
            ch = _GANTT_CHARS[req % len(_GANTT_CHARS)]
            for c in range(c0, c1 + 1):
                rows[s][c] = ch
            if e["event"] == "preempted":
                rows[s][c1] = "!"
    for s, (req, c0) in open_at.items():    # still running at log end
        ch = _GANTT_CHARS[req % len(_GANTT_CHARS)]
        for c in range(c0, width):
            rows[s][c] = ch
    out = [f"slot timeline (t0=+0.000s, span={span:.3f}s, one request "
           f"= its id base-36; '!' = preemption):"]
    for s in slots:
        out.append(f"  slot {s:>2} |{''.join(rows[s])}|")
    return out


def render_serve_report(records, top=20, width=64):
    """The serving story from engine trace events + per-step records."""
    events = [r for r in records if "event" in r and "req" in r]
    steps = [r for r in records
             if r.get("phase") == "serve" and "step" in r
             and not r.get("final")]
    finals = [r for r in records if r.get("final")]
    lines = ["=" * 72, "SERVE REPORT", "=" * 72]
    if not events:
        lines.append("\n(no serve trace events in this RunLog — run the "
                     "engine with ServeConfig(run_log=...))")
        return "\n".join(lines + ["=" * 72])

    byreq = {}
    for e in sorted(events, key=lambda e: e["t"]):
        byreq.setdefault(e["req"], []).append(e)

    def last(req_events, name):
        hits = [e for e in req_events if e["event"] == name]
        return hits[-1] if hits else None

    retired = {r: ev for r, ev in byreq.items() if last(ev, "retired")}
    reasons = {}
    ttfts, tok_lats, slo_flags = [], [], []
    for r, ev in retired.items():
        ret = last(ev, "retired")
        reasons[ret.get("reason", "?")] = \
            reasons.get(ret.get("reason", "?"), 0) + 1
        sub, ft = last(ev, "submitted"), last(ev, "first_token")
        if sub and ft:
            ttfts.append(ft["t"] - sub["t"])
        ntok = ret.get("tokens", 0)
        if ft and ntok > 1:
            tok_lats.append((ret["t"] - ft["t"]) / (ntok - 1))
        if ret.get("slo_ok") is not None:
            slo_flags.append(bool(ret["slo_ok"]))
    preempted = {r: ev for r, ev in byreq.items()
                 if last(ev, "preempted")}

    lines.append(
        f"\nrequests: {len(byreq)} submitted, {len(retired)} retired "
        f"({', '.join(f'{k} {v}' for k, v in sorted(reasons.items()))})"
        + (f", {len(preempted)} preempted" if preempted else ""))
    lines.append(_pctl_line("TTFT:          ", ttfts))
    lines.append(_pctl_line("token latency: ", tok_lats))
    if slo_flags:
        good = sum(slo_flags) / len(slo_flags)
        slo = (finals[-1].get("slo") if finals else None) or {}
        viol = slo.get("violations") or {}
        tgt = ", ".join(f"{k}={slo[k]}" for k in
                        ("slo_ttft_s", "slo_token_latency_s")
                        if slo.get(k))
        lines.append(
            f"goodput:        {good:.4f} over {len(slo_flags)} retired"
            + (f"  (targets: {tgt})" if tgt else "  (no SLO configured)")
            + (f"  violations: "
               + ", ".join(f"{k}={v}" for k, v in sorted(viol.items()))
               if viol else ""))
    if steps:
        walls = [r["wall_s"] for r in steps
                 if isinstance(r.get("wall_s"), (int, float))]
        toks = sum(r.get("new_tokens") or 0 for r in steps)
        lines.append(_pctl_line(
            f"serve steps:    {len(steps)} ({toks} tokens)  step ",
            walls))

    # -- speculation: acceptance trajectory + spec-vs-plain accounting ----
    spec_steps = [r for r in steps
                  if isinstance(r.get("spec_proposed"), int)]
    if spec_steps:
        prop = sum(r["spec_proposed"] for r in spec_steps)
        acc = sum(r.get("spec_accepted") or 0 for r in spec_steps)
        toks = sum(r.get("new_tokens") or 0 for r in steps)
        lines.append(
            f"\nspeculation:    {len(spec_steps)}/{len(steps)} steps ran "
            f"a draft round; {prop} proposed, {acc} accepted, "
            f"{prop - acc} rolled back"
            + (f"  (acceptance {acc / prop:.4f})" if prop else ""))
        lines.append(f"tokens/target-step: {toks / len(steps):.4f} over "
                     f"{len(steps)} target steps (plain decoding is 1.0)")
        rates = [r["spec_accepted"] / r["spec_proposed"]
                 for r in spec_steps if r["spec_proposed"]]
        if rates:
            lines.append(f"acceptance trajectory (per round, max "
                         f"{max(rates):.2f}): [{_bars(_bucket(rates))}]")
        spec_reqs = [(r, last(ev, "retired")) for r, ev in retired.items()]
        spec_reqs = [(r, ret) for r, ret in spec_reqs
                     if ret.get("spec_tokens") is not None]
        if spec_reqs:
            won = [rr for rr in spec_reqs if rr[1]["spec_tokens"]]
            saved = sum(ret["spec_tokens"] for _, ret in spec_reqs)
            lines.append(
                f"spec-vs-plain:  {len(won)}/{len(spec_reqs)} retired "
                f"requests beat one token per step; {saved} target "
                "steps saved in total")
            for r, ret in sorted(
                    spec_reqs, key=lambda kv: -kv[1]["spec_tokens"])[:top]:
                ntok = ret.get("tokens", 0)
                lines.append(
                    f"  req {r}: {ntok} tokens in "
                    f"{ntok - ret['spec_tokens']} target steps "
                    f"(+{ret['spec_tokens']} speculative)")
    fin = finals[-1] if finals else {}
    if fin.get("kv_dtype") or fin.get("kv_pool_bytes"):
        counters = _flatten_counters(fin.get("counters"))
        gauges = fin.get("gauges") or {}

        def _near(table, name):
            return sum(v for k, v in table.items()
                       if k == name or k.startswith(name))

        kv = (f"KV pool:        {fin.get('kv_dtype') or 'f32'}, "
              f"{int(fin.get('kv_pool_bytes') or 0):,} bytes")
        if fin.get("kv_dtype") == "int8":
            kv += (
                f"  (quantized pages in use "
                f"{int(_near(gauges, 'serve.kv_quant_pages'))}, "
                f"overflow clamps "
                f"{int(_near(counters, 'quant.overflow_clamps'))}, "
                f"degraded admits "
                f"{int(_near(counters, 'serve.kv_quant_degraded'))})")
        lines.append(kv)
    lines.append("")
    lines.extend(_slot_gantt(events, width=width))

    if preempted:
        lines.append("\npreemption attribution:")
        for r in sorted(preempted):
            ev = byreq[r]
            for p in (e for e in ev if e["event"] == "preempted"):
                res = [e for e in ev if e["event"] == "resumed"
                       and e["t"] > p["t"]]
                lines.append(
                    f"  req {r}: preempted at slot {p.get('slot')} "
                    f"({p.get('tokens_dropped', 0)} tokens dropped, "
                    + (f"resumed +{res[0]['t'] - p['t']:.3f}s later)"
                       if res else "never resumed)"))

    lines.append(f"\nrequest lifecycles (top {top} by span):")
    t_base = min(e["t"] for ev in byreq.values() for e in ev)

    def req_span(ev):
        return ev[-1]["t"] - ev[0]["t"]

    for r, ev in sorted(byreq.items(), key=lambda kv: -req_span(kv[1]))[
            :top]:
        trace = ev[0].get("trace", "")
        parts = []
        for e in ev:
            tag = e["event"]
            if tag == "retired":
                tag += (f"[{e.get('reason')}, {e.get('tokens')} tok"
                        + (", slo_ok" if e.get("slo_ok")
                           else ", SLO MISS") + "]")
            parts.append(f"{tag} +{e['t'] - t_base:.3f}")
        lines.append(f"  req {r} [{trace}]: " + " -> ".join(parts))
    lines.append("=" * 72)
    return "\n".join(lines)


_FLEET_EVENTS = frozenset((
    "deploy_start", "deploy_done", "deploy_abort", "swap", "swap_fail",
    "scale_up", "scale_up_fail", "scale_down_begin", "scale_down",
    "scale_down_cancelled", "scale_down_fail", "canary_abort"))


def render_fleet_report(records, width=64):
    """The live-ops story of a fleet: the deploy/scale/canary timeline
    (FleetRouter.ops_log events, taken either as raw records or from any
    record carrying an `ops_log` list — e.g. a dumped telemetry
    snapshot) plus the per-version goodput table (`version_stats`
    snapshot when present, else reconstructed from engine trace
    `retired` events that carry a version tag) and, when a record
    carries a `curve` list, the goodput-vs-offered-load curve."""
    ops = [r for r in records if r.get("event") in _FLEET_EVENTS]
    vstats, curve = None, None
    for r in records:
        if isinstance(r.get("ops_log"), list):
            ops.extend(e for e in r["ops_log"]
                       if e.get("event") in _FLEET_EVENTS)
        if isinstance(r.get("version_stats"), dict):
            vstats = r["version_stats"]
        if isinstance(r.get("curve"), list):
            curve = r["curve"]
    if vstats is None:
        # reconstruct from version-tagged retirements in the trace
        tally = {}
        for r in records:
            if r.get("event") == "retired" and r.get("version"):
                st = tally.setdefault(r["version"], [0, 0])
                st[0] += 1
                if r.get("slo_ok"):
                    st[1] += 1
        if tally:
            vstats = {v: {"retired": s[0], "slo_ok": s[1],
                          "goodput": round(s[1] / s[0], 4)}
                      for v, s in tally.items()}
    lines = ["=" * 72, "FLEET REPORT", "=" * 72]
    if not ops and vstats is None and curve is None:
        lines.append("\n(no fleet ops events in this RunLog — dump "
                     "router.telemetry() as a record)")
        return "\n".join(lines + ["=" * 72])

    if ops:
        ops.sort(key=lambda e: e.get("t", 0.0))
        t0 = ops[0].get("t", 0.0)
        deploys = [e for e in ops if e["event"].startswith("deploy")]
        swaps = [e for e in ops if e["event"].startswith("swap")]
        scales = [e for e in ops if e["event"].startswith("scale")]
        aborts = [e for e in ops if e["event"] == "canary_abort"]
        lines.append(
            f"\nops events: {len(ops)} "
            f"({len(deploys)} deploy, {len(swaps)} swap, "
            f"{len(scales)} scale, {len(aborts)} canary_abort)")
        lines.append(f"\ndeploy timeline (t0=+0.000s over "
                     f"{ops[-1].get('t', t0) - t0:.3f}s):")
        for e in ops:
            extra = ", ".join(
                f"{k}={v}" for k, v in e.items()
                if k not in ("event", "t", "at_step"))
            lines.append(f"  +{e.get('t', t0) - t0:9.3f}  "
                         f"{e['event']:<21}" + (f" {extra}" if extra
                                                else ""))

    if vstats:
        lines.append("\nper-version goodput:")
        lines.append(f"  {'version':<16} {'retired':>8} {'slo_ok':>8} "
                     f"{'goodput':>8}")
        for v in sorted(vstats):
            st = vstats[v]
            lines.append(f"  {v:<16} {st.get('retired', 0):>8} "
                         f"{st.get('slo_ok', 0):>8} "
                         f"{st.get('goodput', 0.0):>8.4f}")

    if curve:
        lines.append("\noffered-load ramp (goodput bar scaled to 1.0):")
        lines.append(f"  {'offered':>7} {'done':>5} {'replicas':>8} "
                     f"{'tok/s':>8} {'deploy_s':>8} {'goodput':>8}")
        barw = max(8, width - 52)
        for row in curve:
            g = float(row.get("goodput", 0.0))
            bar = "#" * int(round(g * barw))
            lines.append(
                f"  {row.get('offered', 0):>7} "
                f"{row.get('completed', 0):>5} "
                f"{row.get('replicas', 0):>8} "
                f"{row.get('tokens_per_sec', 0.0):>8} "
                f"{row.get('deploy_s', 0.0):>8} {g:>8.4f} |{bar}|")
    lines.append("=" * 72)
    return "\n".join(lines)


def render_fleet_trace(record_lists, top=20, width=64):
    """The fleet-wide distributed-tracing story: per-replica RunLogs
    merged into ONE causally ordered timeline (per-process wall/mono
    anchor records correct clock skew), then rendered as a clock-skew
    report, a cross-replica per-request Gantt (failover / deploy-drain
    re-admission / preemption / disaggregated prefill->decode handoff
    annotated), and the critical-path phase breakdown (queue ->
    dispatch -> prefill -> first token -> decode -> retire) over
    retired requests. ``record_lists`` maps a source name (one per
    replica RunLog) to its records."""
    from paddle_tpu.observability.trace import (group_by_trace,
                                                merge_fleet_trace)
    merged = merge_fleet_trace(record_lists)
    events = merged["events"]
    lines = ["=" * 72, "FLEET TRACE", "=" * 72]

    lines.append("\nclock-skew report (anchor offsets, relative to the "
                 "earliest source):")
    for src in sorted(merged["skew"]):
        sk = merged["skew"][src]
        if not sk["anchored"]:
            lines.append(f"  {src:<24} NO ANCHOR — raw times, causal "
                         "order not guaranteed")
        else:
            lines.append(f"  {src:<24} offset {sk['offset']:+.3f}s  "
                         f"skew {sk['skew_s']:+.6f}s")

    req_events = [e for e in events if "req" in e and e.get("trace")]
    if not req_events:
        lines.append("\n(no request trace events across these RunLogs)")
        return "\n".join(lines + ["=" * 72])
    traces = group_by_trace(req_events)
    traces.pop(None, None)
    t0 = min(e["wall_t"] for e in req_events)
    t1 = max(e["wall_t"] for e in req_events)
    span_t = max(t1 - t0, 1e-9)

    def col(t):
        return min(width - 1, int((t - t0) / span_t * width))

    def trace_span(evs):
        return evs[-1]["wall_t"] - evs[0]["wall_t"]

    shown = sorted(traces.items(), key=lambda kv: -trace_span(kv[1]))[:top]
    lines.append(
        f"\ncross-replica request Gantt ({len(traces)} traces over "
        f"{span_t:.3f}s; top {len(shown)} by span — one row per "
        "replica a trace touched; A=adopted F=failover-adopt "
        "P=prefill-leg H=handoff-adopt !=preempted .=event R=retired):")
    mark = {"adopted": "A", "preempted": "!", "retired": "R"}
    origin_mark = {"failover": "F", "prefill": "P", "handoff": "H"}
    for tid, evs in shown:
        lines.append(f"  {tid}:")
        sources = sorted({e["source"] for e in evs})
        for src in sources:
            mine = [e for e in evs if e["source"] == src]
            row = [" "] * width
            lo, hi = col(mine[0]["wall_t"]), col(mine[-1]["wall_t"])
            for c in range(lo, hi + 1):
                row[c] = "-"
            # letters outrank "." when events share a column
            rank = {" ": 0, "-": 0, ".": 1}
            for e in mine:
                m = mark.get(e["event"], ".")
                if e["event"] == "adopted":
                    m = origin_mark.get(e.get("origin"), m)
                c = col(e["wall_t"])
                if rank.get(m, 2) < rank.get(row[c], 2):
                    continue
                if rank.get(row[c], 2) >= 2 and row[c] != m:
                    # two letters share a column (e.g. the handoff-adopt
                    # and the retirement of a short decode leg): nudge
                    # sideways so both stay visible
                    for alt in (c + 1, c - 1):
                        if 0 <= alt < width and rank.get(row[alt], 2) < 2:
                            c = alt
                            break
                row[c] = m
            note = ""
            hops = {e.get("span") for e in mine if e.get("span")}
            if hops:
                note = " " + ",".join(sorted(hops))
            ver = next((e.get("version") for e in mine
                        if e.get("version")), None)
            if ver:
                note += f" [{ver}]"
            lines.append(f"    {src:<20} |{''.join(row)}|{note}")

    # critical-path breakdown over retired traces: each phase edge is
    # the time between consecutive lifecycle events (failover restarts
    # a phase; the LAST occurrence wins, matching what the user waited)
    phases = {"queue": [], "prefill": [], "first_token": [],
              "decode": [], "total": []}
    retired_n = 0
    for tid, evs in traces.items():
        def last_t(name, evs=evs):
            hit = [e for e in evs if e["event"] == name]
            return hit[-1]["wall_t"] if hit else None
        start = min(e["wall_t"] for e in evs)
        adopt = last_t("adopted") or last_t("submitted") or start
        admit = max(filter(None, (last_t("admitted"),
                                  last_t("resumed"))), default=None)
        pf, ft, ret = (last_t("prefill_done"), last_t("first_token"),
                       last_t("retired"))
        if ret is None:
            continue
        retired_n += 1
        if admit is not None:
            phases["queue"].append(admit - adopt)
        if pf is not None and admit is not None:
            phases["prefill"].append(pf - admit)
        if ft is not None and pf is not None:
            phases["first_token"].append(ft - pf)
        if ft is not None:
            phases["decode"].append(ret - ft)
        phases["total"].append(ret - start)
    if retired_n:
        lines.append(f"\ncritical-path breakdown ({retired_n} retired "
                     "traces; last occurrence per phase wins across "
                     "failover hops):")
        for name in ("queue", "prefill", "first_token", "decode",
                     "total"):
            lines.append(_pctl_line(f"{name:<15}", phases[name]))
    lines.append("=" * 72)
    return "\n".join(lines)


def _selftest():
    """Tier-1 smoke (CPU-only): a tiny GPT trained through the Trainer
    with telemetry on must produce a RunLog whose records carry wall
    time, tokens/s, MFU, loss, and a memory field, whose final snapshot
    holds pallas-fallback and checkpoint counters — and this CLI must
    render it. Exit 0 + 'SELFTEST OK' on success."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.observability import TelemetryConfig, read_records
    from paddle_tpu.static import Trainer, TrainerConfig

    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    model = GPT(cfg)
    params = model.init(jax.random.key(0))["params"]
    opt = pt.optimizer.Adam(1e-3)
    state = {"params": params, "opt": opt.init(params)}

    @jax.jit
    def step(st, ids):
        def loss_fn(p):
            # fused .loss() path: on CPU the Pallas xent/flash kernels
            # refuse and count their fallbacks — the selftest asserts
            # those counters reach the RunLog snapshot
            return model.apply({"params": p, "state": {}}, ids,
                               method="loss")
        loss, grads = jax.value_and_grad(loss_fn)(st["params"])
        p, o = opt.apply_gradients(st["params"], grads, st["opt"])
        return loss, {"params": p, "opt": o}

    B, S, n_steps = 2, 16, 6
    rng = np.random.RandomState(0)
    ds = pt.data.InMemoryDataset(
        [(rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32),)
         for _ in range(n_steps)])
    tmp = tempfile.mkdtemp(prefix="pt_run_report_selftest_")
    run_log = os.path.join(tmp, "run.jsonl")
    tcfg = TrainerConfig(
        num_ingest_threads=1,
        telemetry=TelemetryConfig(enabled=True, run_log=run_log,
                                  every_n_steps=1),
        checkpoint_dir=os.path.join(tmp, "ck"), checkpoint_every=3)
    _, stats = Trainer(step, tcfg).train(state, ds)
    assert stats["steps"] == n_steps, stats

    records = read_records(run_log)
    steps = [r for r in records if "step" in r and not r.get("final")]
    finals = [r for r in records if r.get("final")]
    assert len(steps) == n_steps, [r.get("step") for r in records]
    ids = [r["step"] for r in steps]
    assert ids == sorted(ids) and len(set(ids)) == len(ids), ids
    for r in steps:
        for key in ("wall_s", "tokens_per_s", "mfu", "loss", "memory"):
            assert key in r, (key, r)
        assert isinstance(r["loss"], float), r
        # a float on a chip with a published peak; the CPU has none
        assert r["mfu"] is None or isinstance(r["mfu"], float), r
        assert r["tokens_per_s"] > 0, r
    assert finals, "final snapshot record missing"
    counters = finals[-1]["counters"]
    assert "pallas.fallback" in counters, counters
    assert "checkpoint.saves" in counters, counters

    report = render_report(records, trace_dir=None)
    print(report)
    assert "step time:" in report and "counters" in report
    print("SELFTEST OK")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("runlog", nargs="?", help="RunLog JSONL path "
                    "(rotated siblings are folded in automatically)")
    ap.add_argument("extra_runlogs", nargs="*",
                    help="additional per-replica RunLog paths "
                         "(--fleet-trace merges them into one timeline)")
    ap.add_argument("--trace", default=None,
                    help="jax.profiler trace dir to join (top-K op table "
                         "via profiler.trace_op_table)")
    ap.add_argument("--top", type=int, default=20,
                    help="rows for the span/op tables")
    ap.add_argument("--device-filter", default="TPU",
                    help="trace lane substring ('TPU', 'CPU'; falls back "
                         "automatically when empty)")
    ap.add_argument("--serve", action="store_true",
                    help="render the serving view: per-request "
                         "lifecycles, per-slot Gantt, TTFT/token-"
                         "latency percentiles, goodput, preemption "
                         "attribution")
    ap.add_argument("--fleet", action="store_true",
                    help="render the fleet live-ops view: deploy/scale/"
                         "canary timeline, per-version goodput table, "
                         "and (from a ramp bench row) the goodput-vs-"
                         "offered-load curve")
    ap.add_argument("--fleet-trace", action="store_true",
                    help="merge the given per-replica RunLogs into one "
                         "skew-corrected timeline: cross-replica "
                         "per-request Gantt, critical-path breakdown, "
                         "clock-skew report")
    ap.add_argument("--train-health", action="store_true",
                    help="render the training-resilience view: guardian "
                         "skips/spikes/rollbacks, watchdog anomalies, "
                         "checkpoint-integrity outcomes, ingest "
                         "failures, AMP loss-scale trail")
    ap.add_argument("--selftest", action="store_true",
                    help="train a tiny GPT with telemetry on (CPU) and "
                         "render its report — the tier-1 smoke")
    args = ap.parse_args()
    if args.selftest:
        _selftest()
        return
    if not args.runlog:
        ap.error("a RunLog path is required (or --selftest)")
    from paddle_tpu.observability.runlog import read_records
    if args.fleet_trace:
        paths = [args.runlog] + list(args.extra_runlogs)
        lists = {}
        for p in paths:
            name = os.path.basename(p)
            lists[p if name in lists else name] = read_records(p)
        print(render_fleet_trace(lists, top=args.top))
        return
    if args.extra_runlogs:
        ap.error("multiple RunLogs only make sense with --fleet-trace")
    records = read_records(args.runlog)
    if not records:
        raise SystemExit(f"no records in {args.runlog}")
    if args.serve:
        print(render_serve_report(records, top=args.top))
        return
    if args.fleet:
        print(render_fleet_report(records))
        return
    if args.train_health:
        print(render_train_health(records))
        return
    print(render_report(records, trace_dir=args.trace, top=args.top,
                        device_filter=args.device_filter))


if __name__ == "__main__":
    main()
