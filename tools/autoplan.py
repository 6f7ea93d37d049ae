#!/usr/bin/env python
"""Auto-parallelism planner CLI — rank dp x tp x pp meshes for a model
on a topology, no accelerator (and no jax) required.

The search+cost-model live in paddle_tpu/parallel/autoplan/; this tool
is the operator front door: a human ranked-candidate table (every
pruned factorization with its recorded reason) plus the repo-standard
last-line JSON row for scripting. `Trainer(mesh_plan=...)` and
`tools/compile_smoke.py --autoplan` consume the same plan at run time;
this tool answers "what would it pick, and why" ahead of time.

Usage:
  python tools/autoplan.py --model gpt --topology cpu4
  python tools/autoplan.py --model bert --topology v5e-8 --batch 32
  python tools/autoplan.py --model gpt --topology 2xv5e-16 --json
  python tools/autoplan.py --selftest        # host-math sanity (tier-1)
  python tools/autoplan.py --model gpt --calibrate   # vs XLA cost_analysis
  python tools/autoplan.py --model gpt --serve-spec  # speculative-decoding
                                         # break-even acceptance/topology
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _config(model, tiny):
    if model == "gpt":
        from paddle_tpu.models.gpt import GPTConfig
        return GPTConfig.tiny() if tiny else GPTConfig.small()
    if model == "bert":
        from paddle_tpu.models.bert import BertConfig
        return BertConfig.tiny() if tiny else BertConfig.base()
    if model == "ernie":
        from paddle_tpu.models.ernie import ErnieConfig
        return ErnieConfig.tiny() if tiny else ErnieConfig.base()
    if model == "transformer":
        from paddle_tpu.models.transformer import TransformerConfig
        return TransformerConfig.tiny() if tiny else TransformerConfig.big()
    raise SystemExit(f"unknown model {model!r}")


def selftest():
    """Fast host-math assertions over the planner stack (no jax import
    — stdlib only). Tier-1 runs this as a subprocess."""
    from paddle_tpu.parallel.autoplan import (
        MeshPlan, ModelSpec, Topology, layouts, search, train_flops)

    # factorization enumeration is exhaustive and exact
    f8 = search.factorizations(8)
    assert all(dp * tp * pp == 8 for dp, tp, pp in f8), f8
    assert (8, 1, 1) in f8 and (1, 8, 1) in f8 and (2, 2, 2) in f8
    assert len(f8) == len(set(f8))

    # LM layout table: the one source of truth answers the known rows
    t, _ = layouts.lm_layout(("tok_emb", "weight"), (50304, 64))
    assert t == ("tp", None), t
    t, _ = layouts.lm_layout(("out_proj", "weight"), (64, 50304))
    assert t == (None, "tp"), t
    t, reason = layouts.lm_layout(("out_proj", "weight"), (64, 50305),
                                  tp_size=4)
    assert t == (None, None) and "SKIPPED" in reason, (t, reason)

    # flop model scales linearly in tokens
    s1 = ModelSpec(name="x", vocab=1000, hidden=64, layers=2, heads=4,
                   intermediate=128, seq=32, batch=4)
    s2 = ModelSpec(name="x", vocab=1000, hidden=64, layers=2, heads=4,
                   intermediate=128, seq=32, batch=8)
    assert train_flops(s2) > 1.9 * train_flops(s1)

    # a huge-vocab model on a tiny-HBM chip must land on tp > 1, and the
    # pure-dp candidate must be pruned with a memory reason on record
    tight = Topology(name="tight4", num_chips=4, hbm_bytes=3 * 2 ** 30,
                     peak_flops=1e12, intra_bw=1e11, inter_bw=1e10)
    big = ModelSpec(name="big-vocab", vocab=512 * 1024, hidden=1024,
                    layers=4, heads=16, intermediate=4096, seq=128,
                    batch=8)
    p = search.plan(big, topology=tight, allow_pp=False)
    assert p.tp > 1, p.axes
    dp_only = next(c for c in p.candidates if c.dp == 4 and c.tp == 1)
    assert not dp_only.feasible and any(
        "HBM" in r or "GiB" in r for r in dp_only.reasons), dp_only.reasons

    # a tiny model on a roomy slice stays pure dp (simplest mesh wins)
    roomy = Topology(name="roomy8", num_chips=8, hbm_bytes=32 * 2 ** 30,
                     peak_flops=1e14, intra_bw=2e11, inter_bw=2.5e10)
    small = ModelSpec(name="tiny", vocab=1024, hidden=64, layers=2,
                      heads=4, intermediate=128, seq=32, batch=64)
    p2 = search.plan(small, topology=roomy, allow_pp=True)
    assert p2.axes == {"dp": 8}, p2.axes
    # pp never exceeds the layer count; the refusal is on record
    pp8 = next(c for c in p2.candidates if c.pp == 8)
    assert not pp8.feasible and any("layers" in r for r in pp8.reasons)

    # the whole decision record survives a JSON round-trip
    rt = MeshPlan.from_json(json.loads(p.dumps()))
    assert rt.axes == p.axes and len(rt.candidates) == len(p.candidates)
    return {"ok": True, "checks": 8}


def calibrate(model, batch, seq):
    """Analytic flops vs XLA's compile().cost_analysis() for a tiny
    value_and_grad train step on CPU."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel.autoplan import costmodel

    cfg = _config(model, tiny=True)
    cfg.dropout = 0.0
    rng = np.random.RandomState(0)
    if model == "gpt":
        from paddle_tpu.models.gpt import GPT
        m = GPT(cfg)
        v = m.init(jax.random.key(0))
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq))
                          .astype(np.int32))

        def step(p):
            return m.apply({"params": p, "state": {}}, ids, pad_id=0,
                           method="loss")
    elif model in ("bert", "ernie"):
        from paddle_tpu.models.bert import BertForPretraining
        from paddle_tpu.models.ernie import ErnieForPretraining
        m = (ErnieForPretraining if model == "ernie"
             else BertForPretraining)(cfg)
        v = m.init(jax.random.key(0))
        n_mask = max(1, int(0.15 * seq))
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq))
                          .astype(np.int32))
        pos = jnp.asarray(np.stack(
            [np.sort(rng.choice(seq, n_mask, replace=False))
             for _ in range(batch)]).astype(np.int32))
        mlm_l = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                        (batch, n_mask)).astype(np.int32))
        nsp_l = jnp.asarray(rng.randint(0, 2, (batch,)).astype(np.int32))
        mm = jnp.asarray(np.ones((batch, n_mask), dtype=np.float32))

        def step(p):
            return m.apply({"params": p, "state": {}}, ids, mlm_l, nsp_l,
                           mm, mask_positions=pos, method="loss")
    else:
        raise SystemExit(f"--calibrate supports gpt/bert/ernie, "
                         f"not {model!r}")
    jitted = jax.jit(jax.value_and_grad(step))
    spec = costmodel.ModelSpec.from_config(cfg, batch=batch, seq=seq,
                                           name=model)
    return costmodel.calibration_report(spec, jitted, v["params"])


def serve_spec_report(model, tiny, topology, spec_k, slots, context,
                      draft_tiny):
    """Price speculative decoding per topology: what acceptance rate a
    draft must clear before spec_k-token rounds beat plain decode, and
    the projected speedup at a few representative acceptance rates.
    Pure host math over costmodel.predict_decode — no jax import."""
    from paddle_tpu.parallel.autoplan import (
        ModelSpec, costmodel, get_topology)
    from paddle_tpu.parallel.autoplan.topology import PRESETS

    cfg = _config(model, tiny)
    spec = ModelSpec.from_config(cfg, batch=slots, seq=context,
                                 name=model)
    draft_spec = None
    if draft_tiny and not tiny:
        # a separate (smaller) draft model instead of self-draft:
        # price the tiny config of the same architecture
        draft_spec = ModelSpec.from_config(
            _config(model, tiny=True), batch=slots, seq=context,
            name=f"{model}-tiny")
    names = ([topology] if topology
             else [n for n in PRESETS if not n.startswith("cpu")
                   or n == "cpu4"])
    probes = (0.3, 0.5, 0.7, 0.9)
    rows = []
    for name in names:
        topo = get_topology(name)
        pred = costmodel.predict_decode(
            spec, topo, slots=slots, context=context, spec_k=spec_k,
            draft_spec=draft_spec)
        row = {
            "topology": name,
            "draft": pred["draft"],
            "spec_k": spec_k,
            "rate_source": pred["rate_source"],
            "draft_overhead": round(pred["draft_overhead"], 4),
            # flops break-even: >= 1.0 by construction (verify work is
            # real) — the energy story, kept for the record
            "break_even_accept_rate":
                round(pred["break_even_accept_rate"], 4),
            # roofline (wall-clock) break-even: the decision figure —
            # memory-bound decode amortizes the weight/KV stream over
            # the verify window
            "break_even_accept_rate_s":
                round(pred["break_even_accept_rate_s"], 4),
        }
        for r in probes:
            p = costmodel.predict_decode(
                spec, topo, slots=slots, context=context,
                spec_k=spec_k, draft_spec=draft_spec, accept_rate=r)
            row[f"speedup@{r}"] = round(p["speedup_vs_plain_s"], 3)
        rows.append(row)
    head = (f"{'topology':<12} {'draft':<10} {'break-even(t)':>13} "
            f"{'(flops)':>8} {'overhead':>9} "
            + " ".join(f"x@{r:<5}" for r in probes))
    print(head)
    print("-" * len(head))
    for row in rows:
        print(f"{row['topology']:<12} {row['draft']:<10} "
              f"{row['break_even_accept_rate_s']:>13.4f} "
              f"{row['break_even_accept_rate']:>8.4f} "
              f"{row['draft_overhead']:>9.4f} "
              + " ".join(f"{row[f'speedup@{r}']:<7.3f}"
                         for r in probes))
    return {"tool": "autoplan", "mode": "serve_spec", "model": model,
            "slots": slots, "context": context, "spec_k": spec_k,
            "rows": rows}


def main():
    ap = argparse.ArgumentParser(
        description="rank dp x tp x pp meshes for a model on a topology")
    ap.add_argument("--model", default="gpt",
                    choices=["gpt", "bert", "ernie", "transformer"])
    ap.add_argument("--topology", default=None,
                    help="preset name (cpu4, v4-8, v5e-16, 2xv5e-16 ...); "
                         "default: PT_FLAGS_autoplan_topology or live "
                         "jax.devices() detection")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default 16, tiny 8)")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default 512, tiny 64)")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--top", type=int, default=None,
                    help="show only the best N candidates")
    ap.add_argument("--json", action="store_true",
                    help="emit the full plan JSON (every candidate, every "
                         "prune reason) instead of the human table")
    ap.add_argument("--no-pp", action="store_true",
                    help="prune pipeline candidates (caller has no "
                         "pipeline executor)")
    ap.add_argument("--selftest", action="store_true",
                    help="host-math sanity assertions; prints {'ok': true}")
    ap.add_argument("--calibrate", action="store_true",
                    help="compare analytic flops vs XLA cost_analysis for "
                         "a tiny train step on CPU")
    ap.add_argument("--serve-spec", action="store_true",
                    help="speculative-decoding break-even acceptance "
                         "rate per topology (host math, no jax)")
    ap.add_argument("--spec-k", type=int, default=7,
                    help="draft tokens per speculation round")
    ap.add_argument("--slots", type=int, default=16,
                    help="decode slots priced (--serve-spec)")
    ap.add_argument("--context", type=int, default=None,
                    help="KV context length priced (--serve-spec; "
                         "default --seq)")
    ap.add_argument("--draft-tiny", action="store_true",
                    help="price a tiny-config draft model instead of "
                         "self-draft (--serve-spec)")
    args = ap.parse_args()

    if args.selftest:
        print(json.dumps(selftest()))
        return
    batch = args.batch or (8 if args.tiny else 16)
    seq = args.seq or (64 if args.tiny else 512)
    if args.serve_spec:
        out = serve_spec_report(
            args.model, args.tiny, args.topology, args.spec_k,
            args.slots, args.context or seq, args.draft_tiny)
        print(json.dumps(out))
        return
    if args.calibrate:
        out = calibrate(args.model, batch, seq)
        print(json.dumps(out))
        return

    from paddle_tpu.parallel.autoplan import (
        ModelSpec, get_topology, plan)
    cfg = _config(args.model, args.tiny)
    spec = ModelSpec.from_config(cfg, batch=batch, seq=seq,
                                 name=args.model)
    topo = get_topology(args.topology)
    p = plan(spec, topology=topo, allow_pp=not args.no_pp)
    if args.json:
        print(p.dumps(indent=2))
        return
    print(p.describe(top=args.top))
    print(json.dumps(p.summary()))


if __name__ == "__main__":
    main()
