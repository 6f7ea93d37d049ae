#!/usr/bin/env python
"""CI smoke: prove the fused train step jit-compiles — on the CPU backend.

``train_program`` builds one model's tiny train step in this process,
from the builders chip_smoke.py runs on the chip (``make_train_step``
over the model's ``.loss()`` binder, the bf16-policy Adam), and compiles
it for the CPU. This is the tier-1 guard for the step-fusion layer: the
chunked fused cross-entropy (custom VJP), the scan-over-layers + remat
encoders, and the fused add+LN path all have to lower and compile
inside one jitted train step — a regression in any of them trips here,
not in the next chip run (what the chip's compiler accepts is
tests/test_mosaic_compile.py's business).

With a mesh (``dp2,tp2``) it compiles the dp x tp GSPMD step on virtual
CPU devices, and ``sharded_vocab_check`` evaluates the model's CONTRACTS
row (paddle_tpu/analysis/contracts.py) against the compiled (post-SPMD,
per-device shapes) HLO: no [rows, V]-scale temporary, no all-gather of
the vocab-sharded projection weight, no f64, no host callback. The
fused run must be clean, and a ``fused_xent=False`` positive-control run
must trip the detector (proving the judge actually detects full-vocab
logits). This tool compiles; the contract engine judges.

Usage:
  python tools/compile_smoke.py --model gpt --tiny
  python tools/compile_smoke.py --model bert --tiny
  python tools/compile_smoke.py --model gpt --tiny --mesh dp2,tp2 --hlo-check
  python tools/compile_smoke.py --model gpt --autoplan cpu4
"""

import argparse
import contextlib
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:           # CLI use; in-suite runs already see it
    sys.path.insert(0, REPO)

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def want_cpu_devices(n):
    """Hold JAX to the CPU with ``n`` virtual devices. For a command's
    entry point: it only takes effect before the first ``import jax``
    (``train_program`` raises when the devices are not there)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEVICE_COUNT_FLAG)]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + [f"{_DEVICE_COUNT_FLAG}={n}"])


def _parse_mesh(spec):
    """'dp2,tp2' -> {"dp": 2, "tp": 2}; 'auto' and None pass through."""
    if spec is None or spec == "auto":
        return spec
    axes = {}
    for part in spec.split(","):
        m = re.fullmatch(r"(dp|tp)(\d+)", part.strip())
        if not m:
            raise ValueError(f"mesh {spec!r}: want dp and tp with "
                             "explicit sizes (e.g. dp2,tp2) or 'auto'")
        axes[m.group(1)] = int(m.group(2))
    return {"dp": 1, "tp": 1, **axes}


def _topology_devices(topology):
    m = re.fullmatch(r"(?:\d+x)?[a-z0-9]+?-?(\d+)", topology)
    if not m:
        raise SystemExit(f"unparseable topology {topology!r}")
    return int(m.group(1))


def _transformer_loss_fn(model, **loss_kwargs):
    def loss_fn(p, src, tgt_in, tgt_out):
        return model.apply({"params": p, "state": {}}, src, tgt_in, tgt_out,
                           method="loss", **loss_kwargs), 0.0
    return loss_fn


def _tiny_model(name, batch, seq, remat):
    """(model, its vocab-sharded dimension, loss binder, one host batch)
    at the tiny config, set up as the chip phases set theirs: no dropout,
    scan over layers, positions to cover ``seq``."""
    import numpy as np

    import chip_smoke
    if name == "transformer_big":
        from paddle_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        cfg = TransformerConfig.tiny()
        cfg.dropout, cfg.max_len = 0.0, max(cfg.max_len, seq)
        rng = np.random.RandomState(chip_smoke.SEED)
        return (Transformer(cfg), cfg.tgt_vocab, _transformer_loss_fn, tuple(
            rng.randint(1, vocab, (batch, seq)).astype(np.int32)
            for vocab in (cfg.src_vocab, cfg.tgt_vocab, cfg.tgt_vocab)))
    if name == "gpt":
        from paddle_tpu.models.gpt import GPT as cls, GPTConfig as cfg_cls
        binder, batches = chip_smoke.gpt_loss_fn, chip_smoke.gpt_batches
    else:
        from paddle_tpu.models.bert import (BertConfig as cfg_cls,
                                            BertForPretraining as cls)
        binder, batches = chip_smoke.bert_loss_fn, chip_smoke.bert_batches
    cfg = cfg_cls.tiny()
    cfg.dropout, cfg.scan_layers = 0.0, True
    cfg.max_position = max(cfg.max_position, seq)
    if remat:
        cfg.remat = remat
    return (cls(cfg), cfg.vocab_size, binder,
            batches(cfg, batch, seq, 1, chip_smoke.SEED)[0])


def train_program(model="gpt", mesh=None, remat=None, flags=None,
                  devices=None):
    """Compile ``model``'s tiny fused train step for the CPU, in this
    process, and return what the judges read: ``{"hlo": the compiled
    (per-device) module's text, "cost": its normalised cost analysis,
    "mesh": the resolved axes or None, "plan": the autoplan summary for
    mesh="auto"}``. Batch and sequence come from
    ``contracts.SHARDED_TRAIN_CASES[model]``; ``mesh`` is None, explicit
    sizes ('dp2,tp2') or 'auto' (the planner picks over ``devices``
    devices of the ``autoplan_topology`` flag's topology); ``remat``
    goes on the config; ``flags`` hold for the build only."""
    import jax

    import chip_smoke
    import paddle_tpu as pt
    from paddle_tpu.analysis import contracts as c
    case = c.SHARDED_TRAIN_CASES[model]
    axes = _parse_mesh(mesh)
    if axes == "auto":
        need = devices or len(jax.devices())
    else:
        need = math.prod(axes.values()) if axes else 1
    if need > len(jax.devices()):
        raise RuntimeError(
            f"mesh {mesh!r} needs {need} devices and this process has "
            f"{len(jax.devices())}: set XLA_FLAGS={_DEVICE_COUNT_FLAG}="
            f"{need} (and JAX_PLATFORMS=cpu) before jax is imported")
    devs = jax.devices()[:need]
    # one chunk for every train program, far below the contract table's
    # row thresholds
    with chip_smoke.flag_scope({"xent_chunk": 64, **(flags or {})}):
        net, vocab, loss_fn_of, batch = _tiny_model(
            model, case.batch, case.seq, remat)
        opt = chip_smoke._amp_optimizer()
        params = net.init(jax.random.key(chip_smoke.SEED))["params"]
        loss_kwargs, plan = {}, None
        if axes == "auto":
            from paddle_tpu.parallel import autoplan
            plan = autoplan.plan(
                autoplan.ModelSpec.from_config(
                    net.cfg, batch=case.batch, seq=case.seq),
                topology=autoplan.get_topology(), devices=need,
                allow_pp=False)
            axes = {k: int(v) for k, v in plan.axes.items()}
        if axes:
            dp, tp = axes.get("dp", 1), axes.get("tp", 1)
            if case.batch % dp or vocab % tp:
                raise ValueError(
                    f"mesh {axes}: train.{model}'s batch {case.batch} "
                    f"must divide over dp={dp} and its vocab {vocab} "
                    f"over tp={tp}")
            if plan:
                grid = plan.build_mesh(devs)
                params = plan.place(params)
            else:
                grid = pt.parallel.make_mesh(axes, devices=devs)
                params = pt.parallel.tp_lm_sharding(grid, params)
            loss_kwargs = {"vocab_axis": "tp" if tp > 1 else None,
                           "batch_axis": "dp" if dp > 1 else None,
                           "mesh": grid}
            batch = pt.parallel.shard_batch(grid, batch)
        state = {"params": params, "opt": opt.init(params)}
        step = jax.jit(chip_smoke.make_train_step(
            opt, loss_fn_of(net, **loss_kwargs)), donate_argnums=(0,))
        with grid if axes else contextlib.nullcontext():
            compiled = step.lower(state, *batch).compile()
    return {"hlo": compiled.as_text(),
            "cost": c.normalize_cost(compiled.cost_analysis()),
            "mesh": axes, "plan": plan.summary() if plan else None}


def dense_score_temporaries(hlo_text, tmax, min_rows):
    """f32/bf16 temporaries spanning the PADDED slot capacity Tmax —
    the gathered-dense K/V or score tensor the paged Pallas decode path
    must never materialize. Thin caller of the NoTemporary contract."""
    from paddle_tpu.analysis import contracts as c
    return c.NoTemporary({tmax}, min_rows).temporaries(hlo_text)


def sharded_vocab_check(model="gpt", mesh="dp2,tp2", positive_control=True,
                        update_snapshots=False):
    """Compile the dp x tp fused train step and evaluate the model's
    full CONTRACTS row (no [rows, V] temporary, no vocab-weight
    all-gather, no f64, no host callback, and — where the row carries
    budget contracts — the XLA cost_analysis flops/bytes priced against
    the autoplan cost model) against its per-device HLO; optionally also
    compile the ``fused_xent=False`` reference step and require the
    row's NoTemporary detector to TRIP on it (positive control). The
    budget detectors get their own positive control: at tolerance=0
    every real compile must exceed a zero budget. When the row has a
    registered HloSnapshot the compiled op histogram is judged against
    the blessed record too (``update_snapshots=True`` re-blesses
    instead)."""
    from paddle_tpu.analysis import contracts as c
    name = f"train.{model}@{mesh}"
    row = c.CONTRACTS[name]
    prog = train_program(model, mesh=mesh)
    ctx = c.ContractContext(hlo_text=prog["hlo"], cost=prog["cost"])
    violations = c.evaluate(row, ctx)
    out = {"model": model, "mesh": prog["mesh"], "cost": prog["cost"]}
    snap = c.CONTRACT_SNAPSHOTS.get(name)
    if snap is not None:
        if update_snapshots:
            out["snapshot_blessed"] = snap.bless(prog["hlo"])["hash"]
        else:
            violations += snap.violations(ctx)
    out.update(violations=[v.format() for v in violations],
               clean=not violations)
    if positive_control:
        detector = next(r for r in row if isinstance(r, c.NoTemporary))
        ref = train_program(model, mesh=mesh, flags={"fused_xent": False})
        out["positive_control_trips"] = bool(
            detector.temporaries(ref["hlo"]))
        budgets = [b for b in row if isinstance(b, c.MaxHloCost)]
        if budgets and prog["cost"] is not None:
            out["budget_control_trips"] = all(
                b.with_tolerance(0).check(ctx) for b in budgets)
    return out


def autoplan_check(model="gpt", topology="cpu4"):
    """Compile the train step on the mesh the autoplan search resolves
    from the named topology, on virtual CPU devices, and evaluate the
    model's ``train.<model>@auto`` CONTRACTS row against the compiled
    per-device HLO. The acceptance gate for the planner: its winning
    mesh must not just compile, it must compile CLEAN under the same
    NoTemporary/no-vocab-all-gather judgments as the hand-picked
    dp2,tp2 row."""
    from paddle_tpu.analysis import contracts as c
    devices = _topology_devices(topology)
    prog = train_program(model, mesh="auto", devices=devices,
                         flags={"autoplan_topology": topology})
    violations = c.evaluate(c.CONTRACTS[f"train.{model}@auto"],
                            c.ContractContext(hlo_text=prog["hlo"]))
    return {"model": model, "topology": topology, "devices": devices,
            "mesh": prog["mesh"], "plan": prog["plan"],
            "violations": [v.format() for v in violations],
            "clean": not violations}


# serve-probe shapes: every dim distinct from TMAX=48 (vocab 512, hidden
# 64, ffn 128, heads 4, hd 16, page 8, pages 13, slots 2, prefill 16) so
# the detector can key on the padded slot capacity alone. min_rows=8
# catches even the [S, H, 1, Tmax] score row of the dense fallback.
# Canonical values live with the contract table.
def _serve_dims():
    from paddle_tpu.analysis import contracts as c
    return c.SERVE_TMAX, c.SERVE_MIN_ROWS


def _serve_engine(num_pages=13, num_slots=2, **cfg_kw):
    import jax
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.serving import ServeConfig, ServingEngine
    cfg = GPTConfig.tiny()
    cfg.dropout = 0.0
    cfg.use_flash = False
    model = GPTDecoder(cfg)
    variables = model.init(jax.random.key(0))
    tmax, _ = _serve_dims()
    sc = ServeConfig(num_slots=num_slots, page_size=8, max_len=tmax,
                     prefill_len=16, num_pages=num_pages, **cfg_kw)
    return model, variables, ServingEngine(model, variables, sc)


def serve_smoke(positive_control=True, update_snapshots=False):
    """Tier-1 contract for the serving fast path, in-process on CPU:

    1. Trace-count probe: mixed-length admission waves through a
       2-slot engine must leave the jitted serve step traced exactly
       ONCE (continuous batching never retraces — the shapes are
       slot-fixed, only values change).
    2. HLO contract: with paging on and the Pallas decode kernel
       engaged (interpret mode off-TPU), the compiled serve step holds
       no [rows, Tmax]-dense gathered-K/V or score temporary; the XLA
       gather-and-mask fallback (use_pallas_decode=0) must TRIP the
       detector (positive control — proves the grep sees dense decode
       attention).
    3. Budget + snapshot gates: the decode step's cost_analysis flops
       and bytes stay under the costmodel.predict_decode budgets (with
       a tolerance=0 positive control), and its op histogram matches
       the blessed serve.decode snapshot (``update_snapshots=True``
       re-blesses instead).
    4. Quantized-KV leg: the same waves through a serve_kv_dtype=int8
       engine must stay traced-once and clean against the
       serve.decode@int8 row — no f32 tensor at page-pool scale (the
       dequant lives inside the kernel's tiles), byte budget re-derived
       from predict_decode(kv_dtype=int8), its own snapshot — while the
       f32 engine's compile TRIPS the KV detector (positive control:
       its pool is exactly the wide-KV tensor the row forbids).
    5. Speculative leg: the waves through a 16-slot self-draft engine
       (spec_k=7) with one injected spec.verify degrade must leave all
       FIVE entry points traced exactly once, emit > 1 token per
       target step, and compile a verify module clean against the
       serve.verify row — budgets from predict_decode(spec_k=...), no
       dense [slots, window, vocab] logits lattice (per-position head),
       its own snapshot. Positive controls: a literal dense-lattice
       einsum trips the detector, and the speculation-off engine trips
       the row's TracedOnce.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from paddle_tpu.core.flags import all_flags, set_flags

    from paddle_tpu.analysis import contracts as c
    tmax, min_rows = _serve_dims()
    out = {}
    saved = all_flags()
    try:
        set_flags({"pallas_interpret": True, "use_pallas_decode": True})
        _, _, engine = _serve_engine()
        # admission waves of ragged prompts through 2 slots: every
        # admission lands in a freed slot mid-run. The 40-token prompt
        # exceeds prefill_len=16 — chunked prefill admits it as three
        # calls of the SAME prefill trace (the traced-once assertion
        # below covers it). Sampling knobs are deliberately MIXED —
        # greedy, temperature, top-k, top-p, and a pinned seed in one
        # batch — because they ride as traced [slots] values, not
        # retrace axes
        waves = [
            (3, 7, {}), (9, 5, dict(temperature=0.8)),
            (16, 6, dict(temperature=0.9, top_k=5)),
            (40, 6, {}), (5, 9, dict(temperature=0.7, top_p=0.9)),
            (12, 4, dict(temperature=1.0, top_k=8, top_p=0.95)),
            (2, 8, dict(temperature=0.6, seed=123))]

        def _drive(eng):
            rng = np.random.RandomState(0)
            for plen, mn, kw in waves:
                eng.submit(rng.randint(0, 512, (plen,), dtype=np.int32),
                           max_new=mn, **kw)
            return eng.drain()

        done = _drive(engine)
        out["finished"] = len(done)
        out["decode_traces"] = engine.decode_traces
        out["prefill_traces"] = engine.prefill_traces
        out["traced_once"] = (engine.decode_traces == 1
                              and engine.prefill_traces == 1)

        compiled = engine.compiled_decode()
        hlo = compiled.as_text()
        try:
            cost = c.normalize_cost(compiled.cost_analysis())
        except Exception:
            cost = None
        ctx = c.ContractContext(
            hlo_text=hlo, cost=cost,
            trace_counts={"serve.decode": engine.decode_traces,
                          "serve.prefill": engine.prefill_traces})
        violations = c.evaluate(c.CONTRACTS["serve.decode"]
                                + c.CONTRACTS["serve.prefill"], ctx)
        snap = c.CONTRACT_SNAPSHOTS["serve.decode"]
        if update_snapshots:
            out["snapshot_blessed"] = snap.bless(hlo)["hash"]
        else:
            violations += snap.violations(ctx)
        out["dense_temporaries"] = dense_score_temporaries(
            hlo, tmax, min_rows)
        out["cost"] = cost
        out["violations"] = [v.format() for v in violations]
        out["clean"] = not violations

        # --- quantized-KV leg: the same waves through an int8 pool ----
        # (run before the positive controls flip the pallas flags off)
        _, _, qeng = _serve_engine(kv_dtype="int8")
        _drive(qeng)
        q_compiled = qeng.compiled_decode()
        q_hlo = q_compiled.as_text()
        try:
            q_cost = c.normalize_cost(q_compiled.cost_analysis())
        except Exception:
            q_cost = None
        q_ctx = c.ContractContext(
            hlo_text=q_hlo, cost=q_cost,
            trace_counts={"serve.decode": qeng.decode_traces,
                          "serve.prefill": qeng.prefill_traces})
        q_viol = c.evaluate(c.CONTRACTS["serve.decode@int8"], q_ctx)
        q_snap = c.CONTRACT_SNAPSHOTS["serve.decode@int8"]
        if update_snapshots:
            out["int8_snapshot_blessed"] = q_snap.bless(q_hlo)["hash"]
        else:
            q_viol += q_snap.violations(q_ctx)
        out["int8_kv_pool_bytes"] = qeng.kv_pool_bytes()
        out["f32_kv_pool_bytes"] = engine.kv_pool_bytes()
        out["int8_cost"] = q_cost
        out["int8_violations"] = [v.format() for v in q_viol]
        out["int8_clean"] = not q_viol
        # positive control for the KV detector: the f32 engine's page
        # pool IS the KV-layout-scale f32 tensor the int8 row forbids,
        # so judging the f32 compile with it must trip
        kvdet = next(r for r in c.CONTRACTS["serve.decode@int8"]
                     if isinstance(r, c.NoKvDequantTemporary))
        out["kv_control_trips"] = bool(kvdet.temporaries(hlo))

        # --- speculative leg: the same waves through a self-draft ------
        # engine wide enough that slots x window = 128 rows clears the
        # verify row's MIN_ROWS=96 — a dense [slots, window, vocab]
        # logits lattice cannot hide under the weight allowance. One
        # injected spec.verify fault degrades one round to plain decode,
        # so all five entry points (decode, prefill, draft,
        # draft-prefill, verify) earn their traced-once counts in a
        # single drive.
        import jax
        import jax.numpy as jnp
        from paddle_tpu.testing import chaos as _chaos
        vs, vk = c.SERVE_VERIFY_SLOTS, c.SERVE_VERIFY_SPEC_K
        _, _, veng = _serve_engine(num_pages=c.SERVE_VERIFY_PAGES,
                                   num_slots=vs, draft=True, spec_k=vk)
        plan = _chaos.FaultPlan().fail("fault_point",
                                       path=r"spec\.verify")
        with _chaos.active(plan):
            _drive(veng)
        out["spec_fault_degrades"] = plan.fired()
        st = veng.spec_stats()
        out["spec_stats"] = st
        out["spec_traced_once"] = (
            veng.decode_traces == 1 and veng.prefill_traces == 1
            and veng.draft_traces == 1
            and veng.draft_prefill_traces == 1
            and veng.verify_traces == 1)
        out["spec_wins"] = bool(
            st["tokens_per_target_step"] is not None
            and st["tokens_per_target_step"] > 1.0)
        v_compiled = veng.compiled_verify()
        v_hlo = v_compiled.as_text()
        try:
            v_cost = c.normalize_cost(v_compiled.cost_analysis())
        except Exception:
            v_cost = None
        v_ctx = c.ContractContext(
            hlo_text=v_hlo, cost=v_cost,
            trace_counts={"serve.decode": veng.decode_traces,
                          "serve.draft": veng.draft_traces,
                          "serve.verify": veng.verify_traces})
        v_viol = c.evaluate(c.CONTRACTS["serve.verify"], v_ctx)
        v_snap = c.CONTRACT_SNAPSHOTS["serve.verify"]
        if update_snapshots:
            out["verify_snapshot_blessed"] = v_snap.bless(v_hlo)["hash"]
        else:
            v_viol += v_snap.violations(v_ctx)
        out["verify_cost"] = v_cost
        out["verify_violations"] = [v.format() for v in v_viol]
        out["verify_clean"] = not v_viol
        # lattice positive control: compile the dense [slots, window,
        # vocab] logits stack the per-position head avoids — the
        # detector must trip on it
        latdet = next(r for r in c.CONTRACTS["serve.verify"]
                      if isinstance(r, c.NoTemporary))
        lat_hlo = jax.jit(
            lambda h, e: jnp.einsum("swh,vh->swv", h, e)).lower(
                np.zeros((vs, vk + 1, 64), np.float32),
                np.zeros((512, 64), np.float32)).compile().as_text()
        out["lattice_control_trips"] = bool(latdet.temporaries(lat_hlo))
        # speculation-off positive control: judging the plain engine
        # against the verify row must trip TracedOnce (no draft/verify
        # counts exist there — proves the probe is not vacuous)
        off_trips = c.evaluate(
            [r for r in c.CONTRACTS["serve.verify"]
             if isinstance(r, c.TracedOnce)],
            c.ContractContext(
                hlo_text=hlo, cost=cost,
                trace_counts={"serve.decode": engine.decode_traces,
                              "serve.prefill": engine.prefill_traces}))
        out["spec_off_control_trips"] = bool(off_trips)

        if positive_control:
            budgets = [b for b in c.CONTRACTS["serve.decode"]
                       if isinstance(b, c.MaxHloCost)]
            if budgets and cost is not None:
                out["budget_control_trips"] = all(
                    b.with_tolerance(0).check(ctx) for b in budgets)
            v_budgets = [b for b in c.CONTRACTS["serve.verify"]
                         if isinstance(b, c.MaxHloCost)]
            if v_budgets and v_cost is not None:
                out["verify_budget_control_trips"] = all(
                    b.with_tolerance(0).check(v_ctx) for b in v_budgets)
            set_flags({"use_pallas_decode": False})
            _, _, ref_engine = _serve_engine()
            ref_hlo = ref_engine.compiled_decode().as_text()
            ref_temps = dense_score_temporaries(ref_hlo, tmax, min_rows)
            out["positive_control_trips"] = bool(ref_temps)
            # retrace positive control: widening the page table by one
            # column IS a shape leak, so calling the decode jit with it
            # must register as a retrace and trip the TracedOnce row
            # (proves the probe sees real retraces, including any the
            # per-request sampling args could have introduced)
            s = engine.cfg.num_slots
            wide = np.concatenate(
                [engine._page_table,
                 np.zeros((s, 1), engine._page_table.dtype)], axis=1)
            _, (engine._caches, engine._state) = engine._decode_jit(
                engine._params, (engine._caches, engine._state),
                np.zeros(s, np.int32),
                wide, np.zeros(s, np.int32), np.zeros(s, bool),
                np.zeros(s, np.float32), np.zeros(s, np.int32),
                np.zeros(s, np.float32), np.zeros(s, np.uint32),
                np.zeros(s, np.int32))
            ctx_re = c.ContractContext(
                hlo_text=hlo, cost=cost,
                trace_counts={"serve.decode": engine.decode_traces,
                              "serve.prefill": engine.prefill_traces})
            tripped = c.evaluate(
                [r for r in c.CONTRACTS["serve.decode"]
                 if isinstance(r, c.TracedOnce)], ctx_re)
            out["retrace_control_trips"] = bool(tripped)
    finally:
        set_flags(saved)
    out["ok"] = bool(out.get("traced_once") and out.get("clean")
                     and out.get("int8_clean")
                     and out.get("kv_control_trips")
                     and out.get("spec_traced_once")
                     and out.get("spec_wins")
                     and out.get("verify_clean")
                     and out.get("lattice_control_trips")
                     and out.get("spec_off_control_trips")
                     and out.get("spec_fault_degrades") == 1
                     and out.get("positive_control_trips",
                                 not positive_control)
                     and out.get("retrace_control_trips",
                                 not positive_control))
    return out


def mlp_smoke(positive_control=True):
    """Tier-1 contract for the fused GLU/MLP kernel, in-process on CPU:

    with the Pallas path engaged (interpret mode off-TPU), the compiled
    forward holds no [rows, 4H] activation temporary — the kernel
    streams I-axis tiles through a [block_rows, H] accumulator. The
    unfused composition (use_pallas_mlp=0) must TRIP the detector
    (positive control — proves the grep sees the materialized
    activation). Both the plain MLP and the gated (GLU) variant run
    under the same judgment.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.core.flags import all_flags, set_flags

    from paddle_tpu.analysis import contracts as c
    rows, h, inter = c.MLP_ROWS, c.MLP_HIDDEN, c.MLP_INTER
    rng = np.random.RandomState(0)

    def arr(*s):
        return jnp.asarray(0.02 * rng.randn(*s), jnp.float32)

    x = arr(rows, h)
    mlp_args = (x, arr(h, inter), arr(inter), arr(inter, h), arr(h))
    glu_args = mlp_args + (arr(h, inter), arr(inter))
    detector = c.NoTemporary({inter}, c.MLP_MIN_ROWS)

    def _hlo(*a):
        # fresh jit per flag state: use_pallas_mlp is read at trace time
        from paddle_tpu.ops.pallas.mlp import fused_mlp
        return (jax.jit(lambda *b: fused_mlp(*b))
                .lower(*a).compile().as_text())

    out = {"rows": rows, "hidden": h, "inter": inter}
    saved = all_flags()
    try:
        set_flags({"pallas_interpret": True, "use_pallas_mlp": True})
        violations = []
        for name, a in (("mlp", mlp_args), ("glu", glu_args)):
            hlo = _hlo(*a)
            out[f"{name}_temporaries"] = detector.temporaries(hlo)
            violations += c.evaluate(c.CONTRACTS["mlp.fused"],
                                     c.ContractContext(hlo_text=hlo))
        out["violations"] = [v.format() for v in violations]
        out["clean"] = not violations
        if positive_control:
            set_flags({"use_pallas_mlp": False})
            ref_temps = detector.temporaries(_hlo(*glu_args))
            out["positive_control_trips"] = bool(ref_temps)
    finally:
        set_flags(saved)
    out["ok"] = bool(out.get("clean")
                     and out.get("positive_control_trips",
                                 not positive_control))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt",
                    choices=["gpt", "bert", "transformer_big"])
    ap.add_argument("--tiny", action="store_true",
                    help="accepted for older command lines: every train "
                         "program here is the tiny config")
    ap.add_argument("--mesh", default=None,
                    help="compile the dp x tp sharded step on virtual CPU "
                         "devices, e.g. dp2,tp2")
    ap.add_argument("--hlo-check", action="store_true",
                    help="with --mesh: enforce the sharded-HLO contract "
                         "(no [rows, V] temporary, no vocab-weight "
                         "all-gather) with a positive control")
    ap.add_argument("--autoplan", metavar="TOPOLOGY", default=None,
                    help="autoplan probe: let the planner resolve the "
                         "mesh on the named topology (e.g. cpu4) and "
                         "enforce the train.<model>@auto HLO contract")
    ap.add_argument("--mlp", action="store_true",
                    help="fused GLU/MLP probe: the compiled forward "
                         "holds no [rows, 4H] activation temporary "
                         "(positive control included)")
    ap.add_argument("--serve", action="store_true",
                    help="serving fast-path probe: the jitted serve step "
                         "compiles once across admissions and its paged "
                         "HLO holds no [rows, Tmax]-dense attention "
                         "temporary (positive control included)")
    args = ap.parse_args()
    if args.autoplan:
        want_cpu_devices(_topology_devices(args.autoplan))
        out = autoplan_check(args.model, args.autoplan)
        print(json.dumps(out))
        if not out["clean"]:
            raise SystemExit("autoplan-mesh HLO contract violated")
        return
    if args.mlp:
        out = mlp_smoke()
        print(json.dumps(out))
        if not out["ok"]:
            raise SystemExit("fused-MLP contract violated")
        return
    if args.serve:
        out = serve_smoke()
        print(json.dumps(out))
        if not out["ok"]:
            raise SystemExit("serve-step contract violated")
        return
    if args.mesh == "auto":
        raise SystemExit("--mesh auto: say --autoplan TOPOLOGY")
    want_cpu_devices(math.prod((_parse_mesh(args.mesh) or {}).values()))
    if args.hlo_check:
        if not args.mesh:
            raise SystemExit("--hlo-check needs --mesh")
        out = sharded_vocab_check(args.model, args.mesh)
        print(json.dumps(out))
        if not out["clean"] or not out.get("positive_control_trips", True):
            raise SystemExit("sharded-HLO contract violated")
        return
    prog = train_program(args.model, mesh=args.mesh)
    print(json.dumps({"model": args.model, "compiled": True,
                      "mesh": prog["mesh"],
                      "flops": prog["cost"]["flops"],
                      "bytes accessed": prog["cost"]["bytes accessed"]}))


if __name__ == "__main__":
    main()
