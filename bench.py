"""Benchmark rows — one model's training or serving throughput.

    python bench.py --model bert          # ONE row, in this process
    python bench.py --model all           # suite: one child per row

A single row runs in-process and prints ONE JSON line: {"metric": ...,
"value": N, "unit": ..., "device": {"platform", "kind", "count"}, ...}.
A row that fails prints a ``bench_failed`` line with the error and the
process exits non-zero. Suite mode is a parent that never imports JAX —
a chip belongs to one process at a time — running one child per row,
and it exits non-zero if any row failed.

Every row names the device it ran on; ``mfu`` is null on a device with
no published peak (the CPU), never computed against another chip's.
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np


def _mfu(flops_per_step, step_s):
    """Achieved fraction of the chip's published peak, rounded for the
    row, or None where there is no peak (observability/perf.py)."""
    from paddle_tpu.observability.perf import mfu
    m = mfu(flops_per_step, step_s)
    return None if m is None else round(m, 4)


def _cost_flops(jitted, *args):
    from paddle_tpu.observability.perf import cost_flops
    return cost_flops(jitted, *args)


COMPILE_ONLY = False
TINY = False
DUMP_HLO = None    # --dump-hlo: write the compiled (post-SPMD) HLO text
MESH_AXES = None   # --mesh: {"dp": 2, "tp": 2} parsed from "dp2,tp2",
                   # or the string "auto" until the planner resolves it
AUTO_PLAN = None   # --mesh auto: the winning autoplan MeshPlan
DP_COLLECTIVE = None   # dp>1 mesh rows: {"dp_collective", "dp_wire_bytes"}
RUN_LOG = None     # --run-log: RunLog streaming per-step bench records


def _kv_dtype_env():
    """PT_BENCH_KV_DTYPE=int8 stores the serve benches' paged KV
    quantized (the serve_kv_dtype flag's bench knob); default f32."""
    v = os.environ.get("PT_BENCH_KV_DTYPE", "").strip().lower()
    return "int8" if v == "int8" else None


def _quant_clamps():
    """Cumulative quant.overflow_clamps counter — int8 values pinned at
    the rail by a quantized write/collective (0 in a healthy run)."""
    from paddle_tpu.observability import metrics as _metrics
    return int(_metrics.counter("quant.overflow_clamps").total())


def _parse_mesh(spec):
    """"dp2,tp2" -> {"dp": 2, "tp": 2}. A bare trailing-digit-less axis
    means: the FIRST such axis takes the remaining devices (-1), later
    ones default to 2 — so "--mesh dp,tp" reads as dp x tp=2. "auto"
    defers to the autoplan cost-model search at model-setup time."""
    if not spec:
        return None
    if spec.strip().lower() == "auto":
        return "auto"
    import re
    axes = {}
    first_bare = True
    for part in spec.split(","):
        m = re.fullmatch(r"([a-z]+)(\d*)", part.strip())
        if not m:
            raise SystemExit(f"--mesh: cannot parse {part!r} "
                             "(want e.g. dp2,tp2)")
        name, size = m.group(1), m.group(2)
        if size:
            axes[name] = int(size)
        else:
            axes[name] = -1 if first_bare else 2
            first_bare = False
    return axes


def _mesh_setup(params, opt, cfg_vocab, batch, cfg=None, seq=None):
    """Build the dp x tp mesh, shard params with the Megatron-flavored LM
    plan (vocab-dim embedding/projection over tp), and return everything
    the sharded step needs. Returns (mesh, params, opt_state, vocab_axis,
    batch_axis, batch) — batch rounded up to a dp multiple.

    --mesh auto: the autoplan cost-model search picks the factorization
    (pipeline candidates pruned — this train step has no pipeline
    executor) and its MeshPlan emits the param shardings through the
    DistributionPlanner layer; the plan lands in the JSON row."""
    global MESH_AXES, AUTO_PLAN, DP_COLLECTIVE
    import jax
    import paddle_tpu as pt
    if MESH_AXES == "auto":
        from paddle_tpu.parallel import autoplan
        spec = autoplan.ModelSpec.from_config(cfg, batch=batch, seq=seq)
        plan = autoplan.plan(spec, topology=autoplan.get_topology(),
                             devices=len(jax.devices()), allow_pp=False)
        AUTO_PLAN = plan
        MESH_AXES = {k: int(v) for k, v in plan.axes.items()}
        print(f"--mesh auto: {plan.reason}", file=sys.stderr)
        mesh = plan.build_mesh()
        params = plan.place(params)
    else:
        mesh = pt.parallel.make_mesh(dict(MESH_AXES))
        MESH_AXES.update({k: int(v) for k, v in mesh.shape.items()})
        params = pt.parallel.tp_lm_sharding(mesh, params)
    dp = mesh.shape.get("dp", 1)
    tp = mesh.shape.get("tp", 1)
    if dp > 1 and cfg is not None:
        # record the dp gradient-exchange strategy + bytes on the wire
        # for this mesh (the same resolution/pricing the planner and
        # runtime use), so dp>1 train rows carry the collective choice
        from paddle_tpu.parallel import autoplan as _ap
        from paddle_tpu.parallel import communicator as _comm
        from paddle_tpu.parallel.autoplan import costmodel as _cm
        topo = _ap.get_topology()
        strat = ("int8" if _comm.resolve_quant_allreduce(
            crosses_slices=topo.num_slices > 1) else "f32")
        spec = _ap.ModelSpec.from_config(cfg, batch=batch, seq=seq)
        DP_COLLECTIVE = {
            "dp_collective": strat,
            "dp_wire_bytes": _cm.collective_bytes(
                spec, dp, tp, 1, dp_collective=strat)["dp"],
        }
    batch = ((batch + dp - 1) // dp) * dp
    opt_state = opt.init(params)
    vocab_axis = "tp" if tp > 1 and cfg_vocab % tp == 0 else None
    if tp > 1 and cfg_vocab % tp:
        print(f"--mesh: vocab {cfg_vocab} not divisible by tp={tp}; "
              "fused xent runs unsharded", file=sys.stderr)
    batch_axis = "dp" if dp > 1 else None
    return mesh, params, opt_state, vocab_axis, batch_axis, batch


def _mesh_ctx(mesh):
    import contextlib
    return mesh if mesh is not None else contextlib.nullcontext()


def _mesh_row(row):
    if MESH_AXES and MESH_AXES != "auto":
        row["mesh"] = dict(MESH_AXES)
    if AUTO_PLAN is not None:
        row["autoplan"] = AUTO_PLAN.summary()
    if DP_COLLECTIVE is not None:
        row.update(DP_COLLECTIVE)
    return row


def _scan_env(cfg):
    """Step-fusion defaults for the transformer-family benches:
    scan-over-layers on (PT_BENCH_SCAN=0 restores unrolled), remat policy
    from PT_BENCH_REMAT (else the remat_policy flag)."""
    cfg.scan_layers = os.environ.get("PT_BENCH_SCAN", "1") == "1"
    remat = os.environ.get("PT_BENCH_REMAT", "").strip()
    if remat:
        cfg.remat = remat
    return cfg


def _co(name, jitted, *args):
    """--compile-only: compile the step (populating the persistent XLA
    cache so later bench runs start executing immediately) and stop.
    --dump-hlo additionally writes the compiled
    (post-SPMD-partitioning, per-device shapes) HLO text — what
    tools/compile_smoke.py greps for full-vocab-scale temporaries."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    row = {"metric": f"{name}_compile_only", "value": 1.0,
           "unit": "compiled", "vs_baseline": 0.0,
           "compile_s": round(time.perf_counter() - t0, 1)}
    if DUMP_HLO:
        with open(DUMP_HLO, "w") as f:
            f.write(compiled.as_text())
        row["hlo"] = DUMP_HLO
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            # XLA's own pricing of the compiled module — what the
            # MaxHloFlops/MaxHloBytes budget contracts judge against
            with open(DUMP_HLO + ".cost.json", "w") as f:
                json.dump({k: float(v) for k, v in ca.items()}, f)
            row["cost"] = DUMP_HLO + ".cost.json"
    return _mesh_row(row)


def _timed_steps(step_once, steps, tokens_per_step=None):
    """Mean wall time per step over ``steps`` steps, the last one waited
    for (``block_until_ready``): JAX returns before the device finishes,
    and each step consumes the one before it, so the device runs them
    back to back and the wait at the end covers them all.

    Side channel: each step's host-visible dispatch time feeds the
    `bench.step_time_s` histogram (p50/p95 land in the row's `telemetry`
    field) and, under --run-log, a per-step RunLog record — dispatch
    wall, not device time, but enough to see stragglers."""
    import jax
    from paddle_tpu.observability import metrics as _metrics
    hist = _metrics.histogram("bench.step_time_s")

    def run(n, first_step):
        t0 = time.perf_counter()
        out = None
        for i in range(n):
            s0 = time.perf_counter()
            out = step_once()
            dt_s = time.perf_counter() - s0
            hist.observe(dt_s)
            if RUN_LOG is not None:
                rec = {"phase": "bench", "step": first_step + i,
                       "wall_s": dt_s}
                if tokens_per_step:
                    # decode rows: each "step" emits a whole generation
                    # burst, so the record carries its own tokens/s
                    rec["tokens"] = tokens_per_step
                    rec["tokens_per_s"] = round(tokens_per_step
                                                / max(dt_s, 1e-9), 1)
                RUN_LOG.write(rec)
        out = jax.block_until_ready(out)
        return time.perf_counter() - t0, float(out)

    total, lv = run(steps, 1)
    prof_dir = os.environ.get("PT_BENCH_PROFILE")
    if prof_dir:
        # one-shot per-fusion breakdown, in a window of its own:
        # PT_BENCH_PROFILE=/tmp/prof python bench.py ...
        from paddle_tpu.profiler import trace_op_table
        with jax.profiler.trace(prof_dir):
            run(steps, steps + 1)
        rows = trace_op_table(prof_dir, steps=steps, top=25)
        if not rows:  # CPU run: the device lane is named differently
            rows = trace_op_table(prof_dir, device_filter="CPU",
                                  steps=steps, top=25)
        for row in rows:
            print(f"PROF {row['per_step_us']:>10.1f}us "
                  f"x{row['count']:>4} {row['name'][:90]}",
                  file=sys.stderr)
    return max(total, 1e-9) / steps, lv


def bench_bert(steps, batch, seq, use_flash=False):
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    cfg = BertConfig.tiny() if TINY else BertConfig.base()
    return _bench_mlm(BertForPretraining, cfg, "bert_base", steps, batch,
                      seq, use_flash)


def bench_ernie(steps, batch, seq, use_flash=False):
    """ERNIE 1.0 pretraining step (a BASELINE.json target row).
    Architecturally BERT-base with knowledge masking; the training step is the same
    MXU-dominated MLM+NSP compute, so it shares the harness."""
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining
    cfg = ErnieConfig.tiny() if TINY else ErnieConfig.base()
    return _bench_mlm(ErnieForPretraining, cfg, "ernie_1.0", steps, batch,
                      seq, use_flash)


def _bench_mlm(model_cls, cfg, name, steps, batch, seq, use_flash=False):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    cfg.dropout = 0.0  # bench the compute path
    cfg.use_flash = use_flash
    cfg.max_position = max(cfg.max_position, seq)
    _scan_env(cfg)
    model = model_cls(cfg)
    variables = model.init(jax.random.key(0))
    params = variables["params"]

    policy = pt.amp.bf16_policy()
    opt = pt.amp.decorate(pt.optimizer.Adam(1e-4), policy)
    mesh = vocab_axis = batch_axis = None
    if MESH_AXES:
        mesh, params, opt_state, vocab_axis, batch_axis, batch = \
            _mesh_setup(params, opt, cfg.vocab_size, batch, cfg=cfg,
                        seq=seq)
    else:
        opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,), dtype=np.int32))
    # Masked-position gather (reference parity: the recipe gathers mask_pos
    # before the vocab fc). PT_BENCH_FULL_MLM=1 restores the all-positions
    # head for A/B.
    full_mlm = os.environ.get("PT_BENCH_FULL_MLM", "0") == "1"
    if full_mlm:
        mask_pos = None
        mlm_labels = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
        mask = jnp.asarray((rng.rand(batch, seq) < 0.15).astype(np.float32))
    else:
        n_mask = max(1, int(0.15 * seq))
        mask_pos = jnp.asarray(np.stack([
            np.sort(rng.choice(seq, n_mask, replace=False))
            for _ in range(batch)]).astype(np.int32))
        mlm_labels = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (batch, n_mask), dtype=np.int32))
        mask = jnp.ones((batch, n_mask), jnp.float32)

    if mesh is not None:
        # dp-shard the host batch; the sharded train state keeps its
        # donate_argnums (donation works per-shard under pjit/GSPMD)
        ids, mlm_labels, nsp_labels, mask = (
            pt.parallel.shard_batch(mesh, x) for x in (
                ids, mlm_labels, nsp_labels, mask))
        if mask_pos is not None:
            mask_pos = pt.parallel.shard_batch(mesh, mask_pos)

    def loss_fn(p, ids, mlm_l, nsp_l, m):
        # .loss entry point: chunked fused vocab cross-entropy (no
        # [B, M, V] logits; PT_FUSED_XENT=0 restores logits+pretrain_loss).
        # Under --mesh the vocab-sharded fused path combines per-shard
        # stats with pmax/psum instead of gathering the tied table.
        return model.apply({"params": p, "state": {}}, ids, mlm_l, nsp_l, m,
                           mask_positions=mask_pos, method="loss",
                           vocab_axis=vocab_axis, batch_axis=batch_axis,
                           mesh=mesh), 0.0

    def train_step(params, opt_state, ids, mlm_l, nsp_l, m):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, params, opt_state, ids, mlm_l, nsp_l, m)
        return loss, params, opt_state

    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    with _mesh_ctx(mesh):
        if COMPILE_ONLY:
            return _co(name, jitted, params, opt_state, ids, mlm_labels,
                       nsp_labels, mask)
        flops_per_step = _cost_flops(jitted, params, opt_state, ids,
                                     mlm_labels, nsp_labels, mask)
        # warmup/compile
        loss, params, opt_state = jitted(params, opt_state, ids, mlm_labels,
                                         nsp_labels, mask)
        _ = float(loss)

    st = {"params": params, "opt": opt_state}

    def step_once():
        loss, st["params"], st["opt"] = jitted(st["params"], st["opt"], ids,
                                               mlm_labels, nsp_labels, mask)
        return loss

    dt, loss_v = _timed_steps(step_once, steps)
    tokens_per_sec = batch * seq / dt
    return _mesh_row({
        "metric": f"{name}_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "mfu": _mfu(flops_per_step, dt),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_v,
        "flash": bool(use_flash),
        "seq": seq,
    })


def bench_transformer(steps, batch, seq):
    """Transformer big (WMT en-de config) training step — the seq2seq
    flagship from BASELINE.json's target list."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig.tiny() if TINY else TransformerConfig.big()
    cfg.dropout = 0.0
    cfg.max_len = max(cfg.max_len, seq)
    model = Transformer(cfg)
    variables = model.init(jax.random.key(0))
    params = variables["params"]

    policy = pt.amp.bf16_policy()
    opt = pt.amp.decorate(pt.optimizer.Adam(1e-4), policy)
    mesh = vocab_axis = batch_axis = None
    if MESH_AXES:
        mesh, params, opt_state, vocab_axis, batch_axis, batch = \
            _mesh_setup(params, opt, cfg.tgt_vocab, batch, cfg=cfg,
                        seq=seq)
    else:
        opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(1, cfg.src_vocab, (batch, seq),
                                  dtype=np.int32))
    tgt_in = jnp.asarray(rng.randint(1, cfg.tgt_vocab, (batch, seq),
                                     dtype=np.int32))
    tgt_out = jnp.asarray(rng.randint(1, cfg.tgt_vocab, (batch, seq),
                                      dtype=np.int32))
    if mesh is not None:
        src, tgt_in, tgt_out = (pt.parallel.shard_batch(mesh, x)
                                for x in (src, tgt_in, tgt_out))

    def loss_fn(p, src, tgt_in, tgt_out):
        # .loss entry point: fused label-smoothed vocab cross-entropy (no
        # [B, T, V] logits or one-hot; PT_FUSED_XENT=0 restores nmt_loss).
        # Under --mesh the hv-layout out_proj stays vocab-sharded.
        return model.apply({"params": p, "state": {}}, src, tgt_in, tgt_out,
                           method="loss", vocab_axis=vocab_axis,
                           batch_axis=batch_axis, mesh=mesh), 0.0

    def train_step(params, opt_state, src, tgt_in, tgt_out):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, params, opt_state, src, tgt_in, tgt_out)
        return loss, params, opt_state

    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    with _mesh_ctx(mesh):
        if COMPILE_ONLY:
            return _co("transformer_big", jitted, params, opt_state, src,
                       tgt_in, tgt_out)
        flops_per_step = _cost_flops(jitted, params, opt_state, src, tgt_in,
                                     tgt_out)
        loss, params, opt_state = jitted(params, opt_state, src, tgt_in,
                                         tgt_out)
        _ = float(loss)

    st = {"params": params, "opt": opt_state}

    def step_once():
        loss, st["params"], st["opt"] = jitted(st["params"], st["opt"], src,
                                               tgt_in, tgt_out)
        return loss

    dt, loss_v = _timed_steps(step_once, steps)
    return _mesh_row({
        "metric": "transformer_big_tokens_per_sec_per_chip",
        "value": round(batch * seq / dt, 1),
        "unit": "tokens/s/chip",
        "mfu": _mfu(flops_per_step, dt),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_v,
        "seq": seq,
    })


def bench_gpt_decode(steps, batch, seq):
    """GPT-small KV-cache greedy decode throughput (the serving path:
    batched prefill, then lax.scan decode steps over
    dynamic_update_slice caches). Emits decoded tokens/s/chip; prompt
    length seq//4, decodes 128 new tokens per call. Bandwidth-bound by
    design: every token reads all params AND streams the padded KV
    cache (the larger term at serving batch sizes; bf16 cache default,
    PT_BENCH_CACHE_F32 / PT_BENCH_INT8_DECODE for the A/Bs)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder

    cfg = GPTConfig.small()
    cfg.dropout = 0.0
    cfg.max_position = max(cfg.max_position, seq)
    model = GPTDecoder(cfg)
    variables = model.init(jax.random.key(0))
    # PT_BENCH_INT8_DECODE=1: weight-only int8 serving — every decode
    # step reads the whole parameter set, so int8-resident weights halve
    # the bf16 HBM bytes per token (quant.weight_only; v5e int8 ride)
    int8 = os.environ.get("PT_BENCH_INT8_DECODE", "0") == "1"
    if int8:
        from paddle_tpu.quant import quantize_weights_int8
        variables = {"params": quantize_weights_int8(
            model, variables["params"]), "state": {}}
    max_new = 128
    prompt_len = max(8, seq // 4)

    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt_len),
                                     dtype=np.int32))

    # TPU-first serving defaults: batched prefill + bf16 KV cache (the
    # padded cache reads dominate per-token HBM traffic at serving batch
    # sizes). PT_BENCH_CACHE_F32=1 restores the f32 cache for A/B.
    cache_dtype = (jnp.float32
                   if os.environ.get("PT_BENCH_CACHE_F32", "0") == "1"
                   else jnp.bfloat16)

    def decode(p, prompt):
        return model.apply(
            {"params": p, "state": {}}, prompt,
            method=lambda pr: model.generate(pr, max_new,
                                             cache_dtype=cache_dtype))

    jitted = jax.jit(decode)
    if COMPILE_ONLY:
        return _co("gpt_decode", jitted, variables["params"], prompt)
    out = jitted(variables["params"], prompt)
    assert out.shape == (batch, prompt_len + max_new)
    _ = np.asarray(out[0, -1])  # true barrier (host fetch)

    st = {"prompt": prompt}

    def step_once():
        # chain calls (next prompt = tail of the last output) so the n /
        # 2n timing runs serialize on a real data dependency
        out = jitted(variables["params"], st["prompt"])
        st["prompt"] = out[:, -prompt_len:]
        return out[0, -1]

    dt, _ = _timed_steps(step_once, steps, tokens_per_step=batch * max_new)
    toks_per_s = batch * max_new / dt
    # decode is bandwidth-bound: every decode step reads all params once
    # AND streams the whole padded KV cache (at serving batch sizes the
    # cache is the larger term). vs_baseline = fraction of the chip's
    # HBM roofline achieved over the decode steps (prefill's one
    # batched forward is excluded from the byte count — it under-counts,
    # never over-counts).
    param_bytes = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(variables["params"]))
    # analytic (eval_shape over a nullary closure would still allocate:
    # only *arguments* are abstracted): K + V per layer, padded length
    cache_bytes = (model.cfg.num_layers * 2 * batch
                   * (prompt_len + max_new) * model.cfg.hidden_size
                   * jnp.dtype(cache_dtype).itemsize)
    # fraction of the chip's HBM bandwidth (autoplan/topology.py's table
    # row for the chip JAX reports); null off the TPU
    from paddle_tpu.parallel.autoplan.topology import detect
    hbm_util = None
    if jax.devices()[0].platform == "tpu":
        hbm_util = round((max_new * (param_bytes + cache_bytes)) / dt
                         / detect().hbm_bw, 4)
    return {
        "metric": ("gpt_small_decode_int8_tokens_per_sec_per_chip"
                   if int8 else "gpt_small_decode_tokens_per_sec_per_chip"),
        "value": round(toks_per_s, 1),
        "unit": "decoded tokens/s/chip",
        "step_ms": round(dt * 1e3, 2),
        "batch": batch,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "hbm_util": hbm_util,
        "vs_baseline": hbm_util or 0.0,
        "note": "KV-cache greedy decode; bandwidth-bound — vs_baseline "
                "is fraction of HBM roofline over params + padded KV "
                "cache per decoded token",
    }


def bench_gpt_serve(steps, batch, seq):
    """Continuous-batching serving throughput (paddle_tpu/serving/):
    mixed-length prompts streamed through `batch` decode slots over the
    paged KV cache — the production serving shape, vs gpt_decode's
    fixed lockstep batch. Reports decoded tokens/s/chip plus
    telemetry-backed p50/p95 per-token latency and TTFT from the
    serve.* histograms (the PR-4 registry). Request mix: 4x slots
    requests, prompt lengths uniform in [seq//8, prefill_len],
    max_new=64 each. PT_BENCH_PAGE_SIZE overrides the page size
    (default 64; 128 fills a TPU lane tile). PT_BENCH_PREFIX_SHARE
    (default 0.5) is the fraction of requests opening with a common
    full-page prefix — the prefix-cache workload; the row reports
    prefix_hit_rate / pages_shared / prefill_tokens_skipped, and
    serve_prefix_cache=0 in PT_FLAGS gives the uncached A/B on the
    identical request stream. PT_BENCH_KV_DTYPE=int8 stores the paged
    KV quantized (per-token scales ride the pool); the row reports
    kv_dtype / kv_pool_bytes / quant_overflow_clamps either way, so
    the quantized-vs-f32 A/B is one env flip on the same stream.
    PT_BENCH_DRAFT=1 turns on speculative decoding (self-draft;
    PT_BENCH_SPEC_K overrides the serve_spec_k window) — the row then
    reports acceptance_rate / tokens_per_target_step from the engine's
    speculation counters plus the cost-model draft_overhead, and the
    speculation-off A/B is the same env flip on the same stream."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.serving import ServeConfig, ServingEngine

    cfg = GPTConfig.tiny() if TINY else GPTConfig.small()
    cfg.dropout = 0.0
    model = GPTDecoder(cfg)
    variables = model.init(jax.random.key(0))

    max_new = 32 if TINY else 64
    page = int(os.environ.get("PT_BENCH_PAGE_SIZE", "64"))
    share = float(os.environ.get("PT_BENCH_PREFIX_SHARE", "0.5"))
    # the shared prefix is whole pages so cache hits skip real prefill
    # work; max_len grows by the same amount so the suffix length
    # distribution (and the uncached A/B shape) is unchanged
    shared_len = page if share > 0 else 0
    prefill_len = min(max(page, seq // 2),
                      cfg.max_position - max_new - shared_len)
    cache_dtype = (jnp.float32
                   if os.environ.get("PT_BENCH_CACHE_F32", "0") == "1"
                   else jnp.bfloat16)
    # SLO targets for the goodput column (generous CPU-safe defaults;
    # tighten on the chip)
    slo_ttft = float(os.environ.get("PT_BENCH_SLO_TTFT", "2.0"))
    slo_tok = float(os.environ.get("PT_BENCH_SLO_TOKEN", "0.5"))
    draft = os.environ.get("PT_BENCH_DRAFT", "0") == "1"
    spec_k_env = os.environ.get("PT_BENCH_SPEC_K", "").strip()
    sc = ServeConfig(num_slots=batch, page_size=page,
                     max_len=shared_len + prefill_len + max_new,
                     prefill_len=prefill_len, cache_dtype=cache_dtype,
                     kv_dtype=_kv_dtype_env(),
                     run_log=RUN_LOG, slo_ttft_s=slo_ttft,
                     slo_token_latency_s=slo_tok,
                     draft=draft or None,
                     spec_k=int(spec_k_env) if spec_k_env else None)
    engine = ServingEngine(model, variables, sc)

    if COMPILE_ONLY:
        t0 = time.perf_counter()
        engine.compiled_decode()
        if draft:
            engine.compiled_verify()
        return {"metric": "gpt_serve_compile_only", "value": 1.0,
                "unit": "compiled", "vs_baseline": 0.0,
                "compile_s": round(time.perf_counter() - t0, 1)}

    rng = np.random.RandomState(0)
    shared_prefix = (rng.randint(0, cfg.vocab_size, (shared_len,),
                                 dtype=np.int32)
                     if shared_len else None)

    def mixed_requests(n):
        for _ in range(n):
            plen = int(rng.randint(max(1, seq // 8), prefill_len + 1))
            ids = rng.randint(0, cfg.vocab_size, (plen,),
                              dtype=np.int32)
            if shared_len and rng.random_sample() < share:
                ids = np.concatenate([shared_prefix, ids])
            engine.submit(ids, max_new=max_new)

    # warmup: compile prefill + decode and fill the latency histograms'
    # cold-start tail outside the timed window; reset_stats also zeroes
    # the SLO tallies so compile-time TTFTs don't poison goodput
    mixed_requests(batch)
    engine.drain()
    engine.reset_stats()
    pc = engine._prefix_cache
    hits0, miss0 = (pc.hits, pc.misses) if pc else (0, 0)
    skip0 = engine.prefill_tokens_skipped
    n_req = max(4 * batch, steps)
    mixed_requests(n_req)
    t0 = time.perf_counter()
    done = engine.drain()
    dt = max(time.perf_counter() - t0, 1e-9)
    total_tokens = sum(len(r.tokens) for r in done)
    stats = engine.latency_stats()
    slo = engine.slo_stats()
    spec_row = {}
    if draft:
        # speculation accounting (measured) + the cost-model overhead
        # figure the autoplan --serve-spec report prices from — the
        # same predict_decode call, zero bench-local constants
        from paddle_tpu.parallel.autoplan import (
            ModelSpec, costmodel, get_topology)
        spec = engine.spec_stats()
        pred = costmodel.predict_decode(
            ModelSpec.from_config(cfg, batch=batch, seq=sc.max_len,
                                  name="gpt"),
            get_topology(), slots=batch, context=sc.max_len,
            spec_k=spec["spec_k"])
        spec_row = {
            "spec_k": spec["spec_k"],
            "spec_rounds": spec["rounds"],
            "acceptance_rate": spec["acceptance_rate"],
            "tokens_per_target_step": spec["tokens_per_target_step"],
            "draft_overhead": round(pred["draft_overhead"], 4),
        }
    return {
        "metric": "gpt_serve_tokens_per_sec_per_chip",
        "value": round(total_tokens / dt, 1),
        "unit": "decoded tokens/s/chip",
        "vs_baseline": 0.0,
        "requests": n_req,
        "slots": batch,
        "page_size": page,
        "max_new": max_new,
        "kv_dtype": engine.kv_dtype_name(),
        "kv_pool_bytes": engine.kv_pool_bytes(),
        "quant_overflow_clamps": _quant_clamps(),
        "token_ms": stats.get("token_ms"),
        "ttft_ms": stats.get("ttft_ms"),
        "goodput": slo["goodput"],
        "slo_ttft_s": slo_ttft,
        "slo_token_latency_s": slo_tok,
        "slo_violations": slo["violations"],
        "decode_traces": engine.decode_traces,
        "prefix_share": share,
        "prefix_hit_rate": (
            round((pc.hits - hits0)
                  / max((pc.hits - hits0) + (pc.misses - miss0), 1), 4)
            if pc else 0.0),
        "pages_shared": pc.pages_shared() if pc else 0,
        "prefill_tokens_skipped": engine.prefill_tokens_skipped - skip0,
        # resilience trajectory: non-completion terminals + step crashes
        # recovered (all 0 in a healthy bench; a regression here means
        # the bench itself hit the resilience path)
        "rejected": sum(1 for r in engine.requests.values()
                        if r.status == "rejected"),
        "shed": sum(1 for r in engine.requests.values()
                    if r.status == "shed"),
        "recovered": engine.recoveries,
        **spec_row,
        "note": "continuous batching over the paged KV cache; mixed "
                "prompt lengths, admissions between decode steps",
    }


def bench_gpt_serve_fleet(steps, batch, seq):
    """Fleet-router serving (paddle_tpu/serving/fleet.py): aggregate
    goodput + decoded tokens/s vs replica count (PT_BENCH_FLEET_REPLICAS,
    default "1,2,4"; `batch` decode slots per replica), with each run's
    per-replica telemetry snapshot in the row JSON. Under
    PT_BENCH_FLEET_KILL=1 every multi-replica run also exercises the
    failover path itself — one busy replica killed mid-stream — and
    reports the recovery round's wall time (respawn + token-exact
    re-route) against the mean healthy round as the failover overhead.
    PT_BENCH_PREFIX_SHARE (default 0.5) mixes in requests opening with
    a common full-page prefix; each replica-count row then reports the
    fleet-wide prefix_hit_rate plus the router's affinity_hits (the
    prefix-affinity dispatch steering same-prefix traffic to the
    replica already holding the pages). PT_BENCH_FLEET_RAMP=1 switches
    to an offered-load ramp against ONE autoscaling router: the row
    carries a goodput-vs-offered-load curve with replica-count and
    deploy-overhead columns (a rolling v0 -> v1 deploy lands at the
    peak level), plus the router's ops_log for `tools/run_report.py
    --fleet`. The standard mode closes with a short untraced window
    (trace_fleet=0, flight_ring=0) at the max replica count and reports
    `trace_overhead` (untraced/traced tokens/s — ~1.0 proves the trace
    plane never syncs the device) plus the path of the most recent
    flight-recorder bundle, if an anomaly dumped one.
    PT_BENCH_DISAGG=1 appends a prefill/decode disaggregation A/B at
    the max replica count (floor 2): the SAME mixed prompt-length
    stream (half the prompts longer than prefill_len — multi-chunk
    admissions) runs through a mixed fleet and through one with the
    first replica carved out as a prefill role, reporting goodput and
    tokens/s for both plus the token-exact handoff count."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import flags as _F
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.serving import FleetConfig, FleetRouter, ServeConfig

    cfg = GPTConfig.tiny() if TINY else GPTConfig.small()
    cfg.dropout = 0.0
    model = GPTDecoder(cfg)
    variables = model.init(jax.random.key(0))

    max_new = 16 if TINY else 64
    page = int(os.environ.get("PT_BENCH_PAGE_SIZE", "64"))
    share = float(os.environ.get("PT_BENCH_PREFIX_SHARE", "0.5"))
    shared_len = page if share > 0 else 0
    prefill_len = min(max(page, seq // 2),
                      cfg.max_position - max_new - shared_len)
    cache_dtype = (jnp.float32
                   if os.environ.get("PT_BENCH_CACHE_F32", "0") == "1"
                   else jnp.bfloat16)
    slo_ttft = float(os.environ.get("PT_BENCH_SLO_TTFT", "2.0"))
    slo_tok = float(os.environ.get("PT_BENCH_SLO_TOKEN", "0.5"))
    kill = os.environ.get("PT_BENCH_FLEET_KILL", "0") == "1"
    counts = [int(x) for x in os.environ.get(
        "PT_BENCH_FLEET_REPLICAS", "1,2,4").split(",") if x.strip()]

    def serve_cfg():
        return ServeConfig(num_slots=batch, page_size=page,
                           max_len=shared_len + prefill_len + max_new,
                           prefill_len=prefill_len,
                           cache_dtype=cache_dtype,
                           kv_dtype=_kv_dtype_env(),
                           slo_ttft_s=slo_ttft,
                           slo_token_latency_s=slo_tok, metrics_port=0)

    def fleet_kv_stats(router):
        """(kv_dtype, total pool bytes) across the router's replicas."""
        engines = [rep.engine for rep in router._replicas
                   if getattr(rep, "engine", None) is not None]
        if not engines:
            return "f32", 0
        return (engines[0].kv_dtype_name(),
                sum(e.kv_pool_bytes() for e in engines))

    if COMPILE_ONLY:
        router = FleetRouter(model, variables,
                             FleetConfig(num_replicas=1, metrics_port=0),
                             serve_config=serve_cfg())
        t0 = time.perf_counter()
        router._replicas[0].engine.compiled_decode()
        router.close()
        return {"metric": "gpt_serve_fleet_compile_only", "value": 1.0,
                "unit": "compiled", "vs_baseline": 0.0,
                "compile_s": round(time.perf_counter() - t0, 1)}

    def settle(router):
        # step (never drain) until quiet: drain() latches the router
        # draining and would reject the next window's submissions
        while any(r.status not in ("done", "rejected", "shed",
                                   "cancelled", "failed")
                  for r in router.requests.values()):
            router.step()

    if os.environ.get("PT_BENCH_FLEET_RAMP", "0") == "1":
        # Ramp mode: ONE autoscaling router pushed through an offered-load
        # ramp (PT_BENCH_FLEET_RAMP_LEVELS are per-level multipliers of
        # `batch` requests) instead of a fresh router per replica count.
        # A rolling deploy (v0 -> v1) lands at the peak level so its
        # overhead shows up in-curve. Each curve row: offered load,
        # windowed goodput, live replica count after the level settles,
        # decoded tokens/s, and the deploy's wall time (0 when the level
        # had no deploy). Feed the row JSON to `tools/run_report.py
        # --fleet` for the deploy timeline + per-version goodput table.
        levels = [int(x) for x in os.environ.get(
            "PT_BENCH_FLEET_RAMP_LEVELS", "1,2,4,8,4,1").split(",")
            if x.strip()]
        router = FleetRouter(
            model, variables,
            FleetConfig(num_replicas=1, heartbeat_s=60.0, metrics_port=0,
                        autoscale_min=1, autoscale_max=max(counts),
                        scale_cooldown_s=0.0),
            serve_config=serve_cfg())
        rng = np.random.RandomState(0)
        shared_prefix = (rng.randint(0, cfg.vocab_size, (shared_len,),
                                     dtype=np.int32)
                         if shared_len else None)

        def submit(k):
            for _ in range(k):
                plen = int(rng.randint(max(1, seq // 8),
                                       prefill_len + 1))
                ids = rng.randint(0, cfg.vocab_size, (plen,),
                                  dtype=np.int32)
                if shared_len and rng.random_sample() < share:
                    ids = np.concatenate([shared_prefix, ids])
                router.submit(ids, max_new=max_new)

        def alive_now():
            return sum(1 for s in router.telemetry()["states"]
                       if s in ("live", "stalled", "draining"))

        def settle_tracked():
            # settle, reporting the PEAK live replica count: the idle
            # scale-down usually lands before the level finishes, so a
            # post-settle sample would always read autoscale_min
            peak = alive_now()
            while any(r.status not in ("done", "rejected", "shed",
                                       "cancelled", "failed")
                      for r in router.requests.values()):
                router.step()
                peak = max(peak, alive_now())
            return peak

        submit(batch)            # warmup: compile prefill + decode
        settle(router)
        deploy_at = levels.index(max(levels))
        curve = []
        for li, lvl in enumerate(levels):
            mark = len(router.requests)
            n_req = lvl * batch
            t0 = time.perf_counter()
            submit(n_req)
            deploy_s = 0.0
            if li == deploy_at:
                d0 = time.perf_counter()
                router.deploy(variables, version="v1", budget_s=600.0)
                deploy_s = round(time.perf_counter() - d0, 3)
            live = settle_tracked()
            dt = max(time.perf_counter() - t0, 1e-9)
            recs = [r for r in router.requests.values()
                    if r.id >= mark]
            done = [r for r in recs if r.status == "done"]
            acct = [r for r in recs if r.status != "cancelled"]
            curve.append({
                "offered": n_req,
                "completed": len(done),
                "goodput": round(sum(1 for r in acct if r.slo_ok)
                                 / max(len(acct), 1), 4),
                "replicas": live,
                "tokens_per_sec": round(
                    sum(len(r.tokens) for r in done) / dt, 1),
                "deploy_s": deploy_s,
            })
        tel = router.telemetry()
        kv_name, kv_bytes = fleet_kv_stats(router)
        router.close()
        peak = max(curve, key=lambda row: row["tokens_per_sec"])
        return {
            "metric": "gpt_serve_fleet_ramp_peak_tokens_per_sec",
            "value": peak["tokens_per_sec"],
            "unit": "decoded tokens/s (fleet aggregate, ramp peak)",
            "vs_baseline": 0.0,
            "slots_per_replica": batch,
            "page_size": page,
            "max_new": max_new,
            "kv_dtype": kv_name,
            "kv_pool_bytes": kv_bytes,
            "quant_overflow_clamps": _quant_clamps(),
            "autoscale_max": max(counts),
            "deployed_version": tel["baseline_version"],
            "version_stats": tel["version_stats"],
            "ops_log": tel["ops_log"],
            "curve": curve,
            "note": "PT_BENCH_FLEET_RAMP=1: goodput-vs-offered-load ramp "
                    "against one autoscaling router; a rolling deploy "
                    "(v0 -> v1) lands at the peak level so deploy "
                    "overhead appears in-curve",
        }

    by_replicas = {}
    for n in counts:
        router = FleetRouter(
            model, variables,
            FleetConfig(num_replicas=n, heartbeat_s=60.0,
                        metrics_port=0),
            serve_config=serve_cfg())
        rng = np.random.RandomState(0)
        shared_prefix = (rng.randint(0, cfg.vocab_size, (shared_len,),
                                     dtype=np.int32)
                         if shared_len else None)

        def submit(k, router=router, rng=rng):
            for _ in range(k):
                plen = int(rng.randint(max(1, seq // 8),
                                       prefill_len + 1))
                ids = rng.randint(0, cfg.vocab_size, (plen,),
                                  dtype=np.int32)
                if shared_len and rng.random_sample() < share:
                    ids = np.concatenate([shared_prefix, ids])
                router.submit(ids, max_new=max_new)

        def fleet_prefix_stats(router=router):
            hits = miss = skipped = 0
            for rep in router._replicas:
                eng = getattr(rep, "engine", None)
                pc = getattr(eng, "_prefix_cache", None)
                if pc is not None:
                    hits, miss = hits + pc.hits, miss + pc.misses
                    skipped += eng.prefill_tokens_skipped
            return hits, miss, skipped

        # warmup: compile every replica's prefill + decode outside the
        # timed window
        submit(n * batch)
        settle(router)
        warm = len(router.requests)
        hits0, miss0, skip0 = fleet_prefix_stats()
        aff0 = _metrics.counter("fleet.affinity_hits").total()
        n_req = max(4 * batch * n, steps)
        submit(n_req)
        step_times = []
        failover_ms = None
        t0 = time.perf_counter()
        if kill and n > 1:
            for _ in range(3):           # measure healthy rounds first
                s0 = time.perf_counter()
                router.step()
                step_times.append(time.perf_counter() - s0)
            victim = max(range(n),
                         key=lambda i: router._replicas[i].load())
            router.kill_replica(victim)
            s0 = time.perf_counter()
            router.step()                # the failover round
            failover_ms = round((time.perf_counter() - s0) * 1e3, 1)
        settle(router)
        dt = max(time.perf_counter() - t0, 1e-9)
        recs = [r for r in router.requests.values()
                if r.id >= warm and r.status == "done"]
        tokens = sum(len(r.tokens) for r in recs)
        hits1, miss1, skip1 = fleet_prefix_stats()
        d_hits, d_miss = hits1 - hits0, miss1 - miss0
        entry = {
            "requests": n_req,
            "completed": len(recs),
            "tokens_per_sec": round(tokens / dt, 1),
            "goodput": round(router.goodput(), 4),
            "failovers": router.failovers,
            "prefix_hit_rate": round(
                d_hits / max(d_hits + d_miss, 1), 4),
            "prefill_tokens_skipped": skip1 - skip0,
            "affinity_hits": int(
                _metrics.counter("fleet.affinity_hits").total() - aff0),
            "telemetry": router.telemetry(),
        }
        if failover_ms is not None:
            mean_ms = 1e3 * sum(step_times) / len(step_times)
            entry["mean_step_ms"] = round(mean_ms, 1)
            entry["failover_step_ms"] = failover_ms
            entry["failover_overhead_ms"] = round(failover_ms - mean_ms,
                                                  1)
        kv_name, kv_bytes = fleet_kv_stats(router)
        entry["kv_dtype"] = kv_name
        entry["kv_pool_bytes"] = kv_bytes
        by_replicas[str(n)] = entry
        router.close()

    # tracing overhead: one more short window at the max replica count
    # with the trace plane off (trace_fleet=0, flight_ring=0). Every
    # trace event is a host-side dict append (+ one RunLog line when
    # configured) — traced/untraced tokens/s should read ~1.0; a drift
    # here means something synced the device on the trace path.
    nmax = max(counts)
    saved_flags = _F.all_flags()
    try:
        _F.set_flags({"trace_fleet": False, "flight_ring": 0})
        router = FleetRouter(
            model, variables,
            FleetConfig(num_replicas=nmax, heartbeat_s=60.0,
                        metrics_port=0),
            serve_config=serve_cfg())
        rng = np.random.RandomState(0)
        shared_prefix = (rng.randint(0, cfg.vocab_size, (shared_len,),
                                     dtype=np.int32)
                         if shared_len else None)

        def submit_untraced(k):
            for _ in range(k):
                plen = int(rng.randint(max(1, seq // 8),
                                       prefill_len + 1))
                ids = rng.randint(0, cfg.vocab_size, (plen,),
                                  dtype=np.int32)
                if shared_len and rng.random_sample() < share:
                    ids = np.concatenate([shared_prefix, ids])
                router.submit(ids, max_new=max_new)

        submit_untraced(nmax * batch)      # warmup (fresh jits)
        settle(router)
        warm = len(router.requests)
        n_req = max(4 * batch * nmax, steps)
        t0 = time.perf_counter()
        submit_untraced(n_req)
        settle(router)
        dt = max(time.perf_counter() - t0, 1e-9)
        recs = [r for r in router.requests.values()
                if r.id >= warm and r.status == "done"]
        untraced_tps = round(sum(len(r.tokens) for r in recs) / dt, 1)
        router.close()
    finally:
        _F.set_flags(saved_flags)

    disagg_row = None
    if os.environ.get("PT_BENCH_DISAGG", "0") == "1":
        # prefill/decode disaggregation A/B: identical mixed-length
        # stream (same seed), mixed fleet vs first-replica-prefill
        # fleet. Half the prompts exceed prefill_len so their admission
        # is a multi-chunk prefill — the work disaggregation moves off
        # the decode replicas.
        nd = max(max(counts), 2)

        def disagg_cfg():
            return ServeConfig(num_slots=batch, page_size=page,
                               max_len=2 * prefill_len + max_new,
                               prefill_len=prefill_len,
                               cache_dtype=cache_dtype,
                               kv_dtype=_kv_dtype_env(),
                               chunked_prefill=True,
                               slo_ttft_s=slo_ttft,
                               slo_token_latency_s=slo_tok,
                               metrics_port=0)

        def disagg_run(prefill_replicas):
            router = FleetRouter(
                model, variables,
                FleetConfig(num_replicas=nd, heartbeat_s=60.0,
                            metrics_port=0,
                            prefill_replicas=prefill_replicas),
                serve_config=disagg_cfg())
            rng = np.random.RandomState(0)

            def submit(k):
                for j in range(k):
                    if j % 2:      # prefill-heavy half
                        plen = int(rng.randint(
                            prefill_len + 1, 2 * prefill_len))
                    else:
                        plen = int(rng.randint(max(1, seq // 8),
                                               prefill_len + 1))
                    ids = rng.randint(0, cfg.vocab_size, (plen,),
                                      dtype=np.int32)
                    router.submit(ids, max_new=max_new)

            submit(nd * batch)     # warmup (fresh jits per replica)
            settle(router)
            warm = len(router.requests)
            n_req = max(4 * batch * nd, steps)
            t0 = time.perf_counter()
            submit(n_req)
            settle(router)
            dt = max(time.perf_counter() - t0, 1e-9)
            recs = [r for r in router.requests.values() if r.id >= warm]
            done = [r for r in recs if r.status == "done"]
            acct = [r for r in recs if r.status != "cancelled"]
            tel = router.telemetry()
            out = {
                "prefill_replicas": prefill_replicas,
                "completed": len(done),
                "tokens_per_sec": round(
                    sum(len(r.tokens) for r in done) / dt, 1),
                "goodput": round(sum(1 for r in acct if r.slo_ok)
                                 / max(len(acct), 1), 4),
                "handoffs": tel["handoffs"],
                "roles": tel["roles"],
            }
            router.close()
            return out

        mixed_ab = disagg_run(0)
        split_ab = disagg_run(1)
        disagg_row = {
            "replicas": nd,
            "mixed": mixed_ab,
            "disaggregated": split_ab,
            "goodput_delta": round(
                split_ab["goodput"] - mixed_ab["goodput"], 4),
        }

    top = by_replicas[str(max(counts))]
    return {
        "metric": "gpt_serve_fleet_tokens_per_sec",
        "value": top["tokens_per_sec"],
        "unit": "decoded tokens/s (fleet aggregate)",
        "vs_baseline": 0.0,
        "slots_per_replica": batch,
        "page_size": page,
        "max_new": max_new,
        "kv_dtype": top["kv_dtype"],
        "kv_pool_bytes": top["kv_pool_bytes"],
        "quant_overflow_clamps": _quant_clamps(),
        "goodput": top["goodput"],
        "fleet_kill": kill,
        "prefix_share": share,
        "untraced_tokens_per_sec": untraced_tps,
        "trace_overhead": round(
            untraced_tps / max(top["tokens_per_sec"], 1e-9), 3),
        "flight_bundle": _flight.last_bundle(),
        "by_replicas": by_replicas,
        **({"disagg": disagg_row} if disagg_row else {}),
        "note": "FleetRouter over in-process engine replicas; "
                "least-loaded dispatch, heartbeat liveness, token-exact "
                "failover replay (PT_BENCH_FLEET_KILL=1 kills a busy "
                "replica mid-stream); trace_overhead = untraced/traced "
                "tokens per second (~1.0 when the trace plane stays off "
                "the hot path); flight_bundle = the most recent "
                "flight-recorder dump this process produced, if any",
    }


def bench_gpt(steps, batch, seq):
    """GPT-small causal-LM training step (long-context flagship; flash
    causal attention default-on)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig.tiny() if TINY else GPTConfig.small()
    cfg.dropout = 0.0
    cfg.max_position = max(cfg.max_position, seq)
    _scan_env(cfg)
    model = GPT(cfg)
    variables = model.init(jax.random.key(0))
    params = variables["params"]

    policy = pt.amp.bf16_policy()
    opt = pt.amp.decorate(pt.optimizer.Adam(1e-4), policy)
    mesh = vocab_axis = batch_axis = None
    if MESH_AXES:
        mesh, params, opt_state, vocab_axis, batch_axis, batch = \
            _mesh_setup(params, opt, cfg.vocab_size, batch, cfg=cfg,
                        seq=seq)
    else:
        opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq),
                                  dtype=np.int32))
    if mesh is not None:
        ids = pt.parallel.shard_batch(mesh, ids)

    def loss_fn(p, ids):
        # .loss entry point: fused shifted CE against the tied embedding
        # (no [B, T, V] logits; PT_FUSED_XENT=0 restores logits+lm_loss).
        # Under --mesh the tied table stays vocab-sharded over tp.
        return model.apply({"params": p, "state": {}}, ids,
                           method="loss", vocab_axis=vocab_axis,
                           batch_axis=batch_axis, mesh=mesh), 0.0

    def train_step(params, opt_state, ids):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, params, opt_state, ids)
        return loss, params, opt_state

    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    with _mesh_ctx(mesh):
        if COMPILE_ONLY:
            return _co("gpt", jitted, params, opt_state, ids)
        flops_per_step = _cost_flops(jitted, params, opt_state, ids)
        loss, params, opt_state = jitted(params, opt_state, ids)
        _ = float(loss)

    st = {"params": params, "opt": opt_state}

    def step_once():
        loss, st["params"], st["opt"] = jitted(st["params"], st["opt"], ids)
        return loss

    dt, loss_v = _timed_steps(step_once, steps)
    return _mesh_row({
        "metric": "gpt_small_tokens_per_sec_per_chip",
        "value": round(batch * seq / dt, 1),
        "unit": "tokens/s/chip",
        "mfu": _mfu(flops_per_step, dt),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_v,
        "seq": seq,
    })


def bench_resnet(steps, batch):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.ops import loss as L

    # PT_BENCH_NHWC_FEED=1: feed bf16 NHWC batches straight from the host
    # (what a TPU-first input pipeline produces) instead of the reference's
    # f32 NCHW convention — removes the per-step transpose+cast copy.
    nhwc_feed = os.environ.get("PT_BENCH_NHWC_FEED", "0") == "1"
    model = resnet50(num_classes=1000,
                     input_layout="NHWC" if nhwc_feed else "NCHW")
    variables = model.init(jax.random.key(0))
    params, state = variables["params"], variables["state"]

    policy = pt.amp.bf16_policy()
    # PT_BENCH_BF16_VELOCITY=1: store momentum velocity in bf16 (halves
    # optimizer-state HBM traffic; see Momentum.state_dtype)
    vel_dt = (jnp.bfloat16
              if os.environ.get("PT_BENCH_BF16_VELOCITY", "0") == "1"
              else None)
    opt = pt.amp.decorate(
        pt.optimizer.Momentum(0.1, 0.9, state_dtype=vel_dt), policy)
    opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    if nhwc_feed:
        images = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32),
                             dtype=jnp.bfloat16)
    else:
        images = jnp.asarray(rng.rand(batch, 3, 224, 224).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, (batch, 1), dtype=np.int32))

    def loss_fn(p, images, labels, state):
        out, new_state = model.apply({"params": p, "state": state}, images,
                                     training=True)
        loss = jnp.mean(L.softmax_with_cross_entropy(out, labels))
        return loss, new_state

    def train_step(params, opt_state, state, images, labels):
        loss, params, opt_state, new_state = opt.minimize(
            loss_fn, params, opt_state, images, labels, state)
        return loss, params, opt_state, new_state

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))
    if COMPILE_ONLY:
        return _co("resnet50", jitted, params, opt_state, state, images,
                   labels)
    # analytic: ResNet-50 fwd = 4.089 GMACs/image @224 (the paper's
    # "~3.8-4.1 GFLOPs" figure counts a multiply-add as ONE op) = 8.178
    # GFLOPs at the FMA=2 convention the bf16 peak uses; train = 3x fwd.
    # XLA cost_analysis double-counts conv FLOPs, so the analytic count is
    # the honest MFU numerator. (Rows before 2026-07-31 used the MAC count
    # directly and under-reported ResNet MFU 2x — e.g. the silicon
    # 2647.5 img/s row is 0.33 MFU, not 0.165.)
    flops_per_step = 3 * 2 * 4.089e9 * batch
    loss, params, opt_state, state = jitted(params, opt_state, state, images,
                                            labels)
    _ = float(loss)

    st = {"params": params, "opt": opt_state, "state": state}

    def step_once():
        loss, st["params"], st["opt"], st["state"] = jitted(
            st["params"], st["opt"], st["state"], images, labels)
        return loss

    dt, loss_v = _timed_steps(step_once, steps)
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(batch / dt, 1),
        "unit": "images/s/chip",
        "mfu": _mfu(flops_per_step, dt),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_v,
    }


def bench_ctr(steps, batch):
    """DeepFM CTR through the sparse-row pull-push path (BASELINE.json
    "DeepFM / Wide&Deep CTR" target row; ref dist_ctr.py's
    embedding+pserver workload). Criteo-shaped: 26 sparse slots, 13 dense,
    100k hash per slot. Bandwidth/gather-bound by design — examples/s is
    the headline number, MFU is reported for completeness only."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.ctr import (CTRConfig, DeepFM,
                                       make_sparse_deepfm_train_step)
    from paddle_tpu.parallel.sparse import SparseTable

    cfg = CTRConfig(num_sparse_fields=26, num_dense_fields=13,
                    vocab_size=100_000, embed_dim=16, hidden=(400, 400, 400))
    model = DeepFM(cfg, sparse_tables=True)
    params = model.init(jax.random.key(0))["params"]
    opt = pt.optimizer.Adam(1e-3)
    opt_state = opt.init(params)
    vtot = cfg.vocab_size * cfg.num_sparse_fields
    embed_tbl = SparseTable(vtot, cfg.embed_dim, pt.optimizer.Adagrad(0.05))
    linear_tbl = SparseTable(vtot, 1, pt.optimizer.Adagrad(0.05))
    emb_st = embed_tbl.init(jax.random.key(1))
    lin_st = linear_tbl.init(jax.random.key(2))

    rng = np.random.RandomState(0)
    dense = jnp.asarray(rng.rand(batch, cfg.num_dense_fields)
                        .astype(np.float32))
    sparse_ids = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (batch, cfg.num_sparse_fields), dtype=np.int32))
    labels = jnp.asarray(rng.randint(0, 2, (batch, 1), dtype=np.int32)
                         .astype(np.float32))

    raw_step = make_sparse_deepfm_train_step(model, opt, embed_tbl,
                                             linear_tbl)
    jitted = jax.jit(raw_step, donate_argnums=(0, 1, 2, 3))
    if COMPILE_ONLY:
        return _co("ctr", jitted, params, opt_state, emb_st, lin_st,
                   dense, sparse_ids, labels)
    flops_per_step = _cost_flops(jitted, params, opt_state, emb_st, lin_st,
                                 dense, sparse_ids, labels)
    loss, params, opt_state, emb_st, lin_st = jitted(
        params, opt_state, emb_st, lin_st, dense, sparse_ids, labels)
    _ = float(loss)

    st = {"p": params, "o": opt_state, "e": emb_st, "l": lin_st}

    def step_once():
        loss, st["p"], st["o"], st["e"], st["l"] = jitted(
            st["p"], st["o"], st["e"], st["l"], dense, sparse_ids, labels)
        return loss

    dt, loss_v = _timed_steps(step_once, steps)
    return {
        "metric": "deepfm_ctr_examples_per_sec_per_chip",
        "value": round(batch / dt, 1),
        "unit": "examples/s/chip",
        "mfu": _mfu(flops_per_step, dt),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_v,
        "note": "sparse pull-push path; gather/bandwidth-bound, "
                "examples/s is the headline",
    }


def _autotune_presweep(args):
    """--autotune: sweep the Pallas tile space eagerly at this row's
    flagship kernel shapes BEFORE the jitted step traces. A traced
    contact can only consume the tile cache (sweeps need eager
    execution), so without this the flag would quietly bench the static
    defaults. Returns the sweep wall time; the chosen tiles ride along
    in the row JSON (``autotune`` key) so a BENCH artifact records which
    tiles the run had."""
    import jax.numpy as jnp
    from paddle_tpu.core.flags import set_flags
    set_flags({"autotune": True})
    fam = {"bert": "mlm", "ernie": "mlm", "gpt": "lm", "gpt_decode": "lm",
           "gpt_serve": "lm"}.get(args.model)
    if fam is None:  # resnet/ctr/transformer_big: no autotuned kernels;
        return 0.0   # the flag is on, the jitted step just finds no cache
    t0 = time.monotonic()
    batch = args.batch or {"bert": 64, "ernie": 64, "gpt": 16,
                           "gpt_decode": 16, "gpt_serve": 8}[args.model]
    seq = args.seq
    if args.model == "bert":
        from paddle_tpu.models.bert import BertConfig as _C
    elif args.model == "ernie":
        from paddle_tpu.models.ernie import ErnieConfig as _C
    else:
        from paddle_tpu.models.gpt import GPTConfig as _C
    cfg = _C.tiny() if TINY else (_C.base() if fam == "mlm" else _C.small())
    causal = fam == "lm"
    # the bench steps run under the amp bf16 policy — sweep the same
    # dtype or the cache signatures won't match the traced lookups
    from paddle_tpu.ops.pallas import on_tpu
    dtype = jnp.bfloat16 if on_tpu() else jnp.float32
    rng = np.random.RandomState(0)

    def arr(*s):
        return jnp.asarray(0.02 * rng.randn(*s), dtype)

    rows = batch * seq
    # bert/ernie gather masked positions before the vocab fc
    rows_x = batch * max(1, int(0.15 * seq)) if fam == "mlm" else rows
    hd = cfg.hidden_size // cfg.num_heads
    if hd % 64 == 0 and seq % 8 == 0:
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q = arr(batch, cfg.num_heads, seq, hd)
        flash_attention(q, q, q, causal=causal).block_until_ready()
    from paddle_tpu.ops.pallas.layer_norm import layer_norm_fused
    layer_norm_fused(arr(rows, cfg.hidden_size), arr(cfg.hidden_size),
                     arr(cfg.hidden_size)).block_until_ready()
    from paddle_tpu.ops.pallas.mlp import fused_mlp
    fused_mlp(arr(rows, cfg.hidden_size),
              arr(cfg.hidden_size, cfg.intermediate_size),
              arr(cfg.intermediate_size),
              arr(cfg.intermediate_size, cfg.hidden_size),
              arr(cfg.hidden_size)).block_until_ready()
    from paddle_tpu.ops.pallas.xent import xent_stats
    lbl = jnp.asarray(rng.randint(0, cfg.vocab_size, rows_x), jnp.int32)
    st = xent_stats(arr(rows_x, cfg.hidden_size),
                    arr(cfg.vocab_size, cfg.hidden_size),
                    arr(cfg.vocab_size), lbl)
    if st is not None:
        st[0].block_until_ready()
    return round(time.monotonic() - t0, 2)


def _autotune_row(presweep_s):
    """The ``autotune`` block of the row JSON: the chip's chosen tiles
    per (kernel, signature) plus where they came from."""
    from paddle_tpu.ops.pallas import autotune
    chip = autotune.chip_key()
    entries = autotune.cache().load().entries
    tiles = {k.rsplit("|", 1)[0]: v.get("blocks")
             for k, v in sorted(entries.items())
             if k.endswith("|" + chip)}
    return {"cache": autotune.cache().path, "chip": chip,
            "presweep_s": presweep_s, "tiles": tiles}


def run_row(args):
    """Run ONE bench row in this process and return its JSON-able dict."""
    global COMPILE_ONLY, TINY, DUMP_HLO, MESH_AXES, RUN_LOG
    COMPILE_ONLY = bool(args.compile_only)
    TINY = bool(args.tiny)
    DUMP_HLO = args.dump_hlo
    MESH_AXES = _parse_mesh(args.mesh)
    if args.run_log:
        from paddle_tpu.observability.runlog import RunLog
        RUN_LOG = RunLog(args.run_log)
    if MESH_AXES and args.model not in ("bert", "ernie", "gpt",
                                        "transformer_big"):
        raise SystemExit(f"--mesh supports the transformer LM rows "
                         f"(bert/ernie/gpt/transformer_big), not "
                         f"{args.model}")
    import jax
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    presweep_s = None
    if args.autotune:
        presweep_s = _autotune_presweep(args)
    if args.model == "bert":
        res = bench_bert(args.steps, args.batch or 64, args.seq,
                         use_flash=args.flash)
    elif args.model == "transformer_big":
        seq = min(args.seq, 256)
        if seq != args.seq:
            print(f"transformer_big: clamping --seq {args.seq} -> {seq} "
                  "(WMT sentence-length regime; pass --seq <=256 to "
                  "silence)", file=sys.stderr)
        res = bench_transformer(args.steps, args.batch or 32, seq)
    elif args.model == "gpt":
        res = bench_gpt(args.steps, args.batch or 16, args.seq)
    elif args.model == "gpt_decode":
        res = bench_gpt_decode(args.steps, args.batch or 16, args.seq)
    elif args.model == "gpt_serve":
        res = bench_gpt_serve(args.steps, args.batch or 8, args.seq)
    elif args.model == "gpt_serve_fleet":
        res = bench_gpt_serve_fleet(args.steps, args.batch or 4,
                                    args.seq)
    elif args.model == "ernie":
        res = bench_ernie(args.steps, args.batch or 64, args.seq,
                          use_flash=args.flash)
    elif args.model == "ctr":
        res = bench_ctr(args.steps, args.batch or 512)
    else:
        res = bench_resnet(args.steps, args.batch or 128)
    # every row names the device it ran on
    dev = jax.devices()[0]
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    if presweep_s is not None:
        res["autotune"] = _autotune_row(presweep_s)
    if res.get("mfu") is not None:
        res["vs_baseline"] = round(res["mfu"] / 0.45, 4)
    else:  # no peak (CPU), or a bandwidth-bound row with no MFU framing
        res.setdefault("vs_baseline", 0.0)
    # self-describing row: which degraded paths fired (pallas fallbacks,
    # retries) + step-time p50/p95 from the registry
    from paddle_tpu.observability import bench_telemetry
    res["telemetry"] = bench_telemetry()
    if RUN_LOG is not None:
        RUN_LOG.write({"final": True, "metric": res.get("metric"),
                       **res["telemetry"]})
        RUN_LOG.close()
    return res


_MODELS = ["bert", "resnet50", "transformer_big", "gpt", "gpt_decode",
           "gpt_serve", "gpt_serve_fleet", "ernie", "ctr"]


def _suite_list():
    """Suite rows, flagship first. Override with PT_BENCH_SUITE="bert,gpt"."""
    raw = os.environ.get(
        "PT_BENCH_SUITE", "bert,ctr,resnet50,gpt,ernie,transformer_big")
    names = [n.strip() for n in raw.split(",") if n.strip()]
    bad = [n for n in names if n not in _MODELS]
    if bad:
        raise SystemExit(f"PT_BENCH_SUITE: unknown models {bad} "
                         f"(choices: {_MODELS})")
    return names


def _failed_row(error):
    return {"metric": "bench_failed", "value": 0.0, "unit": "error",
            "vs_baseline": 0.0, "error": error}


def _run_suite(args):
    """Run every suite row in its own child process, ONE at a time (a
    chip belongs to one process; this parent never imports JAX),
    printing each row as it lands; finish by re-printing the flagship
    row with a compact suite summary. Returns the exit code: non-zero
    when any row failed."""
    import subprocess
    cap = float(os.environ.get("PT_BENCH_TIMEOUT", "1200"))
    extra = ["--steps", str(args.steps), "--seq", str(args.seq)]
    if args.batch:
        extra += ["--batch", str(args.batch)]
    if not args.flash:
        extra += ["--no-flash"]
    if args.compile_only:
        extra += ["--compile-only"]
    if args.tiny:
        extra += ["--tiny"]
    rows, failed = {}, []
    for model in _suite_list():
        # --mesh only applies to the transformer LM rows; other suite
        # rows keep their single-chip configuration
        mesh_extra = (["--mesh", args.mesh]
                      if args.mesh and model in ("bert", "ernie", "gpt",
                                                 "transformer_big")
                      else [])
        # per-model run logs: suite children must not interleave one file
        log_extra = (["--run-log", f"{args.run_log}.{model}"]
                     if args.run_log else [])
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--model", model, *extra, *mesh_extra, *log_extra],
                stdout=subprocess.PIPE, text=True, timeout=cap)
        except subprocess.TimeoutExpired:
            print(f"suite: {model} timed out after {cap:.0f}s",
                  file=sys.stderr)
            failed.append(model)
            continue
        lines = proc.stdout.strip().splitlines()
        if proc.returncode != 0 or not lines:
            print(f"suite: {model} failed (rc={proc.returncode}): "
                  f"{proc.stdout.strip()[-300:]}", file=sys.stderr)
            failed.append(model)
            continue
        rows[model] = json.loads(lines[-1])
        print(json.dumps(rows[model]), flush=True)
    if not rows:
        print(json.dumps(_failed_row(
            f"no suite row completed (failed: {failed})")))
        return 1
    flag = rows.get("bert") or next(iter(rows.values()))
    summary = dict(flag)
    summary["suite"] = {m: {"value": r["value"], "unit": r["unit"],
                            "mfu": r.get("mfu")} for m, r in rows.items()}
    summary["suite_failed"] = failed
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="all", choices=["all", *_MODELS])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--flash", action="store_true", default=True,
                    help="use the Pallas flash-attention path (default)")
    ap.add_argument("--no-flash", dest="flash", action="store_false")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile every step into the persistent XLA cache "
                         "and exit without timing (prewarm pass — timed "
                         "runs then never straddle a compile)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model configs (CI smoke: proves the fused "
                         "step compiles without paying the full-size "
                         "trace; transformer-family models only)")
    ap.add_argument("--mesh", default=None,
                    help="dp x tp sharded train step, e.g. 'dp2,tp2': "
                         "params shard with the Megatron LM plan (vocab-"
                         "dim embedding over tp), the batch over dp, and "
                         "the fused cross-entropy runs vocab-sharded. "
                         "'auto' lets the autoplan cost-model search "
                         "pick the factorization (plan recorded in the "
                         "JSON row). bert/ernie/gpt/transformer_big "
                         "only.")
    ap.add_argument("--dump-hlo", default=None,
                    help="with --compile-only: write the compiled (post-"
                         "SPMD) HLO text here (tools/compile_smoke.py "
                         "asserts no full-vocab temporaries on it)")
    ap.add_argument("--autotune", action="store_true",
                    help="pre-sweep the Pallas tile space at this row's "
                         "kernel shapes (eager, cached), then bench with "
                         "the tuned tiles; the chosen tiles are recorded "
                         "in the row JSON under 'autotune'")
    ap.add_argument("--run-log", default=None,
                    help="stream a per-step RunLog (observability JSONL) "
                         "of the timed bench steps here; suite mode "
                         "writes one file per model (PATH.<model>). "
                         "tools/run_report.py renders it.")
    args = ap.parse_args()

    if args.model == "all":
        sys.exit(_run_suite(args))
    try:
        row = run_row(args)
    except Exception as e:
        # the failure is the result: say so on stdout for whoever parses
        # rows, show the traceback, and exit non-zero
        traceback.print_exc()
        print(json.dumps(_failed_row(f"{type(e).__name__}: {e}"[:500])))
        sys.exit(1)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
