"""chip_smoke.py — the quickest proof that the trainer and the server
still start on the chip.

    python chip_smoke.py            # one TPU chip: train, train-causal, serve
    python chip_smoke.py --chips 4  # four chips: the dp2 x tp2 step only

One process. It drives the main path once through the entry points a
user calls — ``Trainer.train`` over a model's ``.loss()``, and
``ServingEngine`` — at the full width of the models the repo benches
(BERT-base, GPT-small), with weights and batches made from a seed. Each
phase prints one JSON line with its own numbers; any failed check is
fatal. It needs a TPU: on any other backend it raises before it prints
a result. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

What "right" means here, by the repo's own means: finite losses; the
first step's loss and the gradients agree with the same weights and
batch run through the XLA twins (every Pallas flag off, ``use_flash``
off) on the same device; greedy serving output agrees with per-request
``GPTDecoder.generate``; every step's compiled HLO holds a
``tpu_custom_call`` for each kernel family it should use; no kernel
refused (``pallas.fallback`` is zero) and the engine recovered from
nothing.

There is no size switch and no rehearsal option: tests import this file
and call the phase functions with tiny configs
(tests/test_chip_smoke.py).
"""

import argparse
import contextlib
import json
import re
import time

import numpy as np

SEED = 0

#: every user-settable kernel switch, off: the XLA twin of each family
TWIN_FLAGS = {"use_pallas_layer_norm": False, "use_pallas_mlp": False,
              "use_pallas_xent": False, "use_pallas_xent_bwd": False,
              "use_pallas_decode": False}

#: kernel names (ops/pallas/*.py ``kernel_call(name=...)``) each step's
#: compiled HLO must hold as a tpu_custom_call
TRAIN_KERNELS = ("flash_attention", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "layer_norm", "mlp",
                 "xent_stats", "xent_bwd_dh", "xent_bwd_dwb")
PREFILL_KERNELS = ("layer_norm", "mlp", "flash_attention")
DECODE_KERNELS = ("layer_norm", "mlp", "decode_attention")

# stated tolerances (bf16 compute, f32 master weights). The twins differ
# from the kernels in accumulation order and in where bf16 rounding
# lands, not in math.
# On the v5e (PR 21's runs) the loss differences were 2e-5 and 7e-6, the
# gradient errors 0.3% (BERT) and 1.0% (GPT, causal flash backward), the
# widest serving logit gap 0.008.
LOSS_TOL = 2e-3        # |kernel loss - twin loss| / |twin loss|
GRAD_TOL = 3e-2        # ||g_kernel - g_twin|| / ||g_twin||, whole tree
TRAINER_TOL = 1e-3     # Trainer's first loss vs the same program's loss
MESH_TOL = 1e-3        # 4-chip loss vs one-chip loss, per step (measured 7e-6)
# serving: where the engine's greedy token leaves generate()'s, the two
# candidates must be a near-tie in a third (dense, teacher-forced)
# evaluation: |logit gap| under this many logit units (the logits'
# standard deviation is about 0.5)
TIE_TOL = {"bf16": 0.03, "int8": 0.05}


def emit(record):
    print(json.dumps(record), flush=True)


# ------------------------------------------------------------- plumbing

_CACHE_EVENTS = {"hits": 0, "watching": False}


def _watch_compile_cache():
    """Count persistent-compilation-cache hits (jax.monitoring);
    installed once, on first use."""
    if _CACHE_EVENTS["watching"]:
        return
    import jax

    def on_event(event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            _CACHE_EVENTS["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    _CACHE_EVENTS["watching"] = True


@contextlib.contextmanager
def timed_compile(record, name):
    """Time a block that compiles one program; note in ``record`` whether
    the persistent cache served it."""
    _watch_compile_cache()
    hits, t0 = _CACHE_EVENTS["hits"], time.perf_counter()
    yield
    record.setdefault("compile", {})[name] = {
        "seconds": round(time.perf_counter() - t0, 2),
        "cache": "hit" if _CACHE_EVENTS["hits"] > hits else "cold"}


@contextlib.contextmanager
def flag_scope(overrides):
    from paddle_tpu.core import flags
    saved = {k: flags.get_flag(k) for k in overrides}
    flags.set_flags(overrides)
    try:
        yield
    finally:
        flags.set_flags(saved)


def kernel_counts(hlo_text):
    """{kernel name: tpu_custom_call count} from a compiled module's
    text. kernel_call names every Mosaic kernel, and the name lands in
    the custom call's op_name (".../<name>/pallas_call", wrapped as
    "transpose(jvp(<name>))" in a backward pass)."""
    counts = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def fallback_counts():
    from paddle_tpu.observability import metrics
    return dict(metrics.counter("pallas.fallback").snapshot())


def peak_bytes():
    """The allocator's high-water mark of live buffers on device 0
    (None where the backend keeps no stats). A program's own
    temporaries are not in it: those are ``program_bytes``."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


def program_bytes(compiled):
    """What the compiler says one program needs on a device."""
    m = compiled.memory_analysis()
    return {"arguments": int(m.argument_size_in_bytes),
            "temporaries": int(m.temp_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes)}


def finish(record, checks, expect_kernels, hlo):
    """Fold the checks every phase shares into ``record``: kernel
    evidence per compiled program (``hlo``: its text; ``expect_kernels``:
    the names it must hold), zero refusals, peak memory. Returns the
    record; ``record["ok"]`` is the conjunction."""
    record["kernels"] = {k: kernel_counts(t) for k, t in hlo.items()}
    for prog, want in expect_kernels.items():
        missing = [k for k in want if not record["kernels"][prog].get(k)]
        checks[f"kernels_present.{prog}"] = not missing
        if missing:
            record.setdefault("kernels_missing", {})[prog] = missing
    record["pallas_fallback"] = fallback_counts()
    checks["no_kernel_refused"] = not record["pallas_fallback"]
    record["peak_bytes_in_use"] = peak_bytes()
    record["checks"] = checks
    record["ok"] = all(checks.values())
    return record


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def tree_rel_err(got, ref):
    """||got - ref|| / ||ref|| over a whole gradient tree, in f32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(g, r):
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                           for x in jax.tree_util.tree_leaves(t))
        diff = jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            g, r)
        return jnp.sqrt(sq(diff)), jnp.sqrt(sq(r))

    d, n = norms(got, ref)
    return float(d) / max(float(n), 1e-30)


# -------------------------------------------------------- train phases

def _amp_optimizer():
    import paddle_tpu as pt
    return pt.amp.decorate(pt.optimizer.Adam(1e-4), pt.amp.bf16_policy())


def bert_batches(cfg, batch, seq, steps, seed):
    """``steps`` seeded MLM+NSP batches: masked-position gather (15% of
    the sequence, what the reference recipe gathers before the vocab
    fc) and a ragged key-padding mask, so attention runs the MASKED
    flash kernel."""
    rng = np.random.RandomState(seed)
    n_mask = max(1, int(0.15 * seq))
    out = []
    for _ in range(steps):
        lens = rng.randint(seq // 2, seq + 1, (batch,))
        out.append((
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
            rng.randint(0, cfg.vocab_size,
                        (batch, n_mask)).astype(np.int32),
            rng.randint(0, 2, (batch,)).astype(np.int32),
            np.ones((batch, n_mask), np.float32),
            np.stack([np.sort(rng.choice(seq, n_mask, replace=False))
                      for _ in range(batch)]).astype(np.int32),
            (np.arange(seq)[None, :] < lens[:, None]).astype(np.float32),
        ))
    return out


def bert_loss_fn(model, **loss_kwargs):
    def loss_fn(p, ids, mlm_labels, nsp_labels, mlm_mask, mask_pos,
                attn_mask):
        return model.apply(
            {"params": p, "state": {}}, ids, mlm_labels, nsp_labels,
            mlm_mask, attention_mask=attn_mask, mask_positions=mask_pos,
            method="loss", **loss_kwargs), 0.0
    return loss_fn


def gpt_batches(cfg, batch, seq, steps, seed):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),)
            for _ in range(steps)]


def gpt_loss_fn(model, **loss_kwargs):
    def loss_fn(p, ids):
        return model.apply({"params": p, "state": {}}, ids, method="loss",
                           **loss_kwargs), 0.0
    return loss_fn


def make_train_step(opt, loss_fn):
    """The Trainer's step contract: ``step(state, *batch) -> (loss,
    state)`` around ``opt.minimize``. tools/compile_smoke.py compiles
    this same step for its CPU HLO contracts."""
    def train_step(state, *batch):
        loss, params, opt_state, _ = opt.minimize(
            loss_fn, state["params"], state["opt"], *batch)
        return loss, {"params": params, "opt": opt_state}
    return train_step


def make_value_and_grad(opt, loss_fn):
    """(loss, grads wrt the f32 master weights) of the same bf16-policy
    forward ``opt.minimize`` differentiates — the twin comparison's
    probe."""
    import jax
    import jax.numpy as jnp
    policy = opt.policy

    def vg(params, *batch):
        def f(p):
            loss, _ = loss_fn(policy.cast_to_compute(p),
                              *policy.cast_to_compute(batch))
            return loss.astype(jnp.float32)
        return jax.value_and_grad(f)(params)
    return vg


def run_train_phase(name, model_cls, cfg, batches, loss_fn_of,
                    kernels=TRAIN_KERNELS, seed=SEED):
    """Train ``len(batches)`` steps through ``Trainer.train`` on the
    kernel path, and compare the first batch's loss and gradients with
    the XLA twins on the same weights. ``cfg.use_flash`` must be on."""
    import copy

    import jax

    from paddle_tpu.static.trainer import Trainer, TrainerConfig

    record = {"phase": name, "steps": len(batches),
              "batch": int(batches[0][0].shape[0]),
              "seq": int(batches[0][0].shape[1])}
    model = model_cls(cfg)
    # the twin: XLA attention, and each layer recomputed in the backward
    # pass — without the flash kernel a layer keeps its [B, H, T, T]
    # scores, and twelve of them do not fit the chip at these batches
    twin_cfg = copy.copy(cfg)
    twin_cfg.use_flash = False
    twin_cfg.remat = "full"
    twin = model_cls(twin_cfg)
    params = model.init(jax.random.key(seed))["params"]
    opt = _amp_optimizer()
    first = tuple(jax.device_put(a) for a in batches[0])

    # the twin comparison, before the train step donates the weights
    vg = jax.jit(make_value_and_grad(opt, loss_fn_of(model)))
    with timed_compile(record, "value_and_grad"):
        vg_exe = vg.lower(params, *first).compile()
    loss_k, grads_k = vg_exe(params, *first)
    with flag_scope(TWIN_FLAGS):
        vg_twin = jax.jit(make_value_and_grad(opt, loss_fn_of(twin)))
        with timed_compile(record, "value_and_grad_twin"):
            twin_exe = vg_twin.lower(params, *first).compile()
    loss_t, grads_t = twin_exe(params, *first)
    loss_k, loss_t = float(loss_k), float(loss_t)
    grad_err = tree_rel_err(grads_k, grads_t)
    twin_kernels = kernel_counts(twin_exe.as_text())
    del grads_k, grads_t, vg_exe, twin_exe

    state = {"params": params, "opt": opt.init(params)}
    step = jax.jit(make_train_step(opt, loss_fn_of(model)),
                   donate_argnums=(0,))
    with timed_compile(record, "train_step"):
        step_exe = step.lower(state, *first).compile()
    hlo = {"train_step": step_exe.as_text()}
    record["program_bytes"] = {"train_step": program_bytes(step_exe)}
    trainer = Trainer(step_exe, TrainerConfig(
        max_steps=len(batches), log_every=1, num_ingest_threads=1))
    t0 = time.perf_counter()
    state, stats = trainer.train(state, lambda: iter(batches))
    losses = [lv for _, lv in trainer.history]

    record.update(
        losses=losses, train_wall_s=round(time.perf_counter() - t0, 2),
        loss_kernels=loss_k, loss_twin=loss_t,
        loss_rel_diff=_rel(loss_k, loss_t), loss_tol=LOSS_TOL,
        grad_rel_err=grad_err, grad_tol=GRAD_TOL,
        trainer_first_loss_rel_diff=_rel(losses[0], loss_k)
        if losses else None,
        twin_kernels=twin_kernels)
    checks = {
        "steps_run": stats["run_steps"] == len(batches)
        and len(losses) == len(batches),
        "losses_finite": bool(losses) and bool(np.all(np.isfinite(losses))),
        "loss_matches_twin": _rel(loss_k, loss_t) <= LOSS_TOL,
        "grads_match_twin": grad_err <= GRAD_TOL,
        "trainer_loss_is_step_loss":
            bool(losses) and _rel(losses[0], loss_k) <= TRAINER_TOL,
        "twin_has_no_kernel": not twin_kernels,
    }
    return finish(record, checks, {"train_step": kernels}, hlo)


def phase_train(cfg=None, batch=64, seq=512, steps=5, seed=SEED):
    """BERT-base pretraining: masked flash, bf16 policy, scan over
    layers, the fused-xent ``.loss()`` entry."""
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    cfg = cfg or BertConfig.base()
    cfg.dropout = 0.0
    cfg.use_flash = True
    cfg.scan_layers = True
    cfg.max_position = max(cfg.max_position, seq)
    return run_train_phase(
        "train", BertForPretraining, cfg,
        bert_batches(cfg, batch, seq, steps, seed), bert_loss_fn,
        kernels=TRAIN_KERNELS + ("add_layer_norm",), seed=seed)


def phase_train_causal(cfg=None, batch=16, seq=512, steps=3, seed=SEED):
    """GPT-small causal LM: causal flash forward and backward, fused
    xent over rows = batch * (seq - 1)."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    cfg = cfg or GPTConfig.small()
    cfg.dropout = 0.0
    cfg.use_flash = True
    cfg.scan_layers = True
    cfg.max_position = max(cfg.max_position, seq)
    return run_train_phase(
        "train-causal", GPT, cfg, gpt_batches(cfg, batch, seq, steps, seed),
        gpt_loss_fn, seed=seed)


# -------------------------------------------------------- serve phase

def serve_prompts(vocab, page, prefill_len, seed):
    """A dozen seeded prompts of mixed length: one longer than
    ``prefill_len`` (chunked prefill), two sharing a full-page prefix
    (the prefix cache), the rest drawn from a few lengths so the
    per-request ``generate`` reference compiles a handful of shapes."""
    rng = np.random.RandomState(seed)
    short, mid, long_, over = (max(1, page // 4), page // 2 + page // 8,
                               page + page // 8, prefill_len + page // 8)
    shared_len = page + page // 2
    lengths = [short, mid, long_, shared_len, shared_len, over,
               mid, long_, short, shared_len, mid, long_]
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32)
               for n in lengths]
    prompts[4][:page] = prompts[3][:page]          # the shared full page
    return prompts


def phase_serve(cfg=None, kv="bf16", slots=8, page=64, prefill_len=128,
                max_len=256, max_new=24, seed=SEED):
    """GPT-small under ServingEngine: bf16 cache, or int8 pools with
    per-row scales (``kv="int8"``), against per-request generate()."""
    import copy

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import ServeConfig, ServingEngine

    cfg = cfg or GPTConfig.small()
    cfg.dropout = 0.0
    record = {"phase": "serve", "kv": kv, "slots": slots, "page": page,
              "prefill_len": prefill_len, "max_new": max_new}
    model = GPTDecoder(cfg)
    # the reference decoder: same weights, attention through XLA (the
    # prompts' odd lengths are shapes the flash kernel refuses)
    ref_cfg = copy.copy(cfg)
    ref_cfg.use_flash = False
    ref_model = GPTDecoder(ref_cfg)
    variables = model.init(jax.random.key(seed))
    engine = ServingEngine(model, variables, ServeConfig(
        num_slots=slots, page_size=page, max_len=max_len,
        prefill_len=prefill_len, cache_dtype=jnp.bfloat16,
        kv_dtype=jnp.int8 if kv == "int8" else None))
    retraces0 = metrics.counter("jit.retraces").total()
    with timed_compile(record, "prefill"):
        prefill_exe = engine.compiled_prefill()
    with timed_compile(record, "decode"):
        decode_exe = engine.compiled_decode()
    hlo = {"prefill": prefill_exe.as_text(), "decode": decode_exe.as_text()}
    record["program_bytes"] = {"prefill": program_bytes(prefill_exe),
                               "decode": program_bytes(decode_exe)}
    del prefill_exe, decode_exe

    prompts = serve_prompts(cfg.vocab_size, page, prefill_len, seed)
    t0 = time.perf_counter()
    ids = [engine.submit(p, max_new=max_new) for p in prompts]
    engine.drain()
    record["serve_wall_s"] = round(time.perf_counter() - t0, 2)
    reqs = [engine.requests[i] for i in ids]
    outs = [np.asarray(r.output) for r in reqs]
    hits = engine._prefix_cache.hits
    statuses = sorted({r.status for r in reqs})
    # one trace per step program: the live calls reuse the AOT trace
    traces = {"prefill": engine.prefill_traces, "decode": engine.decode_traces}
    recoveries = engine.recoveries
    engine.close()
    del engine

    # the reference: per-request generate() on the same device, one
    # compile per distinct prompt length
    gen = {}
    refs = []
    with timed_compile(record, "generate_all"):
        for p in prompts:
            if p.size not in gen:
                gen[p.size] = jax.jit(lambda v, pr: ref_model.apply(
                    v, pr, method=lambda q: ref_model.generate(
                        q, max_new, cache_dtype=jnp.bfloat16)))
            refs.append(np.asarray(gen[p.size](variables, p[None]))[0])

    # where a request's tokens leave the reference, a third evaluation
    # (dense causal forward, teacher-forced on the reference) says
    # whether the two candidates were a near-tie
    dense = jax.jit(lambda v, x: ref_model.apply(v, x)[0])
    exact = agree = total = 0
    gaps = []
    for p, out, ref in zip(prompts, outs, refs):
        n = min(out.size, ref.size)
        same = out[:n] == ref[:n]
        total += max_new
        if out.size == ref.size and same.all():
            exact += 1
            agree += max_new
            continue
        t = int(np.argmin(same))               # first differing position
        agree += t - p.size
        padded = np.zeros((1, max_len), np.int32)
        padded[0, :t] = ref[:t]
        logits = np.asarray(dense(variables, padded)[t - 1], np.float32)
        gaps.append(abs(float(logits[ref[t]] - logits[out[t]])))
    tol = TIE_TOL[kv]
    record.update(
        requests=len(prompts), statuses=statuses,
        prompt_lengths=[int(p.size) for p in prompts],
        recoveries=recoveries, traces=traces, prefix_hits=hits,
        token_exact_requests=exact,
        token_agreement_rate=round(agree / total, 4),
        divergence_logit_gaps=[round(g, 5) for g in gaps], tie_tol=tol)
    if gaps:
        record["note"] = (
            "token-exactness against generate() is not reachable here: "
            "seeded weights give near-tied logits and the paged path "
            "rounds bf16 products in another order; each first "
            "divergence is held to a near-tie instead")
    checks = {
        "all_done": statuses == ["done"],
        "all_lengths": all(o.size == p.size + max_new
                           for o, p in zip(outs, prompts)),
        "no_recoveries": recoveries == 0,
        "steps_traced_once": traces == {"prefill": 1, "decode": 1}
        and metrics.counter("jit.retraces").total() == retraces0,
        "chunked_prompt_served": any(
            p.size > prefill_len and r.status == "done"
            for p, r in zip(prompts, reqs)),
        "prefix_cache_hit": hits >= 1,
        "agrees_with_generate": all(g <= tol for g in gaps),
    }
    return finish(record, checks, {"prefill": PREFILL_KERNELS,
                                   "decode": DECODE_KERNELS}, hlo)


# ---------------------------------------------------- four-chip phase

def phase_mesh(cfg=None, batch=16, seq=512, steps=3, seed=SEED):
    """The dp2 x tp2 GPT-small fused sharded ``.loss()`` step against
    the one-chip step: same weights, same global batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = cfg or GPTConfig.small()
    cfg.dropout = 0.0
    cfg.use_flash = True
    cfg.scan_layers = True
    cfg.max_position = max(cfg.max_position, seq)
    record = {"phase": "mesh", "mesh": {"dp": 2, "tp": 2}, "steps": steps,
              "batch": batch, "seq": seq}
    devices = jax.devices()[:4]
    model = GPT(cfg)
    opt = _amp_optimizer()
    # host copies: each layout below places (and donates) its own
    params = jax.device_get(model.init(jax.random.key(seed))["params"])
    batches = gpt_batches(cfg, batch, seq, steps, seed)

    # one chip: everything on device 0
    one = jax.device_put(params, devices[0])
    state1 = {"params": one, "opt": opt.init(one)}
    step1 = jax.jit(make_train_step(opt, gpt_loss_fn(model)),
                    donate_argnums=(0,))
    ids0 = jax.device_put(batches[0][0], devices[0])
    with timed_compile(record, "one_chip_step"):
        exe1 = step1.lower(state1, ids0).compile()
    losses1 = []
    for (ids,) in batches:
        loss, state1 = exe1(state1, jax.device_put(ids, devices[0]))
        losses1.append(float(loss))
    del state1, exe1, one

    # four chips: Megatron-flavoured LM plan, vocab-dim table over tp
    mesh = pt.parallel.make_mesh({"dp": 2, "tp": 2}, devices=devices)
    sharded = pt.parallel.tp_lm_sharding(mesh, params)
    on_mesh = NamedSharding(mesh, PartitionSpec())
    state4 = jax.tree_util.tree_map(      # scalars like the step count
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, on_mesh),
        {"params": sharded, "opt": opt.init(sharded)})
    table = sharded["tok_emb"]["weight"]
    ids_sh = pt.parallel.shard_batch(mesh, batches[0][0])
    spread = {
        "param_devices": len({s.device for leaf in
                              jax.tree_util.tree_leaves(sharded)
                              for s in leaf.addressable_shards}),
        "table_shape": list(table.shape),
        "table_shard_on_device0": list(
            next(s.data.shape for s in table.addressable_shards
                 if s.device == devices[0])),
        "batch_shard_shapes": sorted({tuple(s.data.shape) for s in
                                      ids_sh.addressable_shards}),
        "batch_devices": len({s.device for s in
                              ids_sh.addressable_shards}),
    }
    # the state keeps its placement across steps (left to itself the
    # partitioner re-lays the optimizer slots out, and step 2 would be
    # a second program)
    step4 = jax.jit(make_train_step(opt, gpt_loss_fn(
        model, vocab_axis="tp", batch_axis="dp", mesh=mesh)),
        donate_argnums=(0,), out_shardings=(None, jax.tree_util.tree_map(
            lambda x: x.sharding, state4)))
    losses4 = []
    with mesh:
        with timed_compile(record, "mesh_step"):
            exe4 = step4.lower(state4, ids_sh).compile()
        hlo = {"mesh_step": exe4.as_text()}
        record["program_bytes"] = {"mesh_step": program_bytes(exe4)}
        for (ids,) in batches:
            loss, state4 = exe4(state4, pt.parallel.shard_batch(mesh, ids))
            losses4.append(float(loss))
    diffs = [_rel(a, b) for a, b in zip(losses4, losses1)]
    record.update(losses_mesh=losses4, losses_one_chip=losses1,
                  loss_rel_diffs=diffs, loss_tol=MESH_TOL, spread=spread,
                  all_reduces=hlo["mesh_step"].count("all-reduce"))
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses4 + losses1))),
        "mesh_matches_one_chip": all(d <= MESH_TOL for d in diffs),
        "params_on_four_devices": spread["param_devices"] == 4,
        "vocab_table_split":
            spread["table_shard_on_device0"][0] * 2 == table.shape[0],
        "batch_split_over_dp":
            spread["batch_shard_shapes"] == [(batch // 2, seq)]
            and spread["batch_devices"] == 4,
        "all_reduce_in_hlo": record["all_reduces"] > 0,
    }
    return finish(record, checks, {"mesh_step": TRAIN_KERNELS}, hlo)


# ----------------------------------------------------------------- main

def require_tpu(chips):
    """The live devices, or an exception: no CPU carry-on."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX reports platform "
            f"{devices[0].platform!r} ({devices[0].device_kind!r})")
    if len(devices) < chips:
        raise RuntimeError(f"--chips {chips} needs {chips} devices; JAX "
                           f"reports {len(devices)}")
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp2 x tp2 step and the one-chip "
                         "step it is compared with")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.parallel.autoplan.topology import chip_name
    devices = require_tpu(args.chips)
    cache_dir = enable_compile_cache()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit({"phase": "start", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
          "chip": chip_name(devices[0]), "compile_cache": cache_dir,
          "seed": SEED})

    if args.chips == 4:
        phases = [phase_mesh]
    else:
        phases = [phase_train, phase_train_causal, phase_serve,
                  lambda: phase_serve(kv="int8")]
    for phase in phases:
        record = phase()
        emit(record)
        if not record["ok"]:
            failed = [k for k, v in record["checks"].items() if not v]
            raise SystemExit(
                f"chip_smoke: phase {record['phase']!r} failed: {failed}")
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})


if __name__ == "__main__":
    main()
