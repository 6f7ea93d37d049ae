"""Global framework flags.

Ref: /root/reference/paddle/fluid/platform/flags.cc:33-451 — the reference
defines ~40 process-level gflags (allocator_strategy, eager_delete_tensor_gb,
check_nan_inf, cudnn knobs, communicator tuning) exported to Python via
pybind.cc:1355. Here flags are a plain validated registry; env vars prefixed
``PT_FLAGS_`` override defaults at import time (mirrors how the reference reads
FLAGS_* from environment in __bootstrap__).

XLA-level tuning goes through XLA_FLAGS / jax.config — not duplicated here.
"""

import os

_FLAGS = {}
_DEFS = {}


def define_flag(name, default, help_str=""):
    _DEFS[name] = (type(default) if default is not None else str, help_str)
    env = os.environ.get("PT_FLAGS_" + name)
    if env is not None:
        ty = _DEFS[name][0]
        if ty is bool:
            _FLAGS[name] = env.lower() in ("1", "true", "yes")
        else:
            _FLAGS[name] = ty(env)
    else:
        _FLAGS[name] = default


def get_flag(name):
    if name not in _FLAGS:
        raise KeyError(f"Unknown flag: {name}")
    return _FLAGS[name]


def _coerce(ty, v):
    if v is None or isinstance(v, ty):
        return v
    if ty is bool and isinstance(v, str):
        return v.lower() in ("1", "true", "yes")
    return ty(v)


def set_flags(flags_dict):
    for k, v in flags_dict.items():
        if k not in _FLAGS:
            raise KeyError(f"Unknown flag: {k}")
        _FLAGS[k] = _coerce(_DEFS[k][0], v)


def all_flags():
    return dict(_FLAGS)


# --- framework flags (counterparts cited to reference flags.cc) ---
# ref flags.cc:44 FLAGS_check_nan_inf — validate op outputs for NaN/Inf
define_flag("check_nan_inf", False, "Check outputs of every op for NaN/Inf.")
# matmul precision on TPU MXU: 'default' | 'high' | 'highest'
define_flag("matmul_precision", "default", "jax.lax matmul precision.")
# conv2d fast backward (physically-transposed dgrad kernels, ~3x on TPU).
# custom_vjp does not support forward-mode autodiff — disable for jvp/hessian
define_flag("conv_custom_vjp", True,
            "Use the TPU-fast custom conv backward (no jvp support).")
define_flag("resnet_s2d_stem", False,
            "Compute the ResNet 7x7/s2 stem as an exact 4x4/s1 conv over "
            "space-to-depth(2) input (NHWC only). Avoids the C=3 lane-"
            "padding traffic on TPU; flip after silicon measurement.")
# run Pallas kernels through the interpreter — engages the kernels even
# off-TPU (CPU testing of kernel logic)
define_flag("pallas_interpret", False,
            "Run Pallas kernels in interpreter mode (CPU testing).")
define_flag("flash_block_q", 512,
            "Flash attention query-block size (the autotuner sweeps it: "
            "tools/autotune.py sweep --kernel flash_attention).")
define_flag("flash_block_k", 512,
            "Flash attention key-block size (swept with flash_block_q).")
# escape hatch for the Pallas fused layer_norm (ADVICE r1: gate the kernel)
define_flag("use_pallas_layer_norm", True,
            "Route layer_norm through the Pallas TPU kernel; False forces "
            "the XLA twin.")
# step-fusion: chunked softmax-cross-entropy over the vocab axis (never
# materializes [batch, seq, vocab] logits or one-hot targets). The env
# spelling PT_FUSED_XENT is also honored (see ops/fused.py).
define_flag("fused_xent", True,
            "Route model .loss() train paths through the chunked/fused "
            "softmax-cross-entropy; False restores the reference "
            "logits-then-loss composition.")
define_flag("xent_chunk", 8192,
            "Vocab-axis tile size for the fused cross-entropy (rows x chunk "
            "logits are the largest temporary on the loss path).")
define_flag("use_pallas_xent", True,
            "Use the Pallas forward-stats kernel for the fused cross-"
            "entropy on TPU; False forces the chunked XLA formulation.")
# fused-xent backward: Pallas dh + dw/db kernels recomputing chunk
# probabilities from the saved logsumexp (flash-attn-2 style) vs the
# chunked-XLA recompute
define_flag("use_pallas_xent_bwd", True,
            "Use the Pallas backward kernels for the fused cross-entropy "
            "on TPU; False falls back to the chunked XLA recompute.")
# scan-over-layers remat policy for transformer encoders (models pass
# cfg.remat to override per-model): nothing | dots_saveable | full
define_flag("remat_policy", "nothing",
            "Gradient checkpointing policy for scan-over-layers encoder "
            "blocks: 'nothing' (save all), 'dots_saveable' (save matmul "
            "outputs, recompute elementwise), 'full' (recompute the whole "
            "block).")
# flash-attention backward: Pallas dq/dkv kernels (flash-attn-2 style) vs
# the recompute-based chunked-XLA fallback
define_flag("flash_pallas_bwd", True,
            "Use the Pallas flash-attention backward kernels; False falls "
            "back to recompute via the chunked XLA formulation.")
# serving fast path — paged KV cache decode attention (ops/attention.py
# paged_decode_attention; kernel in ops/pallas/decode_attention.py). The
# XLA escape hatch gathers live pages densely and masks by length — the
# parity reference, but it materializes a [slots, Tmax]-scale score
# temporary the kernel never does.
define_flag("use_pallas_decode", True,
            "Use the Pallas paged decode-attention kernel on TPU; False "
            "falls back to the XLA gather-and-mask formulation.")
define_flag("serve_page_size", 16,
            "Tokens per KV-cache page in the serving engine (multiples of "
            "8: a page is page_size rows of the pool's H*hd-wide "
            "token rows).")
define_flag("serve_slots", 4,
            "Concurrent decode slots in the serving engine (the fixed "
            "batch dimension of the jitted serve step).")
# serving resilience (serving/engine.py): bounded admission, chunked
# prefill, and crash-isolated step recovery — degraded conditions produce
# degraded service (rejected/shed/recovered requests), never lost ones
define_flag("serve_queue_limit", 0,
            "Max queued (not yet admitted) requests in the serving "
            "engine; submissions beyond it are REJECTED with a terminal "
            "status and a retriable hint. 0 = unbounded.")
define_flag("serve_default_deadline_s", 0.0,
            "Default per-request deadline (seconds from submit) applied "
            "when submit() passes none; queued requests past their "
            "deadline are shed. 0 = no default deadline.")
define_flag("serve_step_retries", 3,
            "Consecutive failed serve steps (prefill or decode) the "
            "engine recovers from — quarantine pools, re-admit in-flight "
            "requests recompute-style — before giving up and re-raising.")
define_flag("serve_chunked_prefill", True,
            "Admit prompts longer than prefill_len in fixed-shape "
            "prefill_len chunks (one prefill trace, page tables grown "
            "per chunk); False restores the long-prompt rejection.")
# prefix caching + per-request sampling (serving/engine.py +
# serving/prefix_cache.py): shared prompt prefixes map to refcounted
# read-only KV pages (prefill skipped for the hit), copy-on-write on
# divergence; sampling knobs ride per-slot traced arrays in the ONE
# decode trace
define_flag("serve_prefix_cache", True,
            "Cache full prompt pages by rolling content hash and map "
            "shared prefixes read-only into new slots (prefill skipped "
            "for the matched tokens, copy-on-write on divergence); "
            "False prefills every prompt privately.")
define_flag("serve_prefix_pages", 0,
            "Max refcount-zero (idle) pages the prefix cache retains "
            "for future hits; beyond it, least-recently-released idle "
            "entries are evicted eagerly. 0 = bounded only by the pool "
            "(idle pages are reclaimed on demand).")
define_flag("serve_top_k", 0,
            "Default per-request top-k for sampled decoding (keep the k "
            "highest logits; 0 = no top-k cut). Per-request submit() "
            "values override; greedy requests (temperature 0) ignore it.")
define_flag("serve_top_p", 0.0,
            "Default per-request nucleus (top-p) mass for sampled "
            "decoding; 0 = no nucleus cut. Per-request submit() values "
            "override; greedy requests (temperature 0) ignore it.")
define_flag("serve_kv_dtype", "",
            "Paged KV pool storage dtype for the serving engine: "
            "'int8' stores quantized values with per-row scales beside "
            "each page (roughly halving KV bytes vs bf16, 4x vs f32 — "
            "doubled servable context), dequantized inside the fused "
            "decode kernel and the XLA fallback alike. '' or 'f32' "
            "keeps the unquantized pool (ServeConfig.cache_dtype).")
# speculative decoding (serving/engine.py): a draft model proposes
# serve_spec_k tokens per active slot each round and ONE batched verify
# step scores every position against the paged KV cache — more than one
# emitted token per target-model step at high acceptance, token-exact
# with the plain path by construction (the emitted tokens are always the
# target's own per-position samples)
define_flag("serve_draft", False,
            "Enable speculative decoding in the serving engine: the "
            "draft model (ServeConfig.draft_spec, or the target model "
            "itself when none is configured — self-draft) proposes "
            "serve_spec_k tokens per slot per round and one jitted "
            "verify step scores all of them; accepted prefixes emit "
            "multiple tokens per target step, rejection rolls back via "
            "a host-side length edit.")
define_flag("serve_spec_k", 3,
            "Draft tokens proposed per active slot per speculative "
            "round (the verify window is spec_k + 1 positions); only "
            "read when serve_draft is on.")
# fleet serving (serving/fleet.py): a router in front of N ServingEngine
# replicas — least-loaded dispatch, heartbeat liveness, failover replay
# of in-flight requests, bounded respawn, graceful drain
define_flag("serve_replicas", 1,
            "Engine replicas owned by the fleet router (FleetConfig "
            "fields left unset resolve from the fleet_* flags).")
define_flag("fleet_heartbeat_s", 1.0,
            "Fleet router heartbeat timeout per replica, in seconds: a "
            "replica whose ping is older than this is marked stalled "
            "(no new dispatch); silent past heartbeat_dead_factor x "
            "this, it is declared dead and failed over.")
define_flag("fleet_respawn_budget", 3,
            "Consecutive failures (crash, heartbeat death, failed "
            "respawn) the fleet router tolerates per replica before it "
            "stops respawning that replica and leaves it dead.")
define_flag("fleet_drain_timeout_s", 120.0,
            "Wall-clock budget for FleetRouter.drain() to retire every "
            "accepted request while quiescing replicas one at a time; "
            "0 = unbounded.")
define_flag("fleet_canary_weight", 0.1,
            "Fraction of fresh fleet traffic routed to the canary "
            "version while one is deployed (deploy(..., canary=True)); "
            "in [0, 1]. A request never switches versions mid-stream.")
define_flag("fleet_autoscale_min", 1,
            "Floor on live replicas the fleet autoscaler may drain down "
            "to (never below 1).")
define_flag("fleet_autoscale_max", 0,
            "Ceiling on live replicas the fleet autoscaler may spawn up "
            "to; 0 disables autoscaling entirely.")
define_flag("fleet_prefill_replicas", 0,
            "Prefill/decode disaggregation: carve the first N fleet "
            "replicas out as dedicated prefill replicas (role "
            "'prefill'); the rest serve decode. Prefill-heavy requests "
            "(prompt longer than the engine's prefill_len) run their "
            "chunked prefill plus first token on a prefill replica, "
            "then hand off token-exactly to a decode replica via the "
            "adopt() replay path. 0 = every replica mixed-mode.")
define_flag("fleet_scale_cooldown_s", 5.0,
            "Minimum seconds between fleet autoscaling actions (spawn "
            "or drain-then-retire), so one load spike produces one "
            "deliberate step, not a thrash.")
define_flag("fleet_deploy_verify", 1,
            "Verify a deployed checkpoint against its crc32 integrity "
            "manifest before any replica is touched (FleetRouter."
            "deploy); a corrupt manifest aborts the rollout with the "
            "fleet still serving the old version. 0 skips verification.")
# profiler
define_flag("profiler_dir", "/tmp/paddle_tpu_trace", "Profiler trace dir.")
# data loader
define_flag("reader_queue_size", 2, "Device prefetch depth for DataLoader.")
# distributed
define_flag("dist_heartbeat_interval_s", 10.0, "Heartbeat interval (DCN).")
define_flag("dist_heartbeat_timeout_s", 300.0, "Peer failure timeout.")
# fault tolerance — remote I/O retry (core/retry.py RetryPolicy; the ONE
# retry implementation: io/fs.py remote primitives, checkpoint mirroring,
# and ElasticRunner restart pacing all resolve these defaults)
define_flag("retry_max_attempts", 4,
            "Max attempts (1 = no retry) for remote I/O operations.")
define_flag("retry_backoff_base_s", 0.05,
            "Initial retry backoff in seconds (grows per attempt).")
define_flag("retry_backoff_max_s", 2.0,
            "Cap on a single retry backoff sleep, in seconds.")
define_flag("retry_backoff_multiplier", 2.0,
            "Backoff growth factor between attempts.")
define_flag("retry_jitter", 0.25,
            "Backoff jitter fraction in [0, 1]: each sleep is scaled by a "
            "uniform factor in [1-j, 1+j] to decorrelate retry storms.")
define_flag("retry_deadline_s", 60.0,
            "Overall deadline for one retried operation (<= 0 = none): "
            "give up rather than start a sleep that would cross it.")
# observability (observability/): Trainer step telemetry defaults —
# TelemetryConfig fields left None resolve from these, so a run can be
# instrumented with env vars alone (PT_FLAGS_telemetry=1
# PT_FLAGS_telemetry_run_log=/runs/x/run.jsonl python train.py)
define_flag("telemetry", False,
            "Default-enable Trainer step telemetry (TelemetryConfig "
            "fields left unset resolve from the telemetry_* flags).")
define_flag("telemetry_run_log", "",
            "Default RunLog JSONL path for step telemetry ('' keeps "
            "records in memory only).")
define_flag("telemetry_every_n", 1,
            "Emit a step telemetry record every N steps.")
# live observability plane (observability/exporter.py + watchdog.py):
# a stdlib HTTP server scraping the whole metrics registry in Prometheus
# text exposition, plus serving SLO targets and the anomaly watchdog
define_flag("metrics_port", 0,
            "Serve /metrics (Prometheus text exposition of the metrics "
            "registry) and /healthz on this port while a Trainer or "
            "ServingEngine runs; 0 disables the exporter.")
define_flag("slo_ttft_s", 0.0,
            "Serving SLO: max time-to-first-token in seconds; retired "
            "requests above it count serve.slo_violations{kind=ttft} and "
            "lower serve.goodput. 0 = unbounded.")
define_flag("slo_token_latency_s", 0.0,
            "Serving SLO: max mean per-token decode latency in seconds; "
            "violations count serve.slo_violations{kind=token_latency}. "
            "0 = unbounded.")
define_flag("watchdog", False,
            "Default-enable the runtime anomaly watchdog (slow-step, "
            "ingest-stall, steady-state-retrace, goodput-collapse "
            "detection) in the Trainer and serving loops.")
define_flag("watchdog_window", 64,
            "Rolling window (steps) for the watchdog's step-time median.")
define_flag("watchdog_slow_factor", 3.0,
            "A step slower than slow_factor x the rolling median latches "
            "a slow_step anomaly.")
define_flag("watchdog_stall_s", 1.0,
            "Per-step ingest-channel wait above this latches an "
            "ingest_stall anomaly.")
define_flag("watchdog_goodput_min", 0.5,
            "serve.goodput below this (after enough retired requests) "
            "latches a goodput_collapse anomaly.")
# distributed tracing + flight recorder (observability/trace.py +
# flight.py): fleet-durable trace contexts and the anomaly-triggered
# evidence bundle
define_flag("trace_fleet", True,
            "Mint durable fleet-wide trace contexts at FleetRouter."
            "submit() and carry them across dispatch/failover hops so "
            "one trace id covers a request's whole life; off falls back "
            "to engine-run-scoped ids.")
define_flag("flight_ring", 256,
            "Per-process flight-recorder ring size (recent trace events "
            "+ metric deltas kept in memory for anomaly bundles); 0 "
            "disables recording.")
define_flag("flight_profile_s", 0.0,
            "Seconds of jax.profiler XPlane capture to include in a "
            "flight bundle (0 skips the capture — dumps stay instant).")
define_flag("flight_dir", "/tmp/paddle_tpu_flight",
            "Directory flight-recorder bundles are dumped into (one "
            "timestamped subdir per dump).")
# training guardian (static/guardian.py): in-trace non-finite
# containment, host-side loss-spike detection, and the skip -> re-read ->
# rollback mitigation ladder (GuardianConfig fields left unset resolve
# from these)
define_flag("trainer_rollback_budget", 3,
            "Consecutive checkpoint rollbacks the training guardian may "
            "perform without an intervening healthy checkpoint before it "
            "gives up and re-raises (TrainingDiverged), mirroring "
            "serve_step_retries exhaustion semantics.")
define_flag("trainer_spike_factor", 10.0,
            "A finite loss above spike_factor x the rolling median of "
            "recent healthy losses latches a loss_spike anomaly and "
            "advances the guardian's mitigation ladder.")
define_flag("trainer_ingest_fail_fast", True,
            "Abort the Trainer step loop as soon as an ingest reader "
            "thread dies (the error still raises with full context); "
            "False drains the surviving readers first and raises at "
            "end of stream.")
# checkpoint integrity (io/checkpoint.py): per-leaf crc32 manifests
# written beside each step and checked on restore
define_flag("checkpoint_verify", True,
            "Verify restored checkpoint leaves against the step's crc32 "
            "manifest; a corrupt leaf degrades to a clean mirror re-fetch "
            "or the previous committed step instead of loading garbage.")
# fault tolerance — checkpoint mirroring (io/checkpoint.py): False = a
# mirror push that still fails after retries is logged and queued for the
# next save (training continues on the durable local copy); True = raise
# into the train loop (pre-fault-tolerance behavior)
define_flag("strict_mirror", False,
            "Fail training when a checkpoint remote-mirror push fails "
            "after retries, instead of degrading to queue-and-continue.")
# auto-parallelism (parallel/autoplan): cost-model-driven mesh planning —
# model + topology in, dp x tp x pp mesh + shardings out
define_flag("auto_mesh", False,
            "Treat an unset strategy as strategy='auto' in "
            "fleet.build_mesh / fleet.distributed_optimizer: resolve the "
            "mesh through the autoplan cost-model search (requires a "
            "prior fleet.auto_plan(...) or uses its cached plan).")
define_flag("autoplan_topology", "",
            "Topology preset the autoplan search prices against (e.g. "
            "cpu4, v5e-8, 2xv5e-16); '' auto-detects from jax.devices().")
define_flag("autoplan_hbm_fraction", 0.9,
            "Fraction of per-chip HBM the planner may budget; candidates "
            "whose memory estimate exceeds it are pruned with a recorded "
            "reason.")
define_flag("quant_allreduce", "auto",
            "Data-parallel gradient all-reduce strategy: 'auto' lets the "
            "autoplan cost model choose between the f32 psum and the "
            "chunked int8 quantize->psum->dequant collective per "
            "topology (quantized wins on DCN-bandwidth dp axes, loses "
            "on ICI); 'on' forces quantized, 'off' forces f32.")
define_flag("quant_allreduce_chunk", 65536,
            "Chunk size (elements) of the quantized all-reduce: each "
            "chunk carries one shared f32 scale, so smaller chunks "
            "track gradient dynamic range tighter at 4/chunk bytes of "
            "scale overhead on the wire.")
# Pallas tile autotuner (ops/pallas/autotune.py): sweep candidate block
# sizes on first eager contact with a (kernel, shape, chip) triple, cache
# winners, and feed measured achieved-flops/s into the autoplan cost model
define_flag("autotune", False,
            "Autotune Pallas kernel tile sizes: sweep candidate block "
            "shapes on first eager contact with a (kernel, shape, chip) "
            "triple and reuse the cached winner afterwards; False keeps "
            "the static defaults.")
define_flag("autotune_cache", "/tmp/paddle_tpu_autotune.json",
            "JSON cache file for autotuned tile winners (and the measured "
            "per-tile times the autoplan cost model consumes).")
# fused MLP/GLU block (ops/pallas/mlp.py) — the first kernel built on the
# shared primitive core; used by the GPT/BERT feed-forward
define_flag("use_pallas_mlp", True,
            "Route the transformer feed-forward through the fused Pallas "
            "MLP kernel (never materializes the [rows, intermediate] "
            "activation in HBM); False keeps the unfused XLA composition.")
