"""Continuous-batching serving engine over the paged KV cache.

The cache protocol (what a served model implements; GPTDecoder and
HybridDecoder do, and nothing else here knows a model's insides). The
engine holds a model's caches as TWO parts and donates them, as one
pair ``(pools, state)``, to its two step programs, which hand the pair
back:

  * the page pools, ``model.init_paged_caches(num_pages, page_size,
    dtype, kv_dtype)``: a list of K/V pools ``{"k", "v"} [num_pages,
    page_size, KVH*hd]`` (ops/attention.py), one per attention layer.
    They grow with the context: allocated by pages, duplicated by
    ``copy_pages``, shared through the prefix cache.
  * the per-slot state, ``model.init_slot_state(num_slots, dtype)``: a
    pytree with one entry per slot in every leaf, fixed in size, for
    layers that carry a recurrent state (models/hybrid.py). It belongs to
    a slot, not to pages: no page operation sees it. A model without the
    method has none (``()``) and runs as it always did.
  * the slots' pending tokens, ONE device array ``[num_slots]`` int32
    that both step programs take and hand back (below, "the round"):
    the engine's own, no model sees it.
  * ``model.paged_prefill_chunk(prompt, starts, chunk_lengths, caches,
    page_rows, write_floor)`` and ``model.paged_decode_step(tokens,
    caches, page_table, lengths, active)`` -> (logits, new pools). A
    model WITH state takes ``state=`` (and the chunk's ``slots=``) too
    and returns (logits, new pools, new state): a chunk at ``starts ==
    0`` begins its slot's state from zeros inside the program, so
    admission, release, preemption and recovery need no device work for
    it; padding and inactive slots leave it alone. What has not been
    kept cannot be served: the prefix cache (a hit skips positions whose
    state nobody holds) and speculative decoding (a rejected proposal
    would have to roll the state back) are refused for such a model.
  * a model with routed experts (nn.HeldExperts) returns one value
    more from both methods: the rows routed to each expert it holds,
    int32 ``[expert layers, held experts]``. Both step programs hand it
    back beside the tokens and it rides the same trailing fetch (no
    sync of its own); what it says goes into ``serve.step``'s counts
    (``moe_rows``, ``moe_rows_max``, ``moe_experts_hit``, ``moe_calls``;
    ``moe_items`` / ``moe_item_slots``: the grouped kernel's work items
    that carried rows and its static grid's items, by the model's
    ``moe_grid`` at the program's row count) and the counters
    ``serve.moe.rows`` / ``serve.moe.experts_idle`` /
    ``serve.moe.items``. A model without experts returns nothing there
    and counts nothing.

The round: the fetch trails the launch by one round. step() number n
(1) admits: every admitted request's prefill chunks are LAUNCHED, none
waited for, queued on the device behind round n-1, which is still
running; (2) grows pages and launches decode round n. Each slot's
pending token comes from the device's token array: the decode program
returns it (its samples ARE the next round's input; an inactive slot
keeps its token) and the prefill program writes the admitted slot's
first token into it, so no token value crosses to the host between two
launches; (3) only then waits for round n-1's tokens and this step's
first tokens (`_fetch`) and advances the requests that sat in those
slots WHEN THE ROUND WAS LAUNCHED (`_Flight.rows`, not `_running`). What
a launch needs it knows without the token's value: a slot's length and
its count of generated tokens grow by one a round whatever is sampled,
the page it needs next follows from the length, the next draw's key is
fold(seed, count), and max_new is reached by count; so `_lengths`,
`_gen_counts` and the max_new test live at the launch, and only the
token itself (`req.tokens`, EOS, the lifecycle events) waits for the
read. Consequences, all held here:

  * EOS is learnt one round late: a request whose round n-1 token is
    its eos_id has a row in round n already. That row's token is
    discarded (`late_rows`), its K/V write lands in a page the request
    still owned at the launch, and the slot is released at the read. A
    request that ends by max_new gets no surplus row. Output tokens are
    the serial engine's, token for token, greedy and sampled alike.
  * a slot or page released at the read may be taken by the next
    step's admission while the round in flight still touches it: safe
    by the device's program order (the later prefill is queued behind
    that round; a model's per-slot state is zeroed inside the prefill
    program).
  * a failure surfaces at the trailing fetch: `_recover` drops the
    rounds in flight and every request replays from the tokens READ.
  * the host arrays a launch takes are copies (`_launch_round`).
  * a speculative engine reads before it launches (after a speculative
    round a slot's length depends on what was accepted): `step()` reads
    everything in flight before `_spec_round`, which is the only round
    that waits for its own tokens. That follows from `_spec_on`, not
    from a switch: there is no serial plain round.
  * cancel(), preemption and recovery take a request's rows out of the
    flights; close() lets the round in flight go unread; drain() ends
    when nothing is queued, running or in flight; export_inflight()
    reads only tokens already read.

Architecture (the three serving invariants):

  * ONE jitted decode step, fixed slot count, donated page pools — its
    shapes never depend on which requests are live, so admissions,
    completions and ragged lengths never retrace it (`decode_traces`
    counts trace-time entries; tools/compile_smoke.py asserts == 1
    across admission waves).
  * Paged KV memory — requests own pages, not a [B, Tmax] rectangle.
    A finished request frees its pages between steps; an admitted one
    takes pages for its prompt and grows one page at a time as it
    decodes. The page table / length / active arrays are tiny host
    numpy state, copied to the step each call (values change, shapes
    don't).
  * Prefill-on-admit — a second fixed-shape jit (prompts padded to
    `prefill_len`) runs once per admission, writes the prompt K/V into
    the request's pages and samples the first token into the device's
    token array, so time-to-first-token is one forward, not
    `prompt_len` decode steps.

Telemetry (PR-4 registry): serve.queue_depth / serve.active_slots
gauges, serve.ttft_s + serve.token_latency_s histograms, serve.tokens +
serve.requests{status} + serve.page_stalls + serve.rounds_overlapped +
serve.late_rows + serve.kv_rows_written counters; optional per-step
RunLog records (`ServeConfig.run_log`) that tools/run_report.py renders.

Live observability plane (this layer's serving half):

  * per-request lifecycle traces — every request carries a trace id and
    emits timestamped RunLog events (`submitted`, `admitted`,
    `prefill_done`, `first_token`, `preempted`, `resumed`,
    `retired{reason}`). Pure host work (a clock read + a JSONL append at
    request-rate, not token-rate): no device sync is added to the decode
    hot path, asserted by a flush-spy test. `tools/run_report.py
    --serve` reconstructs per-slot timelines from these events.
  * SLO/goodput accounting — `ServeConfig.slo_ttft_s` /
    `slo_token_latency_s` (flag-resolvable) classify every retirement;
    `serve.goodput` (gauge: fraction of retired requests inside every
    SLO) and `serve.slo_violations{kind}` are the objective function the
    ROADMAP's SLO-aware scheduler optimizes.
  * `jit.retraces{fn=serve.decode|serve.prefill}` — the traced-once
    invariant as a counter: any steady-state recompile is visible to
    the watchdog and /metrics, not just to compile-smoke tests.
  * `ServeConfig.metrics_port` starts the /metrics exporter
    (observability/exporter.py) for the run; `ServeConfig.watchdog`
    attaches the anomaly watchdog (observability/watchdog.py).

Resilience layer (degraded conditions produce degraded service, never
lost requests — terminal statuses: done | rejected | shed | cancelled |
failed):

  * chunked prefill — prompts up to max_len are admitted as
    ceil(len / prefill_len) calls of the ONE prefill trace
    (the model's paged_prefill_chunk), page tables grown per chunk; the
    long-prompt rejection class is gone (`serve_chunked_prefill` flag).
  * bounded admission — submit() takes optional deadline_s / priority;
    the `serve_queue_limit` flag bounds the queue, and over-limit or
    infeasible-deadline submissions get a terminal `rejected` status
    with `req.retriable = True` (back off and resubmit). Admission picks
    highest-priority / earliest-deadline first; the pool-deadlock
    preemption victim becomes lowest-priority / latest-deadline (the
    old youngest-first order is the all-defaults special case).
  * crash-isolated step recovery — `fault_point("serve.prefill")` /
    `fault_point("serve.step")` hooks plus an exception barrier around
    both jitted calls: on failure the engine quarantines device state
    (page pools are donated, hence poisoned), rebuilds them, and
    re-admits every in-flight request recompute-style — the host-side
    prompt + generated tokens are the durable state, so a recovered
    greedy request finishes token-exact. Bounded by a RetryPolicy
    budget (`serve_step_retries` consecutive failures, then the
    engine fails every request and re-raises). Recovery re-runs the
    SAME step programs: a kernel that fails at run time is a failed
    step that surfaces, never a switch to another implementation.
  * watchdog mitigation — goodput_collapse / ingest_stall anomalies
    invoke the engine's load-shedding action: expired-deadline queued
    requests are shed first, else the single lowest-priority one
    (terminal `shed` status, serve.shed{cause}).
"""

import collections
import dataclasses
import itertools
import threading
import time
import typing
import uuid

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.core.flags import get_flag
from paddle_tpu.observability import flight as _flight
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability import spans as _spans
from paddle_tpu.observability.spans import phase, span
from paddle_tpu.ops.pallas.moe_mlp import live_items
from paddle_tpu.testing.chaos import fault_point


@dataclasses.dataclass
class ServeConfig:
    num_slots: int = None        # None -> serve_slots flag
    page_size: int = None        # None -> serve_page_size flag
    max_len: int = 256           # per-request cap: prompt + generated
    prefill_len: int = 64        # padded admission prompt length (fixed)
    num_pages: int = None        # None -> num_slots * ceil(max_len/page)
    cache_dtype: typing.Any = jnp.float32
    kv_dtype: typing.Any = None  # None -> serve_kv_dtype flag; jnp.int8
    #                              stores paged K/V quantized (per-token
    #                              symmetric scales ride the page pool)
    temperature: float = 0.0     # 0 = greedy; >0 samples per step
    top_k: int = None            # default per-request top-k (None -> flag)
    top_p: float = None          # default per-request top-p (None -> flag)
    seed: int = 0
    prefix_cache: bool = None    # None -> flag serve_prefix_cache
    prefix_pages: int = None     # None -> flag serve_prefix_pages
    #                              (max idle cached pages; 0 = pool-bounded)
    eos_id: int = None           # default EOS (submit() can override)
    default_max_new: int = 32
    run_log: str = None          # per-step RunLog JSONL path
    prefetch: int = None         # host->device staging depth (None->flag)
    slo_ttft_s: float = None     # None -> flag; 0 = unbounded
    slo_token_latency_s: float = None   # None -> flag; 0 = unbounded
    metrics_port: int = None     # None -> flag metrics_port; 0 = off
    watchdog: object = None      # None -> flag; True or WatchdogConfig
    queue_limit: int = None      # None -> flag serve_queue_limit; 0 = off
    default_deadline_s: float = None   # None -> flag; 0 = none
    step_retries: int = None     # None -> flag serve_step_retries
    chunked_prefill: bool = None  # None -> flag serve_chunked_prefill
    model_version: str = None    # "model_id@version" identity tag the
    #                              fleet router stamps on a replica's
    #                              engine; surfaces in slo_stats() and
    #                              trace records (per-version SLO plane)
    # speculative decoding: a draft model proposes spec_k tokens per
    # active slot per round; ONE jitted verify step scores every window
    # position against the paged cache and the engine emits the
    # accepted prefix + one target token — token-identical to the plain
    # path by construction (emitted tokens are always the target's own
    # per-position samples under the fold(seed, count) keys)
    draft: bool = None           # None -> serve_draft flag
    spec_k: int = None           # None -> serve_spec_k flag
    draft_spec: typing.Any = None   # GPTConfig of the draft model;
    #                                 None + draft=True = self-draft
    #                                 (draft == target: the plumbing
    #                                 probe with ~100% acceptance)
    draft_variables: typing.Any = None  # draft weights ({"params": ...})
    draft_checkpoint: str = None  # else: restore newest step from this
    #                               CheckpointManager path

    def resolve(self):
        if self.num_slots is None:
            self.num_slots = get_flag("serve_slots")
        if self.page_size is None:
            self.page_size = get_flag("serve_page_size")
        if self.slo_ttft_s is None:
            self.slo_ttft_s = get_flag("slo_ttft_s")
        if self.slo_token_latency_s is None:
            self.slo_token_latency_s = get_flag("slo_token_latency_s")
        if self.queue_limit is None:
            self.queue_limit = int(get_flag("serve_queue_limit"))
        if self.default_deadline_s is None:
            self.default_deadline_s = float(
                get_flag("serve_default_deadline_s"))
        if self.step_retries is None:
            self.step_retries = int(get_flag("serve_step_retries"))
        if self.chunked_prefill is None:
            self.chunked_prefill = bool(get_flag("serve_chunked_prefill"))
        if self.top_k is None:
            self.top_k = int(get_flag("serve_top_k"))
        if self.top_p is None:
            self.top_p = float(get_flag("serve_top_p"))
        if self.prefix_cache is None:
            self.prefix_cache = bool(get_flag("serve_prefix_cache"))
        if self.prefix_pages is None:
            self.prefix_pages = int(get_flag("serve_prefix_pages"))
        if self.kv_dtype is None:
            f = str(get_flag("serve_kv_dtype")).lower()
            if f == "int8":
                self.kv_dtype = jnp.int8
            elif f not in ("", "f32", "float32"):
                raise ValueError(f"serve_kv_dtype={f!r}: expected "
                                 "'int8' or ''/'f32'")
        elif self.kv_dtype in ("", "f32", "float32"):
            self.kv_dtype = None       # explicit f32 = the plain pool
        elif isinstance(self.kv_dtype, str):
            self.kv_dtype = jnp.dtype(self.kv_dtype).type
        if self.draft is None:
            self.draft = bool(get_flag("serve_draft"))
        if self.draft_spec is not None or self.draft_checkpoint:
            self.draft = True    # an explicit draft model implies draft
        if self.spec_k is None:
            self.spec_k = int(get_flag("serve_spec_k"))
        if self.draft:
            enforce(self.spec_k >= 1,
                    f"serve_spec_k={self.spec_k}: speculative decoding "
                    "needs at least one draft proposal per round")
        pages_per_slot = -(-self.max_len // self.page_size)
        if self.num_pages is None:
            self.num_pages = self.num_slots * pages_per_slot
        enforce(self.prefill_len <= self.max_len,
                "prefill_len must not exceed max_len")
        enforce(self.num_pages >= pages_per_slot,
                f"num_pages={self.num_pages} cannot hold even one "
                f"max_len={self.max_len} request "
                f"({pages_per_slot} pages of {self.page_size}) — the "
                "preemption guarantee needs a lone request to fit")
        return self


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray            # true (unpadded) prompt, int32 [L]
    max_new: int
    eos_id: int = None
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "queued"        # queued -> running -> terminal (done |
    #                               rejected | shed | cancelled | failed)
    slot: int = None
    pages: list = dataclasses.field(default_factory=list)
    # prefix-cache pages mapped read-only into the slot's table; ALWAYS a
    # contiguous row prefix: table row = shared_pages ++ pages
    shared_pages: list = dataclasses.field(default_factory=list)
    temperature: float = 0.0      # per-request sampling (set at submit)
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0                 # per-request PRNG seed: token i of this
    #                               request samples with fold(seed, i), so
    #                               replay after preemption / recovery /
    #                               re-route re-draws identically
    submit_t: float = None
    first_token_t: float = None
    done_t: float = None
    device_prompt: typing.Any = None   # staged [1, Lp] chunks (async put)
    trace_id: str = None          # lifecycle trace id: engine-run-scoped
    #                               when minted here, fleet-durable when
    #                               adopt() received a router context
    span_id: str = None           # this hop's span in the fleet trace
    parent_span_id: str = None    # the causal parent hop (None = root)
    trace: list = dataclasses.field(default_factory=list)  # (event, t)
    preemptions: int = 0
    retire_reason: str = None     # "eos"|"length" or the terminal cause
    slo_ok: bool = None           # every configured SLO met at retire
    priority: int = 0             # higher admits first, evicts last
    deadline_t: float = None      # absolute clock() deadline, or None
    retriable: bool = False       # rejected-but-worth-resubmitting hint
    recoveries: int = 0           # times re-admitted after a step crash
    spec_tokens: int = 0          # tokens this request gained beyond
    #                               one-per-target-step (accepted draft
    #                               proposals) — the per-request
    #                               speculative-vs-plain accounting

    @property
    def output(self):
        """prompt + generated tokens (the generate()-shaped sequence)."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])


@dataclasses.dataclass
class _Flight:
    """Tokens a launched program computes that the host has not read:
    the device's ``[slots]`` token array as that program returned it,
    and which request sat in which slot WHEN IT WAS LAUNCHED (the
    lagged read must not ask ``_running``, which has moved on)."""
    toks: typing.Any              # device int32 [slots]
    rows: dict                    # slot -> Request, active at the launch
    first: bool = False           # an admission's prefill: the row's
    #                               token is its request's first
    routed: tuple = ()            # device int32 [expert layers, held]
    #                               of each program behind this flight


class ServingEngine:
    """submit()/step()/drain() continuous batching for any model that
    implements the cache protocol (the module's docstring)."""

    def __init__(self, model, variables, config=None, clock=time.perf_counter):
        config = config or ServeConfig()
        # what the state is follows from the model
        self._stateful = hasattr(model, "init_slot_state")
        if self._stateful:
            enforce(not config.prefix_cache,
                    "prefix_cache=True cannot serve a model with a "
                    "per-slot recurrent state: a prefix hit skips the "
                    "prefill of positions whose state no page holds")
            config = dataclasses.replace(config, prefix_cache=False)
        self.cfg = config.resolve()
        cfg = self.cfg
        enforce(not (self._stateful and cfg.draft),
                "draft=True cannot serve a model with a per-slot "
                "recurrent state: a rejected proposal would have to roll "
                "the state back, and no snapshot of it is kept")
        self._model = model
        self._params = variables["params"]
        # a model with routed experts: the grouped kernel's (row tile,
        # items a layer's call) at a decode round's and a chunk's rows
        grid = getattr(model, "moe_grid", None)
        self._moe_grid = None if grid is None else {
            t: model.apply({"params": self._params, "state": {}}, t,
                           method=grid)
            for t in (cfg.num_slots, cfg.prefill_len)}
        self.version = cfg.model_version
        self._clock = clock
        self._pages_per_slot = -(-cfg.max_len // cfg.page_size)
        self._caches = model.init_paged_caches(
            cfg.num_pages, cfg.page_size, dtype=cfg.cache_dtype,
            kv_dtype=cfg.kv_dtype)
        self._state = self._init_state()
        self._state_bytes_per_slot = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self._state)
        ) // cfg.num_slots
        # speculative decoding: the draft model keeps its OWN page
        # pools, built with the SAME page count/size so one page table
        # indexes both (draft pages and target pages for a slot live at
        # identical pool indices)
        self._spec_on = bool(cfg.draft)
        self._draft_model = None
        self._draft_params = None
        self._draft_caches = None
        if self._spec_on:
            self._draft_model, self._draft_params = self._resolve_draft()
            self._draft_caches = self._draft_model.init_paged_caches(
                cfg.num_pages, cfg.page_size, dtype=cfg.cache_dtype,
                kv_dtype=cfg.kv_dtype)

        s = cfg.num_slots
        self._page_table = np.zeros((s, self._pages_per_slot), np.int32)
        self._lengths = np.zeros(s, np.int32)
        self._active = np.zeros(s, bool)
        # every slot's pending token (the one its next decode row feeds)
        # lives ON THE DEVICE: the decode program hands its samples back
        # as this array and the prefill program writes an admitted
        # slot's first token into it, so no token value crosses to the
        # host between two launches (the module's docstring, "the round")
        self._tokens_dev = jnp.zeros(s, jnp.int32)
        # launched and not yet read, oldest first: at most one decode
        # round between two step() calls. Rows are dropped as their
        # slots are freed, so a flight only ever names running requests
        self._inflight = []                 # graft-guard: self._lock
        self._routed_read = []              # graft-guard: self._lock
        self.rounds_overlapped = 0    # rounds launched before the round
        #                               before them was read
        self.late_rows = 0            # rows computed for a request that
        #                               had already ended at EOS
        self.kv_rows_written = 0      # K/V rows the launched programs
        #                               write into the pools: a running
        #                               slot's a round, a chunk's real
        #                               tokens past its write floor
        self._free_slots = list(range(s))
        self._free_pages = collections.deque(range(cfg.num_pages))
        # per-slot sampling state: traced [slots] VALUES of the one
        # decode jit (updated on admit, never retrace axes)
        self._temps = np.zeros(s, np.float32)
        self._top_ks = np.zeros(s, np.int32)
        self._top_ps = np.zeros(s, np.float32)
        self._seeds = np.zeros(s, np.uint32)
        self._gen_counts = np.zeros(s, np.int32)
        from paddle_tpu.serving.prefix_cache import PrefixCache
        # refcounted content-hash index over the page pool; None = off
        self._prefix_cache = (      # graft-guard: self._lock
            PrefixCache(cfg.page_size, max_idle_pages=cfg.prefix_pages)
            if cfg.prefix_cache else None)
        self.prefill_tokens_skipped = 0   # prompt tokens never prefilled
        #                                   (covered by prefix-cache hits)
        # One reentrant lock guards the request tables: clients may
        # submit()/cancel() from their own threads while step()/drain()
        # run elsewhere, and the watchdog's anomaly callback re-enters
        # shed_queued() from under a step already holding the lock.
        self._lock = threading.RLock()
        self._queue = collections.deque()   # graft-guard: self._lock
        self._running = {}                  # graft-guard: self._lock
        self.requests = {}   # id -> Request; graft-guard: self._lock
        self._ids = itertools.count()
        self._step_no = 0
        self._base_key = jax.random.key(cfg.seed)
        self.decode_traces = 0
        self.prefill_traces = 0
        self.draft_traces = 0
        self.draft_prefill_traces = 0
        self.verify_traces = 0
        self.spec_proposed = 0        # draft tokens offered to verify
        self.spec_accepted = 0        # proposals the target confirmed
        self.spec_rollbacks = 0       # proposals rejected (length edit)
        self.spec_rounds = 0          # speculative rounds run
        self.spec_slot_rounds = 0     # per-slot round participations
        #                               (denominator of the per-slot
        #                               tokens-per-target-step win)
        self.target_steps = 0         # target-model steps (decode OR
        #                               verify) — tokens/target_steps is
        #                               the speculation win
        self.recoveries = 0           # step crashes recovered (engine-wide)
        from paddle_tpu.core.retry import RetryBudget, RetryPolicy
        self._retry_budget = RetryBudget(
            RetryPolicy(max_attempts=cfg.step_retries + 1), "serve.step")

        # host->device prompt staging reuses the DataLoader placement path
        # (async device_put; depth knob = the reader_queue_size flag), so
        # admission never pays the transfer inside step()
        from paddle_tpu.data.loader import DataLoader
        self._stager = DataLoader(None, prefetch=cfg.prefetch)

        self.anomaly_sink = None      # fleet router watchdog uplink
        self.replica = None           # fleet replica index; stamps every
        #                               trace event once the router sets it
        self._run_log = None
        self._own_run_log = False
        if cfg.run_log:
            if isinstance(cfg.run_log, str):
                from paddle_tpu.observability.runlog import RunLog
                self._run_log = RunLog(cfg.run_log)
                self._own_run_log = True
            else:                      # an already-open RunLog: the caller's
                self._run_log = cfg.run_log
        if self._run_log is not None:
            # wall/monotonic anchor: the fleet-trace merge rebases this
            # log's perf_counter event times onto the wall clock with it
            from paddle_tpu.observability import trace as _trace
            _trace.write_anchor(self._run_log,
                                model_version=cfg.model_version)

        # live observability plane: preregister the serve metric family
        # (so /metrics advertises HELP/TYPE before any traffic), SLO
        # tallies, the optional exporter, and the anomaly watchdog
        from paddle_tpu.observability import catalog as _catalog
        _catalog.preregister([
            "serve.queue_depth", "serve.active_slots", "serve.ttft_s",
            "serve.token_latency_s", "serve.tokens", "serve.requests",
            "serve.page_stalls", "serve.preemptions", "serve.goodput",
            "serve.slo_violations", "serve.recoveries", "serve.shed",
            "serve.prefix_hits", "serve.prefix_misses",
            "serve.cow_copies", "serve.pages_shared",
            "serve.kv_quant_pages", "serve.kv_pages_in_use",
            "serve.state_bytes_in_use", "serve.spec_proposed",
            "serve.spec_accepted", "serve.spec_rollbacks",
            "serve.rounds_overlapped", "serve.late_rows",
            "serve.kv_rows_written", "jit.retraces"])
        self._retired = 0
        self._retired_ok = 0
        self._viol_base = dict(
            _metrics.counter("serve.slo_violations").snapshot())
        self._trace_run = uuid.uuid4().hex[:8]
        self._aot_trace = False
        from paddle_tpu.observability.exporter import start_metrics_server
        self._metrics_server = start_metrics_server(cfg.metrics_port)
        from paddle_tpu.observability.watchdog import maybe_watchdog
        self._watchdog = maybe_watchdog(cfg.watchdog,
                                        run_log=self._run_log,
                                        action=self._on_anomaly)

        base_key = self._base_key

        def _sample(logits, temps, top_ks, top_ps, seeds, counts):
            """Per-request masked sampling, one trace for every mix of
            greedy / temperature / top-k / top-p rows. logits [B, V];
            the knobs are traced [B] VALUES (batch-size-shaped, so
            admissions never retrace). temperature == 0 rows take
            jnp.argmax, bit-exact with the pre-sampling greedy path.

            The work follows the rows, inside the one program: a
            lax.cond on the traced value any(temps > 0).
              * no row samples (released slots are reset to zeroed
                knobs, so an all-greedy round reads all zeros): the
                argmax is the answer; no sort of the vocabulary, no
                softmax, cumsum, key or draw runs.
              * some row samples: the law below for EVERY row, then
                the greedy rows take their argmax back, so a mixed
                round answers bit for bit as the straight-line law.
                Row b's key is fold(fold(base, seeds[b]), counts[b])
                (counts[b] is how many tokens request b has generated),
                so token i of a request always draws with the same
                key, making sampled replay (preemption / recovery /
                re-route) deterministic."""
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)

            def sampled():
                v = logits.shape[-1]
                scaled = (logits.astype(jnp.float32)
                          / jnp.maximum(temps, 1e-6)[:, None])
                desc = -jnp.sort(-scaled, axis=-1)          # descending
                k_eff = jnp.where(top_ks > 0,
                                  jnp.minimum(top_ks, v),
                                  v).astype(jnp.int32)
                kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None],
                                          axis=1)
                probs = jax.nn.softmax(desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                p_eff = jnp.where((top_ps > 0.0) & (top_ps < 1.0),
                                  top_ps.astype(jnp.float32), 1.0)
                # smallest set of top rows whose mass reaches p (the
                # nucleus always keeps at least the argmax row)
                n_keep = jnp.maximum(
                    jnp.sum((cum - probs) < p_eff[:, None], axis=-1), 1)
                pth = jnp.take_along_axis(desc, (n_keep - 1)[:, None],
                                          axis=1)
                masked = jnp.where((scaled >= kth) & (scaled >= pth),
                                   scaled, -1e30)

                def row_key(s, c):
                    return jax.random.fold_in(
                        jax.random.fold_in(base_key, s), c)

                keys = jax.vmap(row_key)(seeds, counts)
                drawn = jax.vmap(jax.random.categorical)(keys, masked)
                return jnp.where(temps > 0.0, drawn.astype(jnp.int32),
                                 greedy)

            return jax.lax.cond(jnp.any(temps > 0.0), sampled,
                                lambda: greedy)

        self._sample = _sample
        self._build_jits()

    def _init_state(self):
        """The per-slot state, zeros (``()`` for a model without one)."""
        if not self._stateful:
            return ()
        return self._model.init_slot_state(self.cfg.num_slots,
                                           self.cfg.cache_dtype)

    def _resolve_draft(self):
        """(draft model, draft params). No draft_spec = self-draft (the
        target model drafts for itself — ~100% acceptance, the plumbing
        and determinism probe). With a draft_spec, weights come from
        cfg.draft_variables, else the newest step under
        cfg.draft_checkpoint (the checkpoint manager's verified-restore
        path), else a deterministic seeded init."""
        cfg = self.cfg
        if cfg.draft_spec is None:
            return self._model, self._params
        from paddle_tpu.models.gpt import GPTDecoder
        draft = GPTDecoder(cfg.draft_spec)
        variables = cfg.draft_variables
        if variables is None and cfg.draft_checkpoint:
            from paddle_tpu.io.checkpoint import CheckpointManager
            template = draft.init(jax.random.key(cfg.seed))
            state, step = CheckpointManager(
                cfg.draft_checkpoint).restore(template)
            enforce(state is not None,
                    f"draft_checkpoint={cfg.draft_checkpoint!r} holds "
                    "no restorable step")
            variables = state
        if variables is None:
            variables = draft.init(jax.random.key(cfg.seed))
        return draft, variables["params"]

    def _build_jits(self):
        """Create the jitted step closures, once, at construction."""
        model = self._model
        _sample = self._sample

        def _count_trace(attr, fn):
            n = getattr(self, attr) + 1
            setattr(self, attr, n)
            if n > 1 and not self._aot_trace:
                # traced-once invariant broken in live serving —
                # visible to /metrics and the watchdog, not just
                # compile smokes
                _metrics.counter("jit.retraces").inc(fn=fn)

        stateful = self._stateful

        # both step programs take the model's caches as ONE donated
        # argument, the pair (page pools, per-slot state), and hand the
        # pair back. Both take the slots' pending tokens as a device
        # array and return the array the NEXT program takes: a decode
        # round's samples are the next round's input (an inactive slot
        # keeps its pending token), a prefill writes the admitted
        # slot's first token into its row
        def decode(params, caches, tokens, page_table, lengths, active,
                   temps, top_ks, top_ps, seeds, counts):
            _count_trace("decode_traces", "serve.decode")
            pools, state = caches

            def run(tok):
                routed = ()
                if stateful:
                    logits, new_pools, new_state, *routed = \
                        model.paged_decode_step(
                            tok, pools, page_table, lengths, active, state)
                else:
                    logits, new_pools = model.paged_decode_step(
                        tok, pools, page_table, lengths, active)
                    new_state = state
                sampled = _sample(logits, temps, top_ks, top_ps, seeds,
                                  counts)
                return (jnp.where(active, sampled, tok),
                        (new_pools, new_state), *routed)

            return model.apply({"params": params, "state": {}}, tokens,
                               method=run)

        def prefill(params, caches, tokens, prompt, starts, lengths,
                    page_rows, floors, slots, temps, top_ks, top_ps, seeds,
                    counts):
            _count_trace("prefill_traces", "serve.prefill")
            pools, state = caches

            def run(pr):
                routed = ()
                if stateful:
                    # no prefix cache with a state: nothing to floor
                    logits, new_pools, new_state, *routed = \
                        model.paged_prefill_chunk(
                            pr, starts, lengths, pools, page_rows,
                            state=state, slots=slots)
                else:
                    logits, new_pools = model.paged_prefill_chunk(
                        pr, starts, lengths, pools, page_rows,
                        write_floor=floors)
                    new_state = state
                sampled = _sample(logits, temps, top_ks, top_ps, seeds,
                                  counts)
                return (tokens.at[slots].set(sampled),
                        (new_pools, new_state), *routed)

            return model.apply({"params": params, "state": {}}, prompt,
                               method=run)

        def copy_pages(caches, src, dst):
            # copy-on-write divergence: duplicate whole pages src -> dst
            # in every layer's pool ([1]-shaped ids -> one trace ever)
            from paddle_tpu.ops import attention as _att
            return [_att.copy_pages(pool, src, dst) for pool in caches]

        self._decode_jit = jax.jit(decode, donate_argnums=(1,))
        self._prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        self._copy_jit = jax.jit(copy_pages, donate_argnums=(0,))

        if not self._spec_on:
            return
        draft_model = self._draft_model
        spec_w = self.cfg.spec_k + 1

        def draft_decode(params, caches, tokens, page_table, lengths,
                         active, temps, top_ks, top_ps, seeds, counts):
            # one draft proposal step: decode-shaped, called spec_k
            # times per round with lengths+i / counts+i — same shapes
            # every call, ONE trace
            _count_trace("draft_traces", "serve.draft")

            def run(tok):
                logits, new_caches = draft_model.paged_decode_step(
                    tok, caches, page_table, lengths, active)
                return _sample(logits, temps, top_ks, top_ps, seeds,
                               counts), new_caches

            return draft_model.apply({"params": params, "state": {}},
                                     tokens, method=run)

        def draft_prefill(params, caches, prompt, starts, lengths,
                          page_rows, floors):
            # admission-time draft cache fill (no sampling — only the
            # written K/V matters; the target's prefill emits the token)
            _count_trace("draft_prefill_traces", "serve.draft_prefill")

            def run(pr):
                _, new_caches = draft_model.paged_prefill_chunk(
                    pr, starts, lengths, caches, page_rows,
                    write_floor=floors)
                return new_caches

            return draft_model.apply({"params": params, "state": {}},
                                     prompt, method=run)

        def verify(params, caches, window, starts, win_lens, page_rows,
                   temps, top_ks, top_ps, seeds, counts):
            # ONE batched verify step: score every window position
            # against the paged cache (gathered-prefix chunk attention),
            # then sample position i with the SAME fold(seed, count+i)
            # key the plain path would use — emitted tokens are the
            # target's own draws, so speculation is token-exact by
            # construction. The head + sampling run per position:
            # temporaries stay [slots, V], never a dense
            # [slots, window, V] lattice.
            _count_trace("verify_traces", "serve.verify")

            def run(wt):
                hidden, new_caches = model.paged_verify_chunk(
                    wt, starts, win_lens, caches, page_rows)
                cols = [_sample(model.verify_head(hidden[:, i]), temps,
                                top_ks, top_ps, seeds, counts + i)
                        for i in range(spec_w)]
                return jnp.stack(cols, 1), new_caches

            return model.apply({"params": params, "state": {}}, window,
                               method=run)

        self._draft_jit = jax.jit(draft_decode, donate_argnums=(1,))
        self._draft_prefill_jit = jax.jit(draft_prefill,
                                          donate_argnums=(1,))
        self._draft_copy_jit = jax.jit(copy_pages, donate_argnums=(0,))
        self._verify_jit = jax.jit(verify, donate_argnums=(1,))

    # --- public API ---

    def submit(self, prompt, max_new=None, eos_id=None, deadline_s=None,
               priority=0, temperature=None, top_k=None, top_p=None,
               seed=None):
        """Queue a prompt; returns the request id. The padded prompt is
        staged host->device immediately (async), so admission inside a
        later step() issues no host transfer. Prompts longer than
        prefill_len stage as multiple fixed-shape chunks (chunked
        prefill).

        Bounded admission: `deadline_s` (None resolves the
        serve_default_deadline_s flag; 0 there means none) sets an
        absolute deadline — a queued request past it is shed, and a
        non-positive explicit value is rejected up front as infeasible.
        `priority` (higher first) orders admission and inverts the
        preemption victim choice. When the serve_queue_limit flag bounds
        the queue, over-limit submissions get a terminal `rejected`
        status with `req.retriable = True` instead of queueing — check
        `engine.requests[rid].status` after submit.

        Per-request sampling: `temperature` / `top_k` / `top_p` default
        to the ServeConfig values (themselves flag-resolvable) and ride
        per-slot traced arrays of the ONE decode trace — mixing greedy
        and sampled requests in a batch never retraces. `seed` pins the
        request's sampling stream (None derives one from cfg.seed and
        the request id); token i always draws with fold(seed, i), so a
        sampled request replays deterministically after preemption,
        recovery, or a fleet re-route."""
        cfg = self.cfg
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = max_new if max_new is not None else cfg.default_max_new
        cap = cfg.max_len if cfg.chunked_prefill else cfg.prefill_len
        enforce(1 <= prompt.size <= cap,
                f"prompt length {prompt.size} not in [1, {cap}] "
                + ("(max_len)" if cfg.chunked_prefill
                   else "(prefill_len; serve_chunked_prefill is off)"))
        enforce(prompt.size + max_new <= cfg.max_len,
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"max_len {cfg.max_len}")
        with self._lock, span("serve.submit", rid=next(self._ids)) as sp:
            req = Request(id=sp.rid, prompt=prompt,
                          max_new=max_new,
                          eos_id=eos_id if eos_id is not None
                          else cfg.eos_id,
                          priority=int(priority))
            self._resolve_sampling(req, temperature, top_k, top_p, seed)
            req.trace_id = f"{self._trace_run}/{req.id}"
            self.requests[req.id] = req
            extra = {}
            if priority:
                extra["priority"] = int(priority)
            if deadline_s is not None:
                extra["deadline_s"] = float(deadline_s)
            req.submit_t = self._trace_event(req, "submitted",
                                             prompt_len=int(prompt.size),
                                             max_new=int(max_new), **extra)
            _metrics.counter("serve.requests").inc(status="submitted")
            if deadline_s is None and cfg.default_deadline_s > 0:
                deadline_s = cfg.default_deadline_s
            if deadline_s is not None:
                if deadline_s <= 0:
                    self._reject(req, "infeasible_deadline")
                    return req.id
                req.deadline_t = req.submit_t + float(deadline_s)
            if cfg.queue_limit and len(self._queue) >= cfg.queue_limit:
                self._reject(req, "queue_full")
                return req.id
            req.device_prompt = self._stage_chunks(prompt)
            self._queue.append(req)
            _metrics.gauge("serve.queue_depth").set(len(self._queue))
            return req.id

    def adopt(self, prompt, tokens=(), max_new=None, eos_id=None,
              priority=0, deadline_t=None, submit_t=None,
              first_token_t=None, origin="fleet", temperature=None,
              top_k=None, top_p=None, seed=None, trace=None):
        """Failover/dispatch entry for the fleet router: queue a request
        whose generation may already be `tokens` deep, preserving the
        caller's accounting clock — submit_t, first_token_t and the
        ABSOLUTE deadline_t survive verbatim, so TTFT/SLO classification
        lands on the engine that completes the request, not the one that
        first saw it. The full replay sequence (prompt + tokens) is
        staged exactly like a crash-recovery requeue: greedy adoption
        finishes token-exact. Bypasses the queue_limit bound — the
        router does its own dispatch bounding, and a failover re-route
        must never be rejected. Returns the request id.

        ``trace`` is the router-minted durable trace context (a
        TraceContext wire dict); when present the adopted request KEEPS
        the fleet trace id across the hop instead of re-minting an
        engine-run-scoped one, so one id covers the request's whole
        life across replicas."""
        cfg = self.cfg
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tokens = [int(t) for t in tokens]
        max_new = max_new if max_new is not None else cfg.default_max_new
        cap = cfg.max_len if cfg.chunked_prefill else cfg.prefill_len
        enforce(1 <= prompt.size <= cap,
                f"prompt length {prompt.size} not in [1, {cap}]")
        enforce(prompt.size + max_new <= cfg.max_len,
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"max_len {cfg.max_len}")
        enforce(len(tokens) <= max_new,
                f"adopted with {len(tokens)} tokens > max_new {max_new}")
        with self._lock, span("serve.submit", rid=next(self._ids)) as sp:
            req = Request(id=sp.rid, prompt=prompt,
                          max_new=max_new,
                          eos_id=eos_id if eos_id is not None
                          else cfg.eos_id,
                          priority=int(priority))
            self._resolve_sampling(req, temperature, top_k, top_p, seed)
            req.tokens = tokens
            req.deadline_t = deadline_t
            req.first_token_t = first_token_t
            from paddle_tpu.observability.trace import TraceContext
            ctx = TraceContext.from_wire(trace) if trace else None
            if ctx is not None:
                req.trace_id = ctx.trace_id
                req.span_id = ctx.span_id
                req.parent_span_id = ctx.parent_span_id
            else:          # legacy: no router context, engine-run scope
                req.trace_id = f"{self._trace_run}/{req.id}"
            self.requests[req.id] = req
            t = self._trace_event(req, "adopted", origin=origin,
                                  prompt_len=int(prompt.size),
                                  tokens_kept=len(tokens))
            req.submit_t = submit_t if submit_t is not None else t
            _metrics.counter("serve.requests").inc(status="adopted")
            req.device_prompt = self._stage_chunks(req.output if tokens
                                                   else prompt)
            self._queue.append(req)
            _metrics.gauge("serve.queue_depth").set(len(self._queue))
            return req.id

    def export_inflight(self):
        """Replica-side export of every non-terminal request's durable
        host state — the fleet router's failover mirror, refreshed each
        healthy round so a later kill replays token-exact from the last
        synced point. Host-only reads (no device sync) of the tokens
        already READ: the round in flight is not waited for, and is
        recomputed, token-exact, by whoever adopts the request. The
        prompt stays with the router, so entries carry ids, token
        mirrors, and the accounting clocks `adopt()` preserves."""
        out = []
        with self._lock:
            live = list(self._queue) + sorted(self._running.values(),
                                              key=lambda r: r.id)
            for req in live:
                out.append(dict(
                    rid=req.id, status=req.status,
                    tokens=list(req.tokens),
                    prompt_len=int(req.prompt.size),
                    priority=req.priority, submit_t=req.submit_t,
                    first_token_t=req.first_token_t,
                    deadline_t=req.deadline_t))
        return out

    def cancel(self, request_id):
        """Client-initiated cancellation: a first-class terminal status.
        A queued request leaves the queue; a running one frees its slot
        and pages immediately, and its rows in the round in flight are
        dropped unread. Returns True if cancelled, False when the
        id is unknown or already terminal. Cancelled requests do not
        count against goodput (the client walked away; the engine did
        not fail them)."""
        with self._lock:
            req = self.requests.get(request_id)
            if req is None or req.status not in ("queued", "running"):
                return False
            if req.status == "queued":
                try:
                    self._queue.remove(req)
                except ValueError:
                    pass
            else:
                self._free_slot_state(req)
            self._retire_terminal(req, "cancelled", "cancelled",
                                  account=False)
            _metrics.gauge("serve.queue_depth").set(len(self._queue))
            _metrics.gauge("serve.active_slots").set(len(self._running))
            return True

    def step(self):
        """One scheduling round, launched BEFORE the round before it is
        read (the module's docstring, "the round"): admit queued prompts
        into free slots (their prefill chunks are launched, their first
        tokens not read), grow page tables where the next token opens a
        page, launch ONE jitted decode step over all slots, and only
        then wait for the tokens of the round the step before launched
        and of this step's admissions, append them and retire what hit
        EOS or its token budget. Returns the requests whose last token
        was READ this round (and those shed or failed in it)."""
        with self._lock, span("serve.step") as sp:
            t0 = self._clock()
            finished = []
            late0 = self.late_rows
            rows0 = self.kv_rows_written
            self._routed_read = []
            with phase("serve.admit"):
                self._shed_expired(finished)
                self._admit(finished)
            new_tokens = sampled_rows = overlapped = 0
            spec = None
            spec_proposed = spec_accepted = None
            try:
                with phase("serve.grow"):
                    stalled = self._grow_pages()
                    while stalled and not self._active.any():
                        # pool deadlock: every live slot needs a fresh
                        # page and none is free, so no round can be
                        # launched. First read what is in flight (a
                        # request that ended there gives its pages
                        # back; nothing could have overlapped it).
                        # Then preempt the lowest-priority / latest-
                        # deadline stalled request (free its pages,
                        # requeue it for re-prefill) so higher-value
                        # work always makes progress — with all-default
                        # requests this reduces to the youngest. Greedy
                        # decoding regenerates the dropped tokens
                        # exactly; sampled runs re-draw with the same
                        # keys (recompute preemption).
                        if self._inflight:
                            new_tokens += self._read(finished, t0)
                        else:
                            victim = min((self._running[s] for s in stalled),
                                         key=self._victim_key)
                            self._preempt(victim)
                        stalled = self._grow_pages()
                if self._spec_on:
                    # after a speculative round a slot's length depends
                    # on how many proposals were accepted, so such an
                    # engine reads before it launches: this step's
                    # first tokens (and a degraded plain round) now
                    new_tokens += self._read(finished, t0)
                launched = None
                if self._active.any():
                    use_spec = self._spec_on
                    if use_spec:
                        try:
                            fault_point("spec.verify")
                        except Exception:
                            # chaos degrade: this round runs as ONE plain
                            # decode step — token-exact either way (the
                            # emitted token follows the same sample law)
                            use_spec = False
                    fault_point("serve.step")
                    # sampled_rows: which branch of _sample the round
                    # takes (0 = the argmax alone), from the host's own
                    # copy of the knobs as the program gets them
                    if use_spec:
                        sampled_rows = int(
                            np.count_nonzero(self._temps > 0.0))
                        spec = self._spec_round()
                        self._round_read()
                    else:
                        sampled_rows = int(np.count_nonzero(
                            self._temps[self._active] > 0.0))
                        overlapped = int(any(
                            not fl.first for fl in self._inflight))
                        with phase("serve.decode"):
                            launched = self._launch_round()
                # only now wait for the device: the round the step
                # before launched and this step's first tokens, with
                # round n queued behind them
                new_tokens += self._read(finished, t0, keep=launched)
            except Exception as e:
                spec = None
                overlapped = 0
                self._recover("serve.step", e)
            if spec is not None:
                with phase("serve.advance"):
                    n, spec_proposed, spec_accepted = self._advance_spec(
                        spec, self._clock() - t0, finished)
                    new_tokens += n
            late_rows = self.late_rows - late0
            kv_rows = self.kv_rows_written - rows0
            if overlapped:
                self.rounds_overlapped += 1
                _metrics.counter("serve.rounds_overlapped").inc()
            if late_rows:
                _metrics.counter("serve.late_rows").inc(late_rows)
            if kv_rows:
                _metrics.counter("serve.kv_rows_written").inc(kv_rows)
            _metrics.counter("serve.tokens").inc(new_tokens)
            _metrics.gauge("serve.active_slots").set(len(self._running))
            _metrics.gauge("serve.queue_depth").set(len(self._queue))
            if self.cfg.kv_dtype is not None:
                _metrics.gauge("serve.kv_quant_pages").set(
                    self.cfg.num_pages - len(self._free_pages))
            in_use = self.pages_in_use()
            _metrics.gauge("serve.kv_pages_in_use").set(in_use)
            sp.count(pages_in_use=in_use, pages_cached=self.pages_cached(),
                     num_pages=self.cfg.num_pages, sampled_rows=sampled_rows,
                     overlapped=overlapped, late_rows=late_rows,
                     kv_rows_written=kv_rows)
            if self._stateful:
                # every running slot holds its recurrent state whole
                state_bytes = len(self._running) * self._state_bytes_per_slot
                _metrics.gauge("serve.state_bytes_in_use").set(state_bytes)
                sp.count(state_slots=len(self._running),
                         state_bytes=state_bytes,
                         state_bytes_reserved=self.state_bytes())
            if self._routed_read:
                # rows routed to each held expert, [expert layers, held]
                # of every program whose tokens this step read
                routed = np.stack([r for r, _ in self._routed_read])
                rows = int(routed.sum())
                # the grouped kernel's items that streamed an expert,
                # and all its grid's items (the rest move nothing)
                items = slots = 0
                for r, t in self._routed_read:
                    tile_m, per_call = self._moe_grid[t]
                    items += live_items(r, tile_m)
                    slots += len(r) * per_call
                _metrics.counter("serve.moe.rows").inc(rows)
                _metrics.counter("serve.moe.experts_idle").inc(
                    int((routed == 0).sum()))
                _metrics.counter("serve.moe.items").inc(items)
                sp.count(moe_rows=rows, moe_rows_max=int(routed.max()),
                         moe_experts_hit=float(
                             (routed > 0).sum(-1).mean()),
                         moe_calls=len(routed), moe_items=items,
                         moe_item_slots=slots)
            wall_s = self._clock() - t0
            if self._run_log is not None:
                rec = {
                    "phase": "serve", "step": self._step_no,
                    "wall_s": wall_s, "new_tokens": new_tokens,
                    "active": len(self._running),
                    "queue_depth": len(self._queue),
                    "goodput": round(self.goodput(), 4)}
                if spec_proposed is not None:
                    # speculative round: per-round acceptance so
                    # tools/run_report.py --serve can plot the
                    # acceptance-rate trajectory
                    rec["spec_proposed"] = spec_proposed
                    rec["spec_accepted"] = spec_accepted
                self._run_log.write(rec)
            if self._watchdog is not None:
                self._watchdog.tick(self._step_no, wall_s=wall_s,
                                    goodput=self.goodput(),
                                    retired=self._retired)
            self._step_no += 1
            return finished

    def _launch_round(self):
        """Launch ONE plain decode round over the active slots and move
        on, for each of them, everything that does not depend on the
        token's VALUE: the length grows by one whatever is sampled, and
        so does the count that keys the next draw (fold(seed, i)) and
        ends the request at max_new. The pending tokens come from the
        device's own array. The host arrays are handed over as copies:
        they are edited in place for the next round while the backend
        may not have consumed them (the CPU backend aliases an aligned
        numpy argument: a later write shows in the program). A row that
        gets no token this round (stalled, or waiting for its last
        token to be read) is handed over as greedy, so that it never
        sends the sampler down its long branch."""
        rows = {slot: req for slot, req in self._running.items()
                if self._active[slot]}
        if self._spec_on:
            # a speculative engine keeps the pending tokens on the host
            # (everything was read before this launch)
            self._tokens_dev = jnp.asarray(self._pending_tokens())
        self._tokens_dev, (self._caches, self._state), *routed = \
            self._decode_jit(
                self._params, (self._caches, self._state), self._tokens_dev,
                self._page_table.copy(), self._lengths.copy(),
                self._active.copy(), self._temps * self._active,
                self._top_ks.copy(), self._top_ps.copy(),
                self._seeds.copy(), self._gen_counts.copy())
        # an active slot's row lands; the others are routed to drop
        self.kv_rows_written += int(np.count_nonzero(self._active))
        self._lengths[self._active] += 1     # the pending token is cached
        self._gen_counts[self._active] += 1  # next draw = fold(seed, i)
        return self._took_off(rows, routed=routed)

    def _took_off(self, rows, first=False, routed=()):
        """Record that the program just launched computes ``rows``'
        tokens into the device's token array, and start that array's
        copy to the host as soon as it exists (the later read then
        waits for the program, not for a transfer behind it).
        ``routed``: the expert-row counts of the programs behind this
        flight (a model with experts), which travel with the tokens."""
        self._tokens_dev.copy_to_host_async()
        for r in routed:
            r.copy_to_host_async()
        flight = _Flight(self._tokens_dev, rows, first, tuple(routed))
        self._inflight.append(flight)
        return flight

    def _read(self, finished, t0, keep=None):
        """Wait for every flight but ``keep`` (the round this step
        launched), oldest first, and advance the requests that sat in
        their rows; a flight whose every row has left since (cancelled,
        preempted, ended) is let go unread. Returns the decode tokens
        emitted."""
        take = [fl for fl in self._inflight if fl is not keep and fl.rows]
        self._inflight = [fl for fl in self._inflight if fl is keep]
        got = []
        for fl in take:
            if fl.first:
                (req,) = fl.rows.values()
                name, rid = "serve.prefill.fetch", req.id
            else:
                name, rid = "serve.fetch", None
            with phase(name, rid=rid):
                toks, routed = jax.device_get((fl.toks, fl.routed))  # graft-lint: disable=hot-path-sync (the one deliberate wait a decode round, one round BEHIND its launch, and one an admission: the python scheduler needs the token values to append them and to free slots; the device already runs the round launched after this one)
                got.append((fl, toks))
                # with each program's row count: a chunk's or a round's
                program_rows = self.cfg.prefill_len if fl.first else \
                    self.cfg.num_slots
                self._routed_read.extend((r, program_rows) for r in routed)
            if not fl.first:
                self._round_read()
        if not got:
            return 0
        with phase("serve.advance"):
            return self._advance(got, self._clock() - t0, finished)

    def _round_read(self):
        """A target-model round came back whole."""
        self._retry_budget.success()   # consecutive-failure reset
        self.target_steps += 1

    def _advance(self, got, dt, finished):
        """What waits for the token's value: append each row's token to
        the request that sat there at the launch, emit an admission's
        first-token events, retire what is done. Returns the decode
        tokens emitted."""
        new_tokens = 0
        lat = _metrics.histogram("serve.token_latency_s")
        for fl, toks in got:
            # these flights are out of `_inflight`: no release below
            # edits their rows
            for slot, req in fl.rows.items():
                tok = int(toks[slot])
                if fl.first:
                    self._trace_event(req, "prefill_done")
                    t = self._trace_event(req, "first_token")
                    if req.first_token_t is None:  # a replay keeps the 1st
                        req.first_token_t = t
                        _metrics.histogram("serve.ttft_s").observe(
                            t - req.submit_t)
                    _metrics.counter("serve.tokens").inc()
                else:
                    lat.observe(dt)
                    new_tokens += 1
                req.tokens.append(tok)
                reason = self._done_reason(req, tok)
                if reason:
                    self._release(req, finished, reason)
        return new_tokens

    def _advance_spec(self, spec, dt, finished):
        """After a speculative round: per slot, accept the leading run
        of draft proposals that match the target's own samples and emit
        accepted + 1 tokens; rejection rollback is the length simply
        advancing fewer positions than the verify window wrote (stale
        KV/scale rows beyond the accepted prefix are overwritten by
        later writes). Returns (tokens emitted, proposed, accepted)."""
        self.spec_rounds += 1
        lat = _metrics.histogram("serve.token_latency_s")
        sampled, props, win = spec
        new_tokens = spec_proposed = spec_accepted = 0
        for slot, req in list(self._running.items()):
            if not self._active[slot]:
                continue               # page-stalled this round
            w = int(win[slot])
            self.spec_slot_rounds += 1
            a = 0
            while (a < w - 1
                   and int(props[slot, a]) == int(sampled[slot, a])):
                a += 1
            m = a + 1                  # tokens safe to emit
            spec_proposed += w - 1
            spec_accepted += a
            emitted = 0
            for j in range(m):
                tok = int(sampled[slot, j])
                self._lengths[slot] += 1   # its KV is cached
                req.tokens.append(tok)
                self._gen_counts[slot] += 1
                lat.observe(dt / m)
                new_tokens += 1
                emitted += 1
                reason = self._done_reason(req, tok)
                if reason:
                    self._release(req, finished, reason)
                    break
            req.spec_tokens += max(0, emitted - 1)
        self.spec_proposed += spec_proposed
        self.spec_accepted += spec_accepted
        self.spec_rollbacks += spec_proposed - spec_accepted
        _metrics.counter("serve.spec_proposed").inc(spec_proposed)
        _metrics.counter("serve.spec_accepted").inc(spec_accepted)
        _metrics.counter("serve.spec_rollbacks").inc(
            spec_proposed - spec_accepted)
        return new_tokens, spec_proposed, spec_accepted

    def drain(self, max_steps=100000):
        """Run step() until every submitted request finishes: nothing
        queued, nothing running and nothing in flight (a request whose
        last token is launched and not read is still running). Returns
        the finished requests in completion order."""
        out = []
        # the lock is released between rounds so client threads can
        # still reach submit()/cancel() while the drain loop runs
        for _ in range(max_steps):
            with self._lock:
                more = bool(self._queue or self._running
                            or self._inflight)
            if not more:
                break
            out.extend(self.step())
        else:
            with self._lock:
                queued, running = len(self._queue), len(self._running)
            raise RuntimeError(
                f"drain: {queued} queued / {running} "
                f"running requests left after {max_steps} steps")
        if self._run_log is not None:
            snap = _metrics.snapshot()
            self._run_log.write({"final": True, "phase": "serve",
                                 "kv_dtype": self.kv_dtype_name(),
                                 "kv_pool_bytes": self.kv_pool_bytes(),
                                 "counters": snap.get("counters", {}),
                                 "gauges": snap.get("gauges", {}),
                                 "slo": self.slo_stats()})
        return out

    def close(self):
        with self._lock:
            # a round nobody will read is let go: no request holds its
            # tokens, and whoever adopts the requests recomputes them
            self._inflight = []
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        if self._run_log is not None and self._own_run_log:
            self._run_log.close()
        self._run_log = None

    def compiled_decode(self):
        """AOT-compile the decode step (one extra trace) and return the
        compiled executable — compile-smoke greps its HLO, bench prewarms
        with it."""
        cfg = self.cfg
        s = cfg.num_slots
        self._aot_trace = True    # a deliberate extra trace, not a retrace
        try:
            return self._decode_jit.lower(
                self._params, (self._caches, self._state),
                np.zeros(s, np.int32), self._page_table,
                np.zeros(s, np.int32), np.zeros(s, bool),
                np.zeros(s, np.float32), np.zeros(s, np.int32),
                np.zeros(s, np.float32), np.zeros(s, np.uint32),
                np.zeros(s, np.int32)).compile()
        finally:
            self._aot_trace = False

    def compiled_prefill(self):
        """AOT-compile the admission prefill step (one extra trace,
        absorbed like compiled_decode's) and return the compiled
        executable, for HLO inspection."""
        cfg = self.cfg
        self._aot_trace = True    # a deliberate extra trace, not a retrace
        try:
            return self._prefill_jit.lower(
                self._params, (self._caches, self._state),
                np.zeros(cfg.num_slots, np.int32),
                np.zeros((1, cfg.prefill_len), np.int32),
                np.zeros(1, np.int32), np.zeros(1, np.int32),
                self._page_table[:1], np.zeros(1, np.int32),
                np.zeros(1, np.int32), np.zeros(1, np.float32), np.zeros(1, np.int32),
                np.zeros(1, np.float32), np.zeros(1, np.uint32),
                np.zeros(1, np.int32)).compile()
        finally:
            self._aot_trace = False

    def compiled_verify(self):
        """AOT-compile the speculative verify step (one extra trace,
        absorbed like compiled_decode's) and return the compiled
        executable — compile-smoke greps its HLO for the no-dense-
        lattice and budget contracts."""
        enforce(self._spec_on, "compiled_verify() needs draft=True")
        cfg = self.cfg
        s, w = cfg.num_slots, cfg.spec_k + 1
        self._aot_trace = True    # a deliberate extra trace, not a retrace
        try:
            return self._verify_jit.lower(
                self._params, self._caches,
                np.zeros((s, w), np.int32), np.zeros(s, np.int32),
                np.zeros(s, np.int32), self._page_table,
                np.zeros(s, np.float32), np.zeros(s, np.int32),
                np.zeros(s, np.float32), np.zeros(s, np.uint32),
                np.zeros(s, np.int32)).compile()
        finally:
            self._aot_trace = False

    def spec_stats(self):
        """Speculation accounting for bench rows / reports. Per-slot
        semantics: in every round each active slot costs ONE target-model
        evaluation (decode or verify); a slot's speculative round emits
        1 + accepted tokens. tokens_per_target_step > 1.0 is the whole
        point of the feature."""
        prop, acc = self.spec_proposed, self.spec_accepted
        sr = self.spec_slot_rounds
        return {
            "enabled": self._spec_on,
            "spec_k": self.cfg.spec_k if self._spec_on else 0,
            "rounds": self.spec_rounds,
            "target_steps": self.target_steps,
            "proposed": prop,
            "accepted": acc,
            "rollbacks": self.spec_rollbacks,
            "acceptance_rate": round(acc / prop, 4) if prop else None,
            "tokens_per_target_step":
                round((sr + acc) / sr, 4) if sr else None}

    def export_decode(self, path):
        """Export ONE greedy serve step as a StableHLO / jax.export
        artifact through io.inference.save_train_program's
        state-feedback contract: state = (params, page pools) fed back
        output->input each iteration, batch = (tokens, page_table,
        lengths, active) — so the C++ predictor loop (csrc/) can run the
        continuous-batching decode with no Python at serve time (the
        host scheduler only rewrites the tiny page_table/lengths/active
        inputs between steps)."""
        from paddle_tpu.io.inference import save_train_program
        enforce(not self._stateful,
                "export_decode() exports (params, page pools) as the fed-"
                "back state; a model with a per-slot recurrent state is "
                "not exported yet")
        model = self._model
        cfg = self.cfg

        def step(state, tokens, page_table, lengths, active):
            params, caches = state

            def run(tok):
                logits, new_caches = model.paged_decode_step(
                    tok, caches, page_table, lengths, active)
                return jnp.argmax(logits, -1).astype(jnp.int32), \
                    new_caches

            nxt, new_caches = model.apply(
                {"params": params, "state": {}}, tokens, method=run)
            return nxt, (params, new_caches)

        example = (np.zeros(cfg.num_slots, np.int32), self._page_table,
                   np.zeros(cfg.num_slots, np.int32),
                   np.zeros(cfg.num_slots, bool))
        return save_train_program(path, step,
                                  (self._params, self._caches), example)

    def kv_dtype_name(self):
        """"int8" for a quantized page pool, else "f32" — the bench /
        report label for the serve_kv_dtype choice in effect."""
        return "int8" if self.cfg.kv_dtype is not None else "f32"

    def kv_pool_bytes(self):
        """Device bytes held by the paged KV pools across layers (value
        tensors plus, for quantized pools, their scale tensors) —
        shape/dtype metadata only, never a device sync."""
        return int(sum(arr.nbytes for pool in list(self._caches)
                       for arr in pool.values()))

    def state_bytes(self):
        """Device bytes reserved for the per-slot recurrent state of all
        slots (0 for a model without one): metadata only, no sync."""
        return self._state_bytes_per_slot * self.cfg.num_slots

    def goodput(self):
        """Fraction of retired requests that met every configured SLO
        (1.0 before the first retirement) — the SLO scheduler's
        objective."""
        return self._retired_ok / self._retired if self._retired else 1.0

    def slo_stats(self):
        """SLO accounting for bench rows / reports: goodput, targets,
        and violation counts since construction (or reset_stats)."""
        viol = _metrics.counter("serve.slo_violations").snapshot()
        delta = {k.split("=", 1)[1]: v - self._viol_base.get(k, 0)
                 for k, v in viol.items()}
        return {"goodput": round(self.goodput(), 4),
                "retired": self._retired,
                "version": self.version,
                "slo_ttft_s": self.cfg.slo_ttft_s or None,
                "slo_token_latency_s":
                    self.cfg.slo_token_latency_s or None,
                "violations": {"ttft": delta.get("ttft", 0),
                               "token_latency":
                                   delta.get("token_latency", 0)}}

    def reset_stats(self):
        """Zero the serve latency histograms, this engine's SLO tallies
        and its speculation counters (bench warmup isolation:
        compile-time TTFTs and warmup acceptance must not poison the
        timed window's row)."""
        for name in ("serve.ttft_s", "serve.token_latency_s"):
            h = _metrics.registry().get(name)
            if h is not None:
                h.reset()
        self.spec_proposed = self.spec_accepted = 0
        self.spec_rollbacks = self.spec_rounds = 0
        self.spec_slot_rounds = self.target_steps = 0
        self._retired = self._retired_ok = 0
        self._viol_base = dict(
            _metrics.counter("serve.slo_violations").snapshot())
        _metrics.gauge("serve.goodput").set(1.0)

    def latency_stats(self):
        """{"ttft_ms": {p50,p95,n}, "token_ms": {...}} from the registry
        histograms (the bench row's telemetry-backed percentiles)."""
        out = {}
        for name, hist in (("ttft_ms", "serve.ttft_s"),
                           ("token_ms", "serve.token_latency_s")):
            h = _metrics.registry().get(hist)
            st = h.stats() if h is not None else None
            if st:
                out[name] = {"p50": round(st["p50"] * 1e3, 3),
                             "p95": round(st["p95"] * 1e3, 3),
                             "n": st["count"]}
        return out

    # --- scheduling internals ---

    def _trace_event(self, req, event, **extra):
        """One lifecycle trace point: a host clock read, a list append,
        a bounded-ring append, (when a RunLog is configured) a JSONL
        write, and (while a profiler session is on) a span-store record
        that shares the request's id with its spans — never a device
        sync (the flush-spy test's contract). Returns the timestamp."""
        t = self._clock()
        req.trace.append((event, t))
        rec = {"event": event, "req": req.id, "trace": req.trace_id,
               "t": t, "at_step": self._step_no}
        if req.slot is not None:
            rec["slot"] = req.slot
        if self.version is not None:
            rec["version"] = self.version
        if self.replica is not None:
            rec["replica"] = self.replica
        if req.span_id is not None:
            rec["span"] = req.span_id
            rec["parent_span"] = req.parent_span_id
        rec.update(extra)
        if self._run_log is not None:
            self._run_log.write(rec)
        fl = _flight.recorder()
        if fl is not None:           # deque append — no I/O, no sync
            fl.note(rec)
        # the span store stamps the event on its own clock (this
        # engine's can be injected), and only under a profiler session
        _spans.event(event, req.id)
        return t

    def _stage_chunks(self, seq):
        """Stage `seq` host->device (async) as ceil(len / prefill_len)
        padded [1, prefill_len] chunk arrays — one for an ordinary
        prompt, more under chunked prefill or a recovery replay. Staging
        MORE than currently needed is harmless: each prefill call masks
        by its chunk length, so a preempted request (tokens dropped)
        reuses the same chunk list without restaging."""
        lp = self.cfg.prefill_len
        seq = np.asarray(seq, np.int32).reshape(-1)
        n = max(1, -(-seq.size // lp))
        padded = np.zeros((n * lp,), np.int32)
        padded[:seq.size] = seq
        return [self._stager.place(padded[i * lp:(i + 1) * lp][None, :])
                for i in range(n)]

    # --- page allocation + prefix cache ---------------------------------

    def pages_cached(self):
        """K/V pages the prefix cache keeps for no running request:
        evictable, so an admission can still obtain them."""
        with self._lock:
            return (self._prefix_cache.evictable()
                    if self._prefix_cache is not None else 0)

    def pages_in_use(self):
        """K/V pages no admission could obtain right now: pinned by the
        running requests (their private pages and the shared pages they
        map)."""
        with self._lock:
            return self.cfg.num_pages - self._pages_available()

    def _pages_available(self):
        """Pages an admission could obtain right now: the free list plus
        idle (refcount-zero) prefix-cache pages, which _alloc_page
        reclaims LRU-first."""
        n = len(self._free_pages)
        if self._prefix_cache is not None:
            n += self._prefix_cache.evictable()
        return n

    def _alloc_page(self):
        """One free page id, evicting the least-recently-released idle
        prefix-cache page when the free list is dry. None when nothing
        is reclaimable (true pool famine)."""
        if self._free_pages:
            return self._free_pages.popleft()
        if self._prefix_cache is not None:
            for page in self._prefix_cache.evict(1):
                return page
        return None

    def _return_pages(self, req):
        """Give a request's pages back: private pages to the free list,
        shared pages to the cache (refcount drop — they STAY cached for
        future hits unless the serve_prefix_pages cap trims them)."""
        self._free_pages.extend(req.pages)
        req.pages = []
        if req.shared_pages:
            if self._prefix_cache is not None:
                self._free_pages.extend(
                    self._prefix_cache.release(req.shared_pages))
                _metrics.gauge("serve.pages_shared").set(
                    self._prefix_cache.pages_shared())
            else:
                self._free_pages.extend(req.shared_pages)
            req.shared_pages = []

    def _map_prefix(self, req, total):
        """Match the request's prompt against the prefix cache and map
        the hit pages read-only into the slot's table row. Returns the
        number of leading tokens whose K/V is already cached (prefill
        below that position is skipped / write-masked). The match is
        capped at total - 1 so the final position always prefills (its
        logits produce the first token); when the cap cuts into the last
        matched page, that page is copy-on-write duplicated up front —
        the slot's next writes land in the private copy. Any cache
        failure (the serve.prefix_cache fault point injects them)
        degrades to a full miss: private pages, never corruption."""
        if self._prefix_cache is None or total <= 1:
            return 0
        cache = self._prefix_cache
        try:
            fault_point("serve.prefix_cache")
            shared, matched = cache.match(req.prompt, cap=total - 1)
        except Exception:
            shared, matched = [], 0
        full = req.prompt.size // self.cfg.page_size
        _metrics.counter("serve.prefix_hits").inc(len(shared))
        _metrics.counter("serve.prefix_misses").inc(full - len(shared))
        if not shared:
            return 0
        cache.acquire(shared)
        req.shared_pages = list(shared)
        for idx, page in enumerate(shared):
            self._page_table[req.slot, idx] = page
        if matched % self.cfg.page_size:
            if not self._cow_last_shared(req):
                # no page for the private copy: shrink the match to the
                # page boundary and let the tail prefill normally
                drop = req.shared_pages.pop()
                self._free_pages.extend(cache.release([drop]))
                matched = (matched // self.cfg.page_size) \
                    * self.cfg.page_size
        _metrics.gauge("serve.pages_shared").set(cache.pages_shared())
        return matched

    def _cow_last_shared(self, req):
        """Copy-on-write divergence: duplicate the request's LAST shared
        page into a fresh private page (device-side whole-page copy) and
        remap the table row. Returns False when no page is allocatable —
        the caller degrades the match instead."""
        dst = self._alloc_page()
        if dst is None:
            return False
        src = req.shared_pages.pop()     # held: refcount protects it
        self._caches = self._copy_jit(
            self._caches, np.asarray([src], np.int32),
            np.asarray([dst], np.int32))
        if self._spec_on:
            # the draft pools index by the same page ids — divergence
            # must carry the draft K/V too or the draft's view of the
            # shared prefix goes stale
            self._draft_caches = self._draft_copy_jit(
                self._draft_caches, np.asarray([src], np.int32),
                np.asarray([dst], np.int32))
        self._free_pages.extend(self._prefix_cache.release([src]))
        req.pages.append(dst)
        self._page_table[req.slot, len(req.shared_pages)] = dst
        _metrics.counter("serve.cow_copies").inc()
        return True

    def _publish_prefix(self, req):
        """Register a just-prefilled prompt's full pages in the cache so
        later admissions share them. Newly-registered pages change owner
        (private -> shared) but keep their table positions; the cache
        skips pages already shared into this row and stops at a private
        duplicate, so the shared run stays a contiguous row prefix."""
        if self._prefix_cache is None:
            return
        row = self._page_table[req.slot]
        full = req.prompt.size // self.cfg.page_size
        for page in self._prefix_cache.insert(req.prompt, row[:full]):
            req.pages.remove(page)
            req.shared_pages.append(page)
        _metrics.gauge("serve.pages_shared").set(
            self._prefix_cache.pages_shared())

    def prefix_lookup_depth(self, prompt):
        """Leading full prompt pages this engine's prefix cache holds —
        the fleet router's affinity probe (read-only, lock-held)."""
        with self._lock:
            if self._prefix_cache is None:
                return 0
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            return self._prefix_cache.lookup_depth(prompt)

    # --- per-request sampling -------------------------------------------

    def _resolve_sampling(self, req, temperature, top_k, top_p, seed):
        """Fill a request's sampling knobs: explicit values win, else the
        ServeConfig defaults; a missing seed derives deterministically
        from the engine seed and the request id."""
        cfg = self.cfg
        req.temperature = (cfg.temperature if temperature is None
                           else float(temperature))
        req.top_k = cfg.top_k if top_k is None else int(top_k)
        req.top_p = cfg.top_p if top_p is None else float(top_p)
        req.seed = ((cfg.seed * 1_000_003 + req.id) & 0xFFFFFFFF
                    if seed is None else int(seed) & 0xFFFFFFFF)

    def _sampling_rows(self, req):
        """The prefill jit's [1]-shaped sampling arguments for one
        request (count = tokens generated so far, so a replayed
        request's next draw reuses its original key)."""
        return (np.asarray([req.temperature], np.float32),
                np.asarray([req.top_k], np.int32),
                np.asarray([req.top_p], np.float32),
                np.asarray([req.seed], np.uint32),
                np.asarray([len(req.tokens)], np.int32))

    def _admission_key(self, req):
        """Admission order: highest priority, then earliest deadline
        (None last), then FIFO — all-default traffic stays pure FIFO."""
        dl = req.deadline_t if req.deadline_t is not None else float("inf")
        return (-req.priority, dl, req.id)

    def _victim_key(self, req):
        """Preemption/shed victim order: LOWEST priority, then latest
        deadline (None counts as latest), then youngest — the exact
        inverse of admission, so the all-defaults case reduces to the
        old youngest-first rule."""
        dl = req.deadline_t if req.deadline_t is not None else float("inf")
        return (req.priority, -dl, -req.id)

    def _admit(self, finished):
        cfg = self.cfg
        while self._queue and self._free_slots:
            req = min(self._queue, key=self._admission_key)
            total = req.prompt.size + len(req.tokens)  # recovery replays
            first = min(cfg.prefill_len, total)        # prompt + tokens
            if -(-first // cfg.page_size) > self._pages_available():
                _metrics.counter("serve.page_stalls").inc(where="admit")
                break                      # head-of-line waits for pages
            self._queue.remove(req)
            with phase("serve.prefill", rid=req.id):
                ok = self._prefill_request(req, total)
            if not ok:
                break          # mid-admission page stall or a recovery
        _metrics.gauge("serve.queue_depth").set(len(self._queue))

    def _prefill_request(self, req, total):
        """Admit one request: take a slot, match the prompt's leading
        full pages against the prefix cache (hits map read-only shared
        pages into the table — prefill for those tokens is SKIPPED),
        then for each remaining prefill_len chunk of the replay sequence
        grow the page table and LAUNCH the ONE prefill trace. No chunk
        is waited for: the final chunk's sampled token goes into the
        slot's row of the device's token array, where the next decode
        round finds it, and the host reads it at the end of this step
        (`_fetch`). On the way out the prompt's own full pages are
        registered in the cache so later admissions share them. Returns
        False when admission must back off (pages ran out between
        chunks, or a prefill failure triggered recovery)."""
        cfg = self.cfg
        ps = cfg.page_size
        slot = self._free_slots.pop()
        req.slot = slot
        self._trace_event(
            req, "resumed" if (req.preemptions or req.recoveries)
            else "admitted")
        self._page_table[slot] = 0
        req.pages = []
        req.shared_pages = []
        quant_ok = True
        if self.cfg.kv_dtype is not None:
            try:
                fault_point("quant.kv_write")
            except Exception:
                # quantized-write fault: degrade THIS admission to
                # private pages only (no cache mapping, no publish on
                # the way out) so a suspect write can never be shared
                # into another request's table row
                _metrics.counter("serve.kv_quant_degraded").inc()
                quant_ok = False
        matched = self._map_prefix(req, total) if quant_ok else 0
        skipped = 0
        routed = []     # each chunk's expert-row counts (a model with
        #                 experts): they ride the admission's flight
        for ci in range(-(-total // cfg.prefill_len)):
            start = ci * cfg.prefill_len
            clen = min(cfg.prefill_len, total - start)
            if start + clen <= matched:
                skipped += clen   # fully cache-covered: no prefill call
                continue
            need = -(-(start + clen) // ps)
            while len(req.shared_pages) + len(req.pages) < need:
                page = self._alloc_page()
                if page is None:
                    # pool drained between chunks: undo this admission
                    # (pages already written are masked by length and
                    # will be overwritten on retry) and wait
                    _metrics.counter("serve.page_stalls").inc(
                        where="admit")
                    self._abort_admission(req)
                    return False
                self._page_table[
                    slot, len(req.shared_pages) + len(req.pages)] = page
                req.pages.append(page)
            starts = np.asarray([start], np.int32)
            lens = np.asarray([clen], np.int32)
            floors = np.asarray([matched], np.int32)
            # its own copy: the table is edited while the chunk is queued
            page_row = self._page_table[slot][None, :].copy()
            try:
                fault_point("serve.prefill")
                self._tokens_dev, (self._caches, self._state), *chunk = \
                    self._prefill_jit(
                        self._params, (self._caches, self._state),
                        self._tokens_dev, req.device_prompt[ci], starts,
                        lens, page_row, floors,
                        np.asarray([slot], np.int32),
                        *self._sampling_rows(req))
                routed.extend(chunk)
                # the chunk's real tokens at or past the write floor
                self.kv_rows_written += start + clen - max(start, matched)
                if self._spec_on:
                    # mirror the chunk into the draft pools (same pages,
                    # same write floor — shared prefix pages keep their
                    # published draft K/V) so the first speculative
                    # round sees a fully warm draft cache
                    self._draft_caches = self._draft_prefill_jit(
                        self._draft_params, self._draft_caches,
                        req.device_prompt[ci], starts, lens, page_row,
                        floors)
            except Exception as e:
                self._recover("serve.prefill", e, pending=req)
                return False
        self.prefill_tokens_skipped += skipped
        if quant_ok:
            self._publish_prefix(req)
        self._lengths[slot] = total
        req.status = "running"
        self._running[slot] = req
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._top_ps[slot] = req.top_p
        self._seeds[slot] = req.seed
        # the first token is counted though not read: the slot's next
        # draw is fold(seed, count) and max_new is reached by count
        self._gen_counts[slot] = len(req.tokens) + 1
        self._active[slot] = True
        self._took_off({slot: req}, first=True, routed=routed)
        return True

    def _abort_admission(self, req):
        """Undo a half-done admission (mid-chunk page famine): free the
        slot and pages (shared ones back to the cache), requeue at the
        front."""
        slot = req.slot
        self._return_pages(req)
        self._page_table[slot] = 0
        self._lengths[slot] = 0
        self._active[slot] = False
        self._running.pop(slot, None)
        self._free_slots.append(slot)
        req.slot = None
        req.status = "queued"
        self._queue.appendleft(req)

    def _grow_pages(self):
        """Allocate the page each slot's next token write needs where
        lengths crossed a boundary; slots that cannot get one stall
        (deactivate) for this round and retry next step. A slot whose
        request has reached max_new by count gets no further row.
        Returns the stalled slots. Idempotent — safe to re-run after a preemption
        freed pages."""
        stalled = []
        ps = self.cfg.page_size
        for slot, req in self._running.items():
            if self._gen_counts[slot] >= req.max_new:
                # every token it may have is launched: the slot only
                # waits for the last one to be read
                self._active[slot] = False
                continue
            self._active[slot] = True
            ln = int(self._lengths[slot])
            owned = len(req.shared_pages) + len(req.pages)
            if ln % ps or ln // ps < owned:
                continue                   # room in the current page
            page = self._alloc_page()
            if page is not None:
                req.pages.append(page)
                self._page_table[slot, ln // ps] = page
            else:
                _metrics.counter("serve.page_stalls").inc(where="decode")
                self._active[slot] = False
                stalled.append(slot)
        return stalled

    def _spec_round(self):
        """One speculative round: the draft model proposes up to spec_k
        tokens per active slot (spec_k decode-shaped calls of the ONE
        draft trace, lengths+i / counts+i), then the target scores the
        whole [slots, spec_k+1] window — pending token + proposals — in
        ONE batched verify step against the paged cache and re-draws
        every position with the exact fold(seed, count+i) key the plain
        path would use. Returns (sampled [S, W], proposals [S, K],
        win [S]) as host arrays; step() accepts the leading run of
        matching proposals and emits accepted + 1 target draws.

        Window sizing: win[slot] = min(spec_k+1, remaining token
        budget), then shrunk to what the slot's pages can hold when the
        pool is drained (never below 1 — _grow_pages already made the
        pending position writable, so a famine degrades the slot to
        plain-decode behavior instead of stalling it)."""
        with phase("serve.decode"):
            cfg = self.cfg
            ps = cfg.page_size
            k = cfg.spec_k
            win = np.zeros(cfg.num_slots, np.int32)
            for slot, req in self._running.items():
                if not self._active[slot]:
                    continue               # page-stalled this round
                w = min(k + 1, req.max_new - len(req.tokens))
                ln = int(self._lengths[slot])
                while w > 1:
                    owned = len(req.shared_pages) + len(req.pages)
                    if (ln + w - 1) // ps < owned:
                        break              # window fully covered
                    page = self._alloc_page()
                    if page is None:
                        # pool famine: shrink the window to the pages the
                        # slot already owns (>= 1 position past _grow_pages)
                        w = owned * ps - ln
                        break
                    req.pages.append(page)
                    self._page_table[slot, owned] = page
                win[slot] = w
            # draft phase: proposal i+1 is drawn with count+i — the same
            # key verify re-draws position i+1 with, so a well-matched
            # draft's proposals survive acceptance token-for-token. Tokens
            # feed back as device arrays; nothing syncs until the window is
            # scored.
            props_dev = []
            last = tok = self._pending_tokens()
            for i in range(k):
                step_act = self._active & (win > i + 1)
                tok, self._draft_caches = self._draft_jit(
                    self._draft_params, self._draft_caches, tok,
                    self._page_table, self._lengths + i, step_act,
                    self._temps, self._top_ks, self._top_ps,
                    self._seeds, self._gen_counts + i)
                props_dev.append(tok)
            window = jnp.stack([jnp.asarray(last)] + props_dev, axis=1)
            self.kv_rows_written += int(win.sum())   # the target's
            sampled_dev, self._caches = self._verify_jit(
                self._params, self._caches, window, self._lengths, win,
                self._page_table, self._temps, self._top_ks, self._top_ps,
                self._seeds, self._gen_counts)
        with phase("serve.fetch"):
            props = np.stack([np.asarray(p) for p in props_dev], axis=1)
            sampled = np.asarray(sampled_dev)  # graft-lint: disable=hot-path-sync (the speculative round's one deliberate sync point, fetching proposals + verify draws together: acceptance is a host-side compare, and the scheduler needs this round's tokens to advance/free slots)
        return sampled, props, win

    def _pending_tokens(self):
        """Every running slot's pending token from the host's record,
        [slots] int32: what a speculative engine, which has read every
        token before it launches, feeds its round."""
        last = np.zeros(self.cfg.num_slots, np.int32)
        for slot, req in self._running.items():
            last[slot] = req.tokens[-1]
        return last

    def _free_slot_state(self, req):
        """Return a request's slot and pages to the free lists (shared
        pages back to the prefix cache), zero the slot's scheduler rows
        and take its rows out of what is in flight: whatever a launched
        program still computes for the slot is nobody's. Safe under a
        round in flight by the device's program order: a later prefill
        into the slot or its pages is queued behind the round that still
        reads them. Leaves req.slot set (terminal trace events carry
        it); requeue paths null it themselves. Returns the decode rows
        dropped."""
        slot = req.slot
        dropped = 0
        for fl in self._inflight:
            if fl.rows.pop(slot, None) is not None and not fl.first:
                dropped += 1
        self._inflight = [fl for fl in self._inflight if fl.rows]
        self._return_pages(req)
        self._page_table[slot] = 0
        self._lengths[slot] = 0
        self._active[slot] = False
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 0.0
        self._seeds[slot] = 0
        self._gen_counts[slot] = 0
        self._running.pop(slot, None)
        self._free_slots.append(slot)
        return dropped

    def _preempt(self, req):
        """Recompute preemption: drop the request's device state and
        requeue it at the FRONT of the queue (its staged prompt is still
        device-resident, so re-admission pays only the prefill)."""
        self._trace_event(req, "preempted",
                          tokens_dropped=len(req.tokens))
        self._free_slot_state(req)
        req.slot = None
        req.tokens = []
        req.status = "queued"
        req.preemptions += 1
        self._queue.appendleft(req)
        _metrics.counter("serve.preemptions").inc()

    def _recover(self, where, exc, pending=None):
        """Crash-isolated step recovery. The decode/prefill jits donate
        the page pools, so after ANY failure inside them the device
        state is suspect — quarantine it: rebuild the pools, zero the
        scheduler arrays, and re-admit every in-flight request
        recompute-style (host-side prompt + generated tokens are the
        durable state; a greedy request finishes token-exact). The same
        step programs run again: a kernel that fails at run time keeps
        failing and surfaces when the budget is spent, never a quiet
        change of implementation. Bounded: `serve_step_retries`
        consecutive failures, then every request is failed and `exc`
        re-raised."""
        cfg = self.cfg
        self.recoveries += 1
        _metrics.counter("serve.recoveries").inc(where=where)
        victims = sorted(self._running.values(), key=lambda r: r.id)
        if pending is not None:
            victims.append(pending)
        if self._run_log is not None:
            self._run_log.write({
                "phase": "serve", "recovery": where,
                "step": self._step_no, "in_flight": len(victims),
                "error": f"{type(exc).__name__}: {exc}"[:200]})
        # quarantine: drop the (donated, possibly poisoned) pools
        self._caches = self._model.init_paged_caches(
            cfg.num_pages, cfg.page_size, dtype=cfg.cache_dtype,
            kv_dtype=cfg.kv_dtype)
        # the per-slot state was donated with them; the replay's first
        # chunk starts every slot from zeros anyway
        self._state = self._init_state()
        if self._spec_on:
            # the draft pools were donated to the same failed round
            self._draft_caches = self._draft_model.init_paged_caches(
                cfg.num_pages, cfg.page_size, dtype=cfg.cache_dtype,
                kv_dtype=cfg.kv_dtype)
        self._page_table[:] = 0
        self._lengths[:] = 0
        self._active[:] = False
        # the rounds in flight are dropped with the state they ran on:
        # every request replays from the tokens that were READ
        self._inflight = []
        self._tokens_dev = jnp.zeros(cfg.num_slots, jnp.int32)
        self._temps[:] = 0.0
        self._top_ks[:] = 0
        self._top_ps[:] = 0.0
        self._seeds[:] = 0
        self._gen_counts[:] = 0
        self._free_slots = list(range(cfg.num_slots))
        self._free_pages = collections.deque(range(cfg.num_pages))
        if self._prefix_cache is not None:
            # every cached page id now points at zeroed pools — forget
            # the index (sharing degrades; re-admissions re-publish)
            self._prefix_cache.clear()
            _metrics.gauge("serve.pages_shared").set(0)
        self._running = {}
        for req in reversed(victims):      # appendleft keeps id order
            req.slot = None
            req.pages = []
            req.shared_pages = []
            req.status = "queued"
            req.recoveries += 1
            if req.tokens or req.device_prompt is None:
                # the staged chunks hold only the prompt — restage the
                # full replay sequence (prompt + generated tokens, the
                # durable host-side state)
                req.device_prompt = self._stage_chunks(req.output)
            self._trace_event(req, "requeued", cause=where,
                              tokens_kept=len(req.tokens))
            self._queue.appendleft(req)
        _metrics.gauge("serve.active_slots").set(0)
        _metrics.gauge("serve.queue_depth").set(len(self._queue))
        try:
            self._retry_budget.failure(exc)   # backoff sleep, or raise
        except Exception:
            self._fail_all(exc)
            raise

    def _fail_all(self, exc):
        """Recovery budget spent: retire every queued + running request
        with terminal status `failed` before the engine re-raises, so no
        caller is left waiting on a request that can never finish."""
        doomed = list(self._queue) + list(self._running.values())
        self._queue.clear()
        for req in doomed:
            if req.slot is not None:
                self._free_slot_state(req)
            self._retire_terminal(req, "failed", "engine_error")
        _metrics.gauge("serve.queue_depth").set(0)
        _metrics.gauge("serve.active_slots").set(0)

    # --- terminal statuses beyond completion -----------------------------

    def _retire_terminal(self, req, status, why, finished=None,
                         account=True):
        """Retire a request on a non-completion terminal path (rejected |
        shed | cancelled | failed). `account=True` counts it as an
        SLO-failed retirement (lowering goodput — the engine failed the
        client); cancel passes False."""
        req.status = status
        req.retire_reason = why
        req.done_t = self._clock()
        req.device_prompt = None
        if account:
            req.slo_ok = False
            self._retired += 1
            _metrics.gauge("serve.goodput").set(self.goodput())
        self._trace_event(req, "retired", reason=status, why=why,
                          tokens=len(req.tokens),
                          slo_ok=bool(req.slo_ok),
                          preemptions=req.preemptions)
        _metrics.counter("serve.requests").inc(status=status)
        if finished is not None:
            finished.append(req)

    def _reject(self, req, why):
        """Terminal `rejected` at submit time — with the retriable hint:
        the request was never started, so resubmitting (after backoff,
        or with a feasible deadline) is the right client move."""
        req.retriable = True
        self._retire_terminal(req, "rejected", why)

    def _shed_expired(self, finished):
        """Drop every queued request whose deadline has passed (terminal
        `shed`) — serving a request that can no longer meet its deadline
        wastes pages the live ones need."""
        if not self._queue:
            return 0
        now = self._clock()
        expired = [r for r in self._queue
                   if r.deadline_t is not None and now > r.deadline_t]
        for req in expired:
            self._queue.remove(req)
            _metrics.counter("serve.shed").inc(cause="deadline")
            self._retire_terminal(req, "shed", "deadline_expired",
                                  finished)
        return len(expired)

    def shed_queued(self, cause="overload"):
        """Load shedding (the watchdog's mitigation action): shed every
        expired queued request; when none is expired, shed the single
        lowest-priority / latest-deadline one. Returns the shed ids."""
        with self._lock:
            shed = []
            now = self._clock()
            for req in [r for r in self._queue
                        if r.deadline_t is not None
                        and now > r.deadline_t]:
                self._queue.remove(req)
                shed.append((req, "deadline_expired"))
            if not shed and self._queue:
                victim = min(self._queue, key=self._victim_key)
                self._queue.remove(victim)
                shed.append((victim, cause))
            for req, why in shed:
                _metrics.counter("serve.shed").inc(cause=cause)
                self._retire_terminal(req, "shed", why)
            _metrics.gauge("serve.queue_depth").set(len(self._queue))
            return [req.id for req, _ in shed]

    def _on_anomaly(self, event):
        """Watchdog mitigation hook: a goodput collapse or ingest stall
        sheds queued load instead of only latching a counter. When a
        fleet router owns this engine it installs `anomaly_sink` so the
        same signal also sheds expired/lowest-priority work fleet-wide
        (a supervisor decision no single replica can make) — and owns
        the flight-recorder dump, fanned out across every replica; a
        STANDALONE engine dumps its own evidence bundle here."""
        fl = _flight.recorder()
        if fl is not None:
            fl.note_event("anomaly", **{k: v for k, v in event.items()
                                        if k not in ("event", "t")})
        if event.get("anomaly") in ("goodput_collapse", "ingest_stall"):
            self.shed_queued(cause=event["anomaly"])
        if self.anomaly_sink is not None:
            self.anomaly_sink(event)
        elif fl is not None:
            _flight.dump_bundle(
                reason=str(event.get("anomaly", "anomaly")),
                run_logs=(self._run_log,) if self._run_log else (),
                config=dict(serve_config=self.config_summary(),
                            model_version=self.version),
                extra=dict(anomaly=event))

    def config_summary(self):
        """Shallow JSON-friendly view of the active ServeConfig (the
        flight bundle's config section; non-scalar fields repr)."""
        return {f.name: getattr(self.cfg, f.name)
                for f in dataclasses.fields(self.cfg)}

    def _done_reason(self, req, tok):
        """Retirement reason for the token just emitted, or None."""
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        if len(req.tokens) >= req.max_new:
            return "length"
        return None

    def _account_slo(self, req):
        """Classify one retirement against the configured SLOs and
        refresh serve.goodput (SLO targets of 0 are unbounded)."""
        cfg = self.cfg
        ok = True
        if req.first_token_t is not None:
            ttft = req.first_token_t - req.submit_t
            if cfg.slo_ttft_s and ttft > cfg.slo_ttft_s:
                _metrics.counter("serve.slo_violations").inc(kind="ttft")
                ok = False
            if cfg.slo_token_latency_s and len(req.tokens) > 1:
                per_tok = ((req.done_t - req.first_token_t)
                           / (len(req.tokens) - 1))
                if per_tok > cfg.slo_token_latency_s:
                    _metrics.counter("serve.slo_violations").inc(
                        kind="token_latency")
                    ok = False
        req.slo_ok = ok
        self._retired += 1
        self._retired_ok += int(ok)
        _metrics.gauge("serve.goodput").set(self.goodput())

    def _release(self, req, finished, reason="length"):
        # a request that ends by max_new has no row in flight (the host
        # stopped launching it by count); one that ends at EOS learnt it
        # a round late, and that round's row for it is discarded
        self.late_rows += self._free_slot_state(req)
        req.status = "done"
        req.retire_reason = reason
        req.done_t = self._clock()
        req.device_prompt = None
        self._account_slo(req)
        self._trace_event(req, "retired", reason=reason,
                          tokens=len(req.tokens), slo_ok=req.slo_ok,
                          preemptions=req.preemptions,
                          spec_tokens=req.spec_tokens)
        finished.append(req)
        _metrics.counter("serve.requests").inc(status="completed")
