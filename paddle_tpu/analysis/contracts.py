"""Declarative compile-contract engine.

tools/compile_smoke.py used to hold one ad-hoc regex per model for its
HLO assertions (``vocab_temporaries`` / ``weight_all_gathers`` /
``dense_score_temporaries``). This module promotes those checks to
first-class contract objects evaluated against a
:class:`ContractContext` (compiled HLO text, jaxpr text, runtime trace
counts), plus the single per-model table :data:`CONTRACTS` covering the
fused+sharded train steps (gpt / bert / transformer_big) and the
serving prefill/decode steps. compile_smoke stays the thing that
*compiles*; this module is the thing that *judges* — and the planted-
violation fixtures in tests/test_lint.py prove each judge actually
fires.

Two judge families live here beyond the structural HLO checks:

* **budget contracts** (:class:`MaxHloFlops` / :class:`MaxHloBytes`) —
  the compiled module's XLA ``cost_analysis()`` figures may not exceed
  what the autoplan cost model predicted times a calibrated tolerance.
  No hand-written byte constants: retuning the cost model retunes the
  budget.
* **snapshot gates** (:class:`HloSnapshot`, :data:`CONTRACT_SNAPSHOTS`)
  — the normalized opcode histogram of the compiled module must match
  the blessed record under tests/fixtures/hlo_snapshots/; structural
  drift fails until re-blessed with
  ``tools/graft_lint.py --contracts --update-snapshots``.

Stdlib-only: contracts see text and cost dicts, never jax objects, so
the table is importable by the lint CLI without paying the jax import
(the cost model and topology table it prices budgets with are loaded by
file path and are themselves stdlib-only).
"""

import dataclasses
import hashlib
import importlib.util
import json
import math
import os
import re

# every HLO dtype token we may meet in shapes, with its bit width
DTYPE_BITS = {
    "pred": 1, "s2": 2, "s4": 4, "s8": 8, "s16": 16, "s32": 32,
    "s64": 64, "u2": 2, "u4": 4, "u8": 8, "u16": 16, "u32": 32,
    "u64": 64, "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8,
    "f8e4m3fnuz": 8, "f8e5m2fnuz": 8, "f16": 16, "bf16": 16, "f32": 32,
    "f64": 64, "c64": 64, "c128": 128,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(DTYPE_BITS, key=len, reverse=True))
    + r")\[([0-9,]*)\]")


def hlo_shapes(text, dtypes=("f32", "bf16")):
    """All (dtype, shape-tuple) pairs in an HLO module's text, filtered
    to ``dtypes`` (None = all)."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        if dtypes is not None and m.group(1) not in dtypes:
            continue
        dims = m.group(2)
        shp = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((m.group(1), shp))
    return out


@dataclasses.dataclass
class Violation:
    contract: str
    message: str

    def format(self):
        return f"[{self.contract}] {self.message}"


@dataclasses.dataclass
class ContractContext:
    """What a compile produced, as text: per-device compiled HLO
    (``.compile().as_text()``), lowered/jaxpr text when the caller has
    it, runtime trace counts for the TracedOnce contract, and the
    normalized ``cost_analysis()`` dict for the budget contracts."""
    hlo_text: str = None
    jaxpr_text: str = None
    trace_counts: dict = None
    cost: dict = None


def normalize_cost(raw):
    """``compiled.cost_analysis()`` returns a dict on some jax versions
    and a per-device list of dicts on others; flatten to one
    {metric: float} dict (None when there is nothing to judge)."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not raw:
        return None
    return {str(k): float(v) for k, v in raw.items()}


class Contract:
    """One statically-checkable compile invariant. ``check`` returns
    violation messages (empty = the contract holds)."""

    name = None

    def check(self, ctx):
        raise NotImplementedError

    def violations(self, ctx):
        return [Violation(self.name, m) for m in self.check(ctx)]


class NoTemporary(Contract):
    """No f32/bf16 temporary carrying any dim in ``dims`` next to >=
    ``min_rows`` row elements — i.e. no materialized [rows, dim]-scale
    tensor in the per-device module. ``min_rows`` is chosen ABOVE the
    model width so a [dim, hidden] weight shard (a legitimate resident
    on that axis) never trips it."""

    def __init__(self, dims, min_rows, dtypes=("f32", "bf16"),
                 what="temporary"):
        self.dims = frozenset(int(d) for d in dims)
        self.min_rows = int(min_rows)
        self.dtypes = tuple(dtypes)
        self.what = what
        self.name = f"no-temporary({sorted(self.dims)}, rows>={min_rows})"

    def temporaries(self, hlo_text):
        """The offending shapes, sorted — compile_smoke reports these."""
        hits = set()
        for _, shp in hlo_shapes(hlo_text, self.dtypes):
            for d in shp:
                if d in self.dims and d and math.prod(shp) // d >= self.min_rows:
                    hits.add(shp)
        return sorted(hits)

    def check(self, ctx):
        if ctx.hlo_text is None:
            return []
        return [f"{self.what} {shp} materialized in the compiled module"
                for shp in self.temporaries(ctx.hlo_text)]


class NoKvDequantTemporary(Contract):
    """int8-paged-KV serve contract: no wide-float tensor at paged-KV
    layout scale in the compiled module. The page pools are laid out
    [num_pages, page_size, H*hd] (ops/attention.py); with
    serve_kv_dtype=int8 the only f32 KV values allowed are the kernel's
    per-page dequant tiles, so any f32/bf16 tensor that ends in
    [page_size, H*hd] and holds >= ``min_pages`` such pages is a
    dequantized pool or pool-gather materialized outside the kernel —
    the exact temporary int8 storage exists to avoid. ``min_pages``
    sits above the kernel's per-tile dequant (one page) and below the
    smallest whole-pool dequant, so the f32-pool engine is the positive
    control that trips it."""

    def __init__(self, page_size, row_width, min_pages,
                 dtypes=("f32", "bf16")):
        self.page_size = int(page_size)
        self.row_width = int(row_width)
        self.min_pages = int(min_pages)
        self.dtypes = tuple(dtypes)
        self.name = (f"no-kv-dequant-temporary([...,{page_size},"
                     f"{row_width}], pages>={min_pages})")

    def temporaries(self, hlo_text):
        hits = set()
        for _, shp in hlo_shapes(hlo_text, self.dtypes):
            if (len(shp) >= 3
                    and shp[-2:] == (self.page_size, self.row_width)
                    and math.prod(shp[:-2]) >= self.min_pages):
                hits.add(shp)
        return sorted(hits)

    def check(self, ctx):
        if ctx.hlo_text is None:
            return []
        return [f"f32 KV temporary {shp} at page-pool scale in the "
                "compiled int8 serve step — dequantization escaped the "
                "kernel's per-page tiles"
                for shp in self.temporaries(ctx.hlo_text)]


class NoOpMatching(Contract):
    """No HLO instruction line matching ``pattern`` — optionally only
    lines where some bracketed shape satisfies ``shape_test`` (e.g.
    all-gathers at vocab-weight scale, not the benign small ones)."""

    _BRACKET_RE = re.compile(r"\[([0-9,]+)\]")

    def __init__(self, pattern, shape_test=None, what=None):
        self.pattern = re.compile(pattern)
        self.shape_test = shape_test
        self.what = what or f"op matching /{pattern}/"
        self.name = f"no-op-matching({pattern})"

    def matches(self, hlo_text):
        hits = []
        for line in hlo_text.splitlines():
            if not self.pattern.search(line):
                continue
            if self.shape_test is not None:
                ok = False
                for m in self._BRACKET_RE.finditer(line):
                    shp = tuple(int(d) for d in m.group(1).split(","))
                    if self.shape_test(shp):
                        ok = True
                        break
                if not ok:
                    continue
            hits.append(line.strip()[:160])
        return hits

    def check(self, ctx):
        if ctx.hlo_text is None:
            return []
        return [f"{self.what}: {line}" for line in self.matches(ctx.hlo_text)]


class TracedOnce(Contract):
    """Every tracked function was traced exactly once across the run —
    the continuous-batching shapes are slot-fixed; a retrace means a
    shape or dtype leaked into the traced signature."""

    name = "traced-once"

    def __init__(self, fns=None):
        self.fns = tuple(fns) if fns is not None else None

    def check(self, ctx):
        counts = ctx.trace_counts or {}
        out = []
        names = self.fns if self.fns is not None else sorted(counts)
        for fn in names:
            n = counts.get(fn)
            if n is None:
                out.append(f"{fn}: no trace count recorded")
            elif n != 1:
                out.append(f"{fn}: traced {n}x (expected exactly once)")
        return out


class DonationRespected(Contract):
    """The compiled module aliases >= ``min_aliases`` inputs to outputs
    (``input_output_alias={ {0}: (1, {}, may-alias) ... }`` in the
    module header) — donated buffers (KV pools, optimizer state) really
    were reused rather than silently copied."""

    _ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\(")

    def __init__(self, min_aliases=1):
        self.min_aliases = int(min_aliases)
        self.name = f"donation-respected(>={min_aliases})"

    def check(self, ctx):
        if ctx.hlo_text is None:
            return []
        m = re.search(r"input_output_alias=\{(.*)", ctx.hlo_text)
        n = len(self._ENTRY_RE.findall(m.group(1))) if m else 0
        if n < self.min_aliases:
            return [f"only {n} input->output aliases in the compiled "
                    f"module (expected >= {self.min_aliases}) — a "
                    "donated buffer is being copied"]
        return []


class NoHostCallback(Contract):
    """No host round-trip inside the compiled step: no infeed/outfeed
    and no callback custom-call in the HLO; no pure_callback /
    io_callback / debug_callback primitive in the jaxpr (a stray
    jax.debug.print in a hot kernel shows up here)."""

    name = "no-host-callback"

    _HLO_PATTERNS = (re.compile(r"\binfeed\b"), re.compile(r"\boutfeed\b"),
                     re.compile(r"custom-call[^\n]*callback"))
    _JAXPR_RE = re.compile(
        r"\b(pure_callback|io_callback|debug_callback)\b")

    def check(self, ctx):
        out = []
        if ctx.hlo_text is not None:
            for pat in self._HLO_PATTERNS:
                for line in ctx.hlo_text.splitlines():
                    if pat.search(line):
                        out.append(f"host callback in HLO: "
                                   f"{line.strip()[:160]}")
        if ctx.jaxpr_text is not None:
            for m in self._JAXPR_RE.finditer(ctx.jaxpr_text):
                out.append(f"{m.group(1)} primitive in the jaxpr — host "
                           "round-trip inside the staged step")
        return out


class MaxDtypeWidth(Contract):
    """No float/complex tensor wider than ``max_bits`` in the compiled
    module (f64 creeping into a TPU step means an accidental float64
    promotion — x64 math runs at a fraction of MXU rate). Integer types
    are allowlisted by default: RNG and iota legitimately use u64/s64
    counters."""

    def __init__(self, max_bits=32, allow=("s64", "u64", "c64")):
        self.max_bits = int(max_bits)
        self.allow = frozenset(allow)
        self.name = f"max-dtype-width({max_bits})"

    def offending(self, text):
        seen = {}
        for dt, shp in hlo_shapes(text, dtypes=None):
            if dt in self.allow or DTYPE_BITS[dt] <= self.max_bits:
                continue
            seen.setdefault(dt, shp)
        return seen

    def check(self, ctx):
        out = []
        for text in (ctx.hlo_text, ctx.jaxpr_text):
            if text is None:
                continue
            for dt, shp in sorted(self.offending(text).items()):
                out.append(f"{dt} tensor (e.g. {dt}{list(shp)}) exceeds "
                           f"{self.max_bits}-bit width — accidental "
                           "wide-precision promotion")
        return out


class MaxHloCost(Contract):
    """Budget contract: one XLA ``cost_analysis()`` metric of the
    compiled module may not exceed ``predicted * tolerance``, where
    ``predicted`` comes from the autoplan cost model (never a
    hand-written constant). Holds vacuously when the context carries no
    cost dict — text-only evaluations judge the structural contracts
    only."""

    metric = None   # short label ("flops" / "bytes")
    key = None      # cost_analysis dict key

    def __init__(self, predicted, tolerance, source=""):
        self.predicted = float(predicted)
        self.tolerance = float(tolerance)
        self.budget = self.predicted * self.tolerance
        self.source = source
        self.name = f"max-hlo-{self.metric}(<={self.budget:.4g})"

    def with_tolerance(self, tolerance):
        """Clone at a different tolerance — ``with_tolerance(0)`` is the
        positive control proving the detector trips on any real
        compile."""
        return type(self)(self.predicted, tolerance, source=self.source)

    def check(self, ctx):
        if ctx.cost is None:
            return []
        actual = ctx.cost.get(self.key)
        if actual is None:
            return [f"cost analysis carries no {self.key!r} metric — "
                    "cannot judge the budget"]
        if actual > self.budget:
            return [f"compiled {self.metric} {actual:.4g} exceeds budget "
                    f"{self.budget:.4g} (= {self.predicted:.4g} predicted"
                    f" by {self.source or 'the cost model'} x "
                    f"{self.tolerance:g} tolerance)"]
        return []


class MaxHloFlops(MaxHloCost):
    metric = "flops"
    key = "flops"


class MaxHloBytes(MaxHloCost):
    metric = "bytes"
    key = "bytes accessed"


# --- differential snapshot gate --------------------------------------

# one HLO instruction: "%name = <types> opcode(operands), ..." — the
# opcode is the first bare lowercase token followed by '(' after the '='
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?([a-z][a-z0-9\-]*)\(")


def hlo_op_histogram(text):
    """Opcode -> count over every instruction in an HLO module's text.
    Instruction *names* and shapes are ignored, so the histogram is
    stable across recompiles; a pass-pipeline or fusion-decision change
    shows up as a count shift."""
    ops = {}
    for line in text.splitlines():
        m = _HLO_INSTR_RE.match(line)
        if m:
            ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return ops


def _ops_hash(ops):
    blob = json.dumps(sorted(ops.items())).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


_SNAPSHOT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "tests", "fixtures", "hlo_snapshots")


class HloSnapshot(Contract):
    """Differential gate: the opcode histogram of the compiled module
    must hash-match the blessed record for ``key``. Unexplained drift
    (a new op, a vanished op, a count shift) is a violation until the
    change is re-blessed with
    ``tools/graft_lint.py --contracts --update-snapshots``."""

    def __init__(self, key, snapshot_dir=None):
        self.key = key
        self.snapshot_dir = snapshot_dir or _SNAPSHOT_DIR
        self.name = f"hlo-snapshot({key})"

    @property
    def path(self):
        fname = re.sub(r"[^\w.@,-]", "_", self.key) + ".json"
        return os.path.join(self.snapshot_dir, fname)

    def load(self):
        try:
            with open(self.path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def bless(self, hlo_text):
        ops = hlo_op_histogram(hlo_text)
        rec = {"key": self.key, "hash": _ops_hash(ops),
               "ops": dict(sorted(ops.items()))}
        os.makedirs(self.snapshot_dir, exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
            f.write("\n")
        return rec

    def check(self, ctx):
        if ctx.hlo_text is None:
            return []
        blessed = self.load()
        if blessed is None:
            return [f"no blessed snapshot at {self.path} — bless one "
                    "with tools/graft_lint.py --contracts "
                    "--update-snapshots"]
        ops = hlo_op_histogram(ctx.hlo_text)
        if _ops_hash(ops) == blessed.get("hash"):
            return []
        old = blessed.get("ops", {})
        added = sorted(set(ops) - set(old))
        removed = sorted(set(old) - set(ops))
        changed = sorted(op for op in set(ops) & set(old)
                         if ops[op] != old[op])
        detail = "; ".join(p for p in (
            added and ("new ops: " + ", ".join(added[:6])),
            removed and ("vanished ops: " + ", ".join(removed[:6])),
            changed and ("count drift: " + ", ".join(
                f"{op} {old[op]}->{ops[op]}" for op in changed[:6])),
        ) if p)
        return ["op histogram drifted from blessed snapshot "
                f"({detail or 'hash mismatch'}) — if the change is "
                "intended, re-bless with --update-snapshots"]


def evaluate(contracts, ctx):
    """Run each contract; return the flat violation list (empty = every
    contract holds)."""
    out = []
    for c in contracts:
        out.extend(c.violations(ctx))
    return out


# --- the per-model contract table ------------------------------------
#
# Sharded train steps: tiny configs with batch/seq picked so no
# legitimate dim collides with {V, V/tp} and the row threshold clears
# the model width with >= 2x margin (xent_chunk=64 keeps even the fused
# path's per-chunk logits tile far below it). The serve step keys on
# the padded slot capacity Tmax=48, every other dim distinct.

@dataclasses.dataclass(frozen=True)
class ShardedCase:
    """Compile shapes for one model's dp x tp contract run. The depth
    fields (layers/heads/intermediate/max_position) are only filled for
    models with priced budget rows — they must mirror the tiny config
    tools/compile_smoke.py ``train_program`` compiles (a drift-guard test
    in tests/test_lint.py pins the gpt row to GPTConfig.tiny)."""
    batch: int
    seq: int
    vocab: int
    hidden: int
    loss_rows: staticmethod   # (batch, seq) -> rows entering the loss
    layers: int = None
    heads: int = None
    intermediate: int = None
    max_position: int = None

    def min_rows(self, dp=2):
        return self.loss_rows(self.batch, self.seq) // dp // 2


SHARDED_TRAIN_CASES = {
    "gpt": ShardedCase(16, 128, 512, 64, lambda b, s: b * s,
                       layers=2, heads=4, intermediate=128,
                       max_position=128),
    # BERT's MLM head only scores the 15% masked positions
    "bert": ShardedCase(32, 128, 1024, 64,
                        lambda b, s: b * max(1, int(0.15 * s))),
    # NMT transformer: every target position enters the loss
    "transformer_big": ShardedCase(16, 128, 1000, 64, lambda b, s: b * s),
}


def sharded_train_contracts(model, dp=2, tp=2):
    """The fused+sharded train-step contract for one model: no
    [rows, vocab]-scale temporary, no vocab-weight all-gather, no f64,
    no host callback."""
    c = SHARDED_TRAIN_CASES[model]
    vocab, hidden = c.vocab, c.hidden
    return [
        NoTemporary({vocab, vocab // tp}, c.min_rows(dp),
                    what="[rows, vocab]-scale logits temporary"),
        NoOpMatching(
            "all-gather",
            shape_test=lambda shp: (vocab in shp
                                    and math.prod(shp) >= vocab * hidden),
            what="vocab-weight-scale all-gather"),
        MaxDtypeWidth(32),
        NoHostCallback(),
    ]


# fused-MLP probe dims: rows=512, H=256, I=1024 (the 4H convention).
# I must exceed the kernel's 512 intermediate-tile cap so the fused path
# genuinely blocks the I axis — at I <= 512 the single [rows, I] block
# IS the activation and the detector could not tell fused from unfused.
# MLP_MIN_ROWS sits above H=256 so the [H, I] / [I, H] weights
# (legitimate I-axis residents) never trip; the [512, 1024] activation
# of the unfused composition does.
MLP_ROWS = 512
MLP_HIDDEN = 256
MLP_INTER = 1024
MLP_MIN_ROWS = 320


def fused_mlp_contracts(inter=MLP_INTER, min_rows=MLP_MIN_ROWS):
    """The fused GLU/MLP forward contract: the [rows, 4H] activation
    never materializes in the compiled module (the kernel streams
    I-axis tiles through a [block_rows, H] accumulator)."""
    return [
        NoTemporary({inter}, min_rows,
                    what="[rows, 4H] MLP activation temporary"),
        MaxDtypeWidth(32),
        NoHostCallback(),
    ]


SERVE_TMAX = 48
SERVE_MIN_ROWS = 8
# the serve probe's paged-KV layout (tools/compile_smoke._serve_engine:
# GPTConfig.tiny heads=4 x hd=16 = a 64-wide token row, page_size=8, 13
# pages). KV_MIN_PAGES sits above the kernel's per-tile dequant (one
# page) and below both the whole-pool dequant (13 pages) and the dense
# gather (slots x Pmax = 12 pages).
SERVE_PAGE_SIZE = 8
SERVE_KV_ROW = 64
SERVE_KV_MIN_PAGES = 6


def serve_decode_contracts(tmax=SERVE_TMAX, min_rows=SERVE_MIN_ROWS):
    """The paged decode-step contract: no [rows, Tmax]-dense gathered
    K/V or score temporary, the one trace, donated pools really
    aliased, no host callback, no f64."""
    return [
        NoTemporary({tmax}, min_rows,
                    what="[rows, Tmax]-dense attention temporary"),
        TracedOnce(("serve.decode",)),
        DonationRespected(min_aliases=1),
        NoHostCallback(),
        MaxDtypeWidth(32),
    ]


def serve_prefill_contracts():
    return [TracedOnce(("serve.prefill",))]


# speculative-verify probe dims (tools/compile_smoke._verify_engine):
# slots=16 and spec_k=7 give a slots x window = 128-row verify batch, so
# MIN_ROWS=96 sits ABOVE the model width (the tiny gpt's [vocab=512,
# hidden=64] tied embedding carries 64 rows per vocab column — a
# legitimate resident) and BELOW the 128-row dense lattice a verify step
# that materialized [slots, window, vocab] logits would compile. The
# detector works because the engine applies the vocab head + sampling
# PER WINDOW POSITION: no legitimate [slots*window, vocab] tensor exists
# in the module.
SERVE_VERIFY_SLOTS = 16
SERVE_VERIFY_SPEC_K = 7
SERVE_VERIFY_MIN_ROWS = 96
# probe pool: enough pages for the smoke's admission waves plus window
# growth; the byte budget prices the donated pool pass-through from this
# (pool_rows = pages * page_size), so the probe and the budget derive
# from the one constant
SERVE_VERIFY_PAGES = 31


def serve_verify_contracts():
    """The speculative verify-step contract: one trace each for the
    decode / draft / verify entry points, donated pools really aliased,
    no host callback, no f64, and NO dense [slots, window, vocab]
    logits lattice — the head is applied per window position, so
    sampling temporaries stay [slots, vocab]. (The [rows, Tmax] score
    detector of the decode row deliberately does NOT apply: the verify
    window legitimately re-attends the gathered prefix, amortized over
    up to window emitted tokens.)"""
    c = SHARDED_TRAIN_CASES["gpt"]
    return [
        NoTemporary({c.vocab}, SERVE_VERIFY_MIN_ROWS,
                    what="[slots*window, vocab]-dense verify logits "
                         "lattice"),
        TracedOnce(("serve.decode", "serve.draft", "serve.verify")),
        DonationRespected(min_aliases=1),
        NoHostCallback(),
        MaxDtypeWidth(32),
    ]


def serve_verify_budget_contracts(slots=SERVE_VERIFY_SLOTS,
                                  context=SERVE_TMAX,
                                  spec_k=SERVE_VERIFY_SPEC_K):
    """Budget row for the speculative verify step, priced by
    ``costmodel.predict_decode(spec_k=...)`` — zero hand-written
    constants: raising spec_k or slots re-derives the budget from the
    same cost model tools/autoplan.py reports break-even acceptance
    with."""
    cm, topo, rate = _pricing()
    pred = cm.predict_decode(
        _train_spec("gpt"), topo, slots=slots, context=context,
        rate=rate, spec_k=spec_k,
        pool_rows=SERVE_VERIFY_PAGES * SERVE_PAGE_SIZE)
    src = (f"costmodel.predict_decode(gpt, slots={slots}, "
           f"Tmax={context}, spec_k={spec_k})")
    return [
        MaxHloFlops(pred["verify_flops_per_chip"],
                    SERVE_VERIFY_BUDGET_TOLERANCE["flops"], source=src),
        MaxHloBytes(pred["verify_hlo_bytes"],
                    SERVE_VERIFY_BUDGET_TOLERANCE["bytes"], source=src),
    ]


# --- cost-model-priced budgets ---------------------------------------
#
# Tolerances are calibrated against the measured tiny-config compiles
# on jax-cpu (tests/test_compile_smoke.py re-measures every run):
# measured/predicted sits at ~0.85 (train flops), ~4.3 (train bytes —
# the traffic estimate undercounts XLA's interpret-mode and rematerial-
# ization traffic), ~1.02 (decode flops), ~3.4 (decode bytes: 2.117e6
# compiled against 6.298e5 predicted under jax 0.9.0's CPU backend; it
# was ~2.1 under 0.4.37 — the budget follows the CPU compiler, ROADMAP
# D1), so each budget leaves ~1.4-1.5x headroom over today's compiles
# while a real regression (an unfused xent materializing [rows, V]
# traffic, a dense Tmax attention) blows through it.
TRAIN_BUDGET_TOLERANCE = {"flops": 1.25, "bytes": 6.0}
SERVE_BUDGET_TOLERANCE = {"flops": 1.5, "bytes": 5.0}
# verify: measured/predicted sits at ~1.4 (flops) and ~9.2 (bytes — the
# per-position head + sampling unroll re-reads the tied embedding and
# its [slots, vocab] rows window times; that re-read traffic is exactly
# the price of never materializing the [slots, window, vocab] lattice,
# and the analytic model prices each row once). Same ~1.4x headroom
# convention as above.
SERVE_VERIFY_BUDGET_TOLERANCE = {"flops": 2.0, "bytes": 13.0}
SERVE_SLOTS = 2

_AUTOPLAN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "parallel", "autoplan")
_MOD_CACHE = {}


def _load_autoplan(stem):
    """Load a parallel/autoplan module by file path — keeps this module
    importable without the paddle_tpu package (and without jax); the
    cost model and topology table are themselves stdlib-only."""
    mod = _MOD_CACHE.get(stem)
    if mod is None:
        path = os.path.join(_AUTOPLAN_DIR, stem + ".py")
        spec = importlib.util.spec_from_file_location(
            "_contracts_" + stem, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MOD_CACHE[stem] = mod
    return mod


def _train_spec(model):
    cm = _load_autoplan("costmodel")
    c = SHARDED_TRAIN_CASES[model]
    return cm.ModelSpec(
        name=model, vocab=c.vocab, hidden=c.hidden, layers=c.layers,
        heads=c.heads, intermediate=c.intermediate, seq=c.seq,
        batch=c.batch, max_position=c.max_position)


def _pricing():
    """(costmodel module, topology, fixed rate) — the rate is pinned to
    the analytic ``peak * MFU_ASSUMED`` so pricing never consults the
    autotune cache (which would drag jax into a stdlib-only import)."""
    cm = _load_autoplan("costmodel")
    topo = _load_autoplan("topology").get_topology("cpu4")
    return cm, topo, topo.peak_flops * cm.MFU_ASSUMED


def train_budget_contracts(model="gpt", dp=2, tp=2):
    """Budget row for one model's dp x tp train step, priced by
    ``costmodel.predict()``."""
    cm, topo, rate = _pricing()
    pred = cm.predict(_train_spec(model), topo, dp=dp, tp=tp, pp=1,
                      rate=rate)
    src = f"costmodel.predict({model}@dp{dp},tp{tp})"
    return [
        MaxHloFlops(pred["flops_per_chip"],
                    TRAIN_BUDGET_TOLERANCE["flops"], source=src),
        MaxHloBytes(pred["hlo_bytes"],
                    TRAIN_BUDGET_TOLERANCE["bytes"], source=src),
    ]


def serve_budget_contracts(slots=SERVE_SLOTS, context=SERVE_TMAX,
                           kv_dtype=None):
    """Budget row for the paged decode step, priced by
    ``costmodel.predict_decode()`` on the same tiny-gpt spec the serve
    smoke compiles. ``kv_dtype="int8"`` re-derives the byte budget from
    the quantized pool's traffic (1 byte/value + scales) — the int8
    serve row's budget shrinks automatically with the KV footprint."""
    cm, topo, rate = _pricing()
    pred = cm.predict_decode(_train_spec("gpt"), topo, slots=slots,
                             context=context, rate=rate,
                             kv_dtype=kv_dtype)
    src = (f"costmodel.predict_decode(gpt, slots={slots}, Tmax={context}"
           + (f", kv_dtype={kv_dtype}" if kv_dtype else "") + ")")
    return [
        MaxHloFlops(pred["flops_per_chip"],
                    SERVE_BUDGET_TOLERANCE["flops"], source=src),
        MaxHloBytes(pred["hlo_bytes"],
                    SERVE_BUDGET_TOLERANCE["bytes"], source=src),
    ]


def serve_decode_int8_contracts():
    """The quantized-KV serve row: everything the f32 row demands, plus
    the no-f32-KV-temporary detector, with the byte budget re-derived
    from the int8 pool footprint."""
    return (serve_decode_contracts()
            + [NoKvDequantTemporary(SERVE_PAGE_SIZE, SERVE_KV_ROW,
                                    SERVE_KV_MIN_PAGES)]
            + serve_budget_contracts(kv_dtype="int8"))


# name -> contract list; tools/compile_smoke.py compiles each target and
# evaluates its row (tools/graft_lint.py --contracts is the CLI front
# door). tests/test_lint.py proves every contract class fires on a
# planted violation.
CONTRACTS = {
    "train.gpt@dp2,tp2": (sharded_train_contracts("gpt")
                          + train_budget_contracts("gpt")),
    # autoplan-resolved mesh (mesh="auto" on 4 virtual devices):
    # the planner may pick any dp in {1, 2, 4}; dp=4 gives the smallest
    # per-shard row count, so this row is the strictest of the three
    "train.gpt@auto": sharded_train_contracts("gpt", dp=4),
    "train.bert@dp2,tp2": sharded_train_contracts("bert"),
    "train.transformer_big@dp2,tp2":
        sharded_train_contracts("transformer_big"),
    "serve.decode": serve_decode_contracts() + serve_budget_contracts(),
    "serve.decode@int8": serve_decode_int8_contracts(),
    "serve.prefill": serve_prefill_contracts(),
    "serve.verify": (serve_verify_contracts()
                     + serve_verify_budget_contracts()),
    "mlp.fused": fused_mlp_contracts(),
}

# Differential snapshot gates, keyed like CONTRACTS rows but kept in a
# separate registry: a snapshot judges the module against a blessed
# on-disk record, so it only belongs in runs that really compiled the
# canonical target (compile_smoke wires it in; text-only fixture
# evaluations of CONTRACTS stay self-contained).
CONTRACT_SNAPSHOTS = {
    "train.gpt@dp2,tp2": HloSnapshot("train.gpt@dp2,tp2"),
    "serve.decode": HloSnapshot("serve.decode"),
    "serve.decode@int8": HloSnapshot("serve.decode@int8"),
    "serve.verify": HloSnapshot("serve.verify"),
}
