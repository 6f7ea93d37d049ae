"""Factorization search: enumerate dp x tp x pp candidates, prune with
recorded reasons, score the rest, emit a :class:`MeshPlan`.

The search is exhaustive over divisor triples of the device count (the
space is tiny — O(d(n)^2) for n devices) per arxiv 2110.10548: legal
placements are enumerated against the hierarchical topology, each is
priced by the analytic cost model, and the argmin wins. Every pruned
candidate carries a `reasons` list (the `PlanEntry.reason` discipline
lifted to whole factorizations) so an operator can see *why* the
planner refused a mesh, not just that it did.

The winning MeshPlan is the one object the rest of the framework
consumes: `fleet.distributed_optimizer(strategy="auto")`,
`Trainer(mesh_plan=...)`, model `.loss(mesh_plan=...)`, and
`tools/compile_smoke.py --autoplan` all resolve mesh axes, per-param
PartitionSpecs (via the DistributionPlanner emission layer ->
autoplan/layouts.py), and loss sharding kwargs from it.
JSON-serializable end to end.

Stdlib-only at import; jax enters lazily through build_mesh()/place().
"""

import dataclasses
import json
import time

from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.parallel.autoplan import costmodel
from paddle_tpu.parallel.autoplan import topology as topo_lib

PP_SCHEDULES = ("gpipe", "1f1b", "interleaved")


@dataclasses.dataclass
class Candidate:
    """One (dp, tp, pp) factorization, scored or pruned-with-reasons.
    ``dp_collective`` records the gradient-exchange strategy the cost
    model chose for the dp axis ("f32" | "int8"; defaulted for JSON
    records written before quantized collectives existed)."""
    dp: int
    tp: int
    pp: int
    schedule: str = "1f1b"
    microbatches: int = 1
    feasible: bool = True
    dp_collective: str = "f32"
    reasons: list = dataclasses.field(default_factory=list)
    predicted: dict = dataclasses.field(default_factory=dict)

    @property
    def step_s(self):
        return self.predicted.get("step_s", float("inf"))

    def mesh_axes(self):
        axes = {n: s for n, s in
                (("dp", self.dp), ("tp", self.tp), ("pp", self.pp))
                if s > 1}
        return axes or {"dp": self.dp}

    def label(self):
        return ",".join(f"{n}{s}" for n, s in self.mesh_axes().items())

    def to_json(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d):
        return cls(**d)


def factorizations(n):
    """Every (dp, tp, pp) with dp*tp*pp == n, dp outermost."""
    out = []
    for tp in range(1, n + 1):
        if n % tp:
            continue
        rest = n // tp
        for pp in range(1, rest + 1):
            if rest % pp:
                continue
            out.append((rest // pp, tp, pp))
    return sorted(out)


def _pick_microbatches(local_batch, pp):
    """Smallest divisor of the per-replica batch >= 2*pp (bubble
    fraction <= 1/2), else the largest divisor; 0 when no split at all
    can feed pp stages."""
    if local_batch < pp:
        return 0
    divs = [m for m in range(1, local_batch + 1) if local_batch % m == 0]
    for m in divs:
        if m >= 2 * pp:
            return m
    return divs[-1]


def _check(spec, topology, dp, tp, pp, allow_pp, schedule, usable_hbm,
           quant_allreduce="auto"):
    """Feasibility of one candidate -> (Candidate). Never raises: every
    infeasibility is a recorded reason. ``quant_allreduce`` ("auto" |
    "on" | "off") governs the dp gradient-exchange strategy: "auto"
    prices BOTH the f32 and the chunked-int8 collective and keeps the
    cheaper (the EQuARX decision — quantized bytes change which mesh
    wins), recording why in the candidate's decision record."""
    cand = Candidate(dp=dp, tp=tp, pp=pp, schedule=schedule)
    reasons = cand.reasons
    if spec.batch % dp:
        reasons.append(f"dp={dp}: global batch {spec.batch} not divisible")
    if tp > 1:
        for dim, val in (("hidden", spec.hidden), ("heads", spec.heads),
                         ("intermediate", spec.intermediate),
                         ("vocab", spec.vocab)):
            if val % tp:
                reasons.append(f"tp={tp}: {dim} {val} not divisible")
    if pp > 1:
        if not allow_pp:
            reasons.append(
                f"pp={pp}: pipeline execution disabled for this run "
                "(caller has no pipeline train-step executor)")
        if spec.layers < pp:
            reasons.append(f"pp={pp}: only {spec.layers} layers "
                           "(< stages)")
        elif spec.layers % pp:
            reasons.append(f"pp={pp}: {spec.layers} layers not divisible "
                           "into equal stages")
        if not reasons:
            m = _pick_microbatches(max(1, spec.batch // dp), pp)
            if m == 0:
                reasons.append(
                    f"pp={pp}: per-replica batch {spec.batch // dp} too "
                    "small to microbatch across stages")
            else:
                cand.microbatches = m
    if reasons:
        cand.feasible = False
        return cand
    strategies = {"auto": ("f32", "int8"), "on": ("int8",),
                  "off": ("f32",)}.get(quant_allreduce, ("f32",))
    if dp == 1:
        strategies = ("f32",)       # no dp exchange to quantize
    preds = {s: costmodel.predict(spec, topology, dp, tp, pp,
                                  cand.microbatches, cand.schedule,
                                  dp_collective=s)
             for s in strategies}
    strat = min(preds, key=lambda s: preds[s]["step_s"])
    pred = preds[strat]
    cand.dp_collective = strat
    if pred["mem_bytes"] > usable_hbm:
        cand.feasible = False
        reasons.append(
            f"memory {pred['mem_bytes'] / topo_lib.GIB:.2f} GiB/chip > "
            f"{usable_hbm / topo_lib.GIB:.2f} GiB usable HBM")
    cand.predicted = {k: v for k, v in pred.items()
                      if k not in ("mem", "collective_bytes")}
    cand.predicted["collective_bytes"] = pred["collective_bytes"]
    if dp > 1 and len(preds) > 1:
        other = next(s for s in preds if s != strat)
        cand.predicted["dp_collective_reason"] = (
            f"{strat} all-reduce predicted "
            f"{preds[strat]['step_s'] * 1e3:.3f} ms/step vs "
            f"{preds[other]['step_s'] * 1e3:.3f} for {other} "
            f"(dp wire bytes {preds[strat]['collective_bytes']['dp']:.3g}"
            f" vs {preds[other]['collective_bytes']['dp']:.3g}, quantize "
            f"overhead {preds['int8']['quant_s'] * 1e3:.3f} ms)")
    elif dp > 1:
        cand.predicted["dp_collective_reason"] = (
            f"{strat} forced by quant_allreduce={quant_allreduce}")
    return cand


class MeshPlan:
    """The planner's output: mesh axes + layout + schedule + forecast.

    Mirrors DistributionPlan's inspectability contract — `describe()`
    is a stable human table, `to_json()`/`from_json()` round-trip the
    whole decision record including every pruned candidate's reasons.
    """

    def __init__(self, model, topology, axes, schedule, microbatches,
                 predicted, reason, candidates, entries=None):
        self.model = model
        self.topology = topology
        self.axes = dict(axes)
        self.schedule = schedule
        self.microbatches = microbatches
        self.predicted = dict(predicted)
        self.reason = reason
        self.candidates = list(candidates)
        # param path -> PlanEntry, filled by place()/shardings()
        self.entries = dict(entries or {})
        self._mesh = None

    # -- factorization views ------------------------------------------
    @property
    def dp(self):
        return self.axes.get("dp", 1)

    @property
    def tp(self):
        return self.axes.get("tp", 1)

    @property
    def pp(self):
        return self.axes.get("pp", 1)

    def label(self):
        return ",".join(f"{n}{s}" for n, s in self.axes.items())

    # -- consumption --------------------------------------------------
    def build_mesh(self, devices=None):
        """The jax Mesh for the winning axes (cached)."""
        from paddle_tpu.parallel.mesh import make_mesh
        if self._mesh is None:
            self._mesh = make_mesh(dict(self.axes), devices)
        return self._mesh

    def planner(self, mesh=None):
        """The sharding-emission layer: a DistributionPlanner in LM
        mode (autoplan/layouts.py rules, divisibility-downgrade)."""
        from paddle_tpu.parallel.planner import DistributionPlanner
        return DistributionPlanner(mesh or self.build_mesh(),
                                   lm_rules=True)

    def shardings(self, params, mesh=None):
        """NamedSharding pytree for `params`; records the per-param
        PlanEntry decisions on self.entries."""
        dplan = self.planner(mesh).plan(params)
        self.entries.update(dplan.entries)
        return dplan.param_shardings(params)

    def place(self, params, mesh=None):
        """device_put params per the plan (and record the entries)."""
        dplan = self.planner(mesh).plan(params)
        self.entries.update(dplan.entries)
        return dplan.place(params)

    def loss_kwargs(self):
        """Sharding kwargs for the model `.loss()` entry points."""
        return {"vocab_axis": "tp" if self.tp > 1 else None,
                "batch_axis": "dp" if self.dp > 1 else None,
                "mesh": self._mesh}

    def resolve_loss_axes(self, vocab_axis=None, batch_axis=None,
                          mesh=None):
        """Fill unset loss-sharding kwargs from the plan (the
        `mesh_plan=` path of the model `.loss()` entry points);
        explicitly-passed values win."""
        kw = self.loss_kwargs()
        return (vocab_axis or kw["vocab_axis"],
                batch_axis or kw["batch_axis"],
                mesh if mesh is not None else kw["mesh"])

    def strategy(self):
        """The equivalent fleet.DistributedStrategy."""
        from paddle_tpu.parallel.fleet import DistributedStrategy
        return DistributedStrategy.from_plan(self)

    # -- inspection ---------------------------------------------------
    def summary(self):
        """Compact record for reports / run logs."""
        out = {"axes": dict(self.axes), "schedule": self.schedule,
               "microbatches": self.microbatches,
               "topology": self.topology.name,
               "step_s": round(self.predicted.get("step_s", 0.0), 6),
               "mem_gib": round(
                   self.predicted.get("mem_bytes", 0) / topo_lib.GIB, 3),
               "reason": self.reason}
        if self.dp > 1:
            out["dp_collective"] = self.predicted.get("dp_collective",
                                                      "f32")
            out["dp_wire_bytes"] = self.predicted.get(
                "collective_bytes", {}).get("dp")
        return out

    def describe(self, top=None):
        """Human-readable ranked candidate table."""
        rows = sorted(self.candidates,
                      key=lambda c: (not c.feasible, c.step_s))
        if top:
            rows = rows[:top]
        lines = [f"autoplan: {self.model} on {self.topology.name} "
                 f"({self.topology.num_chips} chips) -> {self.label()}",
                 f"  {self.reason}",
                 f"  {'mesh':<14}{'sched':<8}{'ubs':>4}{'step_ms':>10}"
                 f"{'mem GiB':>9}  note"]
        for c in rows:
            if c.feasible:
                note = "<- winner" if c.mesh_axes() == self.axes else ""
                lines.append(
                    f"  {c.label():<14}"
                    f"{(c.schedule if c.pp > 1 else '-'):<8}"
                    f"{c.microbatches:>4}{c.step_s * 1e3:>10.2f}"
                    f"{c.predicted.get('mem_bytes', 0) / topo_lib.GIB:>9.2f}"
                    f"  {note}")
            else:
                lines.append(f"  {c.label():<14}{'-':<8}{'-':>4}"
                             f"{'-':>10}{'-':>9}  PRUNED: "
                             + "; ".join(c.reasons))
        return "\n".join(lines)

    def to_json(self):
        return {"model": self.model, "topology": self.topology.to_json(),
                "axes": dict(self.axes), "schedule": self.schedule,
                "microbatches": self.microbatches,
                "predicted": self.predicted, "reason": self.reason,
                "candidates": [c.to_json() for c in self.candidates],
                "entries": {name: {"spec": list(e.spec),
                                   "reason": e.reason}
                            for name, e in sorted(self.entries.items())}}

    def dumps(self, **kw):
        return json.dumps(self.to_json(), **kw)

    @classmethod
    def from_json(cls, d):
        from paddle_tpu.parallel.planner import PlanEntry
        entries = {
            name: PlanEntry(name, tuple(e["spec"]), e["reason"])
            for name, e in d.get("entries", {}).items()}
        return cls(model=d["model"],
                   topology=topo_lib.Topology.from_json(d["topology"]),
                   axes=d["axes"], schedule=d["schedule"],
                   microbatches=d["microbatches"],
                   predicted=d["predicted"], reason=d["reason"],
                   candidates=[Candidate.from_json(c)
                               for c in d["candidates"]],
                   entries=entries)


class NoFeasiblePlanError(ValueError):
    """Raised only when *every* factorization is infeasible; the message
    carries each candidate's recorded reasons."""


def plan(spec, topology=None, devices=None, allow_pp=True,
         schedule="1f1b", hbm_fraction=None, quant_allreduce=None):
    """Search dp x tp x pp factorizations of the device count and return
    the argmin-predicted-step-time :class:`MeshPlan`.

    `devices` overrides the topology's chip count (e.g. planning
    over the live `jax.devices()` while a preset supplies per-chip
    characteristics). `allow_pp=False` prunes pipeline candidates with
    a recorded reason — for callers whose train step has no pipeline
    executor. `quant_allreduce` (default: the flag) governs the dp
    gradient-exchange strategy per :func:`_check`.
    """
    t0 = time.perf_counter()
    if topology is None or isinstance(topology, str):
        topology = topo_lib.get_topology(topology)
    if hbm_fraction is None or quant_allreduce is None:
        from paddle_tpu.core.flags import get_flag
        if hbm_fraction is None:
            hbm_fraction = get_flag("autoplan_hbm_fraction")
        if quant_allreduce is None:
            quant_allreduce = get_flag("quant_allreduce")
    n = int(devices) if devices else topology.num_chips
    usable = topology.hbm_bytes * hbm_fraction
    cands = []
    for dp, tp, pp in factorizations(n):
        c = _check(spec, topology, dp, tp, pp, allow_pp, schedule, usable,
                   quant_allreduce=quant_allreduce)
        _metrics.counter("autoplan.candidates").inc(
            outcome="scored" if c.feasible else "pruned")
        cands.append(c)
    feasible = [c for c in cands if c.feasible]
    if not feasible:
        detail = "; ".join(
            f"{c.label()}: {' / '.join(c.reasons)}" for c in cands)
        raise NoFeasiblePlanError(
            f"autoplan: no feasible mesh for {spec.name} on "
            f"{topology.name} ({n} devices) — {detail}")
    # ties break toward the simplest mesh (fewest parallel modes)
    win = min(feasible,
              key=lambda c: (c.step_s, len(c.mesh_axes()), c.tp, c.pp))
    reason = (
        f"argmin predicted step time over {len(feasible)} feasible of "
        f"{len(cands)} candidates: {win.label()} "
        f"(~{win.step_s * 1e3:.2f} ms/step, "
        f"{win.predicted.get('mem_bytes', 0) / topo_lib.GIB:.2f} GiB/chip"
        + (f", {win.schedule} x{win.microbatches} microbatches"
           if win.pp > 1 else "")
        + (f", {win.dp_collective} dp all-reduce" if win.dp > 1 else "")
        + ")")
    out = MeshPlan(model=spec.name, topology=topology,
                   axes=win.mesh_axes(), schedule=win.schedule,
                   microbatches=win.microbatches, predicted=win.predicted,
                   reason=reason, candidates=cands)
    _metrics.histogram("autoplan.plan_s").observe(
        time.perf_counter() - t0)
    return out
