"""Per-layer metrics of a model with routed experts of which this chip
holds a share (``nn.HeldExperts``): where the rows really went.

The engine's step programs hand back, with the tokens, the rows routed
to each held expert, and ``serve.step``'s counts say of every program
whose tokens a round read: ``moe_rows`` ((row, choice) pairs on held
experts, all expert layers), ``moe_rows_max`` (the busiest expert of
any layer), ``moe_experts_hit`` (held experts with a row, mean over
layers) and ``moe_calls`` (programs read: a decode round and that
step's prefill chunks). ``readers/engine_spans.py`` says where those
records come from and how they are laid on the trace's clock. A program
whose ``serve.step`` carries no such counts (the parent of the PR that
brought the layer), or a model without experts, reads nothing.

``rows_per_expert``: pairs a held expert gets a call, mean over the
session: ``sum(moe_rows) / (sum(moe_calls) x expert layers x held)``.
The kernel's weight traffic is paid per call whatever this reads, so it
says how many rows share one read of an expert (in the deployment a
held expert would see ``num_experts / held`` times as many at the same
batch a chip: the other chips' tokens).

``load_max_over_mean``: the busiest expert's rows over the mean
expert's, ``moe_rows_max / (moe_rows / (moe_calls x layers x held))``,
mean over the session's rounds that routed anything: 1 is perfectly
even; a straggler expert sets a grouped kernel's longest run.

``expert_mlp_roofline``: the grouped kernel's share of its roofline,
from what was COUNTED and not from an expectation of the routing. The
least time the chip could take for the pairs the session's programs
routed to held experts (``sum(moe_rows)``) and for one read of every
expert that got a row in a call (``sum(moe_experts_hit x moe_calls) x
expert layers``: the kernel streams no weight of an expert without a
row), by the configuration's ``work`` module, over the summed device
time of the kernel's events in the window. The counts are those of the
programs a step READ, the kernel's events those it launched (PR 33: one
round apart), so the two windows differ by one round at each end of
some 180. A trace without such events or a store without such counts
reads nothing.
"""

import importlib
import statistics

from benchmark.harness import flops, trace_reduce
from benchmark.readers import engine_spans


def routed_rounds(spec, run):
    """(counts of the session's ``serve.step`` records that routed rows,
    expert layers x held experts) or None; worked out once a run."""
    if "moe_rounds" not in run:
        run["moe_rounds"] = _routed_rounds(spec, run)
    return run["moe_rounds"]


def _routed_rounds(spec, run):
    got = engine_spans.session(run)
    if got is None:
        return None
    counts = [s["counts"] for s in engine_spans.named(got[0], "serve.step")
              if s["counts"].get("moe_calls")]
    if not counts:
        return None
    cfg = run["config"]
    layers, _ = importlib.import_module(spec["work_module"]).layer_counts(cfg)
    held = cfg["held_experts"][1]
    engine_spans.say(
        f"routed experts over {len(counts)} rounds: "
        f"{sum(c['moe_rows'] for c in counts)} pairs on held experts in "
        f"{sum(c['moe_calls'] for c in counts)} programs; "
        f"{statistics.fmean(c['moe_experts_hit'] for c in counts):.2f} of "
        f"{held} held experts hit a layer, busiest expert "
        f"{max(c['moe_rows_max'] for c in counts)} rows")
    return counts, layers * held


def rows_per_expert(spec, run):
    got = routed_rounds(spec, run)
    if got is None:
        return None
    counts, cells = got
    calls = sum(c["moe_calls"] for c in counts)
    return sum(c["moe_rows"] for c in counts) / (calls * cells)


def load_max_over_mean(spec, run):
    got = routed_rounds(spec, run)
    if got is None:
        return None
    counts, cells = got
    ratios = [c["moe_rows_max"] * c["moe_calls"] * cells / c["moe_rows"]
              for c in counts if c["moe_rows"]]
    return statistics.fmean(ratios) if ratios else None


def expert_mlp_roofline(spec, run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = trace_reduce.op_durations(run["trace"], spec["pattern"])
    got = routed_rounds(spec, run)
    if not durs or got is None:
        return None
    counts, _ = got
    work = importlib.import_module(spec["work_module"])
    cfg = run["config"]
    layers, _ = work.layer_counts(cfg)
    pairs = sum(c["moe_rows"] for c in counts)
    reads = layers * sum(c["moe_experts_hit"] * c["moe_calls"]
                         for c in counts)
    ops, nbytes = getattr(work, spec["work"])(cfg, pairs, reads)
    t_min, bound = flops.least_seconds(ops, nbytes, run["peaks"])
    engine_spans.say(
        f"{spec['pattern']}: {len(durs)} events, {sum(durs):.3f} s, for "
        f"{pairs} pairs and {reads:.0f} reads of an expert: at the least "
        f"{t_min:.3f} s ({bound})")
    return 100.0 * t_min / sum(durs)
