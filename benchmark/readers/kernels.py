"""A kernel's share of its roofline where the configuration's own
``work`` module counts the kernel's work over the WHOLE window, every
layer and every call together (``state_space.scan_roofline`` takes one
layer's work times the layers that run the kernel; a kernel that layers
of different kinds call with different work has no such factor).

``window_roofline``: the least time the chip could take for
``work(shapes, *facts)`` (operations, bytes; ``facts`` are numbers the
window counted) over the summed device time of the events that match
``pattern``. A trace without such events, or a window that counted
nothing of a fact, reads nothing.
"""

import importlib

from benchmark.harness import flops, trace_reduce


def window_roofline(spec, run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = trace_reduce.op_durations(run["trace"], spec["pattern"])
    facts = [run["facts"].get(name) for name in spec["facts"]]
    if not durs or not all(facts):
        return None
    work = importlib.import_module(spec["work_module"])
    ops, nbytes = getattr(work, spec["work"])(run["config"], *facts)
    t_min, _ = flops.least_seconds(ops, nbytes, run["peaks"])
    return 100.0 * t_min / sum(durs)
