"""The comparisons that decide ``correct``. Each returns plain numbers;
the limits live in the cell's own file (``benchmark/cells/<cell>.json``,
``limits``) and the readings they were set from are in PERF.md.
"""

import statistics

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves under Adam by round-off alone: it is left out of the
#: comparison of the parameters' change (never out of the gradient's)
NOUGHT_GRADIENT = 1e-3


def flatten(tree, prefix=""):
    """{"a/b": value} from nested dicts."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = float(v)
    return out


def worst_leaf_gap(got, ref, leave_out=()):
    """The widest gap between the program's norm and the reference's,
    leaf by leaf: ``|got - ref| / max(ref, median of ref)``. The gap of
    the norms, not the norm of the difference. Returns (gap, leaf)."""
    names = [n for n in ref if n not in leave_out]
    floor = statistics.median(ref[n] for n in names)
    gap, where = 0.0, None
    for n in names:
        g = abs(got[n] - ref[n]) / max(ref[n], floor, 1e-30)
        if g > gap or where is None:
            gap, where = g, n
    return gap, where


def nought_gradient_leaves(ref_grad_norms):
    floor = NOUGHT_GRADIENT * statistics.median(ref_grad_norms.values())
    return sorted(n for n, g in ref_grad_norms.items() if g < floor)


def train_numbers(got, ref):
    """The three numbers of a training cell, from two ``{"losses",
    "grad_norms", "update_norms"}`` records (trees or flat dicts)."""
    g_got, g_ref = flatten(got["grad_norms"]), flatten(ref["grad_norms"])
    u_got, u_ref = flatten(got["update_norms"]), flatten(ref["update_norms"])
    out = nought_gradient_leaves(g_ref)
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(g_got, g_ref)
    upd_gap, upd_leaf = worst_leaf_gap(u_got, u_ref, leave_out=out)
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "update_gap": upd_gap},
            {"grad_leaf": grad_leaf, "update_leaf": upd_leaf,
             "left_out_of_update": out})


def judge(numbers, limits):
    """[(name, value, limit, ok)] and the conjunction. A number with no
    limit in the cell's file is printed and not compared."""
    rows = []
    for name, value in numbers.items():
        limit = limits.get(name)
        ok = True if limit is None else (value == value and value <= limit)
        rows.append((name, value, limit, ok))
    return rows, all(ok for *_, ok in rows)
