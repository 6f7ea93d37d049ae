"""How much of the grouped expert kernel's grid carries work
(``ops/pallas/moe_mlp.py``, PR 36).

The kernel's grid walks ``m / tile_m + G - 1`` work items a call, its
worst case; an item is one (held expert, row tile) pair that shares a
row, and the items past the live ones stream no weight. Since PR 36
``serve.step``'s counts say, of every program whose tokens a round
read, ``moe_items`` (live items, all expert layers) and
``moe_item_slots`` (the items of those calls' static grids), counted on
the host from the rows routed to each held expert by the kernel's own
rule. ``readers/engine_spans.py`` says where the records come from and
how they are laid on the trace's clock.

``live_share``: ``sum(moe_items) / sum(moe_item_slots)`` over the
window's rounds, in percent: the share of the grid's items that stream
an expert. A property of the routing and of the tiles, not of the
kernel's speed. A program whose ``serve.step`` carries no such counts
(the parent of PR 36), or a model without experts, reads nothing.
"""

from benchmark.readers import engine_spans


def live_share(spec, run):
    got = engine_spans.session(run)
    if got is None:
        return None
    counts = [s["counts"] for s in engine_spans.named(got[0], "serve.step")
              if s["counts"].get("moe_item_slots")]
    if not counts:
        return None
    items = sum(c["moe_items"] for c in counts)
    slots = sum(c["moe_item_slots"] for c in counts)
    engine_spans.say(
        f"grouped kernel over {len(counts)} rounds: {items} live work "
        f"items of {slots} in the calls' grids")
    return 100.0 * items / slots
