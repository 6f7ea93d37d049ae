"""Operations and bytes the ALGORITHM of one chip's share of a K-EXAONE
(``exaone_moe``) decoder needs, from shapes alone: what ``work_jamba.py``
is to the hybrid cell. Nothing here asks the compiler and nothing imports
the program. A matmul of [m, k] x [k, n] is 2*m*k*n operations.

A configuration is the ``shapes`` dict of
``benchmark/configs/<name>.json``: ``hidden_size, num_layers, num_heads,
num_kv_heads, head_dim, intermediate_size, moe_intermediate_size,
shared_intermediate_size, num_experts, num_experts_per_tok, held_experts
[first, count], vocab_size`` (the slice held), ``layer_types`` (``window``
/ ``full``), ``ffn_types`` (``dense`` / ``moe``), ``sliding_window``.

The MODEL's operations (``forward_flops``, ``prefill_flops``: what the
window's ``model_ops`` and the whole step's share of the peak are made
of) count the routed experts by the UNIFORM expectation: a row chooses
``num_experts_per_tok`` of ``num_experts`` experts, of which ``held``
live here, so a row brings ``k * held / num_experts`` (row, expert)
pairs to this chip (1 at 8 x 16 / 128). The KERNELS' work is counted
from what the traffic really sent: weights made from a seed do NOT route
evenly (the configuration's ``assumed.weights``: some 10 of the 16 held
experts get a row in a call), the grouped kernel streams no weight of an
expert without a row, and so ``expert_mlp`` takes the pairs and the
expert reads that the program counted (``serve.step``'s ``moe_rows``,
``moe_experts_hit`` x ``moe_calls``; ``benchmark/readers/moe.py``).
A window layer attends ``min(ctx, sliding_window)`` keys.
"""


def layer_counts(cfg):
    """(layers with routed experts: those that run the grouped kernel,
    the others)."""
    moe = sum(1 for kind in cfg["ffn_types"] if kind == "moe")
    return moe, cfg["num_layers"] - moe


def attention_params(cfg):
    """q and o over all heads, k and v over the K/V heads."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * hd * (cfg["num_heads"] + cfg["num_kv_heads"])


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_row(cfg):
    """(row, expert) pairs a row brings to the experts held here, by the
    uniform expectation."""
    return (cfg["num_experts_per_tok"] * cfg["held_experts"][1]
            / cfg["num_experts"])


def parameters(cfg):
    """Every parameter HELD here: the embedding and the head (each its
    slice of the vocabulary), the norms, the selection biases."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    moe, dense = layer_counts(cfg)
    layer = attention_params(cfg) + 2 * h + 2 * hd
    sparse = (3 * h * cfg["shared_intermediate_size"]
              + cfg["num_experts"] * (h + 1)
              + cfg["held_experts"][1] * expert_params(cfg))
    return (cfg["num_layers"] * layer + moe * sparse
            + dense * 3 * h * cfg["intermediate_size"]
            + 2 * cfg["vocab_size"] * h + h)


def head_flops(cfg):
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def keys_seen(cfg, ctx):
    """Keys one query at context ``ctx`` (itself included) attends,
    summed over the layers."""
    return sum(min(ctx, cfg["sliding_window"]) if kind == "window" else ctx
               for kind in cfg["layer_types"])


def matmul_flops(cfg):
    """One token through every layer's matmuls: attention projections,
    the dense FFN, the router, the shared expert and the routed experts
    this chip is expected to run for it."""
    h = cfg["hidden_size"]
    moe, dense = layer_counts(cfg)
    return 2 * (cfg["num_layers"] * attention_params(cfg)
                + dense * 3 * h * cfg["intermediate_size"]
                + moe * (cfg["num_experts"] * h
                         + 3 * h * cfg["shared_intermediate_size"]
                         + pairs_per_row(cfg) * expert_params(cfg)))


def forward_flops(cfg, ctx, with_head):
    """Forward operations for ONE token that attends to ``ctx`` cached
    positions (itself included): the matmuls, QK^T and PV over the keys
    each layer's kind lets it see, and the head where logits are
    needed."""
    flops = (matmul_flops(cfg) + 4 * keys_seen(cfg, ctx)
             * cfg["num_heads"] * cfg["head_dim"])
    return flops + (head_flops(cfg) if with_head else 0)


def prefill_flops(cfg, start, stop):
    """Forward operations to prefill prompt positions [start, stop);
    the head is NOT in here (add one ``head_flops`` a prompt)."""
    keys = sum(keys_seen(cfg, pos + 1) for pos in range(start, stop))
    return ((stop - start) * matmul_flops(cfg)
            + 4 * keys * cfg["num_heads"] * cfg["head_dim"])


# --------------------------------------------------------------- kernels

def expert_mlp(cfg, pairs, expert_reads):
    """(operations, bytes) of the grouped gated MLP over a window, every
    expert layer and every call together, from what the program COUNTED:
    ``pairs`` (row, choice) pairs that fell on held experts and
    ``expert_reads`` (call, layer, held expert) triples in which the
    expert got at least one row. Operations: three products a pair.
    Bytes (bfloat16 weights): an expert that got a row read once in that
    call, a pair's row read (bfloat16) and written (float32). Padding,
    idle slots, the pairs of absent experts, an expert without a row and
    a second read of an expert whose run crosses a row tile are no
    work."""
    return (2 * pairs * expert_params(cfg),
            2 * expert_reads * expert_params(cfg)
            + pairs * cfg["hidden_size"] * (2 + 4))


def decode_attention(cfg, live_rows, slot_steps):
    """(operations, bytes) of the decode kernel over a window, all
    layers together: ``slot_steps`` positions decoded whose contexts,
    in whole pages, sum to ``live_rows`` K/V rows. A full layer reads
    every live row of its pool; a window layer reads its ring,
    ``min(context, sliding_window)`` rows a position, taken here at the
    MEAN context a position (the window's facts hold sums only): the
    whole ring wherever the mean passes the window, which is what the
    kernel streams (a ring is one page) and at most what the positions
    still under the window need. K and V of the K/V heads in bfloat16;
    scores and the weighted sum over the QUERY heads."""
    full = sum(1 for kind in cfg["layer_types"] if kind == "full")
    ring = min(live_rows / slot_steps, cfg["sliding_window"]) * slot_steps
    rows = full * live_rows + (len(cfg["layer_types"]) - full) * ring
    return (4 * rows * cfg["num_heads"] * cfg["head_dim"],
            2 * rows * cfg["num_kv_heads"] * cfg["head_dim"] * 2)
