"""Operations and bytes the ALGORITHM needs, from shapes alone.

Nothing here asks the compiler (XLA's cost analysis counts what an
implementation does: recomputation, padding, fused extras) and nothing
imports the program. A matmul of [m, k] x [k, n] is 2*m*k*n operations;
a backward pass is twice its forward. Recomputation never counts.

A configuration is the dict in ``benchmark/configs/<name>.json``:
``hidden_size, num_layers, num_heads, intermediate_size, vocab_size``.
"""


def block_matmul_params(cfg):
    """Weights of the matmuls in one transformer layer: q, k, v, o
    projections and the two FFN matrices."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * h * h + 2 * h * i


# ------------------------------------------------------------ whole steps

def bert_train_flops_per_token(cfg, seq, masked_per_row):
    """Forward + backward operations per token of a BERT MLM+NSP step:
    6 x the layers' matmul weights, the s x s attention products (QK^T
    and PV: 4*s*H forward per layer per token, three times that with the
    backward), and the MLM head (transform + tied vocab projection) on
    the masked positions only, plus pooler and NSP once a row."""
    h, layers, v = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    blocks = 6 * layers * block_matmul_params(cfg)
    attention = 12 * layers * seq * h
    head = 6 * (h * h + v * h) * masked_per_row / seq
    nsp = 6 * (h * h + 2 * h) / seq
    return blocks + attention + head + nsp


def gpt_forward_flops(cfg, ctx, with_head):
    """Forward operations for ONE token that attends to ``ctx`` cached
    positions (itself included): the layers' matmuls, QK^T and PV over
    the context, and the tied vocab projection where logits are needed."""
    h, layers, v = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    flops = 2 * layers * block_matmul_params(cfg) + 4 * layers * ctx * h
    if with_head:
        flops += 2 * v * h
    return flops


def gpt_prefill_flops(cfg, start, stop):
    """Forward operations to prefill prompt positions [start, stop): each
    token attends causally to everything before it; the head is NOT in
    here (add one ``2*V*H`` for the last prompt token)."""
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2          # sum of (pos + 1)
    return 2 * layers * block_matmul_params(cfg) * n + 4 * layers * h * ctx_sum


def gpt_head_flops(cfg):
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


# --------------------------------------------------------------- kernels
# each returns (operations, bytes) of ONE call of the kernel

def mlp_forward(rows, hidden, intermediate, itemsize=2):
    """fc2(act(fc1(x))): two matmuls; x, both matrices and biases read,
    the output written; the [rows, intermediate] activation stays on
    chip (that is the kernel's point), so it is not in the bytes."""
    ops = 4 * rows * hidden * intermediate
    nbytes = itemsize * (2 * rows * hidden + 2 * hidden * intermediate
                         + hidden + intermediate)
    return ops, nbytes


def flash_forward(batch, heads, seq_q, seq_k, head_dim, causal=False,
                  itemsize=2):
    """QK^T and PV, dense over seq_q x seq_k (halved when causal); q, k,
    v read and the context written."""
    ops = 4 * batch * heads * seq_q * seq_k * head_dim
    if causal:
        ops //= 2
    nbytes = itemsize * batch * heads * head_dim * (2 * seq_q + 2 * seq_k)
    return ops, nbytes


def flash_backward(batch, heads, seq_q, seq_k, head_dim, causal=False,
                   itemsize=2):
    """The whole backward of attention: five matmuls (S again, dP, dV,
    dK, dQ) = 2.5 x the forward. However an implementation splits it
    into kernels, and whatever a split recomputes, this is what it
    needs. Reads q, k, v, o, do; writes dq, dk, dv."""
    fwd, _ = flash_forward(batch, heads, seq_q, seq_k, head_dim, causal)
    ops = fwd * 5 // 2
    nbytes = itemsize * batch * heads * head_dim * (4 * seq_q + 4 * seq_k)
    return ops, nbytes


def decode_attention(live_tokens, heads, head_dim, itemsize=2):
    """One decode round of one layer: every live cached K and V row is
    read once (``live_tokens`` summed over the slots, counted in whole
    pages by the caller); two operations per element for the score and
    two for the weighted sum. Bandwidth-bound by a wide margin."""
    nbytes = 2 * live_tokens * heads * head_dim * itemsize
    ops = 4 * live_tokens * heads * head_dim
    return ops, nbytes


def least_seconds(ops, nbytes, peaks):
    """The roofline: the least time the chip could take, and which of
    the two bounds it ('compute' or 'bandwidth')."""
    t_c = ops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bandwidth")


def flash_backward_part(share, **shape):
    """One kernel's part of ``flash_backward`` where an implementation
    splits the backward pass in two (dq; dk and dv): the parts' shares
    add up to 1, so the sum over the kernels is what the algorithm needs
    and not what the split recomputes."""
    ops, nbytes = flash_backward(**shape)
    return ops * share, nbytes * share
