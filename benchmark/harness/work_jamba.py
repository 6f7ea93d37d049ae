"""Operations and bytes the ALGORITHM of a hybrid (Mamba-1 + attention)
decoder needs, from shapes alone: what ``flops.py`` is to the
transformer cells. Nothing here asks the compiler and nothing imports
the program. A matmul of [m, k] x [k, n] is 2*m*k*n operations.

A configuration is the ``shapes`` dict of
``benchmark/configs/<name>.json``: ``hidden_size, num_layers, num_heads,
num_kv_heads, head_dim, intermediate_size, vocab_size, d_inner, d_state,
d_conv, dt_rank, attn_layer_period, attn_layer_offset``.

The recurrence, per (channel, state) pair and position: three multiplies
for the update (dt*A, exp(.)*h, B*(dt x)), one add, one multiply and one
add for the output: 6. The exponential is not counted (as the softmax's
is not in attention's 4*ctx*h). Per channel besides: dt*x, D*x and its
add, the gate's two multiplies: 5.
"""

SCAN_OPS_PER_PAIR = 6
SCAN_OPS_PER_CHANNEL = 5


def layer_counts(cfg):
    """(Mamba layers, attention layers): layer i attends where
    ``i % attn_layer_period == attn_layer_offset``."""
    attn = sum(1 for i in range(cfg["num_layers"])
               if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return cfg["num_layers"] - attn, attn


def mamba_matmul_params(cfg):
    """in_proj, x_proj, dt_proj and out_proj of one Mamba mixer."""
    h, d = cfg["hidden_size"], cfg["d_inner"]
    r, n = cfg["dt_rank"], cfg["d_state"]
    return h * 2 * d + d * (r + 2 * n) + r * d + d * h


def attention_matmul_params(cfg):
    """q and o over all heads, k and v over the K/V heads."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_heads"] * hd + 2 * h * cfg["num_kv_heads"] * hd


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg):
    """Weights of every layer's matmuls (the tied head is apart)."""
    mamba, attn = layer_counts(cfg)
    return (mamba * mamba_matmul_params(cfg)
            + attn * attention_matmul_params(cfg)
            + cfg["num_layers"] * mlp_params(cfg))


def parameters(cfg):
    """Every parameter of the model, the tied embedding once."""
    h, d, n = cfg["hidden_size"], cfg["d_inner"], cfg["d_state"]
    mamba, _ = layer_counts(cfg)
    small = (d * cfg["d_conv"] + d        # conv taps and bias
             + d                          # dt bias
             + d * n + d                  # A_log, D
             + cfg["dt_rank"] + 2 * n)    # the three inner norms
    return (matmul_params(cfg) + mamba * small
            + 2 * h * cfg["num_layers"] + h + cfg["vocab_size"] * h)


def scan_ops_per_token(cfg):
    """The recurrence of ONE Mamba layer for one position."""
    d = cfg["d_inner"]
    return d * (SCAN_OPS_PER_PAIR * cfg["d_state"] + SCAN_OPS_PER_CHANNEL)


def head_flops(cfg):
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def forward_flops(cfg, ctx, with_head):
    """Forward operations for ONE token that attends to ``ctx`` cached
    positions (itself included): every layer's matmuls, QK^T and PV over
    the context in the attention layers, the conv and the recurrence in
    the Mamba layers, and the tied head where logits are needed."""
    mamba, attn = layer_counts(cfg)
    flops = (2 * matmul_params(cfg)
             + 4 * attn * ctx * cfg["num_heads"] * cfg["head_dim"]
             + mamba * (scan_ops_per_token(cfg)
                        + 2 * cfg["d_conv"] * cfg["d_inner"]))
    return flops + (head_flops(cfg) if with_head else 0)


def prefill_flops(cfg, start, stop):
    """Forward operations to prefill prompt positions [start, stop);
    the head is NOT in here (add one ``head_flops`` a prompt)."""
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2          # sum of (pos + 1)
    _, attn = layer_counts(cfg)
    return (forward_flops(cfg, 0, False) * n
            + 4 * attn * ctx_sum * cfg["num_heads"] * cfg["head_dim"])


# --------------------------------------------------------------- kernels
# each returns (operations, bytes) of ONE Mamba layer, all float32

def _per_token_bytes(cfg):
    """x, dt, z read and y written (a row of D each), B and C read."""
    return 4 * (4 * cfg["d_inner"] + 2 * cfg["d_state"])


def _state_bytes(cfg):
    """One sequence's ``h``, read once and written once."""
    return 2 * 4 * cfg["d_inner"] * cfg["d_state"]


def selective_scan(cfg, tokens, chunks):
    """Prefill: ``tokens`` real prompt positions in ``chunks`` calls;
    the state is read and written once a call, A and D read once a
    call. A chunk's padding is no work the algorithm needs."""
    d, n = cfg["d_inner"], cfg["d_state"]
    ops = tokens * scan_ops_per_token(cfg)
    nbytes = (tokens * _per_token_bytes(cfg)
              + chunks * (_state_bytes(cfg) + 4 * d * (n + 1)))
    return ops, nbytes


def ssm_state_update(cfg, slot_steps, rounds):
    """Decode: ``slot_steps`` positions (one per RUNNING slot per round)
    in ``rounds`` calls; each reads and writes its slot's state; A and
    D are read once a call. A slot that runs nothing needs nothing."""
    d, n = cfg["d_inner"], cfg["d_state"]
    ops = slot_steps * scan_ops_per_token(cfg)
    nbytes = (slot_steps * (_per_token_bytes(cfg) + _state_bytes(cfg))
              + rounds * 4 * d * (n + 1))
    return ops, nbytes
