"""Mixture-of-Experts layer with expert parallelism over the "ep" axis.

Ref: no MoE exists in the reference (2019-era); its expert-sharding
ancestor is the parameter-server's row-sharded tables
(/root/reference/paddle/fluid/framework/fleet/fleet_wrapper.h:55). This is
the modern successor the brief's scale requirements imply: top-k gating,
capacity-bounded dispatch, experts sharded over a mesh axis with the
token exchange as ONE all_to_all pair per layer (ICI), not RPC.

TPU-first design (static shapes throughout):
  * gating: softmax top-k with load-balancing auxiliary loss (the
    Switch/GShard aux), expressed as dense [T, E] one-hots — no dynamic
    gather/scatter shapes.
  * dispatch: capacity C = ceil(k * T / E * capacity_factor); tokens
    beyond an expert's capacity are DROPPED (their combine weight is
    zero) — the standard static-shape MoE contract.
  * single-device: one einsum pipeline. Expert-parallel: call
    `moe_shard_map`-style under shard_map with experts sharded over
    "ep"; dispatch/combine ride lax.all_to_all.

Two layers live here, and which is for what:

  * ``MoE`` (above): GShard's layer, for TRAINING a model of one's own:
    softmax scores, an auxiliary balance loss, capacity buffers that
    drop what overflows, GELU experts with biases, the exchange over
    "ep". Its dense ``[T, E, C]`` one-hots cost ``T x E x C`` and its
    dropped tokens change the result, so no published sparse model is
    served through it.
  * ``HeldExperts`` (below): the routed layer of today's open sparse
    models (the DeepSeek-V3 router: sigmoid scores, a selection bias,
    gates normalised over the chosen and scaled; SiLU-gated experts
    without biases beside a shared expert), for SERVING one chip's
    share of an expert-parallel deployment. It is told which experts it
    holds, routes over all of them, drops no row and computes its own
    experts' part of the result by a grouped matrix product
    (ops/pallas/moe_mlp.py). It has no backward of its own yet and no
    exchange: on one chip the layer runs without it (ROADMAP R1b, R2b).
"""

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu import initializer as I
from paddle_tpu.nn.module import Module


def top_k_gating(logits, k, capacity):
    """Static-shape top-k gating. logits: [T, E].

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights,
    aux_loss scalar). Position of a token inside its expert's buffer is
    its rank among the tokens routed there (cumsum order); overflow
    positions >= capacity get zero weight.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    # occupancy carried across the k rounds so second choices pack after
    # first choices (GShard's sequential-greedy assignment)
    occupancy = jnp.zeros((e,), jnp.int32)
    masked = probs
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)              # [T]
        onehot = jax.nn.one_hot(choice, e, dtype=probs.dtype)
        # rank of each token within its chosen expert this round
        pos_in_round = (jnp.cumsum(onehot, axis=0) - onehot)  # [T, E]
        pos = (pos_in_round + occupancy[None, :]) * onehot
        pos_idx = jnp.sum(pos, axis=-1).astype(jnp.int32)     # [T]
        keep = pos_idx < capacity
        gate = jnp.sum(probs * onehot, axis=-1) * keep        # [T]
        # pos_oh is all-zero for overflow tokens (the where() routes them
        # to the sliced-off column), so no extra keep factor is needed
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos_idx, capacity),
                                capacity + 1,
                                dtype=probs.dtype)[:, :capacity]
        dispatch = dispatch + onehot[:, :, None] * pos_oh[:, None, :]
        combine = combine + (gate[:, None, None]
                             * onehot[:, :, None] * pos_oh[:, None, :])
        occupancy = occupancy + jnp.sum(onehot, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)                  # exclude chosen

    # load-balancing aux (Switch Transformer eq. 4): E * sum_e f_e * p_e
    first_choice = jax.nn.one_hot(jnp.argmax(probs, -1), e,
                                  dtype=probs.dtype)
    f = jnp.mean(first_choice, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


class MoE(Module):
    """Top-k routed expert FFN. x: [B, T, D] -> [B, T, D].

    Single-call usage computes all experts locally; under shard_map with
    experts sharded over `ep_axis`, the dispatched token buffers are
    exchanged with one all_to_all pair and each device runs only its own
    experts.
    """

    def __init__(self, dim, hidden, num_experts, k=2, capacity_factor=1.25,
                 ep_axis=None, dtype=jnp.float32):
        super().__init__()
        from paddle_tpu.core.enforce import enforce
        enforce(k <= num_experts, "MoE top-k needs k <= num_experts")
        self.dim, self.hidden = dim, hidden
        self.num_experts, self.k = num_experts, k
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.param("w_gate", (dim, num_experts), I.xavier(), dtype)
        # explicit per-expert Linear fans: the default conv-style fans
        # would treat [E, D, H] as OIHW and init experts ~sqrt(E)x too
        # small
        self.param("w1", (num_experts, dim, hidden),
                   I.xavier(fan_in=dim, fan_out=hidden), dtype)
        self.param("b1", (num_experts, hidden), I.zeros(), dtype)
        self.param("w2", (num_experts, hidden, dim),
                   I.xavier(fan_in=hidden, fan_out=dim), dtype)
        self.param("b2", (num_experts, dim), I.zeros(), dtype)

    def _capacity(self, tokens, num_experts):
        import math
        c = math.ceil(self.k * tokens * self.capacity_factor / num_experts)
        return max(c, 1)

    def forward(self, x):
        return self.forward_with_aux(x)[0]

    def forward_with_aux(self, x):
        """Returns (y, aux_loss) — add `aux_loss * coef` to the training
        objective (apply(..., method="forward_with_aux"))."""
        b, t, d = x.shape
        tokens = b * t
        xf = x.reshape(tokens, d)
        logits = xf @ self.p("w_gate")
        e = self.num_experts
        cap = self._capacity(tokens, e)
        dispatch, combine, aux = top_k_gating(logits, self.k, cap)

        def expert_ffn(buf):
            h = jnp.einsum("ecd,edh->ech", buf, self.p("w1")) \
                + self.p("b1")[:, None, :]
            h = jax.nn.gelu(h)
            return jnp.einsum("ech,ehd->ecd", h, self.p("w2")) \
                + self.p("b2")[:, None, :]

        # [E, C, D] expert input buffers
        buf = jnp.einsum("td,tec->ecd", xf, dispatch)
        if self.ep_axis is not None:
            n = lax.axis_size(self.ep_axis)
            el = e // n                           # experts owned locally
            # exchange: split expert dim across devices, gather the
            # capacity dim — each device ends with [el, n*C, D] (its own
            # experts' tokens from every device)
            buf = buf.reshape(n, el, cap, d)
            buf = lax.all_to_all(buf, self.ep_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
            buf = buf.transpose(1, 0, 2, 3).reshape(el, n * cap, d)
            out = expert_ffn(buf)
            out = out.reshape(el, n, cap, d).transpose(1, 0, 2, 3)
            out = lax.all_to_all(out, self.ep_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
            out = out.reshape(e, cap, d)
        else:
            out = expert_ffn(buf)
        y = jnp.einsum("ecd,tec->td", out, combine)
        return y.reshape(b, t, d), aux


def sigmoid_top_k(scores, bias, k, scale):
    """The DeepSeek-V3 router on sigmoid ``scores`` [T, E] (float32):
    the ``k`` experts of each row with the largest ``scores + bias``
    (the selection bias enters the CHOICE only), and their gates
    ``scale * s_e / (sum over the chosen of s + 1e-20)`` from the
    unbiased scores. -> (experts [T, k] int32, gates [T, k] float32)."""
    _, chosen = lax.top_k(scores + bias, k)
    s = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scale * s / (jnp.sum(s, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), gates


class HeldExperts(Module):
    """A sigmoid-routed, dropless expert layer that holds a SHARE of the
    experts: rows [T, dim] -> (the shared expert's output plus the gated
    outputs of the chosen experts that are HELD here [T, dim] float32,
    rows routed to each held expert [count] int32).

    ``num_experts`` is the router's width (every expert of the
    deployment), ``held = (first, count)`` the run of experts whose
    weights live here (all of them where it is None). The gates are
    normalised over all ``k`` chosen experts, held or not; what an
    absent expert would have added is left out and nothing else changes,
    so the parts of all the shares, with the shared expert counted once,
    add up to the whole layer (tests/test_held_experts.py).

    Precision: the router's product, the sigmoid, the choice and the
    gates are float32 whatever the weights' dtype (a score rounded to
    bfloat16 swaps the k-th expert for the next in some rows); the
    experts multiply in the weights' dtype with float32 accumulation.

    Static shapes, no capacity: the ``T x k`` (row, choice) pairs are
    sorted by held expert, the pairs of absent experts and of rows that
    are not ``live`` behind them in a group nobody computes."""

    def __init__(self, dim, hidden, num_experts, k, held=None, scale=1.0,
                 shared_hidden=None, dtype=jnp.float32):
        super().__init__()
        from paddle_tpu.core.enforce import enforce
        first, count = held if held is not None else (0, num_experts)
        enforce(0 <= first and count >= 1 and first + count <= num_experts,
                f"held={held} is no run of the {num_experts} experts")
        enforce(k <= num_experts, "top-k needs k <= num_experts")
        self.dim, self.hidden = dim, hidden
        self.num_experts, self.k, self.scale = num_experts, k, float(scale)
        self.first, self.count = first, count
        self.shared_hidden = shared_hidden
        self.param("router", (num_experts, dim), I.xavier(), dtype)
        self.param("bias", (num_experts,), I.zeros(), dtype)
        self.param("w_gate", (count, dim, hidden),
                   I.xavier(fan_in=dim, fan_out=hidden), dtype)
        self.param("w_up", (count, dim, hidden),
                   I.xavier(fan_in=dim, fan_out=hidden), dtype)
        self.param("w_down", (count, hidden, dim),
                   I.xavier(fan_in=hidden, fan_out=dim), dtype)
        if shared_hidden:
            self.param("shared_gate", (dim, shared_hidden), I.xavier(), dtype)
            self.param("shared_up", (dim, shared_hidden), I.xavier(), dtype)
            self.param("shared_down", (shared_hidden, dim), I.xavier(),
                       dtype)

    def route(self, x):
        """(experts [T, k], gates [T, k]) of rows x [T, dim]."""
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), self.p("router").astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST))
        return sigmoid_top_k(scores, self.p("bias").astype(jnp.float32),
                             self.k, self.scale)

    def routed(self, x, live=None):
        """The held experts' part alone -> ([T, dim] float32, rows routed
        to each held expert [count] int32). ``live`` [T] bool: rows that
        are padding or idle slots are routed nowhere and counted
        nowhere."""
        from paddle_tpu.ops.pallas.moe_mlp import expert_mlp
        t, k, n = x.shape[0], self.k, self.count
        chosen, gates = self.route(x)
        local = chosen - self.first
        here = (local >= 0) & (local < n)
        if live is not None:
            here = here & live[:, None]
        group = jnp.where(here, local, n).reshape(t * k)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
        w = self.p("w_gate")
        y = expert_mlp(x.astype(w.dtype)[order // k], w, self.p("w_up"),
                       self.p("w_down"), sizes)
        # back to (row, choice) order by a GATHER through the inverse
        # permutation (a scatter-add of T x k rows is serial work on a
        # TPU), then each row's k parts weighted and summed. The rows
        # behind the last run are undefined: select, never multiply
        back = jnp.zeros(t * k, jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        y = jnp.where(here[..., None],
                      y[back].reshape(t, k, self.dim) * gates[..., None],
                      0.0)
        return jnp.sum(y, axis=1), sizes

    def kernel_grid(self, tokens):
        """(row tile, work items of its static grid) of the grouped
        kernel's call over ``tokens`` rows (ops/pallas/moe_mlp.py): the
        engine counts how many of those items carried rows."""
        from paddle_tpu.ops.pallas.moe_mlp import pick_tiles
        m = -(-tokens * self.k // 8) * 8
        tile_m, _ = pick_tiles(m, self.dim, self.hidden,
                               self.p("w_gate").dtype.itemsize)
        return tile_m, m // tile_m + self.count - 1

    def shared(self, x):
        """The shared expert (every chip computes it alike) through the
        fused MLP kernel's gate path. -> [T, dim] float32."""
        from paddle_tpu.ops.pallas.mlp import fused_mlp
        return fused_mlp(x, self.p("shared_gate"), None,
                         self.p("shared_down"), None,
                         wg=self.p("shared_up"), act="silu"
                         ).astype(jnp.float32)

    def forward(self, x, live=None):
        y, sizes = self.routed(x, live)
        if self.shared_hidden:
            y = y + self.shared(x)
        return y, sizes
