"""Hybrid decoder: a causal LM whose layers differ in KIND, chosen per
layer by configuration. A layer mixes tokens by a state-space (Mamba-1)
recurrence, by full attention or by attention inside a sliding window,
and its feed-forward is a dense SiLU-gated MLP or a routed expert layer
of which this chip holds a share (the Jamba family, Lieber et al.,
arXiv:2403.19887; the EXAONE 4.0 / K-EXAONE family, arXiv:2507.11407).

Ref: none in the reference (2019-era). The block is what today's open
models share: RMSNorm, residuals, SiLU-gated MLPs without biases. Where
the norms sit is ONE configuration value, ``norm_placement``:

    "pre"   x   = u + mixer_i(norm1(u))
            out = x + ffn_i(norm2(x))
    "post"  x   = u + norm1(mixer_i(u))
            out = x + norm2(ffn_i(x))

``layer_types[i]`` is ``"mamba"``, ``"full"`` or ``"window"`` (where it
is None, Jamba's rule: attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere); ``ffn_types[i]`` is
``"dense"`` or ``"moe"`` (None: all dense). Attention has
``num_kv_heads`` K/V heads of its own (nn.GroupedQueryAttention), an
RMSNorm on q and k where ``qk_norm`` says, and rotary positions in its
WINDOW layers where ``rope_theta`` is given (full layers carry none);
the dense MLP and the shared expert run through the fused MLP kernel's
gate path (ops/pallas/mlp.py), the routed experts through the grouped
kernel (nn.HeldExperts, ops/pallas/moe_mlp.py). The head is tied to the
embedding or, with ``tie_embeddings=False``, a matrix of its own.

Serving: ``HybridDecoder`` implements the serving engine's cache
protocol (serving/engine.py) with BOTH kinds of cache: paged K/V pools
for its full-attention layers and a per-slot state for the others (a
Mamba layer's recurrent state; a window layer's ring of its last
``sliding_window`` K/V rows, which holds a window and never a context).
A model with routed experts hands back, beside the protocol's values,
the rows routed to each held expert, ``[moe layers, held]`` int32.
Precision: weights as stored (bfloat16 in a deployment), matmul
operands in the weights' dtype with float32 accumulation, the residual
stream, the norms, the recurrence, the router and the softmax in
float32.
"""

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.ops.attention import pool_dims
from paddle_tpu.ops.pallas.mlp import fused_mlp


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = None            # None -> hidden_size // num_heads
    intermediate_size: int = 8192
    attn_layer_period: int = 14     # layer i attends where
    attn_layer_offset: int = 7      # i % period == offset
    mamba_expand: int = 2           # d_inner = expand * hidden_size
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = None       # None -> ceil(hidden_size / 16)
    rms_norm_eps: float = 1e-6
    layer_types: tuple = None       # per layer "mamba" | "full" | "window"
    ffn_types: tuple = None         # per layer "dense" | "moe"
    norm_placement: str = "pre"     # or "post": the norm on the OUTPUT
    tie_embeddings: bool = True
    qk_norm: bool = False
    rope_theta: float = None        # rotary positions, window layers only
    sliding_window: int = None
    moe_intermediate_size: int = None
    num_experts: int = None         # the router's width
    num_experts_per_tok: int = None
    held_experts: tuple = None      # (first, count); None -> all
    routed_scaling_factor: float = 1.0
    shared_intermediate_size: int = None

    def mixer_kind(self, i):
        if self.layer_types is not None:
            return self.layer_types[i]
        return "full" if self.is_attention(i) else "mamba"

    def ffn_kind(self, i):
        return "dense" if self.ffn_types is None else self.ffn_types[i]

    def is_attention(self, i):
        return i % self.attn_layer_period == self.attn_layer_offset

    @staticmethod
    def tiny():
        """Two periods of (Mamba, attention), one K/V head: every kind of
        layer and every inner norm at CPU-test size."""
        return HybridConfig(vocab_size=512, hidden_size=64, num_layers=4,
                            num_heads=4, num_kv_heads=1,
                            intermediate_size=128, attn_layer_period=2,
                            attn_layer_offset=1, mamba_dt_rank=8)


class HybridBlock(nn.Module):
    def __init__(self, cfg: HybridConfig, mixer="full", ffn="dense"):
        super().__init__()
        h = cfg.hidden_size
        self.kind, self.ffn_kind = mixer, ffn
        self.attention = mixer != "mamba"     # full or window
        self.pre = cfg.norm_placement == "pre"
        assert cfg.norm_placement in ("pre", "post"), cfg.norm_placement
        self.norm1 = nn.RMSNorm(h, cfg.rms_norm_eps)
        if mixer == "mamba":
            self.mixer = nn.MambaMixer(
                h, cfg.mamba_expand * h, cfg.mamba_d_state,
                cfg.mamba_d_conv, cfg.mamba_dt_rank, cfg.rms_norm_eps)
        else:
            window = mixer == "window"
            self.mixer = nn.GroupedQueryAttention(
                h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                qk_norm=cfg.qk_norm, epsilon=cfg.rms_norm_eps,
                rope_theta=cfg.rope_theta if window else None,
                window=cfg.sliding_window if window else None)
        self.norm2 = nn.RMSNorm(h, cfg.rms_norm_eps)
        if ffn == "moe":
            self.moe = nn.HeldExperts(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                scale=cfg.routed_scaling_factor,
                shared_hidden=cfg.shared_intermediate_size)
        else:
            self.gate_proj = nn.Linear(h, cfg.intermediate_size, bias=False)
            self.up_proj = nn.Linear(h, cfg.intermediate_size, bias=False)
            self.down_proj = nn.Linear(cfg.intermediate_size, h, bias=False)

    def mix(self, x, mixer):
        """x + the token mixer's part; ``mixer(normed or raw x)`` ->
        (y, the layer's new cache)."""
        y, cache = mixer(self.norm1(x) if self.pre else x)
        return x + (y if self.pre else self.norm1(y)), cache

    def mlp(self, x, live=None):
        """x + the feed-forward's part -> (new x, rows routed to each
        held expert or None). The dense MLP is the fused MLP kernel's
        gate path, so the [rows, intermediate] activation stays on
        chip; ``live`` [B, T] marks the rows that are real (an expert
        layer routes padding and idle slots nowhere)."""
        y = self.norm2(x) if self.pre else x
        rows = None
        if self.ffn_kind == "moe":
            flat = y.reshape(-1, y.shape[-1])
            y, rows = self.moe(flat, None if live is None
                               else live.reshape(-1))
            y = y.reshape(x.shape)
        else:
            y = fused_mlp(
                y, self.gate_proj.p("weight"), None,
                self.down_proj.p("weight"), None,
                wg=self.up_proj.p("weight"), act="silu").astype(x.dtype)
        return x + (y if self.pre else self.norm2(y)), rows


class HybridDecoder(nn.Module):
    """The hybrid causal LM, whole sequences (``forward``) and served
    (the three paged methods). Caches: ``init_paged_caches`` gives one
    K/V page pool per FULL-attention layer, ``init_slot_state`` one
    per-slot state per Mamba layer (its recurrent state) and per window
    layer (its ring); both are lists in layer order."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = [HybridBlock(cfg, cfg.mixer_kind(i), cfg.ffn_kind(i))
                       for i in range(cfg.num_layers)]
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias=False)

    def _head(self, x):
        """x [..., H] -> float32 logits [..., V], against the embedding
        or the head's own matrix."""
        if self.cfg.tie_embeddings:
            w, dim = self.tok_emb.p("weight"), 1
        else:
            w, dim = self.lm_head.p("weight"), 0
        return jax.lax.dot_general(
            x.astype(w.dtype), w, (((x.ndim - 1,), (dim,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _run(self, x, caches, state, mixers, live=None):
        """Every block over the residual stream x [B, T, H] (float32).
        ``mixers[kind](mixer, x, cache)`` -> (y, new cache) runs a layer
        of that kind on the next entry of ``caches`` (full attention) or
        of ``state`` (Mamba, window). -> (final-normed x, new caches,
        new state, rows routed to each held expert [moe layers, held]
        or None)."""
        caches, state = iter(caches), iter(state)
        new_caches, new_state, routed = [], [], []
        for blk in self.blocks:
            held = new_caches if blk.kind == "full" else new_state
            cache = next(caches if blk.kind == "full" else state)
            x, cache = blk.mix(
                x, lambda y: mixers[blk.kind](blk.mixer, y, cache))
            held.append(cache)
            x, rows = blk.mlp(x, live)
            if rows is not None:
                routed.append(rows)
        return (self.norm_f(x), new_caches, new_state,
                jnp.stack(routed) if routed else None)

    def _out(self, logits, caches, state, routed):
        """The protocol's triple, with the routed rows behind it where
        the model has experts."""
        if routed is None:
            return logits, caches, state
        return logits, caches, state, routed

    def forward(self, input_ids):
        """Next-token logits [B, T, V] (float32) of whole sequences."""
        b, t = input_ids.shape
        x = self.tok_emb(input_ids).astype(jnp.float32)
        lengths = jnp.full((b,), t, jnp.int32)
        attend = lambda mixer, y, _: (mixer(y), None)  # noqa: E731
        x, _, _, _ = self._run(
            x, [None] * self.cfg.num_layers,
            [blk.mixer.init_state(b, jnp.float32) if blk.kind == "mamba"
             else None for blk in self.blocks if blk.kind != "full"],
            {"mamba": lambda mixer, y, st: mixer(
                y, st, None, lengths, None, "selective_scan"),
             "full": attend, "window": attend})
        return self._head(x)

    def moe_grid(self, tokens):
        """The grouped kernel's (row tile, items a layer's call) for a
        program over ``tokens`` rows (every expert layer alike), or None
        for a model without experts."""
        for blk in self.blocks:
            if blk.ffn_kind == "moe":
                return blk.moe.kernel_grid(tokens)
        return None

    # --- the serving engine's cache protocol (serving/engine.py) ---

    def init_paged_caches(self, num_pages, page_size, dtype=jnp.float32,
                          kv_dtype=None):
        return [blk.mixer.init_page_pool(num_pages, page_size, dtype,
                                         kv_dtype=kv_dtype)
                for blk in self.blocks if blk.kind == "full"]

    def init_slot_state(self, num_slots, dtype=jnp.float32):
        return [blk.mixer.init_state(num_slots, dtype)
                if blk.kind == "mamba"
                else blk.mixer.init_ring(num_slots, dtype)
                for blk in self.blocks if blk.kind != "full"]

    def paged_decode_step(self, tokens, caches, page_table, lengths, active,
                          state):
        """One decode round for every slot (GPTDecoder.paged_decode_step
        with a state): an inactive slot writes no K/V, keeps its
        recurrent state and its ring, and is routed to no expert.
        -> (logits [S, V], new caches, new state[, routed rows])."""
        s = tokens.shape[0]
        num_pages, page_size = pool_dims(caches[0])
        write_pages = page_table[jnp.arange(s), lengths // page_size]
        write_pages = jnp.where(active, write_pages, num_pages)  # drop
        write_offsets = lengths % page_size
        att_lengths = lengths + active.astype(lengths.dtype)
        x = self.tok_emb(tokens).astype(jnp.float32)[:, None]  # [S, 1, H]
        x, caches, state, routed = self._run(
            x, caches, state,
            {"mamba": lambda mixer, y, st: mixer(
                y, st, None, active.astype(jnp.int32), None,
                "ssm_state_update"),
             "full": lambda mixer, y, pool: mixer.paged_decode_step(
                y, pool, page_table, att_lengths, write_pages,
                write_offsets),
             "window": lambda mixer, y, ring: mixer.ring_decode_step(
                y, ring, lengths, active)},
            live=active[:, None])
        return self._out(self._head(x)[:, 0], caches, state, routed)

    def paged_prefill_chunk(self, prompt, starts, chunk_lengths, caches,
                            page_rows, write_floor=None, *, state, slots):
        """A prompt chunk of each of B requests (GPTDecoder's contract for
        prompt, starts, chunk_lengths, page_rows) into slot ``slots[b]``:
        a chunk at ``starts[b] == 0`` begins the slot's recurrent state
        from zeros INSIDE this program and sees nothing of the slot's
        ring, a later chunk continues both, and the chunk's padding
        advances neither the state nor the conv window nor the ring and
        is routed to no expert. ``write_floor`` is refused: a
        prefix-cache hit would skip positions whose state nobody kept
        (the engine refuses the prefix cache for a model with state).
        -> (logits of each request's last real token [B, V], new caches,
        new state[, routed rows])."""
        assert write_floor is None, "no prefix-cache hits with state"
        lp = prompt.shape[1]
        num_pages, page_size = pool_dims(caches[0])
        rel = jnp.arange(lp)
        real = rel[None, :] < chunk_lengths[:, None]            # [B, Lp]
        pos = starts[:, None] + rel[None, :]
        page_ids = jnp.take_along_axis(
            page_rows, jnp.minimum(pos // page_size,
                                   page_rows.shape[1] - 1), axis=1)
        page_ids = jnp.where(real, page_ids, num_pages)
        offsets = pos % page_size
        x = self.tok_emb(prompt).astype(jnp.float32)
        x, caches, state, routed = self._run(
            x, caches, state,
            {"mamba": lambda mixer, y, st: mixer(
                y, st, slots, chunk_lengths, starts == 0,
                "selective_scan"),
             "full": lambda mixer, y, pool: mixer.paged_prefill_chunk(
                y, pool, page_ids, offsets, page_rows, pos),
             "window": lambda mixer, y, ring: mixer.ring_prefill_chunk(
                y, ring, slots, starts, chunk_lengths)},
            live=real)
        last = jnp.take_along_axis(
            x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)
        return self._out(self._head(last)[:, 0], caches, state, routed)
