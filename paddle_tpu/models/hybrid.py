"""Hybrid decoder: a causal LM whose layers mix tokens by a state-space
(Mamba-1) recurrence or by attention, chosen per layer by configuration
(the Jamba family, Lieber et al., arXiv:2403.19887).

Ref: none in the reference (2019-era). The block is what today's open
models share: RMSNorm, pre-norm residuals, a SiLU-gated MLP without
biases, no positional encoding of any kind (the recurrent layers carry
order), a head tied to the embedding.

    x   = u + mixer_i(norm1(u))
    out = x + W_down(silu(W_gate n) * (W_up n)),   n = norm2(x)

Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere; attention has ``num_kv_heads``
K/V heads of its own (nn.GroupedQueryAttention), the gated MLP runs
through the fused MLP kernel's gate path (ops/pallas/mlp.py).

Serving: ``HybridDecoder`` implements the serving engine's cache
protocol (serving/engine.py) with BOTH kinds of cache: paged K/V pools
for its attention layers and a per-slot recurrent state for its Mamba
layers. Precision: weights as stored (bfloat16 in a deployment),
matmul operands in the weights' dtype with float32 accumulation, the
residual stream, the norms and the recurrence in float32.
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
    def __init__(self, cfg: HybridConfig, attention):
        super().__init__()
        h = cfg.hidden_size
        self.attention = attention
        self.norm1 = nn.RMSNorm(h, cfg.rms_norm_eps)
        if attention:
            self.mixer = nn.GroupedQueryAttention(
                h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        else:
            self.mixer = nn.MambaMixer(
                h, cfg.mamba_expand * h, cfg.mamba_d_state,
                cfg.mamba_d_conv, cfg.mamba_dt_rank, cfg.rms_norm_eps)
        self.norm2 = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.gate_proj = nn.Linear(h, cfg.intermediate_size, bias=False)
        self.up_proj = nn.Linear(h, cfg.intermediate_size, bias=False)
        self.down_proj = nn.Linear(cfg.intermediate_size, h, bias=False)

    def mlp(self, x):
        """x + W_down(silu(W_gate n) * (W_up n)): the fused MLP kernel's
        gate path, so the [rows, intermediate] activation stays on chip."""
        return x + fused_mlp(
            self.norm2(x), self.gate_proj.p("weight"), None,
            self.down_proj.p("weight"), None, wg=self.up_proj.p("weight"),
            act="silu").astype(x.dtype)


class HybridDecoder(nn.Module):
    """The hybrid causal LM, whole sequences (``forward``) and served
    (the three paged methods). Caches: ``init_paged_caches`` gives one
    K/V page pool per ATTENTION layer, ``init_slot_state`` one recurrent
    state per MAMBA layer; both are lists in layer order."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = [HybridBlock(cfg, cfg.is_attention(i))
                       for i in range(cfg.num_layers)]
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def _head(self, x):
        """Tied head: x [..., H] -> float32 logits [..., V]."""
        w = self.tok_emb.p("weight")
        return jax.lax.dot_general(
            x.astype(w.dtype), w, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _run(self, x, caches, state, mamba_args, attend):
        """Every block over the residual stream x [B, T, H] (float32).
        Mamba layers take ``mamba_args`` (slots, lengths, fresh, kernel
        name) and the next entry of ``state``; attention layers call
        ``attend(mixer, normed x, pool)`` with the next entry of
        ``caches``. -> (final-normed x, new caches, new state)."""
        caches, state = iter(caches), iter(state)
        new_caches, new_state = [], []
        for blk in self.blocks:
            y = blk.norm1(x)
            if blk.attention:
                y, pool = attend(blk.mixer, y, next(caches))
                new_caches.append(pool)
            else:
                y, st = blk.mixer(y, next(state), *mamba_args)
                new_state.append(st)
            x = blk.mlp(x + y)
        return self.norm_f(x), new_caches, new_state

    def forward(self, input_ids):
        """Next-token logits [B, T, V] (float32) of whole sequences."""
        b, t = input_ids.shape
        x = self.tok_emb(input_ids).astype(jnp.float32)
        x, _, _ = self._run(
            x, [None] * self.cfg.num_layers, self.init_slot_state(b),
            (None, jnp.full((b,), t, jnp.int32), None, "selective_scan"),
            lambda mixer, y, _: (mixer(y), None))
        return self._head(x)

    # --- the serving engine's cache protocol (serving/engine.py) ---

    def init_paged_caches(self, num_pages, page_size, dtype=jnp.float32,
                          kv_dtype=None):
        return [blk.mixer.init_page_pool(num_pages, page_size, dtype,
                                         kv_dtype=kv_dtype)
                for blk in self.blocks if blk.attention]

    def init_slot_state(self, num_slots, dtype=jnp.float32):
        return [blk.mixer.init_state(num_slots, dtype)
                for blk in self.blocks if not blk.attention]

    def paged_decode_step(self, tokens, caches, page_table, lengths, active,
                          state):
        """One decode round for every slot (GPTDecoder.paged_decode_step
        with a state): an inactive slot writes no K/V and keeps its
        recurrent state. -> (logits [S, V], new caches, new state)."""
        s = tokens.shape[0]
        num_pages, page_size = pool_dims(caches[0])
        write_pages = page_table[jnp.arange(s), lengths // page_size]
        write_pages = jnp.where(active, write_pages, num_pages)  # drop
        write_offsets = lengths % page_size
        att_lengths = lengths + active.astype(lengths.dtype)
        x = self.tok_emb(tokens).astype(jnp.float32)[:, None]  # [S, 1, H]
        x, caches, state = self._run(
            x, caches, state,
            (None, active.astype(jnp.int32), None, "ssm_state_update"),
            lambda mixer, y, pool: mixer.paged_decode_step(
                y, pool, page_table, att_lengths, write_pages,
                write_offsets))
        return self._head(x)[:, 0], caches, state

    def paged_prefill_chunk(self, prompt, starts, chunk_lengths, caches,
                            page_rows, write_floor=None, *, state, slots):
        """A prompt chunk of each of B requests (GPTDecoder's contract for
        prompt, starts, chunk_lengths, page_rows) into slot ``slots[b]``:
        a chunk at ``starts[b] == 0`` begins the slot's recurrent state
        from zeros INSIDE this program, a later chunk continues it, and
        the chunk's padding advances neither the state nor the conv
        window. ``write_floor`` is refused: a prefix-cache hit would skip
        positions whose state nobody kept (the engine refuses the prefix
        cache for a model with state). -> (logits of each request's last
        real token [B, V], new caches, new state)."""
        assert write_floor is None, "no prefix-cache hits with state"
        lp = prompt.shape[1]
        num_pages, page_size = pool_dims(caches[0])
        rel = jnp.arange(lp)
        pos = starts[:, None] + rel[None, :]                    # [B, Lp]
        page_ids = jnp.take_along_axis(
            page_rows, jnp.minimum(pos // page_size,
                                   page_rows.shape[1] - 1), axis=1)
        page_ids = jnp.where(rel[None, :] < chunk_lengths[:, None],
                             page_ids, num_pages)
        offsets = pos % page_size
        x = self.tok_emb(prompt).astype(jnp.float32)
        x, caches, state = self._run(
            x, caches, state,
            (slots, chunk_lengths, starts == 0, "selective_scan"),
            lambda mixer, y, pool: mixer.paged_prefill_chunk(
                y, pool, page_ids, offsets, page_rows, pos))
        last = jnp.take_along_axis(
            x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)
        return self._head(last)[:, 0], caches, state
