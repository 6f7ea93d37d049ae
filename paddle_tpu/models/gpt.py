"""Decoder-only causal LM (GPT-style) — the long-context flagship.

Ref: no decoder-only LM exists in the reference (2019-era; its language
models are word2vec + the NMT transformer, tests/book). This family exists
because the brief's long-context requirement (BASELINE.json north star)
needs a first-class consumer: causal flash attention on one chip,
ring/Ulysses sequence parallelism across chips.

Design: pre-norm transformer decoder; attention runs
  * `flash_attention(causal=True)` (Pallas, O(T) memory) on a single chip
  * `ring_flash_attention` over the `sp` mesh axis when `seq_axis` is set
    (call inside shard_map with the sequence dim sharded)
"""

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.ops import activations as A
from paddle_tpu.ops import loss as L
from paddle_tpu.ops.attention import pool_dims


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 2048
    dropout: float = 0.1
    use_flash: bool = True
    seq_axis: str = None       # mesh axis name for ring sequence parallelism
    moe_experts: int = 0       # >0: MoE FFN with this many experts
    moe_k: int = 2
    moe_ep_axis: str = None    # mesh axis for expert parallelism
    scan_layers: bool = False  # stack block params + lax.scan over layers
    remat: str = None          # nothing|dots_saveable|full (None -> flag)

    @staticmethod
    def small():
        return GPTConfig()

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=4, intermediate_size=128,
                         max_position=128)


class GPTBlock(nn.Module):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        # the shared fused-MHA layer (one implementation across BERT /
        # Transformer / GPT); the ring sequence-parallel branch is selected
        # per-call via seq_axis
        self.attn = nn.MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                          dropout=cfg.dropout,
                                          use_flash=cfg.use_flash)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        if cfg.moe_experts:
            from paddle_tpu.nn.moe import MoE
            self.mlp = MoE(cfg.hidden_size, cfg.intermediate_size,
                           cfg.moe_experts, k=cfg.moe_k,
                           ep_axis=cfg.moe_ep_axis)
        else:
            self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
            self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def _ffn(self, x):
        if self.cfg.moe_experts:
            return self.mlp(x)
        return nn.fused_ffn(self.fc1, self.fc2, x)

    def forward(self, x):
        # pre-norm residual blocks (GPT-2 style)
        x = x + self.drop(self.attn(self.ln1(x), causal=True,
                                    seq_axis=self.cfg.seq_axis))
        x = x + self.drop(self._ffn(self.ln2(x)))
        return x

    def decode_step(self, x, cache, pos):
        """Incremental twin of forward: same pre-norm residual structure,
        attention through the KV cache (dropout is inference-off)."""
        h, cache = self.attn.decode_step(self.ln1(x), cache, pos)
        x = x + h
        x = x + self._ffn(self.ln2(x))
        return x, cache

    def prefill(self, x, cache, start=0):
        """Batched cache fill over the whole prompt (inference, no
        dropout): one causal forward instead of T decode_steps."""
        h, cache = self.attn.prefill(self.ln1(x), cache, start)
        x = x + h
        x = x + self._ffn(self.ln2(x))
        return x, cache

    def paged_decode_step(self, x, pool, page_table, att_lengths,
                          write_pages, write_offsets):
        """Incremental twin of forward against the paged serving cache
        (same pre-norm residual structure as decode_step)."""
        h, pool = self.attn.paged_decode_step(
            self.ln1(x), pool, page_table, att_lengths, write_pages,
            write_offsets)
        x = x + h
        x = x + self._ffn(self.ln2(x))
        return x, pool

    def paged_prefill(self, x, pool, page_ids, offsets):
        """Batched prompt fill into this block's page pool."""
        h, pool = self.attn.paged_prefill(self.ln1(x), pool, page_ids,
                                          offsets)
        x = x + h
        x = x + self._ffn(self.ln2(x))
        return x, pool

    def paged_prefill_chunk(self, x, pool, page_ids, offsets, page_rows,
                            q_pos, chunked):
        """Chunked prompt fill (continuation chunks attend the slot's
        whole cached prefix — see MultiHeadAttention.paged_prefill_chunk)."""
        h, pool = self.attn.paged_prefill_chunk(
            self.ln1(x), pool, page_ids, offsets, page_rows, q_pos,
            chunked)
        x = x + h
        x = x + self._ffn(self.ln2(x))
        return x, pool


class GPT(nn.Module):
    """Causal LM: returns next-token logits [B, T, V] (weight-tied head)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.pos_emb = nn.Embedding(cfg.max_position, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        if cfg.scan_layers:
            self.blocks = nn.ScanLayers(GPTBlock(cfg), cfg.num_layers,
                                        remat=cfg.remat,
                                        needs_rng=cfg.dropout > 0)
        else:
            self.blocks = [GPTBlock(cfg) for _ in range(cfg.num_layers)]
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def hidden(self, input_ids, pos_offset=0):
        """Final post-LN hidden states [B, T, H] (the vocab head is applied
        by forward, or fused into the loss by .loss)."""
        b, t = input_ids.shape
        if self.cfg.seq_axis is not None:
            # under shard_map the leading tokens of this shard sit at
            # global position rank * t_local
            from jax import lax
            pos_offset = pos_offset + lax.axis_index(
                self.cfg.seq_axis) * t
        pos = pos_offset + jnp.arange(t)[None, :]
        x = self.drop(self.tok_emb(input_ids) + self.pos_emb(pos))
        if self.cfg.scan_layers:
            x = self.blocks(x)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.ln_f(x)

    def forward(self, input_ids, pos_offset=0):
        return nn.tied_vocab_head(self.tok_emb,
                                  self.hidden(input_ids, pos_offset))

    def loss(self, input_ids, labels=None, pad_id=None, vocab_axis=None,
             batch_axis=None, mesh=None, mesh_plan=None):
        """Shifted next-token CE as an apply() entry point
        (``model.apply(vars, ids, method="loss")``). Default path: the
        chunked fused cross-entropy against the tied embedding table —
        no [B, T, V] logits. PT_FUSED_XENT=0 restores the
        logits-then-lm_loss reference composition.

        vocab_axis/batch_axis: mesh axis names when the tied embedding is
        vocab-partitioned (P(tp, None)) and the batch dp-sharded under
        GSPMD — the fused CE then runs per vocab shard with pmax/psum
        combines instead of gathering the table (ops/fused.py).
        mesh_plan: an autoplan MeshPlan — fills the three kwargs above
        from the planned mesh (explicit values win)."""
        from paddle_tpu.ops.fused import fused_xent, fused_xent_enabled
        if mesh_plan is not None:
            vocab_axis, batch_axis, mesh = mesh_plan.resolve_loss_axes(
                vocab_axis, batch_axis, mesh)
        if labels is None:
            labels = input_ids
        h = self.hidden(input_ids)
        if not fused_xent_enabled() or self.tok_emb.has_p("weight_q"):
            return lm_loss(nn.tied_vocab_head(self.tok_emb, h), labels,
                           pad_id)
        ce = fused_xent(h[:, :-1], self.tok_emb.p("weight"), labels[:, 1:],
                        vocab_axis=vocab_axis, batch_axis=batch_axis,
                        mesh=mesh)
        if pad_id is not None:
            valid = (labels[:, 1:] != pad_id).astype(ce.dtype)
            return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.mean(ce)


def lm_loss(logits, labels, pad_id=None):
    """Shifted next-token cross entropy; optionally ignores pad positions.
    Parity reference for GPT.loss's fused path (PT_FUSED_XENT gates)."""
    lp = logits[:, :-1]
    tgt = labels[:, 1:]
    ce = L.softmax_with_cross_entropy(lp, tgt[..., None])[..., 0]
    if pad_id is not None:
        valid = (tgt != pad_id).astype(ce.dtype)
        return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.mean(ce)


def _gpt_decode_step(model, token, caches, pos):
    """One incremental forward through all blocks with KV caches.
    token: [B, 1] int32 (lookup_table's Paddle trailing-1 squeeze is
    undone with an explicit reshape)."""
    b = token.shape[0]
    e = model.cfg.hidden_size
    x = (model.tok_emb(token)
         + model.pos_emb(jnp.full(token.shape, pos, jnp.int32))
         ).reshape(b, 1, e)
    new_caches = []
    for blk, cache in zip(model.blocks, caches):
        x, cache = blk.decode_step(x, cache, pos)
        new_caches.append(cache)
    x = model.ln_f(x)
    return nn.tied_vocab_head(model.tok_emb, x), new_caches


class GPTDecoder(GPT):
    """GPT + incremental decoding: KV caches make each generated token an
    O(1)-projection step (no full-sequence recompute). No reference
    counterpart — Fluid's decoders re-ran the network per step via the
    beam_search op loop."""

    def __init__(self, cfg: GPTConfig):
        from paddle_tpu.core.enforce import enforce
        enforce(not cfg.scan_layers,
                "GPTDecoder steps per-layer KV caches and needs unrolled "
                "blocks (scan_layers=False); train params saved from a "
                "scan model convert via io.checkpoint.unstack_layer_tree")
        super().__init__(cfg)

    def init_caches(self, batch, max_len, dtype=jnp.float32):
        from paddle_tpu.core.enforce import enforce
        enforce(self.cfg.seq_axis is None,
                "GPTDecoder decoding needs an unsharded sequence "
                "(seq_axis must be None); gather the sequence before "
                "decoding")
        return [blk.attn.init_cache(batch, max_len, dtype)
                for blk in self.blocks]

    def decode_step(self, token, caches, pos):
        """token: [B, 1] int32; pos: scalar. -> (logits [B, 1, V], caches)."""
        return _gpt_decode_step(self, token, caches, pos)

    # --- paged serving cache (slot/page-pool layout; ops/attention.py) ---

    def init_paged_caches(self, num_pages, page_size, dtype=jnp.float32,
                          kv_dtype=None):
        """Per-layer page pools for the serving engine. Unlike
        init_caches, capacity is pages (shared across slots), not a
        padded [B, Tmax] rectangle per request. kv_dtype=int8 stores
        quantized values with per-row scales (ops/attention.py)."""
        from paddle_tpu.core.enforce import enforce
        enforce(self.cfg.seq_axis is None,
                "paged decoding needs an unsharded sequence")
        return [blk.attn.init_page_pool(num_pages, page_size, dtype,
                                        kv_dtype=kv_dtype)
                for blk in self.blocks]

    def paged_decode_step(self, tokens, caches, page_table, lengths,
                          active):
        """One serve-step forward for all slots. tokens: [S] int32 (the
        pending token per slot, sits at position `lengths`); page_table:
        [S, Pmax] int32 (in-range everywhere); lengths: [S] tokens
        already in the cache; active: [S] bool. The new token's K/V lands
        at page_table[s, lengths//ps] offset lengths%ps (dropped for
        inactive slots); attention covers lengths+1 tokens.
        -> (logits [S, V], new_caches)."""
        s = tokens.shape[0]
        num_pages, page_size = pool_dims(caches[0])
        write_pages = page_table[jnp.arange(s), lengths // page_size]
        write_pages = jnp.where(active, write_pages, num_pages)  # drop
        write_offsets = lengths % page_size
        att_lengths = lengths + active.astype(lengths.dtype)
        pos = jnp.minimum(lengths, self.cfg.max_position - 1)
        x = (self.tok_emb(tokens[:, None])
             + self.pos_emb(pos[:, None])
             ).reshape(s, 1, self.cfg.hidden_size)
        new_caches = []
        for blk, pool in zip(self.blocks, caches):
            x, pool = blk.paged_decode_step(x, pool, page_table,
                                            att_lengths, write_pages,
                                            write_offsets)
            new_caches.append(pool)
        x = self.ln_f(x)
        return nn.tied_vocab_head(self.tok_emb, x)[:, 0], new_caches

    def paged_prefill(self, prompt, lengths, caches, page_rows):
        """Admission prefill: one causal forward over the padded prompt
        batch writes each request's K/V into its pages. prompt: [B, Lp]
        int32 (padded; Lp fixed so admission never retraces); lengths:
        [B] true prompt lengths; page_rows: [B, Pmax] int32. Pad
        positions route to the out-of-range drop page. Returns (logits
        of each request's LAST real token [B, V], new_caches).

        The single-chunk (starts = 0) case of paged_prefill_chunk, kept
        as the stable entry point — per-request jnp.where selection makes
        a first chunk numerically identical to the pre-chunking path."""
        b = prompt.shape[0]
        return self.paged_prefill_chunk(
            prompt, jnp.zeros((b,), jnp.int32), lengths, caches,
            page_rows)

    def paged_prefill_chunk(self, prompt, starts, chunk_lengths, caches,
                            page_rows, write_floor=None):
        """Chunked admission prefill: the fixed [B, Lp] window holds
        tokens at ABSOLUTE positions starts[b] .. starts[b] +
        chunk_lengths[b] - 1 of each request, so a prompt longer than Lp
        is admitted as ceil(len / Lp) calls of one trace. First chunks
        (starts == 0) take the in-chunk causal path bit-exactly;
        continuation chunks re-attend the slot's whole cached prefix
        through its page table. write_floor ([B] int32, optional): K/V
        writes below that absolute position are dropped — the serving
        engine's prefix-cache hits map shared read-only pages there, so
        their content must not be rewritten (it is bit-identical anyway;
        dropping the write is what keeps the pages shareable). Returns
        (logits of each request's LAST chunk token [B, V], new_caches)."""
        x, new_caches = self._paged_chunk_hidden(
            prompt, starts, chunk_lengths, caches, page_rows, write_floor)
        last = jnp.take_along_axis(
            x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)
        return nn.tied_vocab_head(self.tok_emb, last)[:, 0], new_caches

    def _paged_chunk_hidden(self, prompt, starts, chunk_lengths, caches,
                            page_rows, write_floor=None):
        """Shared body of paged_prefill_chunk / paged_verify_chunk: run
        the fixed [B, Lp] window through every block's gathered-prefix
        chunk attention and return the FULL post-ln_f hidden states
        [B, Lp, H] plus the updated pools."""
        b, lp = prompt.shape
        num_pages, page_size = pool_dims(caches[0])
        p_max = page_rows.shape[1]
        rel = jnp.arange(lp)
        pos = starts[:, None] + rel[None, :]                    # [B, Lp]
        in_chunk = rel[None, :] < chunk_lengths[:, None]
        page_ids = jnp.take_along_axis(
            page_rows, jnp.minimum(pos // page_size, p_max - 1), axis=1)
        page_ids = jnp.where(in_chunk, page_ids, num_pages)
        if write_floor is not None:
            page_ids = jnp.where(pos >= write_floor[:, None], page_ids,
                                 num_pages)
        offsets = pos % page_size
        emb_pos = jnp.minimum(pos, self.cfg.max_position - 1)
        x = self.tok_emb(prompt) + self.pos_emb(emb_pos)
        chunked = starts > 0
        new_caches = []
        for blk, pool in zip(self.blocks, caches):
            x, pool = blk.paged_prefill_chunk(x, pool, page_ids, offsets,
                                              page_rows, pos, chunked)
            new_caches.append(pool)
        return self.ln_f(x), new_caches

    def paged_verify_chunk(self, window, starts, win_lengths, caches,
                           page_rows):
        """Speculative-decoding verify: score EVERY position of a
        [B, W] token window sitting at absolute positions starts[b] ..
        starts[b] + win_lengths[b] - 1 against the paged cache, through
        the same gathered-prefix chunk-attention path chunked prefill
        uses (starts >= 1 for any live slot, so every window re-attends
        the slot's whole cached prefix plus itself causally). K/V for
        the window tokens is written into the slot's pages as a side
        effect — rejection rollback is the caller's length edit; stale
        rows past the accepted prefix are simply overwritten later.
        Returns (hidden [B, W, H], new_caches); the caller applies
        verify_head per position, keeping sampling temporaries at
        [B, V] — never a dense [B, W, V] lattice."""
        return self._paged_chunk_hidden(window, starts, win_lengths,
                                        caches, page_rows)

    def verify_head(self, hidden_row):
        """Vocab logits for ONE window position's hidden states
        [B, H] -> [B, V] (the weight-tied head, applied per position by
        the speculative verify step)."""
        return nn.tied_vocab_head(self.tok_emb, hidden_row[:, None])[:, 0]

    def generate(self, prompt, max_new, temperature=0.0, key=None,
                 cache_dtype=jnp.float32):
        """Greedy (temperature=0) or sampled generation. prompt: [B, Tp].
        Returns [B, Tp + max_new] (prompt prefix included).

        cache_dtype: KV-cache storage dtype. At serving batch sizes the
        padded cache reads dominate per-token HBM traffic (each decode
        step streams the whole [B, H, Tmax, hd] x 2 x layers cache), so
        bf16 halves the decode bandwidth bill for ~3 decimal digits on
        stored keys/values."""
        from jax import lax

        from paddle_tpu.core.enforce import enforce
        enforce(temperature <= 0.0 or key is not None,
                "sampled generation (temperature > 0) requires a PRNG key")
        b, tp = prompt.shape
        total = tp + max_new
        assert total <= self.cfg.max_position, (total,
                                                self.cfg.max_position)
        caches = self.init_caches(b, total, dtype=cache_dtype)

        # batched prefill: ONE causal forward over the whole prompt fills
        # every layer's cache (vs Tp sequential decode_steps — the
        # prefill/decode split every serving stack uses)
        x = (self.tok_emb(prompt)
             + self.pos_emb(jnp.arange(tp)[None, :]))
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, cache = blk.prefill(x, cache, start=0)
            new_caches.append(cache)
        caches = new_caches
        last_logits = nn.tied_vocab_head(self.tok_emb,
                                         self.ln_f(x[:, -1:, :]))

        def sample(logits, k):
            if temperature <= 0.0:
                return jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            return jax.random.categorical(
                k, logits[:, 0] / temperature, -1).astype(jnp.int32)

        keys = (jax.random.split(key, max_new) if key is not None
                else jnp.zeros((max_new, 2), jnp.uint32))

        def step(carry, inp):
            caches, last_logits = carry
            t, k = inp
            tok = sample(last_logits, k)[:, None]        # [B, 1]
            logits, caches = _gpt_decode_step(self, tok, caches, tp + t)
            return (caches, logits), tok[:, 0]

        (_, _), new_toks = lax.scan(
            step, (caches, last_logits), (jnp.arange(max_new), keys))
        return jnp.concatenate([prompt, new_toks.T.astype(prompt.dtype)], 1)
