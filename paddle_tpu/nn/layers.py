"""Standard layers — counterpart of the reference's dygraph nn modules and
static `fluid.layers` builders.

Ref: /root/reference/python/paddle/fluid/dygraph/nn.py:35-2930 (Conv2D,
Pool2D, FC, BatchNorm, Embedding, GRUUnit, LayerNorm, NCE, PRelu,
BilinearTensorProduct, Conv2DTranspose, SequenceConv, GroupNorm,
SpectralNorm, TreeConv) and python/paddle/fluid/layers/nn.py.
"""

import jax
import jax.numpy as jnp

from paddle_tpu import initializer as I
from paddle_tpu.nn.module import Module
from paddle_tpu.ops import activations as A
from paddle_tpu.ops import nn as F
from paddle_tpu.ops import rnn as R


def _act(name, x):
    if name is None:
        return x
    return getattr(A, name)(x)


def _int8_dot(x, q, scale, rhs_axis=0):
    """x contracted with an int8-resident kernel over x's last axis and
    q's rhs_axis, per-channel scale applied on the output — the one
    mixed-dtype dot all weight-only consumers share (quant.weight_only:
    exact because the scale axis is the non-contracted one)."""
    out = jax.lax.dot_general(
        x, q, (((x.ndim - 1,), (rhs_axis,)), ((), ())),
        preferred_element_type=x.dtype)
    return out * scale.astype(x.dtype)


def matmul(x, w):
    """x @ w with both operands in the WEIGHT's dtype and float32
    accumulation: with bf16-stored weights one bf16 pass of the MXU and
    a float32 result, with float32 weights the ordinary product. What a
    layer whose precision is stated (bf16 operands, f32 accumulation)
    multiplies with, whatever dtype its activations carry."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


class Linear(Module):
    """ref: dygraph/nn.py FC / Linear."""

    def __init__(self, in_features, out_features, bias=True, act=None,
                 weight_init=None, bias_init=None, dtype=jnp.float32):
        super().__init__()
        self.act = act
        self.has_bias = bias
        self.param("weight", (in_features, out_features),
                   weight_init or I.xavier(), dtype)
        if bias:
            self.param("bias", (out_features,), bias_init or I.zeros(), dtype)

    def forward(self, x):
        if self.has_p("weight_q"):
            # weight-only int8 serving (quant.weight_only): the kernel
            # stays int8 in HBM and the mixed-dtype dot reads it directly
            # (1/2 the bf16 bytes, 1/4 of f32)
            out = _int8_dot(x, self.p("weight_q"), self.p("weight_scale"))
        else:
            out = x @ self.p("weight")
        if self.has_bias:
            out = out + self.p("bias")
        return _act(self.act, out)


def fused_ffn(fc1, fc2, x, act="gelu"):
    """The transformer feed-forward ``fc2(act(fc1(x)))`` routed through
    the fused Pallas MLP kernel (ops/pallas/mlp.py) when it applies —
    the [rows, intermediate] activation never reaches HBM. Quantized
    layers (weight-only int8) and layers with their own fused activation
    keep the unfused path: the int8 mixed-dtype dot is its own kernel."""
    if (fc1.has_p("weight_q") or fc2.has_p("weight_q")
            or fc1.act is not None or fc2.act is not None):
        return fc2(_act(act, fc1(x)))
    from paddle_tpu.ops.pallas.mlp import fused_mlp
    return fused_mlp(x, fc1.p("weight"),
                     fc1.p("bias") if fc1.has_bias else None,
                     fc2.p("weight"),
                     fc2.p("bias") if fc2.has_bias else None, act=act)


class Conv2D(Module):
    """ref: dygraph/nn.py Conv2D — weight OIHW (NCHW) or HWIO (NHWC).

    TPU-first: with data_format='NHWC' the weight is stored physically in
    HWIO. This matters: on TPU, NHWC activations + HWIO weights run the conv
    ~3x faster than NCHW/OIHW (measured on v5e — XLA's layout assignment does
    not recover the fast path from NCHW-layouted operands). Initializer fan
    statistics are computed on the OIHW view either way.
    """

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, bias=True, act=None,
                 weight_init=None, dtype=jnp.float32, data_format="NCHW"):
        super().__init__()
        k = (kernel_size, kernel_size) if isinstance(kernel_size, int) \
            else tuple(kernel_size)
        self.stride, self.padding, self.dilation, self.groups = \
            stride, padding, dilation, groups
        self.act = act
        self.has_bias = bias
        self.data_format = data_format
        oihw = (out_channels, in_channels // groups) + k
        w_init = weight_init or I.msra()
        if data_format == "NHWC":
            def hwio_init(key, shape, dtype=jnp.float32, _w=w_init, _s=oihw):
                return jnp.transpose(_w(key, _s, dtype), (2, 3, 1, 0))
            self.param("weight", k + (in_channels // groups, out_channels),
                       hwio_init, dtype)
        else:
            self.param("weight", oihw, w_init, dtype)
        if bias:
            self.param("bias", (out_channels,), I.zeros(), dtype)

    def forward(self, x):
        out = F.conv2d(x, self.p("weight"),
                       self.p("bias") if self.has_bias else None,
                       self.stride, self.padding, self.dilation, self.groups,
                       data_format=self.data_format)
        return _act(self.act, out)


class Conv2DTranspose(Module):
    """ref: dygraph/nn.py Conv2DTranspose — weight [in, out/groups, kh, kw]."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, dilation=1, groups=1, bias=True,
                 act=None, weight_init=None, dtype=jnp.float32):
        super().__init__()
        k = (kernel_size, kernel_size) if isinstance(kernel_size, int) \
            else tuple(kernel_size)
        self.stride, self.padding, self.dilation, self.groups = \
            stride, padding, dilation, groups
        self.output_padding = output_padding
        self.act = act
        self.has_bias = bias
        self.param("weight", (in_channels, out_channels // groups) + k,
                   weight_init or I.xavier(), dtype)
        if bias:
            self.param("bias", (out_channels,), I.zeros(), dtype)

    def forward(self, x):
        out = F.conv2d_transpose(
            x, self.p("weight"), self.p("bias") if self.has_bias else None,
            self.stride, self.padding, self.output_padding, self.dilation,
            self.groups)
        return _act(self.act, out)


class BatchNorm(Module):
    """ref: dygraph/nn.py BatchNorm + operators/batch_norm_op.cc. Running
    stats live in the 'state' collection, updated functionally."""

    def __init__(self, num_channels, momentum=0.9, epsilon=1e-5, act=None,
                 data_format="NCHW", dtype=jnp.float32):
        super().__init__()
        self.momentum, self.epsilon, self.act = momentum, epsilon, act
        self.data_format = data_format
        self.param("scale", (num_channels,), I.ones(), dtype)
        self.param("bias", (num_channels,), I.zeros(), dtype)
        self.state("mean", (num_channels,), I.zeros(), jnp.float32)
        self.state("variance", (num_channels,), I.ones(), jnp.float32)

    def forward(self, x):
        out, new_mean, new_var = F.batch_norm(
            x, self.p("scale"), self.p("bias"), self.s("mean"),
            self.s("variance"), self.epsilon, self.momentum,
            training=self.training, data_format=self.data_format)
        if self.training:
            self.update_state("mean", new_mean)
            self.update_state("variance", new_var)
        return _act(self.act, out)


class SyncBatchNorm(BatchNorm):
    """Cross-replica BN (ref: operators/sync_batch_norm_op.cu + BuildStrategy
    sync_batch_norm pass). Stats are all-reduced over the data-parallel mesh
    axis when running under shard_map/pjit."""

    def __init__(self, num_channels, momentum=0.9, epsilon=1e-5, act=None,
                 axis_name="dp", dtype=jnp.float32):
        super().__init__(num_channels, momentum, epsilon, act, dtype=dtype)
        self.axis_name = axis_name

    def forward(self, x):
        import jax
        if self.training:
            try:
                red = (0, 2, 3)
                m = jnp.mean(x, axis=red)
                m2 = jnp.mean(jnp.square(x), axis=red)
                m = jax.lax.pmean(m, self.axis_name)
                m2 = jax.lax.pmean(m2, self.axis_name)
                v = m2 - jnp.square(m)
            except NameError:  # not under a mapped axis — local BN
                return super().forward(x)
            inv = jax.lax.rsqrt(v + self.epsilon)
            shape = (1, -1, 1, 1)
            out = (x - m.reshape(shape)) * (inv * self.p("scale")).reshape(shape) \
                + self.p("bias").reshape(shape)
            n = x.size // x.shape[1]
            unbiased = v * n / max(n - 1, 1)
            self.update_state("mean", self.momentum * self.s("mean")
                              + (1 - self.momentum) * m)
            self.update_state("variance", self.momentum * self.s("variance")
                              + (1 - self.momentum) * unbiased)
            return _act(self.act, out)
        return super().forward(x)


class LayerNorm(Module):
    """ref: dygraph/nn.py LayerNorm."""

    def __init__(self, normalized_shape, epsilon=1e-5, scale=True, shift=True,
                 dtype=jnp.float32):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.shape = tuple(normalized_shape)
        self.epsilon = epsilon
        self.has_scale, self.has_shift = scale, shift
        n = 1
        for d in self.shape:
            n *= d
        if scale:
            self.param("scale", (n,), I.ones(), dtype)
        if shift:
            self.param("bias", (n,), I.zeros(), dtype)

    def forward(self, x, residual=None):
        """With `residual`, computes ln(x + residual) in one fused HBM
        pass (Pallas add+LN kernel on TPU) — the transformer hot path."""
        begin = x.ndim - len(self.shape)
        scale = self.p("scale") if self.has_scale else None
        bias = self.p("bias") if self.has_shift else None
        if residual is not None:
            from paddle_tpu.ops.pallas.layer_norm import add_layer_norm_fused
            return add_layer_norm_fused(x, residual, scale, bias,
                                        begin_norm_axis=begin,
                                        epsilon=self.epsilon)
        return F.layer_norm(x, scale, bias, begin_norm_axis=begin,
                            epsilon=self.epsilon)


class RMSNorm(Module):
    def __init__(self, dim, epsilon=1e-6, dtype=jnp.float32):
        super().__init__()
        self.epsilon = epsilon
        self.param("scale", (dim,), I.ones(), dtype)

    def forward(self, x):
        return F.rms_norm(x, self.p("scale"), self.epsilon)


class GroupNorm(Module):
    """ref: dygraph/nn.py GroupNorm."""

    def __init__(self, channels, groups=32, epsilon=1e-5, dtype=jnp.float32):
        super().__init__()
        self.groups, self.epsilon = groups, epsilon
        self.param("scale", (channels,), I.ones(), dtype)
        self.param("bias", (channels,), I.zeros(), dtype)

    def forward(self, x):
        return F.group_norm(x, self.p("scale"), self.p("bias"), self.groups,
                            self.epsilon)


class Embedding(Module):
    """ref: dygraph/nn.py Embedding + operators/lookup_table_op.cc."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 weight_init=None, dtype=jnp.float32):
        super().__init__()
        self.padding_idx = padding_idx
        self.param("weight", (num_embeddings, embedding_dim),
                   weight_init or I.normal(0.0, 0.02), dtype)

    def forward(self, ids):
        if self.has_p("weight_q"):
            # weight-only int8 table (per-ROW scale, axis 0): gather the
            # int8 rows from HBM, dequantize the gathered slice only.
            # The scale carries the original table dtype, so a bf16
            # model's activation path stays bf16. ids are normalized ONCE
            # (lookup_table's trailing-1 squeeze) so the row gather and
            # the scale gather can never disagree on indexing.
            idx = (jnp.squeeze(ids, -1)
                   if ids.ndim > 1 and ids.shape[-1] == 1 else ids)
            rows = F.lookup_table(idx, self.p("weight_q"), self.padding_idx)
            s = self.p("weight_scale")
            return rows.astype(s.dtype) * jnp.take(s, idx, axis=0)[..., None]
        return F.lookup_table(ids, self.p("weight"), self.padding_idx)


def tied_vocab_head(emb, x):
    """Weight-tied vocab projection x @ W.T over an Embedding's table
    (BERT/GPT heads). With a weight-only int8 table (quant.weight_only:
    per-row scale) the dot reads the int8 table directly and the row
    scale lands on the logit axis — exact:
    x @ (q*s[:,None]).T == (x @ q.T) * s[None,:]."""
    if emb.has_p("weight_q"):
        return _int8_dot(x, emb.p("weight_q"), emb.p("weight_scale"),
                         rhs_axis=1)
    return x @ emb.p("weight").T


class Dropout(Module):
    """ref: operators/dropout_op.cc; PRNG key from apply(rngs=...)."""

    def __init__(self, rate=0.5, mode="upscale_in_train"):
        super().__init__()
        self.rate, self.mode = rate, mode

    def forward(self, x):
        if not self.training or self.rate == 0.0:
            return F.dropout(x, None, self.rate, training=False,
                             mode=self.mode)
        return F.dropout(x, self.rng("dropout"), self.rate, training=True,
                         mode=self.mode)


class Pool2D(Module):
    """ref: dygraph/nn.py Pool2D."""

    def __init__(self, pool_size=2, pool_type="max", pool_stride=None,
                 pool_padding=0, global_pooling=False):
        super().__init__()
        self.args = (pool_size, pool_type, pool_stride, pool_padding,
                     global_pooling)

    def forward(self, x):
        ps, pt, st, pd, gp = self.args
        return F.pool2d(x, ps, pt, st, pd, global_pooling=gp)


class PRelu(Module):
    """ref: dygraph/nn.py PRelu."""

    def __init__(self, mode="all", channels=None, dtype=jnp.float32):
        super().__init__()
        shape = (1,) if mode == "all" else (channels,)
        self.mode = mode
        self.param("alpha", shape, I.constant(0.25), dtype)

    def forward(self, x):
        a = self.p("alpha")
        if self.mode == "channel":
            a = a.reshape(1, -1, *([1] * (x.ndim - 2)))
        return jnp.where(x >= 0, x, a * x)


class BilinearTensorProduct(Module):
    """ref: dygraph/nn.py BilinearTensorProduct."""

    def __init__(self, in1_features, in2_features, out_features,
                 dtype=jnp.float32):
        super().__init__()
        self.param("weight", (out_features, in1_features, in2_features),
                   I.xavier(), dtype)
        self.param("bias", (out_features,), I.zeros(), dtype)

    def forward(self, x, y):
        out = jnp.einsum("bi,oij,bj->bo", x, self.p("weight"), y)
        return out + self.p("bias")


class SpectralNorm(Module):
    """Spectral normalization of a weight (ref: operators/spectral_norm_op.cc).
    Power-iteration vectors are mutable state."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 dtype=jnp.float32):
        super().__init__()
        self.dim, self.power_iters, self.eps = dim, power_iters, eps
        h = weight_shape[dim]
        w = 1
        for i, d in enumerate(weight_shape):
            if i != dim:
                w *= d
        self.h, self.w = h, w
        self.state("u", (h,), I.normal(0, 1), dtype)
        self.state("v", (w,), I.normal(0, 1), dtype)

    def forward(self, weight):
        from paddle_tpu.ops.tail import spectral_norm as _sn_op
        normed, u, v = _sn_op(weight, self.s("u"), self.s("v"),
                              dim=self.dim, power_iters=self.power_iters,
                              eps=self.eps)
        if self.training:
            # cast back to the declared state dtype: the op promotes u/v to
            # the weight dtype, and a drifting state pytree dtype breaks
            # scan carries / donated buffers (same invariant as Adam slots)
            self.update_state("u", u.astype(self.s("u").dtype))
            self.update_state("v", v.astype(self.s("v").dtype))
        return normed


class LSTM(Module):
    """Multi-layer LSTM (ref: operators/cudnn_lstm_op.cu capabilities)."""

    def __init__(self, input_size, hidden_size, num_layers=1,
                 bidirectional=False, dtype=jnp.float32):
        super().__init__()
        self.hidden_size, self.num_layers = hidden_size, num_layers
        self.bidirectional = bidirectional
        ndir = 2 if bidirectional else 1
        for layer in range(num_layers):
            isz = input_size if layer == 0 else hidden_size * ndir
            for d in range(ndir):
                sfx = f"l{layer}d{d}"
                self.param(f"w_ih_{sfx}", (isz, 4 * hidden_size), I.xavier(), dtype)
                self.param(f"w_hh_{sfx}", (hidden_size, 4 * hidden_size),
                           I.xavier(), dtype)
                self.param(f"b_{sfx}", (4 * hidden_size,), I.zeros(), dtype)

    def forward(self, x, lengths=None):
        b = x.shape[0]
        h0 = jnp.zeros((b, self.hidden_size), x.dtype)
        c0 = jnp.zeros((b, self.hidden_size), x.dtype)
        out = x
        last_h, last_c = [], []
        for layer in range(self.num_layers):
            if self.bidirectional:
                sf, sb = f"l{layer}d0", f"l{layer}d1"
                of, (hf, cf) = R.lstm(out, h0, c0, self.p(f"w_ih_{sf}"),
                                      self.p(f"w_hh_{sf}"), self.p(f"b_{sf}"),
                                      lengths=lengths)
                ob, (hb, cb) = R.lstm(out, h0, c0, self.p(f"w_ih_{sb}"),
                                      self.p(f"w_hh_{sb}"), self.p(f"b_{sb}"),
                                      lengths=lengths, reverse=True)
                out = jnp.concatenate([of, ob], -1)
                last_h += [hf, hb]
                last_c += [cf, cb]
            else:
                s = f"l{layer}d0"
                out, (h, c) = R.lstm(out, h0, c0, self.p(f"w_ih_{s}"),
                                     self.p(f"w_hh_{s}"), self.p(f"b_{s}"),
                                     lengths=lengths)
                last_h.append(h)
                last_c.append(c)
        return out, (jnp.stack(last_h), jnp.stack(last_c))


class GRU(Module):
    """ref: dygraph/nn.py GRUUnit generalized to multi-step (+bidirectional
    like the reference's stacked fwd/bwd gru pattern in book models)."""

    def __init__(self, input_size, hidden_size, num_layers=1,
                 bidirectional=False, dtype=jnp.float32):
        super().__init__()
        self.hidden_size, self.num_layers = hidden_size, num_layers
        self.bidirectional = bidirectional
        ndir = 2 if bidirectional else 1
        for layer in range(num_layers):
            isz = input_size if layer == 0 else hidden_size * ndir
            for d in range(ndir):
                sfx = f"l{layer}d{d}"
                self.param(f"w_ih_{sfx}", (isz, 3 * hidden_size), I.xavier(),
                           dtype)
                self.param(f"w_hh_{sfx}", (hidden_size, 3 * hidden_size),
                           I.xavier(), dtype)
                self.param(f"b_ih_{sfx}", (3 * hidden_size,), I.zeros(), dtype)
                self.param(f"b_hh_{sfx}", (3 * hidden_size,), I.zeros(), dtype)

    def forward(self, x, lengths=None):
        b = x.shape[0]
        h0 = jnp.zeros((b, self.hidden_size), x.dtype)
        out = x
        last = []
        for layer in range(self.num_layers):
            if self.bidirectional:
                sf, sb = f"l{layer}d0", f"l{layer}d1"
                of, hf = R.gru(out, h0, self.p(f"w_ih_{sf}"),
                               self.p(f"w_hh_{sf}"), self.p(f"b_ih_{sf}"),
                               self.p(f"b_hh_{sf}"), lengths=lengths)
                ob, hb = R.gru(out, h0, self.p(f"w_ih_{sb}"),
                               self.p(f"w_hh_{sb}"), self.p(f"b_ih_{sb}"),
                               self.p(f"b_hh_{sb}"), lengths=lengths,
                               reverse=True)
                out = jnp.concatenate([of, ob], -1)
                last += [hf, hb]
            else:
                s = f"l{layer}d0"
                out, h = R.gru(out, h0, self.p(f"w_ih_{s}"),
                               self.p(f"w_hh_{s}"), self.p(f"b_ih_{s}"),
                               self.p(f"b_hh_{s}"), lengths=lengths)
                last.append(h)
        return out, jnp.stack(last)


class MultiHeadAttention(Module):
    """Fused MHA layer (ref: ir/multihead_matmul_fuse_pass.h semantics)."""

    def __init__(self, embed_dim, num_heads, dropout=0.0, bias=True,
                 use_flash=False, dtype=jnp.float32):
        super().__init__()
        self.num_heads, self.dropout_rate = num_heads, dropout
        self.use_flash = use_flash
        self.has_bias = bias
        for n in ("q", "k", "v", "o"):
            self.param(f"w{n}", (embed_dim, embed_dim), I.xavier(), dtype)
            if bias:
                self.param(f"b{n}", (embed_dim,), I.zeros(), dtype)

    def _w(self, n):
        """Projection kernel, dequantized if weight-only int8 (the full
        forward runs once per sequence, so a materialized dequant is
        fine; decode_step keeps the int8-resident mixed-dot path)."""
        if self.has_p(f"w{n}_q"):
            q, s = self.p(f"w{n}_q"), self.p(f"w{n}_scale")
            return q.astype(s.dtype) * s[None, :]
        return self.p(f"w{n}")

    def _project(self, x, n):
        """x @ w{n} (+ bias) over the last axis; consumes int8-resident
        kernels via the shared mixed-dtype dot when weight-only
        quantized."""
        if self.has_p(f"w{n}_q"):
            out = _int8_dot(x, self.p(f"w{n}_q"), self.p(f"w{n}_scale"))
        else:
            out = x @ self.p(f"w{n}")
        if self.has_bias:
            out = out + self.p(f"b{n}")
        return out

    def prefill(self, x, cache, start=0):
        """Batched cache fill: project the WHOLE prompt in one pass,
        write its K/V into the cache at [0, T), and return the causal
        self-attention output — one forward instead of T sequential
        decode_steps (the serving prefill/decode split; no reference
        counterpart: Fluid's decoders re-ran the network per step).
        x: [B, T, E] -> (out [B, T, E], new_cache). Long prompts ride
        the Pallas flash kernel when use_flash is set (O(T) memory,
        like forward)."""
        from jax import lax as _lax
        if start != 0:
            # chunked prefill would need attention over the cached prefix
            # plus a shifted causal mask — not implemented; failing loudly
            # beats silently ignoring the prefix
            raise NotImplementedError(
                "MultiHeadAttention.prefill only supports start=0 "
                "(whole-prompt prefill); decode_step handles the rest")
        b, t, e = x.shape
        hd = e // self.num_heads

        def heads(y):
            return y.reshape(b, t, self.num_heads, hd).transpose(0, 2, 1, 3)

        q = heads(self._project(x, "q"))
        k = heads(self._project(x, "k"))
        v = heads(self._project(x, "v"))
        cache = {
            "k": _lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": _lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
        if self.use_flash:
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention
            ctx = flash_attention(q, k, v, causal=True)
        else:
            from paddle_tpu.ops.attention import \
                scaled_dot_product_attention
            ctx = scaled_dot_product_attention(q, k, v, causal=True)
        out = ctx.transpose(0, 2, 1, 3).reshape(b, t, e)
        return self._project(out, "o"), cache

    def forward(self, x, kv=None, mask=None, causal=False, seq_axis=None):
        from paddle_tpu.ops.attention import multihead_attention
        key = self.rng("dropout") if (self.training and self.dropout_rate > 0) \
            else None
        return multihead_attention(
            x, self._w("q"), self._w("k"), self._w("v"), self._w("o"),
            self.p("bq") if self.has_bias else None,
            self.p("bk") if self.has_bias else None,
            self.p("bv") if self.has_bias else None,
            self.p("bo") if self.has_bias else None,
            num_heads=self.num_heads, mask=mask, causal=causal, kv=kv,
            dropout_rate=self.dropout_rate if self.training else 0.0,
            dropout_key=key, use_flash=self.use_flash, seq_axis=seq_axis)

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        """KV cache for incremental decoding: {k, v} [B, H, Tmax, hd]."""
        e = (self.p("wq_q") if self.has_p("wq_q")
             else self.p("wq")).shape[0]
        hd = e // self.num_heads
        shape = (batch, self.num_heads, max_len, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def decode_step(self, x_t, cache, pos, causal=True):
        """One incremental step: project the new token(s), write K/V into
        the cache at `pos`, attend over positions <= pos. x_t: [B, 1, E];
        pos: scalar int (dynamic ok). Returns (out [B, 1, E], new_cache).

        O(1) projection per step — the full-sequence K/V projections are
        never recomputed (the KV-cache serving pattern; no reference
        counterpart: Fluid decoded via beam_search ops re-running the
        whole decoder per step)."""
        from jax import lax as _lax
        b, one, e = x_t.shape
        hd = e // self.num_heads

        def proj(n):
            return self._project(x_t, n).reshape(
                b, 1, self.num_heads, hd).transpose(
                0, 2, 1, 3)                            # [B, H, 1, hd]

        q = proj("q")
        k_t = proj("k").astype(cache["k"].dtype)
        v_t = proj("v").astype(cache["v"].dtype)
        k = _lax.dynamic_update_slice(cache["k"], k_t, (0, 0, pos, 0))
        v = _lax.dynamic_update_slice(cache["v"], v_t, (0, 0, pos, 0))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (hd ** 0.5)
        if causal:
            valid = jnp.arange(k.shape[2]) <= pos      # [Tmax]
            scores = jnp.where(valid[None, None, None, :], scores, -1e9)
        probs = jnp.exp(scores - jax.nn.logsumexp(
            scores, axis=-1, keepdims=True))
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, 1, e)
        return self._project(ctx, "o"), {"k": k, "v": v}

    # --- paged KV cache (serving fast path; ops/attention.py layout) ---

    def init_page_pool(self, num_pages, page_size, dtype=jnp.float32,
                       kv_dtype=None):
        """This layer's slice of the paged serving cache:
        {"k","v"} [num_pages, page_size, H*hd] (plus per-row
        {"k_scale","v_scale"} for kv_dtype=int8). Reads the embed dim
        from the declaration (ParamSpec), so it works outside apply() —
        the serving engine allocates pools before any forward runs."""
        from paddle_tpu.ops.attention import init_page_pool
        hd = self._params["wq"].shape[0] // self.num_heads
        return init_page_pool(num_pages, self.num_heads, page_size, hd,
                              dtype, kv_dtype=kv_dtype)

    def paged_decode_step(self, x_t, pool, page_table, att_lengths,
                          write_pages, write_offsets):
        """One incremental step against the paged cache. x_t: [S, 1, E]
        (one pending token per slot); page_table: [S, Pmax] int32;
        att_lengths: [S] valid tokens INCLUDING the one written now;
        write_pages/write_offsets: [S] destination of the new K/V
        (out-of-range page id = drop, for inactive slots).
        Returns (out [S, 1, E], new_pool)."""
        from paddle_tpu.ops.attention import (paged_decode_attention,
                                              paged_write)
        s, one, e = x_t.shape
        q = self._project(x_t, "q").reshape(s, self.num_heads, -1)
        pool = paged_write(pool, self._project(x_t, "k").reshape(s, e),
                           self._project(x_t, "v").reshape(s, e),
                           write_pages, write_offsets)
        ctx = paged_decode_attention(q, pool["k"], pool["v"], page_table,
                                     att_lengths,
                                     k_scale=pool.get("k_scale"),
                                     v_scale=pool.get("v_scale"))
        return self._project(ctx.reshape(s, 1, e), "o"), pool

    def _paged_qkv_write(self, x, pool, page_ids, offsets):
        """Project a [B, T, E] window, scatter its K/V token rows to
        (page_ids, offsets) [B, T], and return (q, k, v [B, H, T, hd],
        new_pool)."""
        from paddle_tpu.ops.attention import paged_write
        b, t, e = x.shape

        def heads(y):
            return y.reshape(b, t, self.num_heads, -1).transpose(0, 2, 1, 3)

        k_rows, v_rows = self._project(x, "k"), self._project(x, "v")
        pool = paged_write(
            pool, k_rows.reshape(b * t, e), v_rows.reshape(b * t, e),
            page_ids.reshape(b * t), offsets.reshape(b * t))
        return (heads(self._project(x, "q")), heads(k_rows), heads(v_rows),
                pool)

    def paged_prefill(self, x, pool, page_ids, offsets):
        """Batched prompt fill into pages: one causal forward over the
        (padded) prompt, K/V scattered to (page_ids, offsets) per
        position ([B, T] int32; out-of-range page id drops the write —
        how pad positions are discarded). Returns (out [B, T, E],
        new_pool). Causal masking alone keeps pad-at-the-end garbage out
        of every valid position's context."""
        b, t, e = x.shape
        q, k, v, pool = self._paged_qkv_write(x, pool, page_ids, offsets)
        if self.use_flash:
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention
            ctx = flash_attention(q, k, v, causal=True)
        else:
            from paddle_tpu.ops.attention import \
                scaled_dot_product_attention
            ctx = scaled_dot_product_attention(q, k, v, causal=True)
        out = ctx.transpose(0, 2, 1, 3).reshape(b, t, e)
        return self._project(out, "o"), pool

    def paged_prefill_chunk(self, x, pool, page_ids, offsets, page_rows,
                            q_pos, chunked):
        """Chunked-admission twin of paged_prefill: K/V of this chunk is
        written exactly as there, but a continuation chunk (chunked[b] =
        True, absolute start > 0) must attend over EVERY token its slot
        has cached so far — so its context is recomputed by gathering the
        slot's whole page table (page_rows: [B, Pmax]) and masking keys
        by absolute position (q_pos: [B, T]). First chunks keep the
        in-chunk causal path, selected per request by jnp.where, so
        single-chunk admissions stay bit-exact with paged_prefill.
        Prefill is admission-rate work; the dense [T, Pmax*ps] score
        temporary never appears on the decode hot path."""
        from paddle_tpu.ops.attention import NEG_INF, gather_pages
        b, t, e = x.shape
        hd = e // self.num_heads
        q, k, v, pool = self._paged_qkv_write(x, pool, page_ids, offsets)
        if self.use_flash:
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention
            ctx = flash_attention(q, k, v, causal=True)
        else:
            from paddle_tpu.ops.attention import \
                scaled_dot_product_attention
            ctx = scaled_dot_product_attention(q, k, v, causal=True)
        # full-history path: pool pages were just updated with this
        # chunk, so the gather sees prefix + chunk at absolute positions
        # (int8 pools dequantize the gathered pages through the same
        # per-row scales the decode kernel reads); the pool is
        # token-major, so the gathered pages are [B, Tk, H, hd] as they lie
        kf = gather_pages(pool["k"], page_rows, self.num_heads,
                          pool.get("k_scale"))
        vf = gather_pages(pool["v"], page_rows, self.num_heads,
                          pool.get("v_scale"))
        tk = kf.shape[1]
        scores = jnp.einsum("bhqd,bkhd->bhqk", q.astype(jnp.float32),
                            kf.astype(jnp.float32)) / (hd ** 0.5)
        keep = (jnp.arange(tk)[None, None, None, :]
                <= q_pos[:, None, :, None])
        scores = jnp.where(keep, scores, NEG_INF)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.where(keep, jnp.exp(scores - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        full = jnp.einsum("bhqk,bkhd->bhqd", p, vf.astype(jnp.float32))
        full = jnp.where(l > 0, full / jnp.maximum(l, 1e-30), 0.0)
        ctx = jnp.where(chunked[:, None, None, None],
                        full.astype(ctx.dtype), ctx)
        out = ctx.transpose(0, 2, 1, 3).reshape(b, t, e)
        return self._project(out, "o"), pool


def rotate_half(x, positions, theta):
    """Rotary positions, rotate-half over the whole last dim ``d``:
    ``x * cos + [-x2, x1] * sin`` with ``x = [x1, x2]`` and the angle of
    lane ``i`` and ``i + d/2`` ``positions * theta ** (-2i / d)``.
    x [..., T, heads, d] float32; positions [..., T]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None].astype(jnp.float32) * freqs  # [..., T, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[..., None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


class GroupedQueryAttention(Module):
    """Causal self-attention whose keys and values have a head count of
    their own: ``num_heads`` query heads of ``head_dim`` over
    ``num_kv_heads`` K/V heads, each shared by num_heads / num_kv_heads
    queries (1 = multi-query attention). No biases. The paged pool holds
    the K/V heads as they are, ``[num_pages, page_size, num_kv_heads *
    head_dim]`` (ops/attention.py); no path copies K/V per query head.
    Products are float32 (``matmul`` rounds the projections' operands to
    the weights' dtype).

    Three options, all off by default (then no positional encoding of
    any kind and every key at or before the query is seen):

      * ``qk_norm``: an RMSNorm over each head's ``head_dim`` on q and
        on k (one learned scale each, ``q_norm`` / ``k_norm``), before
        any rotation.
      * ``rope_theta``: rotary positions on q and k (``rotate_half``),
        applied BEFORE a key is cached, so a cached key never moves.
      * ``window``: query at position p sees keys ``p - window + 1 ..
        p``. Served, such a layer keeps NO pages: its K/V is a per-slot
        ring of exactly ``window`` rows (``init_ring``: position p at
        row ``p % window``; softmax does not care for the order of
        keys), read in decode by the paged decode kernel as one page a
        slot with ``min(length, window)`` valid rows, and in a prefill
        chunk beside the chunk's own keys under the window's mask."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim=None,
                 dtype=jnp.float32, qk_norm=False, rope_theta=None,
                 window=None, epsilon=1e-6):
        super().__init__()
        head_dim = head_dim or embed_dim // num_heads
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.rope_theta, self.window = rope_theta, window
        self.param("wq", (embed_dim, num_heads * head_dim), I.xavier(), dtype)
        self.param("wk", (embed_dim, num_kv_heads * head_dim), I.xavier(),
                   dtype)
        self.param("wv", (embed_dim, num_kv_heads * head_dim), I.xavier(),
                   dtype)
        self.param("wo", (num_heads * head_dim, embed_dim), I.xavier(), dtype)
        self.q_norm = self.k_norm = None
        if qk_norm:
            self.q_norm = RMSNorm(head_dim, epsilon)
            self.k_norm = RMSNorm(head_dim, epsilon)

    def _qk(self, x, positions):
        """The projections as the scores see them: q [..., T, H*hd] and
        k [..., T, KVH*hd] float32 of x [..., T, E] at ``positions``
        [..., T], normed per head and rotated where the layer says."""
        q, k = matmul(x, self.p("wq")), matmul(x, self.p("wk"))
        if self.q_norm is None and self.rope_theta is None:
            return q, k

        def shaped(z, norm, heads):
            z = z.reshape(*z.shape[:-1], heads, self.head_dim)
            if norm is not None:
                z = norm(z)
            if self.rope_theta is not None:
                z = rotate_half(z, positions, self.rope_theta)
            return z.reshape(*z.shape[:-2], heads * self.head_dim)
        return (shaped(q, self.q_norm, self.num_heads),
                shaped(k, self.k_norm, self.num_kv_heads))

    def _attend(self, q, k, v, q_pos, k_pos=None, k_valid=None):
        """q [B, T, H*hd]; k, v [B, Tk, KVH, hd]; q_pos [B, T]; key j at
        absolute position ``k_pos[b, j]`` (``j`` where None). Query t
        sees the keys at or before q_pos[b, t], inside the window where
        the layer has one, that ``k_valid`` [B, Tk] allows.
        -> [B, T, H*hd] float32."""
        from paddle_tpu.ops.attention import NEG_INF
        b, t, _ = q.shape
        kvh, hd = self.num_kv_heads, self.head_dim
        q = q.reshape(b, t, kvh, self.num_heads // kvh, hd)
        scores = jnp.einsum("btkgd,bskd->bkgts", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / (hd ** 0.5)
        if k_pos is None:
            k_pos = jnp.arange(k.shape[1])[None]
        k_pos = k_pos[:, None, None, None, :]
        keep = k_pos <= q_pos[:, None, None, :, None]
        if self.window is not None:
            keep &= k_pos > q_pos[:, None, None, :, None] - self.window
        if k_valid is not None:
            keep &= k_valid[:, None, None, None, :]
        scores = jnp.where(keep, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
        return ctx.reshape(b, t, self.num_heads * hd)

    def forward(self, x):
        """Whole sequences, causal. x [B, T, E] -> [B, T, E] float32."""
        b, t, _ = x.shape
        shape = (b, t, self.num_kv_heads, self.head_dim)
        pos = jnp.broadcast_to(jnp.arange(t), (b, t))
        q, k = self._qk(x, pos)
        ctx = self._attend(q, k.reshape(shape),
                           matmul(x, self.p("wv")).reshape(shape), pos)
        return matmul(ctx, self.p("wo"))

    def init_page_pool(self, num_pages, page_size, dtype=jnp.float32,
                       kv_dtype=None):
        from paddle_tpu.ops.attention import init_page_pool
        return init_page_pool(num_pages, self.num_kv_heads, page_size,
                              self.head_dim, dtype, kv_dtype=kv_dtype)

    def paged_decode_step(self, x_t, pool, page_table, att_lengths,
                          write_pages, write_offsets):
        """As MultiHeadAttention.paged_decode_step: x_t [S, 1, E], one
        pending token a slot (at position ``att_lengths - 1`` where it is
        active), its K/V row written first and then read with the slot's
        live pages by the decode kernel."""
        from paddle_tpu.ops.attention import (paged_decode_attention,
                                              paged_write)
        s = x_t.shape[0]
        x_t = x_t.reshape(s, -1)
        q, k = self._qk(x_t, att_lengths - 1)
        pool = paged_write(pool, k, matmul(x_t, self.p("wv")),
                           write_pages, write_offsets)
        ctx = paged_decode_attention(q.reshape(s, self.num_heads, -1),
                                     pool["k"], pool["v"], page_table,
                                     att_lengths,
                                     k_scale=pool.get("k_scale"),
                                     v_scale=pool.get("v_scale"))
        return matmul(ctx.reshape(s, -1), self.p("wo"))[:, None], pool

    def paged_prefill_chunk(self, x, pool, page_ids, offsets, page_rows,
                            q_pos):
        """A prompt chunk against the paged cache: its K/V rows are
        scattered to (page_ids, offsets) [B, T] (an out-of-range page id
        drops a pad position's write), then every query attends the
        slot's whole table (page_rows [B, Pmax]) gathered densely, keys
        masked by absolute position (q_pos [B, T]): a first chunk and a
        continuation are one path, since what a chunk wrote is what it
        reads back. With one K/V head the gather is [B, Pmax*ps, hd]:
        admission-rate work. -> (out [B, T, E] float32, new pool)."""
        from paddle_tpu.ops.attention import gather_pages, paged_write
        b, t, _ = x.shape
        q, k = self._qk(x, q_pos)
        pool = paged_write(
            pool, k.reshape(b * t, -1),
            matmul(x, self.p("wv")).reshape(b * t, -1),
            page_ids.reshape(b * t), offsets.reshape(b * t))
        kf = gather_pages(pool["k"], page_rows, self.num_kv_heads,
                          pool.get("k_scale"))
        vf = gather_pages(pool["v"], page_rows, self.num_kv_heads,
                          pool.get("v_scale"))
        ctx = self._attend(q, kf, vf, q_pos)
        return matmul(ctx, self.p("wo")), pool

    # --- a window layer's K/V: a per-slot ring, no pages ---

    def init_ring(self, num_slots, dtype=jnp.float32):
        """{"k", "v"} [num_slots, window, KVH*hd]: the last ``window``
        positions of every slot, position p at row ``p % window``. It IS
        a page pool of one page a slot (``paged_write`` and the decode
        kernel take it as it lies), and it is never zeroed: which rows
        are live follows from the slot's length."""
        from paddle_tpu.ops.attention import init_page_pool
        return init_page_pool(num_slots, self.num_kv_heads, self.window,
                              self.head_dim, dtype)

    def ring_decode_step(self, x_t, ring, lengths, active):
        """One decode round of a window layer: x_t [S, 1, E], the
        pending token of slot s at position ``lengths[s]``; an active
        slot's (rotated) K/V row goes to ring row ``lengths % window``
        and the query reads the slot's ``min(lengths + 1, window)`` live
        rows; an inactive slot writes nothing and reads nothing.
        -> (out [S, 1, E] float32, new ring)."""
        from paddle_tpu.ops.attention import (paged_decode_attention,
                                              paged_write)
        s = x_t.shape[0]
        x_t = x_t.reshape(s, -1)
        q, k = self._qk(x_t, lengths)
        slot = jnp.arange(s, dtype=jnp.int32)
        ring = paged_write(ring, k, matmul(x_t, self.p("wv")),
                           jnp.where(active, slot, s),
                           lengths % self.window)
        live = jnp.where(active, jnp.minimum(lengths + 1, self.window), 0)
        ctx = paged_decode_attention(q.reshape(s, self.num_heads, -1),
                                     ring["k"], ring["v"], slot[:, None],
                                     live.astype(jnp.int32))
        return matmul(ctx.reshape(s, -1), self.p("wo"))[:, None], ring

    def ring_prefill_chunk(self, x, ring, slots, starts, chunk_lengths):
        """A prompt chunk of a window layer: x [B, T, E] at positions
        ``starts[b] + t`` of slot ``slots[b]``. Every query attends the
        ring's rows (the ``window`` positions before ``starts``; none at
        ``starts == 0``, whatever the slot held before) beside the
        chunk's own real keys under the window's mask; then the chunk's
        last ``window`` real positions are written to their rows.
        -> (out [B, T, E] float32, new ring)."""
        from paddle_tpu.ops.attention import paged_write
        b, t, _ = x.shape
        w, kvh, hd = self.window, self.num_kv_heads, self.head_dim
        rel = jnp.arange(t)
        pos = starts[:, None] + rel[None, :]                    # [B, T]
        q, k = self._qk(x, pos)
        v = matmul(x, self.p("wv"))
        # ring row r holds the last position before ``starts`` that is
        # congruent to r (negative: nothing of this request)
        last = starts[:, None] - 1
        ring_pos = last - (last - jnp.arange(w)[None, :]) % w   # [B, W]
        keys = jnp.concatenate(
            [ring["k"][slots].astype(jnp.float32), k], axis=1)
        vals = jnp.concatenate(
            [ring["v"][slots].astype(jnp.float32), v], axis=1)
        ctx = self._attend(
            q, keys.reshape(b, w + t, kvh, hd),
            vals.reshape(b, w + t, kvh, hd), pos,
            k_pos=jnp.concatenate([ring_pos, pos], axis=1),
            k_valid=jnp.concatenate(
                [ring_pos >= 0, rel[None, :] < chunk_lengths[:, None]],
                axis=1))
        # two positions of one chunk may share a row (a chunk longer
        # than the window): only the last ``window`` real ones write.
        # The last ``n`` positions of each chunk's real ones go over (a
        # negative one, before ``starts``, is dropped), rotated so that
        # the one on ring row 0 comes first: their rows then rise, and
        # rows sharing a tile of the ring are adjacent (paged_write's
        # contract) where the chunk wraps round the ring
        n = min(t, w)
        first = starts + chunk_lengths - n                      # [B]
        wrap = -first % w          # from the first position to row 0
        shift = jnp.where(wrap < n, wrap, 0)
        src = (chunk_lengths - n)[:, None] + (
            jnp.arange(n)[None, :] + shift[:, None]) % n       # [B, n]

        def rotated(y):                                         # [B*n, E]
            return jnp.take_along_axis(
                y, jnp.maximum(src, 0)[:, :, None], axis=1).reshape(b * n,
                                                                    -1)

        ring = paged_write(
            ring, rotated(k), rotated(v),
            jnp.where(src >= 0, slots[:, None], ring["k"].shape[0]
                      ).reshape(b * n),
            ((starts[:, None] + src) % w).reshape(b * n))
        return matmul(ctx, self.p("wo")), ring


class FC(Linear):
    """ref: dygraph/nn.py FC — Linear with num_flatten_dims semantics."""

    def __init__(self, in_features, out_features, num_flatten_dims=1, **kw):
        super().__init__(in_features, out_features, **kw)
        self.num_flatten_dims = num_flatten_dims

    def forward(self, x):
        out = F.fc(x, self.p("weight"),
                   self.p("bias") if self.has_bias else None,
                   num_flatten_dims=self.num_flatten_dims)
        return _act(self.act, out)


class Conv3D(Module):
    """ref: dygraph/nn.py Conv3D — weight OIDHW."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, bias=True, act=None,
                 weight_init=None, dtype=jnp.float32):
        super().__init__()
        k = (kernel_size,) * 3 if isinstance(kernel_size, int) \
            else tuple(kernel_size)
        self.stride, self.padding, self.dilation, self.groups = \
            stride, padding, dilation, groups
        self.act = act
        self.has_bias = bias
        self.param("weight", (out_channels, in_channels // groups) + k,
                   weight_init or I.msra(), dtype)
        if bias:
            self.param("bias", (out_channels,), I.zeros(), dtype)

    def forward(self, x):
        out = F.conv3d(x, self.p("weight"),
                       self.p("bias") if self.has_bias else None,
                       self.stride, self.padding, self.dilation, self.groups)
        return _act(self.act, out)


class GRUUnit(Module):
    """ref: dygraph/nn.py GRUUnit — one GRU step over [B, I] + [B, H];
    origin_mode as in gru_unit_op.h (False default, h' = z*n + (1-z)*h)."""

    def __init__(self, input_size, hidden_size, bias=True,
                 origin_mode=False, dtype=jnp.float32):
        super().__init__()
        self.hidden_size = hidden_size
        self.has_bias = bias
        self.origin_mode = origin_mode
        self.param("w_ih", (input_size, 3 * hidden_size), I.xavier(), dtype)
        self.param("w_hh", (hidden_size, 3 * hidden_size), I.xavier(), dtype)
        if bias:
            self.param("b_ih", (3 * hidden_size,), I.zeros(), dtype)
            self.param("b_hh", (3 * hidden_size,), I.zeros(), dtype)

    def forward(self, x, h):
        return R.gru_cell(x, h, self.p("w_ih"), self.p("w_hh"),
                          self.p("b_ih") if self.has_bias else None,
                          self.p("b_hh") if self.has_bias else None,
                          origin_mode=self.origin_mode)


class NCE(Module):
    """ref: dygraph/nn.py NCE — noise-contrastive estimation head."""

    def __init__(self, dim, num_total_classes, num_neg_samples=10,
                 dtype=jnp.float32):
        super().__init__()
        self.num_total_classes = num_total_classes
        self.num_neg_samples = num_neg_samples
        self.param("weight", (num_total_classes, dim), I.xavier(), dtype)
        self.param("bias", (num_total_classes,), I.zeros(), dtype)

    def forward(self, input, label):
        from paddle_tpu.ops import loss as L_
        key = self.rng("nce")
        return L_.nce_loss(key, input, label, self.p("weight"),
                           self.p("bias"), self.num_total_classes,
                           self.num_neg_samples)


class SequenceConv(Module):
    """ref: dygraph/nn.py SequenceConv — context-window conv over a
    RaggedBatch."""

    def __init__(self, in_dim, out_dim, context_length=3, context_start=-1,
                 bias=True, act=None, dtype=jnp.float32):
        super().__init__()
        self.context_length = context_length
        self.context_start = context_start
        self.act = act
        self.has_bias = bias
        self.param("filter", (context_length * in_dim, out_dim),
                   I.xavier(), dtype)
        if bias:
            self.param("bias", (out_dim,), I.zeros(), dtype)

    def forward(self, rb, max_len=None):
        from paddle_tpu.core.ragged import RaggedBatch
        from paddle_tpu.ops import sequence as S
        out = S.sequence_conv(rb, self.p("filter"), self.context_start,
                              self.context_length,
                              self.p("bias") if self.has_bias else None,
                              max_len=max_len)
        if self.act is not None:
            out = RaggedBatch(_act(self.act, out.values), out.row_lengths)
        return out


class RowConv(Module):
    """ref: dygraph/nn.py RowConv — lookahead conv over a RaggedBatch."""

    def __init__(self, dim, future_context=2, dtype=jnp.float32):
        super().__init__()
        self.param("filter", (future_context + 1, dim), I.xavier(), dtype)

    def forward(self, rb, max_len=None):
        from paddle_tpu.ops import sequence as S
        return S.row_conv(rb, self.p("filter"), max_len=max_len)


class TreeConv(Module):
    """ref: dygraph/nn.py TreeConv — TBCNN over (nodes, edges), with the
    reference's optional [num_filters] bias."""

    def __init__(self, feature_size, output_size, num_filters, max_depth=2,
                 act=None, bias=True, dtype=jnp.float32):
        super().__init__()
        self.max_depth = max_depth
        self.act = act
        self.has_bias = bias
        self.param("filter", (feature_size, 3, output_size, num_filters),
                   I.xavier(), dtype)
        if bias:
            self.param("bias", (num_filters,), I.zeros(), dtype)

    def build_coef(self, edge_set, n_nodes):
        """Host-side tree2col using THIS layer's max_depth — use this so
        the coefficient depth can't drift from the layer config."""
        import numpy as np
        from paddle_tpu.ops.graph import tree_patch_coefficients
        return tree_patch_coefficients(np.asarray(edge_set), n_nodes,
                                       self.max_depth)

    def forward(self, nodes_vector, coef):
        """coef from self.build_coef(edge_set) (host-built)."""
        from paddle_tpu.ops.graph import tree_conv
        out = tree_conv(nodes_vector, coef, self.p("filter"))
        if self.has_bias:
            out = out + self.p("bias")
        return _act(self.act, out)
