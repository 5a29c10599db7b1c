"""Transformer layers (reference: python/paddle/nn/layer/transformer.py —
MultiHeadAttention, TransformerEncoder/DecoderLayer, Transformer).

TPU-native: the attention core routes through
paddle_tpu.ops.pallas.attention (Pallas flash-attention kernel on TPU,
XLA oracle elsewhere); projections are single fused matmuls so XLA can
keep the whole layer on the MXU.  Layout is (batch, seq, d_model)
throughout, (batch, seq, heads, head_dim) inside attention — matching
the reference's 2.x API.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ...fluid.dygraph.tracer import trace_fn, trace_op
from ...fluid.initializer import ConstantInitializer, UniformInitializer
from .. import functional as F
from .activation import GELU, ReLU
from .common import Dropout, Linear
from .container import LayerList
from .layers import Layer
from .norm import LayerNorm, RMSNorm


class MultiHeadAttention(Layer):
    """(reference: nn/layer/transformer.py MultiHeadAttention)."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        h, d = self.num_heads, self.head_dim
        return trace_fn(
            lambda x: x.reshape(x.shape[0], x.shape[1], h, d), {"x": x})

    def gen_cache(self, key, value=None, type=None):
        if type == MultiHeadAttention.StaticCache:
            # cross-attention: precomputed k/v of the (encoder) memory
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None
                                              else key))
            return self.StaticCache(k, v)
        # incremental self-attention: start EMPTY (0-length seq); each
        # forward concatenates the new step's k/v
        from ...fluid.dygraph.varbase import Tensor

        batch = key.shape[0]
        dt = np.asarray(self.k_proj.weight.numpy()).dtype
        empty = np.zeros((batch, 0, self.num_heads, self.head_dim), dt)
        return self.Cache(Tensor(empty), Tensor(np.array(empty)))

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None):
        key = query if key is None else key
        value = key if value is None else value

        q = self._split_heads(self.q_proj(query))
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value))
            if isinstance(cache, self.Cache):
                import jax.numpy as jnp

                k = trace_fn(lambda a, b: jnp.concatenate([a, b], axis=1),
                             {"a": cache.k, "b": k})
                v = trace_fn(lambda a, b: jnp.concatenate([a, b], axis=1),
                             {"a": cache.v, "b": v})
                cache = self.Cache(k, v)

        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            dropout_p=self.dropout if self.training else 0.0,
            training=self.training)
        out = trace_fn(
            lambda x: x.reshape(x.shape[0], x.shape[1], self.embed_dim),
            {"x": out})
        out = self.out_proj(out)
        if cache is not None and not isinstance(cache, self.StaticCache):
            return out, cache
        return out


class GroupedQueryAttention(Layer):
    """Self-attention with fewer key/value heads than query heads,
    rotary positions and (optionally) an RMSNorm of every query and key
    head over head_dim before the rotation — the attention block of
    today's decoder models.  No biases.

    forward(x (B, S, E), positions (B, S) | (S,), attn_mask=None,
    is_causal=False) -> (B, S, E).  Query head j reads key/value head
    j // (num_heads / num_kv_heads); the kernels get the num_kv_heads
    heads as they are (ops/pallas/attention.py).  `attn_mask` is what
    F.scaled_dot_product_attention takes, a `BlockDiffusionMask`
    among it."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim=None,
                 qk_norm=True, rope_theta=10000.0, epsilon=1e-6,
                 weight_attr=None):
        super().__init__()
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim or embed_dim // num_heads
        self.rope_theta = rope_theta
        q_out, kv_out = (n * self.head_dim for n in (num_heads,
                                                     num_kv_heads))
        self.q_proj = Linear(embed_dim, q_out, weight_attr, False)
        self.k_proj = Linear(embed_dim, kv_out, weight_attr, False)
        self.v_proj = Linear(embed_dim, kv_out, weight_attr, False)
        self.out_proj = Linear(q_out, embed_dim, weight_attr, False)
        self.q_norm = RMSNorm(self.head_dim, epsilon) if qk_norm else None
        self.k_norm = RMSNorm(self.head_dim, epsilon) if qk_norm else None

    def forward(self, x, positions, attn_mask=None, is_causal=False):
        d = self.head_dim
        split = lambda y, n: trace_fn(
            lambda y: y.reshape(y.shape[0], y.shape[1], n, d), {"y": y})
        q = split(self.q_proj(x), self.num_heads)
        k = split(self.k_proj(x), self.num_kv_heads)
        v = split(self.v_proj(x), self.num_kv_heads)
        if self.q_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        q, k = F.rotary_embedding(q, k, positions, self.rope_theta)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=is_causal,
            training=self.training)
        out = trace_fn(
            lambda o: o.reshape(o.shape[0], o.shape[1], -1), {"o": out})
        return self.out_proj(out)


class _Float32Linear(Linear):
    """x W with the result left in float32 whatever x's dtype (no
    bias): a narrow projection whose output feeds a transcendental."""

    def forward(self, x):
        return trace_fn(
            lambda x, w: jnp.dot(x, w.astype(x.dtype),
                                 preferred_element_type=jnp.float32),
            {"x": x, "w": self.weight})


class GatedWindowAttention(Layer):
    """Causal self-attention with grouped key/value heads, an optional
    sliding window, rotary positions on all or part of the head, and a
    sigmoid gate per head on the attention output before the output
    projection (Qiu et al. 2025, arXiv:2505.06708: a head-wise gate
    after the scaled dot-product attention) — the attention block of a
    decoder whose layers are windowed or full, each kind with a head
    count and a rotation of its own.  No biases.

        q, k, v = x W_q, x W_k, x W_v     -> H, Hkv, Hkv heads of `head_dim`
        g       = sigmoid(x W_g)          -> H, float32
        o_j     = softmax(RoPE(q_j) RoPE(k_{j // (H / Hkv)})^T
                          / sqrt(head_dim) + mask) v_{j // (H / Hkv)}
        out     = concat_j(g_j o_j) W_o

    `window`: row i sees the keys i - window < j <= i (None: every key
    j <= i).  `rope`: one entry of a config's `rope_parameters` —
    `rope_theta`, `partial_rotary_factor` (the first factor * head_dim
    lanes are rotated, rotate-half within them) and `rope_type`
    "default" or "yarn" (then `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor`: `F.yarn_inv_freq`, the amplitude on cos and
    sin).

    `gate`: "head" (or True) the gate above, one sigmoid a head;
    "element" a gate as wide as the head taken from the query
    projection (Qwen3-Next: W_q gives each head [query | gate], 2 x
    `head_dim`, and out = concat_j(o_j * sigmoid(gate_j)) W_o, the
    sigmoid and the multiply float32); None (or False) no gate.
    `qk_norm`: an RMSNorm of every query and key head over `head_dim`
    before the rotation (sublayers `q_norm`, `k_norm`, scope
    `qk_norm`), `epsilon` its own, zero-centred — a scale (1 + w), w
    from 0 — where `norm_offset`.

    forward(x (B, S, E), positions (B, S) | (S,)) -> (B, S, E); the
    flash kernels read the Hkv heads as they are, a window as a band
    their grids walk (ops/pallas/attention.py).  Scopes beside the
    sublayers': `rope`, `gate` — at `head_dim` 128 on a TPU each one
    Pallas pass over HBM each way (ops/pallas/attn_edge.py:
    `rope_fwd` / `rope_bwd`, `head_gate_fwd` / `head_gate_bwd`), else
    `F.rotary_embedding` and the gate's XLA statement; the element-wise
    gate is always XLA's."""

    def __init__(self, embed_dim, num_heads, num_kv_heads, head_dim,
                 window=None, rope=None, gate=True, weight_attr=None,
                 qk_norm=False, norm_offset=False, epsilon=1e-6):
        super().__init__()
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        gate = {True: "head", False: None}.get(gate, gate)
        if gate not in ("head", "element", None):
            raise ValueError(f"gate {gate!r}: 'head', 'element' or None")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window = head_dim, window
        self._gate = gate
        rope = dict(rope or {})
        kind = rope.get("rope_type", "default")
        self._theta = float(rope.get("rope_theta", 10000.0))
        self._rotary_dim = int(head_dim
                               * rope.get("partial_rotary_factor", 1.0))
        self._inv_freq, self._amplitude = None, 1.0
        if kind == "yarn":
            factor = rope["factor"]
            self._inv_freq = F.yarn_inv_freq(
                self._rotary_dim, self._theta, factor,
                rope["original_max_position_embeddings"],
                rope.get("beta_fast", 32.0), rope.get("beta_slow", 1.0))
            self._amplitude = float(rope.get("attention_factor")
                                    or 0.1 * np.log(factor) + 1.0)
        elif kind != "default":
            raise NotImplementedError(f"rope_type {kind!r}: default or yarn")
        lin = lambda i, o: Linear(i, o, weight_attr, False)
        self.q_proj = lin(embed_dim, num_heads * head_dim
                          * (2 if gate == "element" else 1))
        self.k_proj = lin(embed_dim, num_kv_heads * head_dim)
        self.v_proj = lin(embed_dim, num_kv_heads * head_dim)
        self.g_proj = _Float32Linear(embed_dim, num_heads, weight_attr,
                                     False) if gate == "head" else None
        self.o_proj = lin(num_heads * head_dim, embed_dim)
        self._qk_norm = qk_norm
        if qk_norm:
            self.q_norm, self.k_norm = (
                RMSNorm(head_dim, epsilon, zero_centred=norm_offset)
                for _ in range(2))

    def forward(self, x, positions):
        d = self.head_dim
        split = lambda y, n: trace_fn(
            lambda y: y.reshape(y.shape[0], y.shape[1], n, d), {"y": y})
        if self._gate == "element":
            q, gate = trace_fn(
                lambda y: tuple(jnp.split(y.reshape(
                    y.shape[:2] + (self.num_heads, 2 * d)), 2, axis=-1)),
                {"y": self.q_proj(x)}, multi_out=True)
        else:
            q = split(self.q_proj(x), self.num_heads)
        k = split(self.k_proj(x), self.num_kv_heads)
        v = split(self.v_proj(x), self.num_kv_heads)
        if self._qk_norm:
            with jax.named_scope("qk_norm"):
                q, k = self.q_norm(q), self.k_norm(k)
        with jax.named_scope("rope"):
            if self._inv_freq is not None:
                from ...profiler import stat_add

                stat_add("rope_yarn_total")
            partial = self._rotary_dim != d
            q, k = F.head_rotary_embedding(
                q, k, positions, self._theta,
                rotary_dim=self._rotary_dim if partial else None,
                inv_freq=self._inv_freq, amplitude=self._amplitude)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=self.training,
            window=self.window)
        if self.g_proj is not None:
            g = self.g_proj(x)
            with jax.named_scope("gate"):
                out = F.head_gate(out, g)
        elif self._gate == "element":
            with jax.named_scope("gate"):
                out = trace_fn(
                    lambda o, g: (o.astype(jnp.float32) * jax.nn.sigmoid(
                        g.astype(jnp.float32))).astype(o.dtype),
                    {"o": out, "g": gate})
        out = trace_fn(
            lambda o: o.reshape(o.shape[0], o.shape[1], -1), {"o": out})
        return self.o_proj(out)


class LatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2 §2.1; the V3 family's
    attention block) in its expanded, training form: queries and
    keys/values are projected through low-rank latents, a head's query
    and key are a position-free part of `qk_nope_head_dim` beside a
    rotated part of `qk_rope_head_dim`, and the rotated KEY part is ONE
    head shared by all heads; the value heads have a width of their
    own (`v_head_dim`).  No biases.

        c_q = RMSNorm(x W_qa);  q = c_q W_qb  -> H x (nope ‖ rope)
        [c_kv ‖ k_r] = x W_kva;  c_kv = RMSNorm(c_kv)
        [k_nope ‖ v] = c_kv W_kvb  -> H x (nope ‖ v)
        q = [q_nope ‖ RoPE(q_r)],  k = [k_nope ‖ RoPE(k_r)]
        out = concat_h softmax(q_h k_h^T / sqrt(nope + rope)) v_h  W_o

    `rope_interleave`: rotary pairs are (2i, 2i + 1)
    (F.rotary_embedding).  `q_lora_rank=None`: no query latent, q = x
    W_q directly (sublayer `q_proj`; no `q_a_proj`, `q_a_layernorm`,
    `q_b_proj`).  `use_rope=False`: neither part is rotated (a
    position-free layer beside layers that carry position; the
    "rope" part is then one more shared key head part) and `positions`
    is not read.  The absorbed decode form and its latent cache row
    are not built.

    forward(x (B, S, E), positions (B, S) | (S,), is_causal=True) ->
    (B, S, E); the flash kernels take the (nope + rope)-wide q/k heads
    over the v_head_dim-wide v heads as they are
    (ops/pallas/attention.py)."""

    def __init__(self, embed_dim, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, rope_interleave=True, epsilon=1e-6,
                 weight_attr=None, use_rope=True):
        super().__init__()
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.use_rope = use_rope
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.rope_theta, self.rope_interleave = rope_theta, rope_interleave
        q_out = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
        if q_lora_rank is None:
            self.q_proj = Linear(embed_dim, q_out, weight_attr, False)
        else:
            self.q_a_proj = Linear(embed_dim, q_lora_rank, weight_attr,
                                   False)
            self.q_a_layernorm = RMSNorm(q_lora_rank, epsilon)
            self.q_b_proj = Linear(q_lora_rank, q_out, weight_attr, False)
        self.q_lora_rank = q_lora_rank
        self.kv_a_proj_with_mqa = Linear(
            embed_dim, kv_lora_rank + qk_rope_head_dim, weight_attr, False)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = Linear(
            kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim),
            weight_attr, False)
        self.o_proj = Linear(num_heads * v_head_dim, embed_dim, weight_attr,
                             False)

    def forward(self, x, positions, is_causal=True):
        h, nope, rope, rank = (self.num_heads, self.nope, self.rope,
                               self.kv_lora_rank)
        if self.q_lora_rank is None:
            q = self.q_proj(x)
        else:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        kv_a = self.kv_a_proj_with_mqa(x)
        c_kv, k_r = trace_fn(
            lambda a: (a[..., :rank], a[..., None, rank:]), {"a": kv_a},
            multi_out=True)
        kv = self.kv_b_proj(self.kv_a_layernorm(c_kv))
        q_nope, q_r = trace_fn(
            lambda q: tuple(jnp.split(
                q.reshape(q.shape[:2] + (h, nope + rope)), [nope], axis=-1)),
            {"q": q}, multi_out=True)
        if self.use_rope:
            q_r, k_r = F.rotary_embedding(
                q_r, k_r, positions, self.rope_theta,
                interleaved=self.rope_interleave)

        def assemble(q_nope, q_r, kv, k_r):
            kv = kv.reshape(kv.shape[:2] + (h, nope + self.v_dim))
            k_r = jnp.broadcast_to(k_r, k_r.shape[:2] + (h, rope))
            return (jnp.concatenate([q_nope, q_r], axis=-1),
                    jnp.concatenate([kv[..., :nope], k_r], axis=-1),
                    kv[..., nope:])

        q, k, v = trace_fn(assemble, {"q_nope": q_nope, "q_r": q_r,
                                      "kv": kv, "k_r": k_r}, multi_out=True)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=is_causal, training=self.training)
        out = trace_fn(
            lambda o: o.reshape(o.shape[0], o.shape[1], -1), {"o": out})
        return self.o_proj(out)


class ShortConvSiLU(Layer):
    """SiLU of a depthwise causal convolution of `width` taps along the
    sequence, no bias: x (B, S, C) -> (B, S, C) (F.short_conv1d: shifted
    multiply-adds on the (B, S, C) layout, in XLA).  `weight` (width,
    C), tap `width - 1` on the token itself; drawn from U(-width^-1/2,
    width^-1/2), a depthwise Conv1d's usual default.  In
    `KimiDeltaAttention` it holds the taps only: the layer hands them
    to `F.kda_pre`, which runs the three convolutions with what follows
    them in one pass; `forward` is the convolution on its own."""

    def __init__(self, channels, width=4):
        super().__init__()
        bound = width ** -0.5
        self.weight = self.create_parameter(
            shape=[width, channels],
            default_initializer=UniformInitializer(-bound, bound))

    def forward(self, x):
        return F.short_conv1d(x, self.weight, activation="silu")


class _KDACore(Layer):
    """The gated delta-rule scan, a layer of its own so that all of it —
    the two kernels and the reshapes at their edge — runs under the
    scope its parent names it by: `kda_core` in Kimi Delta Attention,
    `gdn_core` in Gated DeltaNet."""

    def forward(self, q, k, v, g, beta):
        return F.kda_attention(q, k, v, g, beta)


class _GatedHeadNorm(Layer):
    """RMSNorm over each head's channels with a learned scale, times a
    sigmoid gate: (o (B, S, H, D), gate (B, S, H * D)) -> (B, S, H * D)
    (F.kda_post).  `KimiDeltaAttention` reads `weight` and makes that
    call itself, under its scope `kda_post`."""

    def __init__(self, head_dim, epsilon):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[head_dim], default_initializer=ConstantInitializer(1.0))

    def forward(self, o, gate):
        return F.kda_post(o, gate, self.weight, self._epsilon)


class KimiDeltaAttention(Layer):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a gated
    delta-rule linear attention whose decay is a vector per head and
    token.  For token t and head h (dk = dv = `head_dim`):

        q', k', v = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
        q, k   = q' / |q'|, k' / |k'|          per head (rsqrt(sum + 1e-6))
        g_t    = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias)   <= 0
        beta_t = sigmoid(x w_b[h])
        S_t    = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t    = head_dim^-1/2 S_t^T q_t
        out    = [RMSNorm_head(o_t) * sigmoid((x W_ga) W_gb)] W_o

    The convolutions are depthwise and causal (`conv_width` taps);
    W_fa, W_ga project to `head_dim`, W_fb, W_gb back to H * head_dim;
    no biases but `dt_bias`.  `A_log` (H,) starts at log U(1, 16),
    `dt_bias` (H * head_dim,) at the inverse softplus of a log-uniform
    step in [1e-3, 1e-1] (the Mamba-2 / Gated DeltaNet convention);
    both stay float32.  The recurrence runs as the chunked scan of
    ops/pallas/kda.py (scope `kda_core`); all between the projections
    and the scan (the convolutions, SiLU, the unit norms, g) is one
    pass, and all between the scan and `o_proj` another
    (ops/pallas/kda_edge.py; scopes `kda_pre`, `kda_post`).

    forward(x (B, S, E)) -> (B, S, E); causal by construction, and the
    layer carries position itself: it takes none."""

    def __init__(self, embed_dim, num_heads, head_dim=128, conv_width=4,
                 epsilon=1e-5, weight_attr=None):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        width = num_heads * head_dim
        lin = lambda i, o: Linear(i, o, weight_attr, False)
        self.q_proj, self.k_proj, self.v_proj = (
            lin(embed_dim, width) for _ in range(3))
        self.q_conv1d, self.k_conv1d, self.v_conv1d = (
            ShortConvSiLU(width, conv_width) for _ in range(3))
        self.f_a_proj, self.f_b_proj = lin(embed_dim, head_dim), lin(
            head_dim, width)
        self.b_proj = lin(embed_dim, num_heads)
        self.g_a_proj, self.g_b_proj = lin(embed_dim, head_dim), lin(
            head_dim, width)
        self.A_log = self.create_parameter(
            shape=[num_heads], default_initializer=UniformInitializer(1, 16))
        self.A_log._value = jnp.log(self.A_log._value)
        self.dt_bias = self.create_parameter(
            shape=[width], default_initializer=UniformInitializer(
                np.log(1e-3), np.log(1e-1)))
        dt = jnp.exp(self.dt_bias._value)
        self.dt_bias._value = dt + jnp.log(-jnp.expm1(-dt))
        self.kda_core = _KDACore()
        self.o_norm = _GatedHeadNorm(head_dim, epsilon)
        self.o_proj = lin(width, embed_dim)

    def forward(self, x):
        raw = (self.q_proj(x), self.k_proj(x), self.v_proj(x),
               self.f_b_proj(self.f_a_proj(x)))
        with jax.named_scope("kda_pre"):
            q, k, v, g = F.kda_pre(
                *raw, self.q_conv1d.weight, self.k_conv1d.weight,
                self.v_conv1d.weight, self.dt_bias, self.A_log)
        beta = trace_fn(lambda b: jax.nn.sigmoid(b.astype(jnp.float32)),
                        {"b": self.b_proj(x)})
        o = self.kda_core(q, k, v, g, beta)
        gate = self.g_b_proj(self.g_a_proj(x))
        with jax.named_scope("kda_post"):
            o = F.kda_post(o, gate, self.o_norm.weight, self.o_norm._epsilon)
        return self.o_proj(o)


class GatedDeltaNet(Layer):
    """Gated DeltaNet (Yang et al., arXiv:2412.06464) as the Qwen3-Next
    family lays it out: a gated delta-rule linear attention whose decay
    is ONE scalar a value head and token, with `num_v_heads` value heads
    over `num_k_heads` query/key heads (value head h reads key head h //
    (Hv / Hk)).  For token t and value head h (dk, dv the head widths):

        [q~ | k~ | v~ | z] = x W_qkvz          Hk dk, Hk dk, Hv dv, Hv dv
        [b | a]            = x W_ba            Hv, Hv
        q', k', v = SiLU(Conv([q~ | k~ | v~])) depthwise, causal, no bias
        q, k   = q' / |q'|, k' / |k'|          per key head (rsqrt(sum + 1e-6))
        beta_t = sigmoid(b_t[h])
        g_t    = -exp(A_log[h]) softplus(a_t[h] + dt_bias[h])      <= 0
        S_t    = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
        o_t    = dk^-1/2 S_t^T q_t
        out    = [RMSNorm_head(o_t) w * SiLU(z_t)] W_o

    `A_log` (Hv,) starts at log U(0, 16) and `dt_bias` (Hv,) at 1 (the
    released modeling code's), both float32; the head norm's `w` at 1;
    the taps (width, 2 Hk dk + Hv dv) as `ShortConvSiLU`'s.  The work
    before the scan (`F.gdn_pre`, scope `gdn_pre`) is one Pallas pass
    each way over q~, k~, v~, read in place from `in_proj_qkvz`'s output
    (`gdn_pre_fwd` / `gdn_pre_bwd`), beside β and g in XLA; the scan the
    chunked kernels of ops/pallas/kda.py with the decay a head and the
    heads grouped (scope `gdn_core`), the gated head norm the
    `kda_post` pass with SiLU (scope `gdn_post`).  Off the TPU, all of
    `gdn_pre` is the XLA statement.

    forward(x (B, S, E)) -> (B, S, E); causal by construction, and the
    layer carries position itself: it takes none."""

    def __init__(self, embed_dim, num_k_heads, num_v_heads, head_k_dim=128,
                 head_v_dim=128, conv_width=4, epsilon=1e-6,
                 weight_attr=None):
        super().__init__()
        if num_v_heads % num_k_heads:
            raise ValueError(f"{num_v_heads} value heads over {num_k_heads} "
                             "query/key heads")
        self.num_k_heads, self.num_v_heads = num_k_heads, num_v_heads
        key, value = num_k_heads * head_k_dim, num_v_heads * head_v_dim
        lin = lambda i, o: Linear(i, o, weight_attr, False)
        self.in_proj_qkvz = lin(embed_dim, 2 * key + 2 * value)
        self.in_proj_ba = lin(embed_dim, 2 * num_v_heads)
        self.conv1d = ShortConvSiLU(2 * key + value, conv_width)
        self.A_log = self.create_parameter(
            shape=[num_v_heads], default_initializer=UniformInitializer(0, 16))
        self.A_log._value = jnp.log(self.A_log._value)
        self.dt_bias = self.create_parameter(
            shape=[num_v_heads], default_initializer=ConstantInitializer(1.0))
        self.gdn_core = _KDACore()
        self.norm = _GatedHeadNorm(head_v_dim, epsilon)
        self.out_proj = lin(value, embed_dim)

    def forward(self, x):
        qkvz = self.in_proj_qkvz(x)
        ba = self.in_proj_ba(x)
        with jax.named_scope("gdn_pre"):
            q, k, v, g, beta, z = F.gdn_pre(qkvz, ba, self.conv1d.weight,
                                            self.dt_bias, self.A_log,
                                            self.num_k_heads)
        o = self.gdn_core(q, k, v, g, beta)
        with jax.named_scope("gdn_post"):
            o = F.kda_post(o, z, self.norm.weight, self.norm._epsilon,
                           activation="silu")
        return self.out_proj(o)


class GatedFFN(Layer):
    """down(act(gate(x)) * up(x)), no biases: the gated (SwiGLU for
    SiLU) feed-forward block (Shazeer 2020)."""

    def __init__(self, d_model, d_ff, activation="silu", weight_attr=None):
        super().__init__()
        self.gate_proj = Linear(d_model, d_ff, weight_attr, False)
        self.up_proj = Linear(d_model, d_ff, weight_attr, False)
        self.down_proj = Linear(d_ff, d_model, weight_attr, False)
        self.activation = getattr(F, activation)

    def forward(self, x):
        gated = trace_fn(lambda g, u: g * u, {
            "g": self.activation(self.gate_proj(x)), "u": self.up_proj(x)})
        return self.down_proj(gated)


def _dense_ffn_block(layer, x):
    """linear2(dropout(act(linear1(x)))) for encoder AND decoder
    layers — one F.fused_feedforward call when the activation is
    gelu/relu and biases exist; otherwise the layer-by-layer path."""
    # the fused path enters no sublayer: name the block itself, so
    # its device time reads as `<layer>/ffn` on either path
    with jax.named_scope("ffn"):
        if isinstance(layer.activation, GELU):
            act_name = ("gelu_tanh" if layer.activation._approximate
                        else "gelu")
        elif isinstance(layer.activation, ReLU):
            act_name = "relu"
        else:
            act_name = None
        if act_name is not None and layer.linear1.bias is not None \
                and layer.linear2.bias is not None:
            return F.fused_feedforward(
                x, layer.linear1.weight, layer.linear1.bias,
                layer.linear2.weight, layer.linear2.bias,
                activation=act_name, act_dropout=layer.dropout.p,
                training=layer.training)
        return layer.linear2(layer.dropout(layer.activation(
            layer.linear1(x))))


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 moe_experts=None, moe_capacity_factor=1.25):
        super().__init__()
        self._config = (d_model, nhead, dim_feedforward, dropout,
                        activation, attn_dropout, act_dropout,
                        normalize_before, weight_attr, bias_attr,
                        moe_experts, moe_capacity_factor)
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(
            d_model, nhead, dropout=attn_dropout,
            weight_attr=weight_attr, bias_attr=bias_attr)
        if moe_experts:
            # Switch-Transformer layer: the dense FFN becomes a top-1
            # routed expert mixture (nn.SwitchMoE; the reference has no
            # MoE — SURVEY.md §2.9)
            from .common import SwitchMoE

            self.moe = SwitchMoE(d_model, dim_feedforward, moe_experts,
                                 capacity_factor=moe_capacity_factor,
                                 weight_attr=weight_attr)
            self.linear1 = self.linear2 = None
        else:
            self.moe = None
            self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                                  bias_attr)
            self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                                  bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.activation = GELU() if activation == "gelu" else ReLU()

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)

        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        if self.moe is not None:
            # dropped (over-capacity) tokens ride the residual — the
            # standard Switch semantics.  The dense path's activation
            # dropout (inside the FFN at d_ff) is applied at the expert
            # OUTPUT instead: in-expert dropout isn't expressible in
            # the batched dispatch einsums, and Switch's expert dropout
            # regularizes the same signal path
            src = self.dropout(self.moe(src))
        else:
            src = _dense_ffn_block(self, src)
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src, type=MultiHeadAttention.Cache)


def _clone_layer(layer):
    """Fresh instance with the same constructor config: independent
    initialization and unique parameter names (a deepcopy would clone
    both, colliding optimizer state_dict keys)."""
    return type(layer)(*layer._config)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        self.layers = LayerList(
            [encoder_layer] + [_clone_layer(encoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                output = layer(output, src_mask)
            else:
                output, new_cache = layer(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self._config = (d_model, nhead, dim_feedforward, dropout,
                        activation, attn_dropout, act_dropout,
                        normalize_before, weight_attr, bias_attr)
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(
            d_model, nhead, dropout=attn_dropout,
            weight_attr=weight_attr, bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(
            d_model, nhead, dropout=attn_dropout,
            weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                              bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.dropout3 = Dropout(dropout, mode="upscale_in_train")
        self.activation = GELU() if activation == "gelu" else ReLU()

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, incr_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                             cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = _dense_ffn_block(self, tgt)
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incr_cache, cache[1]))

    def gen_cache(self, memory):
        incr = self.self_attn.gen_cache(memory,
                                        type=MultiHeadAttention.Cache)
        static = self.cross_attn.gen_cache(
            memory, memory, type=MultiHeadAttention.StaticCache)
        return incr, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        self.layers = LayerList(
            [decoder_layer] + [_clone_layer(decoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                output = layer(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = layer(output, memory, tgt_mask,
                                          memory_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


class Transformer(Layer):
    """Full encoder-decoder transformer
    (reference: nn/layer/transformer.py Transformer)."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            encoder_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            encoder_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(encoder_layer,
                                              num_encoder_layers,
                                              encoder_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            decoder_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            decoder_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(decoder_layer,
                                              num_decoder_layers,
                                              decoder_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        import jax.numpy as jnp

        from ...fluid.dygraph.varbase import Tensor

        mask = jnp.where(
            jnp.tril(jnp.ones((length, length), jnp.bool_)), 0.0,
            -np.inf).astype(jnp.float32)
        return Tensor(mask)
