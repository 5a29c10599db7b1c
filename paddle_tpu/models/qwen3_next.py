"""Qwen3-Next: a hybrid decoder language model whose layers are Gated
DeltaNet linear attention three in four and gated softmax attention in
the fourth, every one over an expert layer with a gated shared expert,
trained autoregressively.

Architecture (`model_type` `qwen3_next`, Qwen/Qwen3-Next-80B-A3B; the
released modeling code's names): pre-norm residual blocks, every norm
zero-centred — y = x rsqrt(mean(x^2) + eps) (1 + w), w from 0 — and no
biases.  Layer i is full attention where (i + 1) is a multiple of
`full_attention_interval`, else Gated DeltaNet:

    linear_attention   `nn.GatedDeltaNet`: `linear_num_key_heads`
                       query/key heads of `linear_key_head_dim` under
                       `linear_num_value_heads` value heads of
                       `linear_value_head_dim`, a causal convolution of
                       `linear_conv_kernel_dim` taps, a decay that is one
                       scalar a value head and token, a SiLU-gated head
                       norm (sublayer `linear_attn`)
    full_attention     `nn.GatedWindowAttention(gate="element",
                       qk_norm=True, norm_offset=True)`:
                       `num_attention_heads` query heads over
                       `num_key_value_heads` of `head_dim`, the query
                       projection giving each head [query | gate],
                       zero-centred QK norms, rotate-half on the first
                       `partial_rotary_factor` of the head at
                       `rope_theta`, causal (sublayer `self_attn`)

Every layer not in `mlp_only_layers` whose (i + 1) is a multiple of
`decoder_sparse_step` has the expert layer (`nn.RoutedMoE`: softmax over
`num_experts`, the top `num_experts_per_tok` renormalised where
`norm_topk_prob`, dropless SwiGLU experts of `moe_intermediate_size`,
one shared expert of `shared_expert_intermediate_size` times
sigmoid(x w_sg)); the others a SwiGLU FFN of `intermediate_size`.  The
final norm before the untied output head.

One chip's share of an expert-parallel deployment: `experts_held =
(first, count)` gives the routed experts whose weights this model has
(the router keeps its `num_experts` outputs; the shared expert is
whole), and `vocab_size` may be a slice of the published vocabulary.
The multi-token-prediction module the family describes has no key in
the configuration and is not built.

The loss in row chunks, per-layer recomputation and the AdamW step are
`models/joyai_flash.py`'s (`causal_lm_loss`, `_run_layer`,
`train_step_from_loss`: this router has no selection bias, so the step
moves none), the expert layers' counters `models/sdar_moe.py`'s
(`record_moe_stats`).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from .joyai_flash import (_init_attr, _run_layer, causal_lm_loss,
                          train_step_from_loss)
from .kimi_linear import probe_positions  # noqa: F401 - this model's too
from .sdar_moe import record_moe_stats  # noqa: F401 - this model's too

# float32 in the working copy too: the decay's exponent and step
_FLOAT32_LEAVES = (".A_log", ".dt_bias")
# matrices by shape that take no weight decay: the convolution's taps
_NO_DECAY = ("conv1d.weight",)


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120       # dense layers' FFN (none published)
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    hidden_act: str = "silu"
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    rope_scaling: dict | None = None
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512              # the router's width
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    model_type: str = "qwen3_next"
    # not in config.json
    initializer_range: float = 0.02
    experts_held: tuple | None = None   # (first, count); None: all
    recompute: bool = False             # per-layer, under a trace

    def __post_init__(self):
        if self.tie_word_embeddings or self.hidden_act != "silu":
            raise NotImplementedError("qwen3_next: SiLU, an untied head")
        if self.rope_scaling is not None or self.use_sliding_window:
            raise NotImplementedError(
                "no rope scaling and no sliding window, as published")
        if self.shared_expert_intermediate_size \
                % self.moe_intermediate_size:
            raise NotImplementedError(
                "a shared expert that is no multiple of a routed one")
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)

    def kind(self, i: int) -> str:
        """"linear_attention" | "full_attention" for the 0-based layer
        i."""
        return ("full_attention" if (i + 1) % self.full_attention_interval
                == 0 else "linear_attention")

    def is_sparse(self, i: int) -> bool:
        return (i not in self.mlp_only_layers and self.num_experts > 0
                and (i + 1) % self.decoder_sparse_step == 0)

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs: 4 layers (three Gated DeltaNet, one
        full), GDN heads of the kernels' 128 channels (2 value heads over
        1 query/key head), 4 query heads over 2 of 16 in the full one."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 num_hidden_layers=4, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16,
                 linear_num_key_heads=1, linear_num_value_heads=2,
                 moe_intermediate_size=24, shared_expert_intermediate_size=24,
                 num_experts=8, num_experts_per_tok=2)
        d.update(kw)
        return Qwen3NextConfig(**d)


class Qwen3NextDecoderLayer(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig, index: int):
        super().__init__()
        attr = _init_attr(cfg)
        self.kind, self.sparse = cfg.kind(index), cfg.is_sparse(index)
        norm = lambda: nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                  zero_centred=True)
        self.input_layernorm = norm()
        if self.kind == "linear_attention":
            self.linear_attn = nn.GatedDeltaNet(
                cfg.hidden_size, cfg.linear_num_key_heads,
                cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim,
                epsilon=cfg.rms_norm_eps, weight_attr=attr)
        else:
            self.self_attn = nn.GatedWindowAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim,
                rope={"rope_theta": cfg.rope_theta,
                      "partial_rotary_factor": cfg.partial_rotary_factor},
                gate="element", weight_attr=attr, qk_norm=True,
                norm_offset=True, epsilon=cfg.rms_norm_eps)
        self.post_attention_layernorm = norm()
        if self.sparse:
            self.moe = nn.RoutedMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob, weight_attr=attr,
                n_shared_experts=cfg.shared_expert_intermediate_size
                // cfg.moe_intermediate_size, shared_gate=True)
        else:
            self.mlp = nn.GatedFFN(cfg.hidden_size, cfg.intermediate_size,
                                   cfg.hidden_act, weight_attr=attr)

    def forward(self, x, positions):
        """-> (x, the expert layer's (count vector, experts chosen) or
        None)."""
        a = self.input_layernorm(x)
        x = x + (self.linear_attn(a) if self.kind == "linear_attention"
                 else self.self_attn(a, positions))
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h), None
        out, stats, experts = self.moe(h)
        return x + out, (stats, experts)


class Qwen3NextModel(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=_init_attr(cfg))
        self.layers = nn.LayerList([
            Qwen3NextDecoderLayer(cfg, i)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               zero_centred=True)

    def forward(self, input_ids):
        """ids (B, S) -> (hidden (B, S, H) after the final norm, [(count
        vector, experts chosen) of every sparse layer])."""
        positions = np.arange(input_ids.shape[1], dtype=np.int32)
        x = self.embed_tokens(input_ids)
        stats = []
        for layer in self.layers:
            x, st = _run_layer(layer, x, positions, self.config.recompute)
            if st is not None:
                stats.append(st)
        return self.norm(x), stats


class Qwen3NextForCausalLM(nn.Layer):
    """forward(input_ids (B, S)) -> (hidden (B, S, H) after the final
    norm, stats (layers, count + 2), experts (layers, B * S, k)) —
    `layers` the sparse layers in order.  The head is applied where the
    caller needs logits."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.config = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 _init_attr(cfg), False)

    def forward(self, input_ids):
        import jax.numpy as jnp

        from ..fluid.dygraph.tracer import trace_fn

        hidden, stats = self.model(input_ids)
        stack = lambda ts: trace_fn(
            lambda **s: jnp.stack(list(s.values())),
            {f"s{i}": t for i, t in enumerate(ts)})
        return (hidden,) + tuple(
            stack([s[j] for s in stats]) for j in range(2))

    def logits(self, hidden):
        return self.lm_head(hidden)


def fake_batch(cfg: Qwen3NextConfig, batch, seq, seed=0):
    """{"input_ids": (B, S) int32}: the targets are the same sequence
    shifted by one."""
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, cfg.vocab_size, (batch, seq),
                                      dtype=np.int32)}


def build_loss(model: Qwen3NextForCausalLM, bf16=True, probe=0):
    """`loss_fn(params, batch) -> (loss, aux)` over the model's
    functional state: the bfloat16 cast (scope `cast`; `A_log` and
    `dt_bias` stay float32), the forward pass, the next-token
    cross-entropy in row chunks (scope `loss`)."""
    import jax
    import jax.numpy as jnp

    from ..jit import functional_call

    def loss_fn(params, batch):
        if bf16:
            with jax.named_scope("cast"):
                cast = {k: (v.astype(jnp.bfloat16)
                            if v.dtype == jnp.float32
                            and not k.endswith(_FLOAT32_LEAVES) else v)
                        for k, v in params.items()}
        else:
            cast = params
        ids = batch["input_ids"]
        (hidden, stats, experts), _ = functional_call(model, cast, ids)
        seq = ids.shape[1]
        head = cast["lm_head.weight"]
        with jax.named_scope("loss"):
            ce = causal_lm_loss(
                head, hidden, jnp.roll(ids, -1, axis=1), jnp.broadcast_to(
                    jnp.arange(seq)[None, :] < seq - 1, ids.shape))
        aux = {"ce": ce, "moe_stats": stats}
        if probe:
            with jax.named_scope("loss"):
                aux["probe_logits"] = jnp.dot(
                    hidden[:, probe_positions(seq, probe)], head,
                    preferred_element_type=jnp.float32)
            aux["moe_experts"] = experts
        return ce, aux

    return loss_fn


def build_train_step(model: Qwen3NextForCausalLM, weight_decay=0.0,
                     bf16=True, probe=0, take_weights=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW
    (`joyai_flash.train_step_from_loss`).

    Returns (step_fn, state); step_fn(state, batch, lr) -> (state,
    loss, aux), `aux` = {"ce", "moe_stats" (layers, count + 2); where
    `probe`, also "probe_logits" (B, probe, V) at `probe_positions` and
    "moe_experts" (layers, B * S, k)}.  Weight decay on matrices, none
    on norm scales, `A_log`, `dt_bias` or the convolution's taps.
    Per-layer recomputation is the model's `config.recompute`."""
    return train_step_from_loss(
        model, build_loss(model, bf16=bf16, probe=probe), weight_decay,
        take_weights, no_decay=_NO_DECAY)
