"""Laguna: a decoder language model whose layers differ by three
published lists — sliding-window and full causal attention mixed
(`layer_types`), a query head count per layer
(`num_attention_heads_per_layer`) and a dense or an expert FFN
(`mlp_layer_types`) — trained autoregressively.

Architecture (`model_type` `laguna`, poolside Laguna-XS.2): pre-norm
residual blocks, RMSNorm with a learned scale, no biases.  Layer i's
attention (`nn.GatedWindowAttention`) has
`num_attention_heads_per_layer[i]` query heads over
`num_key_value_heads` key/value heads of `head_dim`, a sigmoid gate per
head on the attention output (`gating`), and by `layer_types[i]`

    full_attention      every key j <= i; the rotation of
                        `rope_parameters["full_attention"]` (YaRN on
                        the first `partial_rotary_factor` of the head,
                        its attention factor on cos and sin)
    sliding_attention   the `sliding_window` keys i - window < j <= i;
                        `rope_parameters["sliding_attention"]` (a plain
                        rotation of the whole head).

Its FFN is a gated SiLU FFN of `intermediate_size` where
`mlp_layer_types[i]` is "dense", else the expert layer (`nn.RoutedMoE`:
a softmax router over `num_experts`, the top `num_experts_per_tok`
renormalised and times `moe_routed_scaling_factor`, dropless gated SiLU
experts of `moe_intermediate_size`, and one shared expert of
`shared_expert_intermediate_size` that every row passes, ungated).
RMSNorm before the untied output head.

One chip's share of an expert-parallel deployment: `experts_held =
(first, count)` gives the routed experts whose weights this model has
(the router keeps its `num_experts` outputs; the shared expert is
whole), and `vocab_size` may be a slice of the published vocabulary.

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

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3


def _published_rope():
    return {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}


@dataclasses.dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192       # the dense layers' FFN
    num_hidden_layers: int = 40
    num_attention_heads: int = 48       # published; the list decides
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256              # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool | str = True
    sliding_window: int = 512
    rope_parameters: dict = dataclasses.field(default_factory=_published_rope)
    layer_types: tuple | None = None    # default: full, window x 3, ...
    mlp_layer_types: tuple | None = None    # default: dense, then sparse
    num_attention_heads_per_layer: tuple | None = None  # 48 full, 64 window
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5  # published; rope_parameters decides
    moe_routed_scaling_factor: float = 2.5
    model_type: str = "laguna"
    # not in config.json
    hidden_act: str = "silu"
    router_scoring: str = "softmax"
    norm_topk_prob: bool = True
    initializer_range: float = 0.02
    experts_held: tuple | None = None   # (first, count); None: all
    recompute: bool = False             # per-layer, under a trace

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = (_PERIOD * n)[:n]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = (("dense",) + ("sparse",) * n)[:n]
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                48 if t == "full_attention" else 64
                for t in self.layer_types)
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            value = tuple(getattr(self, name))
            if len(value) < n:
                raise ValueError(f"{name} lists {len(value)} layers of {n}")
            setattr(self, name, value[:n])
        unknown = set(self.layer_types) - set(_PERIOD) | \
            set(self.mlp_layer_types) - {"dense", "sparse"}
        if unknown:
            raise ValueError(f"layer kinds {sorted(unknown)}")
        if self.attention_bias or self.tie_word_embeddings:
            raise NotImplementedError(
                "laguna has no attention bias and an untied head")
        if self.hidden_act != "silu" or self.gating not in (True,
                                                            "per-head"):
            raise NotImplementedError(
                f"{self.hidden_act}, gating {self.gating!r}: SiLU and a "
                "gate per head")
        if self.moe_apply_router_weight_on_input:
            raise NotImplementedError("router weights on the experts' input")
        if self.shared_expert_intermediate_size \
                % self.moe_intermediate_size:
            raise NotImplementedError(
                "a shared expert that is no multiple of a routed one")
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)

    def is_sparse(self, i: int) -> bool:
        return self.mlp_layer_types[i] == "sparse"

    def window(self, i: int):
        return self.sliding_window \
            if self.layer_types[i] == "sliding_attention" else None

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs: 5 layers (full + dense, three
        window layers and a full one with experts), 6 and 8 query heads
        over 2 key/value heads, a window of 8."""
        rope = _published_rope()
        rope["full_attention"].update(original_max_position_embeddings=16,
                                      factor=8, attention_factor=None)
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 num_hidden_layers=5, num_attention_heads=6,
                 num_key_value_heads=2, head_dim=16, num_experts=8,
                 num_experts_per_tok=2, moe_intermediate_size=24,
                 shared_expert_intermediate_size=24, sliding_window=8,
                 rope_parameters=rope,
                 num_attention_heads_per_layer=(6, 8, 8, 8, 6))
        d.update(kw)
        return LagunaConfig(**d)


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__()
        attr = _init_attr(cfg)
        self.sparse = cfg.is_sparse(index)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = nn.GatedWindowAttention(
            cfg.hidden_size, cfg.num_attention_heads_per_layer[index],
            cfg.num_key_value_heads, cfg.head_dim, window=cfg.window(index),
            rope=cfg.rope_parameters[cfg.layer_types[index]],
            gate=bool(cfg.gating), weight_attr=attr)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        if self.sparse:
            self.moe = nn.RoutedMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob, weight_attr=attr,
                scoring=cfg.router_scoring,
                routed_scaling_factor=cfg.moe_routed_scaling_factor,
                n_shared_experts=cfg.shared_expert_intermediate_size
                // cfg.moe_intermediate_size)
        else:
            self.mlp = nn.GatedFFN(cfg.hidden_size, cfg.intermediate_size,
                                   cfg.hidden_act, weight_attr=attr)

    def forward(self, x, positions):
        """-> (x, the expert layer's (count vector, experts chosen) or
        None)."""
        x = x + self.self_attn(self.input_layernorm(x), positions)
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h), None
        out, stats, experts = self.moe(h)
        return x + out, (stats, experts)


class LagunaModel(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=_init_attr(cfg))
        self.layers = nn.LayerList([
            LagunaDecoderLayer(cfg, i)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

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


class LagunaForCausalLM(nn.Layer):
    """forward(input_ids (B, S)) -> (hidden (B, S, H) after the final
    norm, stats (layers, count + 2), experts (layers, B * S, k)) —
    `layers` the sparse layers in order.  The head is applied where the
    caller needs logits."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.config = cfg
        self.model = LagunaModel(cfg)
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


def fake_batch(cfg: LagunaConfig, batch, seq, seed=0):
    """{"input_ids": (B, S) int32}: the targets are the same sequence
    shifted by one."""
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, cfg.vocab_size, (batch, seq),
                                      dtype=np.int32)}


def build_loss(model: LagunaForCausalLM, bf16=True, probe=0):
    """`loss_fn(params, batch) -> (loss, aux)` over the model's
    functional state: the bfloat16 cast (scope `cast`), the forward
    pass, the next-token cross-entropy in row chunks (scope `loss`)."""
    import jax
    import jax.numpy as jnp

    from ..jit import functional_call

    def loss_fn(params, batch):
        if bf16:
            with jax.named_scope("cast"):
                cast = {k: (v.astype(jnp.bfloat16)
                            if v.dtype == jnp.float32 else v)
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


def build_train_step(model: LagunaForCausalLM, weight_decay=0.0, bf16=True,
                     probe=0, take_weights=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW
    (`joyai_flash.train_step_from_loss`).

    Returns (step_fn, state); step_fn(state, batch, lr) -> (state,
    loss, aux), `aux` = {"ce", "moe_stats" (layers, count + 2); where
    `probe`, also "probe_logits" (B, probe, V) at `probe_positions` and
    "moe_experts" (layers, B * S, k)}.  Weight decay on matrices, none
    on norm scales.  Per-layer recomputation is the model's
    `config.recompute`."""
    return train_step_from_loss(
        model, build_loss(model, bf16=bf16, probe=probe), weight_decay,
        take_weights)
