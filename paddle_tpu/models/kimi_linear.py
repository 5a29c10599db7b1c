"""Kimi Linear: a hybrid decoder language model whose layers are of two
kinds by a published list — Kimi Delta Attention (a gated delta-rule
linear attention with a per-channel decay) three layers in four, and
position-free multi-head latent attention in the fourth — over the
DeepSeek-V3-style expert layer, trained autoregressively.

Architecture (`model_type` `kimi_linear`, moonshotai
Kimi-Linear-48B-A3B-Instruct; "Kimi Linear: An Expressive, Efficient
Attention Architecture", arXiv:2510.26692, and the released modeling
code's names): pre-norm residual blocks; layer i (numbered from 1) is
a KDA layer where `linear_attn_config.kda_layers` lists it
(`nn.KimiDeltaAttention`: three short causal convolutions, L2-normalised
q and k, a low-rank decay gate, a per-head sigmoid beta, the
recurrence, a sigmoid-gated per-head RMS norm) and a latent-attention
layer where `full_attn_layers` does (`nn.LatentAttention` with no
query latent and, `mla_use_nope`, no rotation: the KDA layers carry
position).  The first `first_k_dense_replace` layers have a gated
dense FFN, the others the expert layer (`nn.RoutedMoE`: sigmoid
router, top-k of score + selection bias, the k scores renormalised and
times `routed_scaling_factor`, one shared expert).  RMSNorm before the
untied output head; no biases.

The selection bias is a buffer outside the gradient, moved each step
against its layer's load, as in `models/joyai_flash.py`, whose loss in
row chunks, per-layer recomputation, counters and AdamW +
selection-bias step this file imports.

One chip's share of an expert-parallel deployment: `experts_held =
(first, count)` gives the routed experts whose weights this model has
(the router keeps its `num_experts` outputs), and `vocab_size` may be
a slice of the published vocabulary.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from .joyai_flash import (BIAS_LEAF, _init_attr, _run_layer,  # noqa: F401
                          bias_names, causal_lm_loss, record_moe_stats,
                          train_step_from_loss)

# float32 in the working copy too: the decay's exponent and step
_FLOAT32_LEAVES = (BIAS_LEAF, ".A_log", ".dt_bias")
# matrices by shape that take no weight decay: the convolutions' taps
_NO_DECAY = ("conv1d.weight",)


def _published_lists():
    full = [4, 8, 12, 16, 20, 24, 27]
    return {"full_attn_layers": full, "head_dim": 128,
            "kda_layers": [i for i in range(1, 28) if i not in full],
            "num_heads": 32, "short_conv_kernel_size": 4}


@dataclasses.dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216       # the leading dense layers' FFN
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 72                  # published; no layer reads it
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: dict = dataclasses.field(
        default_factory=_published_lists)
    num_experts: int = 256              # the router's width
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_nextn_predict_layers: int = 0
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: dict | None = None
    tie_word_embeddings: bool = False
    model_max_length: int = 1048576
    model_type: str = "kimi_linear"
    initializer_range: float = 0.02
    # not in config.json
    experts_held: tuple | None = None   # (first, count); None: all
    recompute: bool = False             # per-layer, under a trace
    bias_update_rate: float = 1e-3      # gamma

    def __post_init__(self):
        if self.tie_word_embeddings or self.hidden_act != "silu":
            raise NotImplementedError("kimi_linear: SiLU, an untied head")
        if self.rope_scaling is not None or not self.mla_use_nope:
            raise NotImplementedError(
                "the latent layers are position-free, as published")
        if self.num_nextn_predict_layers:
            raise NotImplementedError("no multi-token-prediction module")
        if self.moe_router_activation_func != "sigmoid":
            raise NotImplementedError(self.moe_router_activation_func)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
        kinds = [self.kind(i) for i in range(self.num_hidden_layers)]
        if None in kinds:
            raise ValueError("a layer in neither kda_layers nor "
                             f"full_attn_layers: {kinds.index(None) + 1}")

    def kind(self, i: int):
        """"kda" | "mla" for the 0-based layer i (the lists number from
        1), None where neither list has it."""
        lists = self.linear_attn_config
        return ("kda" if i + 1 in lists["kda_layers"] else
                "mla" if i + 1 in lists["full_attn_layers"] else None)

    def is_sparse(self, i: int) -> bool:
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs: 4 layers (KDA, KDA, latent, KDA),
        KDA heads of the kernels' 128 channels."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 moe_intermediate_size=24, num_hidden_layers=4,
                 num_attention_heads=4, num_key_value_heads=4,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, num_experts=8, num_experts_per_token=2,
                 linear_attn_config={
                     "full_attn_layers": [3], "kda_layers": [1, 2, 4],
                     "head_dim": 128, "num_heads": 2,
                     "short_conv_kernel_size": 4})
        d.update(kw)
        return KimiLinearConfig(**d)


class KimiLinearDecoderLayer(nn.Layer):
    def __init__(self, cfg: KimiLinearConfig, kind: str, sparse: bool):
        super().__init__()
        attr = _init_attr(cfg)
        self.kind, self.sparse = kind, sparse
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if kind == "kda":
            lin = cfg.linear_attn_config
            self.self_attn = nn.KimiDeltaAttention(
                cfg.hidden_size, lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"], epsilon=cfg.rms_norm_eps,
                weight_attr=attr)
        else:
            self.self_attn = nn.LatentAttention(
                cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
                cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim, epsilon=cfg.rms_norm_eps, weight_attr=attr,
                use_rope=not cfg.mla_use_nope)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        if sparse:
            self.moe = nn.RoutedMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_token, held=cfg.experts_held,
                norm_topk_prob=cfg.moe_renormalize, weight_attr=attr,
                scoring=cfg.moe_router_activation_func,
                routed_scaling_factor=cfg.routed_scaling_factor,
                selection_bias=True,
                n_shared_experts=cfg.num_shared_experts,
                n_group=cfg.num_expert_group, topk_group=cfg.topk_group)
        else:
            self.mlp = nn.GatedFFN(cfg.hidden_size, cfg.intermediate_size,
                                   cfg.hidden_act, weight_attr=attr)

    def forward(self, x, positions=None):
        """-> (x, the expert layer's (count vector, experts chosen,
        load over all router outputs) or None).  `positions` is not
        read: neither kind of attention takes any."""
        a = self.input_layernorm(x)
        x = x + (self.self_attn(a) if self.kind == "kda"
                 else self.self_attn(a, None))
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h), None
        out, stats, experts, load = self.moe(h)
        return x + out, (stats, experts, load)


class KimiLinearModel(nn.Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=_init_attr(cfg))
        self.layers = nn.LayerList([
            KimiLinearDecoderLayer(cfg, cfg.kind(i), cfg.is_sparse(i))
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        stats = []
        for layer in self.layers:
            x, st = _run_layer(layer, x, None, self.config.recompute)
            if st is not None:
                stats.append(st)
        return self.norm(x), stats


class KimiLinearForCausalLM(nn.Layer):
    """forward(input_ids (B, S)) -> (hidden (B, S, H) after the final
    norm, stats (layers, count + 2), experts (layers, B * S, k), load
    (layers, num_experts)) — `layers` the sparse layers in order.  The
    head is applied where the caller needs logits."""

    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.config = cfg
        self.model = KimiLinearModel(cfg)
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
            stack([s[j] for s in stats]) for j in range(3))

    def logits(self, hidden):
        return self.lm_head(hidden)


def fake_batch(cfg: KimiLinearConfig, batch, seq, seed=0):
    """{"input_ids": (B, S) int32}: the targets are the same sequence
    shifted by one."""
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, cfg.vocab_size, (batch, seq),
                                      dtype=np.int32)}


def probe_positions(seq, probe):
    """The `probe` positions whose logits a probing step returns:
    evenly spaced over the positions that have a target."""
    return np.linspace(0, seq - 2, probe).astype(np.int32)


def build_loss(model: KimiLinearForCausalLM, bf16=True, probe=0):
    """`loss_fn(params, batch) -> (loss, aux)` over the model's
    functional state: the bfloat16 cast (scope `cast`; the selection
    biases, `A_log` and `dt_bias` stay float32), the forward pass, the
    next-token cross-entropy in row chunks (scope `loss`)."""
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
        (hidden, stats, experts, load), _ = functional_call(model, cast, ids)
        seq = ids.shape[1]
        head = cast["lm_head.weight"]
        with jax.named_scope("loss"):
            ce = causal_lm_loss(
                head, hidden, jnp.roll(ids, -1, axis=1), jnp.broadcast_to(
                    jnp.arange(seq)[None, :] < seq - 1, ids.shape))
        aux = {"ce": ce, "moe_stats": stats, "moe_load": load}
        if probe:
            with jax.named_scope("loss"):
                aux["probe_logits"] = jnp.dot(
                    hidden[:, probe_positions(seq, probe)], head,
                    preferred_element_type=jnp.float32)
            aux["moe_experts"] = experts
        return ce, aux

    return loss_fn


def build_train_step(model: KimiLinearForCausalLM, weight_decay=0.0,
                     bf16=True, probe=0, take_weights=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW + the
    selection biases' update (`joyai_flash.train_step_from_loss`).

    Returns (step_fn, state); step_fn(state, batch, lr) -> (state,
    loss, aux), `aux` = {"ce", "moe_stats" (layers, count + 2),
    "moe_load" (layers, num_experts); where `probe`, also
    "probe_logits" (B, probe, V) at `probe_positions` and "moe_experts"
    (layers, B * S, k)}.  Weight decay on matrices, none on norm
    scales, `A_log`, `dt_bias`, the convolutions' taps or the selection
    biases.  Per-layer recomputation is the model's
    `config.recompute`."""
    return train_step_from_loss(
        model, build_loss(model, bf16=bf16, probe=probe), weight_decay,
        take_weights, no_decay=_NO_DECAY)
