"""JoyAI-LLM-Flash: a DeepSeek-V3-style decoder language model trained
autoregressively with its multi-token-prediction module.

Architecture (`model_type` `joyai_llm_flash`, jdopensource
JoyAI-LLM-Flash; the keys and the layer equations are DeepSeek-V3's,
arXiv:2412.19437 §2.1–2.2, the attention DeepSeek-V2's,
arXiv:2405.04434 §2.1): pre-norm decoder layers of multi-head latent
attention (`nn.LatentAttention`: low-rank query and key/value latents,
q/k heads of `qk_nope_head_dim + qk_rope_head_dim` over v heads of
`v_head_dim`, one rotated key head shared by all heads, causal)
followed by a gated dense FFN in the first `first_k_dense_replace`
layers and by the expert layer in the others: a sigmoid router whose
top-k choice takes a selection bias, the k scores renormalised and
times `routed_scaling_factor`, dropless routed experts and a shared
expert every row passes (`nn.RoutedMoE`).  RMSNorm before the untied
output head; no biases.

Multi-token prediction (DeepSeek-V3 §2.2, depth
`num_nextn_predict_layers` = 1).  With h_i the main model's normed
final state at position i and t_i the tokens,

    h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ‖ RMSNorm_h(h_i)]
    h''  = Block_mtp(h')           one more sparse decoder layer
    L    = CE(t_{i+1} | Head(h_i)) + lambda * CE(t_{i+2} | Head(RMSNorm_mtp(h''_i)))

embedding and head shared with the main model.  Every position runs
through the module — the last with a placeholder token (the roll
brings t_0 there) — and the losses leave out the positions that have
no target: causal attention keeps a later position from every earlier
one.

The selection bias is a buffer outside the gradient
(`…moe.e_score_correction_bias`): the train step returns every expert
layer's row count over ALL router outputs and moves the bias by
`b_e <- b_e + gamma * sign(mean - count_e)` (§2.1.2, auxiliary-loss-free
balancing).

One chip's share of an expert-parallel deployment: `experts_held =
(first, count)` gives the routed experts whose weights this model has
(the router keeps its `n_routed_experts` outputs; the shared expert is
whole), and `vocab_size` may be a slice of the published vocabulary.

`build_train_step` is `sdar_moe.build_blockdiff_train_step`'s twin: one
jitted step of forward, backward and AdamW over float32 master weights
with a bfloat16 cast, the state donated.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from ..fluid.initializer import NormalInitializer
from ..fluid.param_attr import ParamAttr

BIAS_LEAF = "e_score_correction_bias"


@dataclasses.dataclass
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168       # the leading dense layers' FFN
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256         # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    num_nextn_predict_layers: int = 1
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 3.2e7
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # not in config.json
    experts_held: tuple | None = None   # (first, count); None: all
    recompute: bool = False             # per-layer, under a trace
    mtp_loss_weight: float = 0.3        # lambda
    bias_update_rate: float = 1e-3      # gamma

    def __post_init__(self):
        if self.attention_bias or self.tie_word_embeddings:
            raise NotImplementedError(
                "joyai_llm_flash has no attention bias and an untied head")
        if self.hidden_act != "silu":
            raise NotImplementedError(self.hidden_act)
        if self.rope_scaling is not None:
            raise NotImplementedError("rope scaling is not built")
        if self.topk_method != "noaux_tc":
            raise NotImplementedError(self.topk_method)
        if self.num_nextn_predict_layers != 1:
            raise NotImplementedError(
                "one multi-token-prediction module, as published")
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)

    def is_sparse(self, i: int) -> bool:
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 moe_intermediate_size=24, num_hidden_layers=3,
                 num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 n_routed_experts=8, num_experts_per_tok=2)
        d.update(kw)
        return JoyAIFlashConfig(**d)


def _init_attr(cfg):
    return ParamAttr(initializer=NormalInitializer(
        0.0, cfg.initializer_range))


class JoyAIFlashDecoderLayer(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig, sparse: bool):
        super().__init__()
        attr = _init_attr(cfg)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = nn.LatentAttention(
            cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, rope_theta=cfg.rope_theta,
            rope_interleave=cfg.rope_interleave, epsilon=cfg.rms_norm_eps,
            weight_attr=attr)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.sparse = sparse
        if sparse:
            self.moe = nn.RoutedMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
                weight_attr=attr, scoring=cfg.scoring_func,
                routed_scaling_factor=cfg.routed_scaling_factor,
                selection_bias=True,
                n_shared_experts=cfg.n_shared_experts,
                n_group=cfg.n_group, topk_group=cfg.topk_group)
        else:
            self.mlp = nn.GatedFFN(cfg.hidden_size, cfg.intermediate_size,
                                   cfg.hidden_act, weight_attr=attr)

    def forward(self, x, positions):
        """-> (x, the expert layer's (count vector, experts chosen,
        load over all router outputs) or None)."""
        x = x + self.self_attn(self.input_layernorm(x), positions)
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h), None
        out, stats, experts, load = self.moe(h)
        return x + out, (stats, experts, load)


def _run_layer(layer, x, positions, recompute):
    """`layer(x, positions)`; under a trace and `recompute`, inside
    `jax.checkpoint`: all of a layer is recomputed in the backward pass
    but the expert layer's visit plan and the router's choice
    (parallel/moe.py, "moe_plan"), which the backward pass then
    differentiates as the forward pass made them."""
    import jax

    from ..fluid.dygraph.varbase import Tensor

    if not (recompute and isinstance(x._value, jax.core.Tracer)):
        return layer(x, positions)

    def run(xv):
        out, st = layer(Tensor(xv), positions)
        return out._value, None if st is None else tuple(
            t._value for t in st)

    xv, st = jax.checkpoint(
        run, policy=jax.checkpoint_policies.save_only_these_names(
            "moe_plan"))(x._value)
    return Tensor(xv), None if st is None else tuple(Tensor(t) for t in st)


class JoyAIFlashModel(nn.Layer):
    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=_init_attr(cfg))
        self.layers = nn.LayerList([
            JoyAIFlashDecoderLayer(cfg, cfg.is_sparse(i))
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, positions):
        """ids (B, S), positions (S,) -> (hidden (B, S, H) after the
        final norm, [(count vector, experts chosen, load) of every
        sparse layer])."""
        x = self.embed_tokens(input_ids)
        stats = []
        for layer in self.layers:
            x, st = _run_layer(layer, x, positions, self.config.recompute)
            if st is not None:
                stats.append(st)
        return self.norm(x), stats


class JoyAIFlashMTP(nn.Layer):
    """The multi-token-prediction module: two norms, the 2H -> H
    projection `eh_proj`, one sparse decoder layer, a final norm.  The
    embedding and the head are the main model's."""

    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.config = cfg
        self.enorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.hnorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.eh_proj = nn.Linear(2 * cfg.hidden_size, cfg.hidden_size,
                                 _init_attr(cfg), False)
        self.block = JoyAIFlashDecoderLayer(cfg, sparse=True)
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, next_embeds, hidden, positions):
        """next_embeds (B, S, H): Emb(t_{i+1}) at position i; hidden
        (B, S, H): the main model's h_i -> (h''_i after the module's
        final norm, the block's expert-layer statistics)."""
        import jax.numpy as jnp

        from ..fluid.dygraph.tracer import trace_fn

        # the embedding first: the order of the released modeling code
        both = trace_fn(lambda e, h: jnp.concatenate([e, h], axis=-1),
                        {"e": self.enorm(next_embeds),
                         "h": self.hnorm(hidden)})
        x, st = _run_layer(self.block, self.eh_proj(both), positions,
                           self.config.recompute)
        return self.norm(x), st


class JoyAIFlashForCausalLMWithMTP(nn.Layer):
    """The model with its output head and its MTP module.

    forward(input_ids (B, S)) -> (hidden (B, S, H) of the main model
    after its final norm, mtp hidden (B, S, H) after the module's,
    stats (layers, count + 2), experts (layers, B * S, k), load
    (layers, n_routed_experts)) — `layers` the sparse layers in order,
    the MTP block's last.  The head is applied where the caller needs
    logits (`logits`, `causal_lm_loss`), so that a (B, S, vocabulary)
    array exists only where it is asked for."""

    def __init__(self, cfg: JoyAIFlashConfig):
        super().__init__()
        self.config = cfg
        self.model = JoyAIFlashModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 _init_attr(cfg), False)
        self.mtp = JoyAIFlashMTP(cfg)

    def forward(self, input_ids):
        import jax.numpy as jnp

        from ..fluid.dygraph.tracer import trace_fn

        positions = np.arange(input_ids.shape[1], dtype=np.int32)
        hidden, stats = self.model(input_ids, positions)
        next_ids = trace_fn(lambda a: jnp.roll(a, -1, axis=1),
                            {"a": input_ids})
        mtp_hidden, st = self.mtp(self.model.embed_tokens(next_ids), hidden,
                                  positions)
        stats = stats + [st]
        stack = lambda ts: trace_fn(
            lambda **s: jnp.stack(list(s.values())),
            {f"s{i}": t for i, t in enumerate(ts)})
        return (hidden, mtp_hidden) + tuple(
            stack([s[j] for s in stats]) for j in range(3))

    def logits(self, hidden):
        return self.lm_head(hidden)


def bias_names(params) -> list:
    """The selection-bias leaves of a functional state, in the order
    the model stacks its expert layers' statistics (the main model's
    sparse layers by index, the MTP block's last)."""
    names = [k for k in params if k.endswith("." + BIAS_LEAF)]
    return sorted(names, key=lambda k: (
        not k.startswith("model."),
        int(k.split(".")[2]) if k.startswith("model.") else 0))


def fake_batch(cfg: JoyAIFlashConfig, batch, seq, seed=0):
    """{"input_ids": (B, S) int32}: the targets are the same sequence
    shifted by one and by two."""
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, cfg.vocab_size, (batch, seq),
                                      dtype=np.int32)}


def probe_positions(seq, probe):
    """The `probe` positions whose logits a probing step returns:
    evenly spaced over the positions that have both targets."""
    return np.linspace(0, seq - 3, probe).astype(np.int32)


def causal_lm_loss(head_weight, hidden, labels, valid, row_chunk=2048):
    """Mean cross-entropy of `labels` (B, S) under `hidden` (B, S, H) @
    `head_weight` (H, V) over the positions where `valid` (B, S).  The
    (rows, V) logits exist a chunk of `row_chunk` rows at a time,
    recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    b, s, h = hidden.shape
    rows = b * s
    chunk = min(row_chunk, rows)
    pad = -rows % chunk
    flat = lambda a: jnp.pad(a.reshape((rows,) + a.shape[2:]),
                             ((0, pad),) + ((0, 0),) * (a.ndim - 2))
    weights = flat(valid).astype(jnp.float32)
    xs = (flat(hidden).reshape(-1, chunk, h),
          flat(labels).reshape(-1, chunk), weights.reshape(-1, chunk))

    @jax.checkpoint
    def part(x, labels, w):
        logits = jnp.dot(x, head_weight,
                         preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w)

    total = jax.lax.map(lambda a: part(*a), xs)
    return jnp.sum(total) / jnp.maximum(jnp.sum(weights), 1.0)


def build_loss(model: JoyAIFlashForCausalLMWithMTP, bf16=True, probe=0):
    """`loss_fn(params, batch) -> (loss, aux)` over the model's
    functional state: the bfloat16 cast (scope `cast`), the forward
    pass, both cross-entropies (scope `loss`).  `aux` is
    `build_train_step`'s."""
    import jax
    import jax.numpy as jnp

    from ..jit import functional_call

    lam = model.config.mtp_loss_weight

    def loss_fn(params, batch):
        if bf16:
            with jax.named_scope("cast"):
                cast = {k: (v.astype(jnp.bfloat16)
                            if v.dtype == jnp.float32
                            and not k.endswith(BIAS_LEAF) else v)
                        for k, v in params.items()}
        else:
            cast = params
        ids = batch["input_ids"]
        (hidden, mtp_hidden, stats, experts, load), _ = functional_call(
            model, cast, ids)
        seq = ids.shape[1]
        at = jnp.arange(seq)[None, :]
        head = cast["lm_head.weight"]
        with jax.named_scope("loss"):
            ce = causal_lm_loss(head, hidden, jnp.roll(ids, -1, axis=1),
                                jnp.broadcast_to(at < seq - 1, ids.shape))
            mtp_ce = causal_lm_loss(
                head, mtp_hidden, jnp.roll(ids, -2, axis=1),
                jnp.broadcast_to(at < seq - 2, ids.shape))
            loss = ce + lam * mtp_ce
        aux = {"ce": ce, "mtp_ce": mtp_ce, "moe_stats": stats,
               "moe_load": load}
        if probe:
            pos = probe_positions(seq, probe)
            with jax.named_scope("loss"):
                aux["probe_logits"], aux["mtp_probe_logits"] = (
                    jnp.dot(h[:, pos], head,
                            preferred_element_type=jnp.float32)
                    for h in (hidden, mtp_hidden))
            aux["moe_experts"] = experts
        return loss, aux

    return loss_fn


def record_moe_stats(stats, load, bias_updates=0) -> None:
    """Feeds the `profiler` counters from what a step returned (host
    values, fetched with its loss): the four `moe_*` counters of
    `sdar_moe.record_moe_stats` from the (layers, count + 2) count
    vectors, and from the (layers, n_routed) loads
    `moe_router_rows_total` (rows x k over all router outputs),
    `moe_router_rows_max_total` (the fullest output's rows, summed over
    layers) and `moe_bias_updates_total` (selection-bias vectors the
    step moved)."""
    from ..profiler import stat_add
    from .sdar_moe import record_moe_stats as record_held

    record_held(stats)
    load = np.asarray(load)
    stat_add("moe_router_rows_total", int(load.sum()))
    stat_add("moe_router_rows_max_total", int(load.max(1).sum()))
    stat_add("moe_bias_updates_total", int(bias_updates))


def build_train_step(model: JoyAIFlashForCausalLMWithMTP, weight_decay=0.0,
                     bf16=True, probe=0, take_weights=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW + the
    selection biases' update.

    Returns (step_fn, state) where
      state = {"params", "m", "v", "t"}  (fp32 master + adam moments;
              "params" holds the selection biases too, "m" / "v" do not)
      step_fn(state, batch, lr) -> (state, loss, aux)
    `batch` is `fake_batch`'s; `aux` = {"ce": the next-token
    cross-entropy, "mtp_ce": the MTP module's (the loss is ce + lambda
    mtp_ce), "moe_stats": (layers, count + 2) int32 count vectors of the
    expert layers, "moe_load": (layers, n_routed) int32 rows of every
    router output; where `probe`, also "probe_logits" and
    "mtp_probe_logits": (B, probe, V) at `probe_positions`, and
    "moe_experts": (layers, B * S, k), what each router chose}.
    Per-layer recomputation is the model's `config.recompute`.

    The biases get no gradient and no AdamW: after the backward pass
    each moves by `config.bias_update_rate` against its layer's load
    (`parallel.moe.update_selection_bias`; scope `moe_bias_update`).

    `take_weights` as `sdar_moe.build_blockdiff_train_step`'s."""
    return train_step_from_loss(
        model, build_loss(model, bf16=bf16, probe=probe), weight_decay,
        take_weights)


def train_step_from_loss(model, loss_fn, weight_decay=0.0,
                         take_weights=False, no_decay=()):
    """`build_train_step` for any model of this family and its
    `loss_fn(params, batch) -> (loss, aux)` (`aux["moe_load"]` the
    (layers, n_routed) loads, in `bias_names`' order): the one AdamW +
    selection-bias step the expert models with a sigmoid router share.
    `no_decay`: name endings of matrices that take no weight decay."""
    import jax
    import jax.numpy as jnp

    from ..jit import functional_state
    from ..parallel.moe import update_selection_bias
    from ..profiler import stage

    # master weights (copies unless `take_weights`) and AdamW's moments
    with stage("setup.state_build", "state_build_ms"):
        params0 = {k: v if take_weights else jnp.array(v)
                   for k, v in functional_state(model).items()}
        biases = bias_names(params0)
        moments = lambda: {k: jnp.zeros_like(v)
                           for k, v in params0.items() if k not in biases}
        state = {"params": params0, "m": moments(), "v": moments(),
                 "t": jnp.int32(0)}
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(state, batch, lr_s):
        params = state["params"]
        fixed = {k: params[k] for k in biases}
        t = state["t"] + 1
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn({**p, **fixed}, batch), has_aux=True)(
            {k: v for k, v in params.items() if k not in fixed})
        # keep the dW dots out of the AdamW elementwise fusions (see
        # bert.build_pretrain_step)
        grads = jax.lax.optimization_barrier(grads)
        with jax.named_scope("optimizer"):
            tf = t.astype(jnp.float32)
            new_p, new_m, new_v = {}, {}, {}
            for k, g in grads.items():
                p, g = params[k], g.astype(jnp.float32)
                m = b1 * state["m"][k] + (1 - b1) * g
                v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
                upd = (m / (1 - jnp.power(b1, tf))) / (
                    jnp.sqrt(v / (1 - jnp.power(b2, tf))) + eps)
                if weight_decay and p.ndim > 1 and not k.endswith(
                        no_decay):                  # not on norm scales
                    upd = upd + weight_decay * p
                new_p[k], new_m[k], new_v[k] = p - lr_s * upd, m, v
        with jax.named_scope("moe_bias_update"):
            for i, k in enumerate(biases):
                new_p[k] = update_selection_bias(
                    fixed[k], aux["moe_load"][i],
                    model.config.bias_update_rate)
        return ({"params": new_p, "m": new_m, "v": new_v, "t": t},
                loss, aux)

    return jax.jit(step, donate_argnums=(0,)), state
