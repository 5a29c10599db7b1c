"""SDAR-MoE: a decoder language model trained by block diffusion.

Architecture (`model_type` `sdar_moe`, JetLM SDAR-30B-A3B-Chat; the
Qwen3-MoE block): pre-norm decoder layers of grouped-query attention
with an RMSNorm of every query and key head and rotary positions,
followed by a dropless top-k routed expert layer (gated SiLU experts,
softmax router, the k weights renormalised); RMSNorm before the untied
output head; no biases.  Layer i is sparse where (i + 1) is a multiple
of `decoder_sparse_step` and i is not in `mlp_only_layers`, else a
gated dense FFN of `intermediate_size`.

Training (SDAR's recipe: BD3-LM, Arriola et al. 2025).  A sequence x_0
of S tokens is cut into blocks of `block_length`; per block a level
t ~ U(0, 1] is drawn and each of its tokens replaced by the mask id
with probability t, giving x_t.  The model runs once on the 2 S rows
`[x_t ‖ x_0]`, both halves at positions 0 … S-1, under
`ops.pallas.attention.BlockDiffusionMask`; the loss is the 1/t-weighted
cross-entropy of x_0 at the masked positions of the noisy half.  The
batch brings x_t, the masked flags and 1/t (`make_blockdiff_batch`), so
a step is a function of its batch.

One chip's share of an expert-parallel deployment: `experts_held =
(first, count)` gives the experts whose weights this model has (the
router keeps its `num_experts` outputs), and `vocab_size` may be a
slice of the published vocabulary — the logits and the loss are over
the slice, whose last id is the mask id.

`build_blockdiff_train_step` is `bert.build_pretrain_step`'s twin: one
jitted step of forward, backward and AdamW over float32 master weights
with a bfloat16 cast, the state donated.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from ..fluid.initializer import NormalInitializer
from ..fluid.param_attr import ParamAttr


@dataclasses.dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    attention_bias: bool = False
    num_experts: int = 128              # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # not in config.json
    experts_held: tuple | None = None   # (first, count); None: all
    block_length: int = 4
    mask_token_id: int | None = None    # default: the last id held
    qk_norm: bool = True
    recompute: bool = False             # per-layer, under a trace

    def __post_init__(self):
        if self.attention_bias or self.tie_word_embeddings:
            raise NotImplementedError(
                "sdar_moe has no attention bias and an untied head")
        if self.hidden_act != "silu":
            raise NotImplementedError(self.hidden_act)
        if self.mask_token_id is None:
            self.mask_token_id = self.vocab_size - 1
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)

    def is_sparse(self, i: int) -> bool:
        return (i not in self.mlp_only_layers
                and (i + 1) % self.decoder_sparse_step == 0)

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs."""
        d = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, num_experts=8,
                 num_experts_per_tok=2, moe_intermediate_size=24)
        d.update(kw)
        return SdarMoeConfig(**d)


def _init_attr(cfg):
    return ParamAttr(initializer=NormalInitializer(
        0.0, cfg.initializer_range))


class SdarMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig, index: int):
        super().__init__()
        attr = _init_attr(cfg)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = nn.GroupedQueryAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, qk_norm=cfg.qk_norm,
            rope_theta=cfg.rope_theta, epsilon=cfg.rms_norm_eps,
            weight_attr=attr)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.sparse = cfg.is_sparse(index)
        if self.sparse:
            self.moe = nn.RoutedMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob, weight_attr=attr)
        else:
            self.mlp = nn.GatedFFN(cfg.hidden_size, cfg.intermediate_size,
                                   cfg.hidden_act, weight_attr=attr)

    def forward(self, x, positions, attn_mask=None):
        """-> (x, the expert layer's (count vector, experts chosen) or
        None)."""
        x = x + self.self_attn(self.input_layernorm(x), positions,
                               attn_mask=attn_mask)
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(h), None
        out, stats, experts = self.moe(h)
        return x + out, (stats, experts)


class SdarMoeModel(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=_init_attr(cfg))
        self.layers = nn.LayerList([
            SdarMoeDecoderLayer(cfg, i)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, positions, attn_mask=None):
        """ids, positions (B, R) -> (hidden (B, R, H) after the final
        norm, [(count vector, experts chosen (B * R, k)) of every sparse
        layer])."""
        import jax

        from ..fluid.dygraph.varbase import Tensor

        x = self.embed_tokens(input_ids)
        stats = []
        for layer in self.layers:
            if self.config.recompute and isinstance(x._value,
                                                    jax.core.Tracer):
                def run(xv, layer=layer):
                    out, st = layer(Tensor(xv), positions,
                                    attn_mask=attn_mask)
                    return out._value, None if st is None else tuple(
                        t._value for t in st)

                # all of a layer is recomputed in the backward pass but
                # the expert layer's visit plan (parallel/moe.py): its
                # sort runs once a layer a step
                xv, st = jax.checkpoint(
                    run, policy=jax.checkpoint_policies
                    .save_only_these_names("moe_plan"))(x._value)
                x = Tensor(xv)
                st = None if st is None else tuple(Tensor(t) for t in st)
            else:
                x, st = layer(x, positions, attn_mask=attn_mask)
            if st is not None:
                stats.append(st)
        return self.norm(x), stats


class SdarMoeForBlockDiffusion(nn.Layer):
    """The model with its output head, on block-diffusion rows.

    forward(noisy_ids (B, S), clean_ids (B, S)) -> (hidden (B, S, H) of
    the NOISY half after the final norm, stats (layers, count + 2),
    experts (layers, B * 2 S, k)): the head is applied where the caller
    needs logits (`logits`), so that a (B, S, vocabulary) array exists
    only where it is asked for."""

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.config = cfg
        self.model = SdarMoeModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 _init_attr(cfg), False)

    def forward(self, noisy_ids, clean_ids):
        import jax.numpy as jnp

        from ..fluid.dygraph.tracer import trace_fn
        from ..ops.pallas.attention import BlockDiffusionMask

        seq = noisy_ids.shape[1]
        ids = trace_fn(lambda a, b: jnp.concatenate([a, b], axis=1),
                       {"a": noisy_ids, "b": clean_ids})
        positions = np.tile(np.arange(seq, dtype=np.int32), 2)
        hidden, stats = self.model(
            ids, positions,
            attn_mask=BlockDiffusionMask(seq, self.config.block_length))
        noisy = trace_fn(lambda h: h[:, :seq], {"h": hidden})
        if not stats:
            return noisy, None, None
        stack = lambda ts: trace_fn(
            lambda **s: jnp.stack(list(s.values())),
            {f"s{i}": t for i, t in enumerate(ts)})
        return (noisy, stack([s for s, _ in stats]),
                stack([e for _, e in stats]))

    def logits(self, hidden):
        return self.lm_head(hidden)


def make_blockdiff_batch(clean_ids, block_length, mask_token_id, rng,
                         t_min=1e-3):
    """The block-diffusion view of `clean_ids` (B, S) int32, on the
    host: per block one t ~ U(0, 1] (clipped to [t_min, 1]), each of
    its tokens masked with probability t (linear schedule).  ->
    {clean_ids, noisy_ids, masked (B, S) bool, inv_t (B, S) float32}."""
    b, s = clean_ids.shape
    blocks = -(-s // block_length)
    t = np.clip(1.0 - rng.random((b, blocks)), t_min, 1.0)
    t = np.repeat(t, block_length, axis=1)[:, :s]
    masked = rng.random((b, s)) < t
    return {
        "clean_ids": clean_ids.astype(np.int32),
        "noisy_ids": np.where(masked, mask_token_id,
                              clean_ids).astype(np.int32),
        "masked": masked,
        "inv_t": (1.0 / t).astype(np.float32),
    }


def fake_batch(cfg: SdarMoeConfig, batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size - 1, (batch, seq), dtype=np.int32)
    return make_blockdiff_batch(ids, cfg.block_length, cfg.mask_token_id,
                                rng)


def blockdiff_loss(head_weight, hidden, batch, row_chunk=2048,
                   probe=0):
    """(loss, mean CE, probe logits): the 1/t-weighted cross-entropy of
    x_0 at the masked positions over their number, the plain mean CE
    there, and the logits of the first `probe` masked positions of each
    sequence (zeros where a sequence has fewer).

    hidden (B, S, H); head_weight (H, V).  The (rows, V) logits exist a
    chunk of `row_chunk` rows at a time, recomputed in the backward
    pass."""
    import jax
    import jax.numpy as jnp

    b, s, h = hidden.shape
    rows = b * s
    chunk = min(row_chunk, rows)
    pad = -rows % chunk
    flat = lambda a: jnp.pad(a.reshape((rows,) + a.shape[2:]),
                             ((0, pad),) + ((0, 0),) * (a.ndim - 2))
    masked = flat(batch["masked"]).astype(jnp.float32)
    xs = (flat(hidden).reshape(-1, chunk, h),
          flat(batch["clean_ids"]).reshape(-1, chunk),
          masked.reshape(-1, chunk),
          (masked * flat(batch["inv_t"])).reshape(-1, chunk))

    @jax.checkpoint
    def part(x, labels, m, w):
        logits = jnp.dot(x, head_weight,
                         preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w), jnp.sum(nll * m)

    weighted, plain = jax.lax.map(lambda a: part(*a), xs)
    n = jnp.maximum(jnp.sum(masked), 1.0)
    out = (jnp.sum(weighted) / n, jnp.sum(plain) / n)
    if not probe:
        return out + (None,)
    # the first `probe` masked positions of every sequence
    m = batch["masked"]
    rank = jnp.cumsum(m, axis=1) - 1
    slot = jnp.where(m & (rank < probe), rank, probe)
    pos = jnp.zeros((b, probe + 1), jnp.int32).at[
        jnp.arange(b)[:, None], slot].set(
        jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)))[:, :probe]
    at = jnp.take_along_axis(hidden, pos[..., None], axis=1)
    return out + (jnp.dot(at, head_weight,
                          preferred_element_type=jnp.float32),)


def probe_positions(masked, probe):
    """Host twin of the probe gather in `blockdiff_loss`: (positions
    (B, probe), valid (B, probe)) for a (B, S) bool array."""
    masked = np.asarray(masked)
    pos = np.zeros((masked.shape[0], probe), np.int64)
    valid = np.zeros((masked.shape[0], probe), bool)
    for i, row in enumerate(masked):
        idx = np.flatnonzero(row)[:probe]
        pos[i, :len(idx)], valid[i, :len(idx)] = idx, True
    return pos, valid


def build_blockdiff_loss(model: SdarMoeForBlockDiffusion, bf16=True,
                         probe=0):
    """`loss_fn(params, batch) -> (loss, aux)` over the model's
    functional state: the bfloat16 cast (scope `cast`), the forward
    pass, the block-diffusion loss (scope `loss`).  `aux` is
    `build_blockdiff_train_step`'s."""
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
        (hidden, stats, experts), _ = functional_call(
            model, cast, batch["noisy_ids"], batch["clean_ids"])
        with jax.named_scope("loss"):
            loss, ce, logits = blockdiff_loss(
                cast["lm_head.weight"], hidden, batch, probe=probe)
        aux = {"ce": ce}
        if stats is not None:
            aux["moe_stats"] = stats
        if logits is not None:
            aux["probe_logits"] = logits
            if experts is not None:
                aux["moe_experts"] = experts
        return loss, aux

    return loss_fn


def record_moe_stats(stats) -> None:
    """Feeds the `profiler` counters from the (layers, count + 2) count
    vectors a step returned (host values, fetched with its loss):
    `moe_rows_routed_total` (rows x k), `moe_rows_held_total` (visits
    that landed on held experts), `moe_expert_rows_max_total` (the
    fullest held expert's rows, summed over layers) and
    `moe_dropped_total` (held visits not computed: stays 0)."""
    from ..profiler import stat_add

    stats = np.asarray(stats)
    held = stats[:, :-2].sum()
    stat_add("moe_rows_routed_total", int(stats[:, -2].sum()))
    stat_add("moe_rows_held_total", int(held))
    stat_add("moe_expert_rows_max_total", int(stats[:, :-2].max(1).sum()))
    stat_add("moe_dropped_total", int(held - stats[:, -1].sum()))


def build_blockdiff_train_step(model: SdarMoeForBlockDiffusion,
                               weight_decay=0.0, bf16=True, probe=0,
                               take_weights=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW.

    Returns (step_fn, state) where
      state = {"params", "m", "v", "t"}  (fp32 master + adam moments)
      step_fn(state, batch, lr) -> (state, loss, aux)
    `batch` is `make_blockdiff_batch`'s; `aux` = {"ce": the plain mean
    cross-entropy at the masked positions, "moe_stats": (layers, count
    + 2) int32 count vectors of the expert layers; where `probe`, also
    "probe_logits": (B, probe, V) and "moe_experts": (layers, B * 2 S,
    k), what each router chose}.  Per-layer recomputation is the
    model's `config.recompute`.

    The jitted step donates its state, so the state holds COPIES of the
    model's weights — unless `take_weights`: the state then takes the
    model's own arrays (a model that fills most of a chip cannot be
    held twice) and the model is left a structure to run through
    `functional_call` with `state["params"]`; its own tensors are gone
    with the first step."""
    import jax
    import jax.numpy as jnp

    from ..jit import functional_state
    from ..profiler import stage

    # master weights (copies unless `take_weights`) and AdamW's moments
    with stage("setup.state_build", "state_build_ms"):
        params0 = {k: v if take_weights else jnp.array(v)
                   for k, v in functional_state(model).items()}
        moments = lambda: {k: jnp.zeros_like(v) for k, v in params0.items()}
        state = {"params": params0, "m": moments(), "v": moments(),
                 "t": jnp.int32(0)}
    loss_fn = build_blockdiff_loss(model, bf16=bf16, probe=probe)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(state, batch, lr_s):
        params = state["params"]
        t = state["t"] + 1
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        # keep the dW dots out of the AdamW elementwise fusions (see
        # bert.build_pretrain_step)
        grads = jax.lax.optimization_barrier(grads)
        with jax.named_scope("optimizer"):
            tf = t.astype(jnp.float32)
            new_p, new_m, new_v = {}, {}, {}
            for k, p in params.items():
                g = grads[k].astype(jnp.float32)
                m = b1 * state["m"][k] + (1 - b1) * g
                v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
                upd = (m / (1 - jnp.power(b1, tf))) / (
                    jnp.sqrt(v / (1 - jnp.power(b2, tf))) + eps)
                if weight_decay and p.ndim > 1:     # not on norm scales
                    upd = upd + weight_decay * p
                new_p[k], new_m[k], new_v[k] = p - lr_s * upd, m, v
        return ({"params": new_p, "m": new_m, "v": new_v, "t": t},
                loss, aux)

    return jax.jit(step, donate_argnums=(0,)), state
