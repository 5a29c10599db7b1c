"""BERT-base pretraining model (the BASELINE.json north-star flagship).

Reference behavior target: PaddleNLP LARK BERT/ERNIE pretraining built on
the reference's nn.TransformerEncoder (python/paddle/nn/layer/transformer.py)
with masked-LM + next-sentence-prediction heads; fused attention is the
reference's operators/fused/multihead_matmul_op.cu path.

TPU-native: the encoder rides paddle_tpu.nn.MultiHeadAttention whose core
is the Pallas flash-attention kernel on TPU; `bert_pretrain_step` builds a
ONE-XLA-computation jitted train step (functional_call + jax.value_and_grad
+ fused adam update) — forward, backward, and optimizer in a single
compiled program, bf16 activations, fp32 master params.
"""

from __future__ import annotations

import numpy as np

from .. import nn
from ..fluid.initializer import (ConstantInitializer,
                                 TruncatedNormalInitializer)
from ..fluid.param_attr import ParamAttr


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, hidden_act="gelu",
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 max_position_embeddings=512, type_vocab_size=2,
                 initializer_range=0.02, moe_experts=0,
                 moe_capacity_factor=1.25, moe_aux_weight=0.01):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.initializer_range = initializer_range
        # 0 = dense FFN; >0 = Switch-MoE FFN in every encoder layer
        self.moe_experts = moe_experts
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_weight = moe_aux_weight

    @staticmethod
    def base(**kw):
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw):
        """For tests / CPU dry runs."""
        d = dict(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, intermediate_size=128,
                 max_position_embeddings=128)
        d.update(kw)
        return BertConfig(**d)


def _init_attr(cfg):
    return ParamAttr(initializer=TruncatedNormalInitializer(
        0.0, cfg.initializer_range))


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=_init_attr(cfg))
        self.position_embeddings = nn.Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
            weight_attr=_init_attr(cfg))
        self.token_type_embeddings = nn.Embedding(
            cfg.type_vocab_size, cfg.hidden_size,
            weight_attr=_init_attr(cfg))
        self.layer_norm = nn.LayerNorm(cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from ..fluid.dygraph.tracer import trace_fn
        import jax.numpy as jnp

        if position_ids is None:
            seq = input_ids.shape[1]
            position_ids = nn.layer.layers.Tensor(
                np.arange(seq, dtype="int64")[None, :])
        if token_type_ids is None:
            token_type_ids = nn.layer.layers.Tensor(
                np.zeros(input_ids.shape, dtype="int64"))
        we = self.word_embeddings(input_ids)
        pe = self.position_embeddings(position_ids)
        te = self.token_type_embeddings(token_type_ids)
        s = trace_fn(lambda a, b, c: a + b + c, {"a": we, "b": pe, "c": te})
        return self.dropout(self.layer_norm(s))


class BertPooler(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.dense = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                               weight_attr=_init_attr(cfg))
        self.activation = nn.Tanh()

    def forward(self, hidden):
        first = hidden[:, 0]
        return self.activation(self.dense(first))


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_attention_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation=cfg.hidden_act,
            attn_dropout=cfg.attention_probs_dropout_prob,
            weight_attr=_init_attr(cfg),
            moe_experts=getattr(cfg, "moe_experts", 0) or None,
            moe_capacity_factor=getattr(cfg, "moe_capacity_factor",
                                        1.25))
        self.encoder = nn.TransformerEncoder(enc_layer,
                                             cfg.num_hidden_layers)
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        emb = self.embeddings(input_ids, token_type_ids, position_ids)
        encoded = self.encoder(emb, attention_mask)
        pooled = self.pooler(encoded)
        return encoded, pooled


class BertPretrainingHeads(nn.Layer):
    """MLM transform + decoder (weight-tied to the word embedding table)
    and NSP classifier."""

    def __init__(self, cfg, embedding_weight):
        super().__init__()
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                   weight_attr=_init_attr(cfg))
        self.activation = nn.GELU() if cfg.hidden_act == "gelu" \
            else nn.ReLU()
        self.layer_norm = nn.LayerNorm(cfg.hidden_size)
        self.decoder_weight = embedding_weight  # tied
        self.decoder_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True,
            default_initializer=ConstantInitializer(0.0))
        self.seq_relationship = nn.Linear(cfg.hidden_size, 2,
                                          weight_attr=_init_attr(cfg))

    def forward(self, encoded, pooled, masked_positions=None):
        from ..fluid.dygraph.tracer import trace_fn
        import jax.numpy as jnp

        x = self.layer_norm(self.activation(self.transform(encoded)))
        if masked_positions is not None:
            # gather only the masked positions: (B, M, H)
            def gather(x, pos):
                return jnp.take_along_axis(
                    x, pos[..., None].astype(jnp.int32), axis=1)

            x = trace_fn(gather, {"x": x, "pos": masked_positions})

        def logits(x, w, b):
            return jnp.dot(x, w.T) + b

        mlm = trace_fn(logits, {"x": x, "w": self.decoder_weight,
                                "b": self.decoder_bias})
        nsp = self.seq_relationship(pooled)
        return mlm, nsp


class BertForPretraining(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.cls = BertPretrainingHeads(
            cfg, self.bert.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        encoded, pooled = self.bert(input_ids, token_type_ids,
                                    attention_mask=attention_mask)
        return self.cls(encoded, pooled, masked_positions)


class BertPretrainingCriterion(nn.Layer):
    def __init__(self, vocab_size):
        super().__init__()
        self.vocab_size = vocab_size

    def forward(self, mlm_logits, nsp_logits, masked_labels, nsp_labels):
        from ..fluid.dygraph.tracer import trace_fn
        import jax
        import jax.numpy as jnp

        def loss(mlm, nsp, mlab, nlab):
            mlm_lp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
            mlm_loss = -jnp.take_along_axis(
                mlm_lp, mlab[..., None].astype(jnp.int32), axis=-1)
            nsp_lp = jax.nn.log_softmax(nsp.astype(jnp.float32), axis=-1)
            nsp_loss = -jnp.take_along_axis(
                nsp_lp, nlab[..., None].astype(jnp.int32), axis=-1)
            return jnp.mean(mlm_loss) + jnp.mean(nsp_loss)

        return trace_fn(loss, {"mlm": mlm_logits, "nsp": nsp_logits,
                               "mlab": masked_labels, "nlab": nsp_labels})


def fake_batch(cfg, batch_size, seq_len, num_masked=20, seed=0):
    rng = np.random.RandomState(seed)
    # realistic variable-length padding mask: real pretraining batches
    # carry one, and the Pallas kernel handles it in-kernel (key bias)
    lens = rng.randint(max(1, seq_len // 2), seq_len + 1, (batch_size,))
    return {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch_size, seq_len)).astype("int64"),
        "attention_mask": (np.arange(seq_len)[None, :]
                           < lens[:, None]).astype("int64"),
        "token_type_ids": rng.randint(0, cfg.type_vocab_size,
                                      (batch_size, seq_len)).astype("int64"),
        "masked_positions": np.sort(
            rng.randint(0, seq_len, (batch_size, num_masked)),
            axis=1).astype("int64"),
        "masked_labels": rng.randint(
            0, cfg.vocab_size, (batch_size, num_masked)).astype("int64"),
        "nsp_labels": rng.randint(0, 2, (batch_size,)).astype("int64"),
    }


def bert_param_spec(name, shape, mp_axis="mp"):
    """Megatron-style tensor-parallel PartitionSpec for a BERT parameter,
    by structured name (the TPU-native answer to the reference's absent
    TP story — SURVEY.md §2.9 'NOT present in the reference').

    Column-parallel: qkv projections + FFN up (shard output dim).
    Row-parallel: attention out_proj + FFN down (shard input dim).
    Embeddings: vocab-sharded.  Everything else replicated; XLA/GSPMD
    inserts the psum/all-gather collectives."""
    from jax.sharding import PartitionSpec as P

    if len(shape) == 2:
        if any(s in name for s in ("q_proj.w", "k_proj.w", "v_proj.w",
                                   "linear1.w")):
            return P(None, mp_axis)
        if any(s in name for s in ("out_proj.w", "linear2.w")):
            return P(mp_axis, None)
        if "word_embeddings" in name:
            return P(mp_axis, None)
    return P()


def build_pretrain_step(model: BertForPretraining,
                        weight_decay=0.01, bf16=True, remat=False,
                        mesh=None, dp_axis="dp", mp_axis=None,
                        sp_axis=None, use_ring_attention=False,
                        use_ulysses=False):
    """One fully-fused XLA train step: fwd + bwd + AdamW.

    Returns (step_fn, state) where
      state = {"params", "m", "v", "t"}  (fp32 master + adam moments)
      step_fn(state, batch, lr) -> (state, loss)

    With `mesh`, the step is pjit-sharded: batch over `dp_axis`, params
    replicated; gradients psum'd by XLA sharding propagation — the
    TPU-native CollectiveOptimizer (SURVEY.md §2.9 #1/#2).
    """
    import jax
    import jax.numpy as jnp

    from ..jit import functional_call, functional_state
    from ..profiler import stage

    if use_ring_attention and model.bert.config.attention_probs_dropout_prob:
        raise ValueError(
            "use_ring_attention requires attention_probs_dropout_prob=0 "
            "(attention dropout is not supported by the ring path yet)")
    if use_ulysses and model.bert.config.attention_probs_dropout_prob:
        raise ValueError(
            "use_ulysses requires attention_probs_dropout_prob=0 "
            "(attention dropout is not supported by the all-to-all "
            "path)")
    if use_ulysses and use_ring_attention:
        raise ValueError("choose ONE of use_ulysses/use_ring_attention")
    criterion = BertPretrainingCriterion(model.bert.config.vocab_size)
    # copy: the jitted step donates state buffers; the model's live
    # weights must not alias them
    with stage("setup.state_build", "state_build_ms"):
        params0 = {k: jnp.array(v)
                   for k, v in functional_state(model).items()}
        moments = lambda: {k: jnp.zeros_like(v) for k, v in params0.items()}
        state = {"params": params0, "m": moments(), "v": moments(),
                 "t": jnp.int32(0)}

    def loss_fn(params, batch, key):
        from ..fluid.dygraph.tracer import rng_key_scope

        if bf16:
            with jax.named_scope("cast"):
                cast = {k: (v.astype(jnp.bfloat16)
                            if v.dtype == jnp.float32 else v)
                        for k, v in params.items()}
        else:
            cast = params

        def fwd(p, b):
            import contextlib

            from ..ops.pallas.attention import (ring_attention_scope,
                                                sharded_attention_scope,
                                                ulysses_attention_scope)

            ring_active = (use_ring_attention and mesh is not None
                           and sp_axis is not None)
            uly_active = (use_ulysses and mesh is not None
                          and sp_axis is not None)
            if ring_active:
                sp_scope = ring_attention_scope(mesh, sp_axis)
            elif uly_active:
                sp_scope = ulysses_attention_scope(mesh, sp_axis)
            elif mesh is not None:
                # GSPMD cannot partition the flash kernels: run them
                # per (dp, mp) shard
                sp_scope = sharded_attention_scope(mesh, dp_axis, mp_axis)
            else:
                sp_scope = contextlib.nullcontext()
            am = b.get("attention_mask")
            if am is not None and not ring_active:
                # (B, S) int -> (B, 1, 1, S) bool; the flash kernel and
                # the ulysses path both take this key-padding form
                am = (am != 0)[:, None, None, :]
            else:
                am = None  # ring path has no mask support yet
            moe_on = getattr(model.bert.config, "moe_experts", 0)
            with rng_key_scope(key), sp_scope:
                if moe_on:
                    # Switch-MoE encoder: the per-layer differentiable
                    # router aux losses are collected INSIDE fwd and
                    # returned as an output, so jax.checkpoint sees
                    # them as values, not escaping side effects
                    from ..nn.layer.common import moe_aux_scope

                    with moe_aux_scope() as aux_items:
                        (mlm, nsp), _ = functional_call(
                            model, p, b["input_ids"],
                            b["token_type_ids"], attention_mask=am,
                            masked_positions=b["masked_positions"])
                    aux = sum(a._value.astype(jnp.float32)
                              for a in list(aux_items))
                    return mlm, nsp, aux
                (mlm, nsp), _ = functional_call(
                    model, p, b["input_ids"], b["token_type_ids"],
                    attention_mask=am,
                    masked_positions=b["masked_positions"])
                return mlm, nsp, jnp.float32(0.0)

        if remat:
            fwd = jax.checkpoint(fwd)
        mlm, nsp, aux = fwd(cast, batch)
        with jax.named_scope("loss"):
            loss = criterion(
                nn.layer.layers.Tensor(mlm), nn.layer.layers.Tensor(nsp),
                nn.layer.layers.Tensor(batch["masked_labels"]),
                nn.layer.layers.Tensor(batch["nsp_labels"]))
        aux_w = getattr(model.bert.config, "moe_aux_weight", 0.01)
        return loss._value + aux_w * aux

    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(state, batch, lr_s):
        params = state["params"]
        t = state["t"] + 1
        key = jax.random.fold_in(jax.random.PRNGKey(20), t)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, key)
        # keep the dW dots out of the AdamW elementwise fusions: without
        # the barrier XLA output-fuses each weight-grad convolution with
        # its f32 optimizer math and the fused conv runs far off MXU
        # peak (profiled round 3)
        grads = jax.lax.optimization_barrier(grads)
        with jax.named_scope("optimizer"):
            tf = t.astype(jnp.float32)
            new_p, new_m, new_v = {}, {}, {}
            for k, p in params.items():
                g = grads[k].astype(jnp.float32)
                m = b1 * state["m"][k] + (1 - b1) * g
                v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
                mhat = m / (1 - jnp.power(b1, tf))
                vhat = v / (1 - jnp.power(b2, tf))
                upd = mhat / (jnp.sqrt(vhat) + eps)
                # no decay on bias/LN; stacked per-expert MoE biases are
                # 2D ([E, d]) but still biases — exempt by name
                is_bias = p.ndim <= 1 or k.endswith((".b1", ".b2"))
                if weight_decay and not is_bias:
                    upd = upd + weight_decay * p
                new_p[k] = p - lr_s * upd
                new_m[k] = m
                new_v[k] = v
        return ({"params": new_p, "m": new_m, "v": new_v, "t": t},
                loss)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        if mp_axis is not None:
            pspec = {k: bert_param_spec(k, v.shape, mp_axis)
                     for k, v in params0.items()}
        else:
            pspec = {k: P() for k in params0}
        pshard = {k: NamedSharding(mesh, s) for k, s in pspec.items()}
        state_shard = {"params": pshard, "m": pshard, "v": pshard,
                       "t": NamedSharding(mesh, P())}
        # batch: data-parallel over dp; optionally shard the sequence
        # dim over sp (per-token work partitions; GSPMD gathers at
        # attention) — the compiler-driven sequence-parallel layout
        seq2 = P(dp_axis, sp_axis) if sp_axis else P(dp_axis)
        batch_shard = {
            "input_ids": NamedSharding(mesh, seq2),
            "attention_mask": NamedSharding(mesh, seq2),
            "token_type_ids": NamedSharding(mesh, seq2),
            "masked_positions": NamedSharding(mesh, P(dp_axis)),
            "masked_labels": NamedSharding(mesh, P(dp_axis)),
            "nsp_labels": NamedSharding(mesh, P(dp_axis)),
        }
        state = jax.device_put(state, state_shard)
        step_fn = jax.jit(
            step,
            in_shardings=(state_shard, batch_shard, None),
            out_shardings=(state_shard, NamedSharding(mesh, P())),
            donate_argnums=(0,))
    else:
        step_fn = jax.jit(step, donate_argnums=(0,))
    return step_fn, state


def build_pipeline_pretrain_step(model: BertForPretraining, mesh,
                                 num_microbatches=4, axis="pp",
                                 learning_rate=1e-3, dp_axis=None,
                                 remat_stages=False):
    """BERT pretraining over a NON-UNIFORM pipeline: embedding stage ->
    n_stages of encoder blocks (params sharded over `axis`) -> pooler+
    heads stage (VERDICT r3 task 9; reference behavior: PipelineTrainer/
    SectionWorker ran sectioned BERT programs, pipeline_trainer.cc:25,
    section_worker.cc:44).

    Dropout must be 0 (the pipelined schedule cannot reproduce the
    non-pipelined dropout mask stream, so parity is only defined
    deterministically).  Returns (step_fn, state); step_fn(state, batch)
    -> (state, loss).  SGD update; the tied word-embedding/MLM-decoder
    table gets the SUM of its first-stage and last-stage gradients —
    megatron-style tied-embedding handling.
    """
    import jax
    import jax.numpy as jnp

    from ..jit import functional_call, functional_state

    cfg = model.bert.config
    assert cfg.hidden_dropout_prob == 0.0 \
        and cfg.attention_probs_dropout_prob == 0.0, \
        "pipeline parity requires dropout=0"
    n_stages = mesh.shape[axis]
    L = cfg.num_hidden_layers
    assert L % n_stages == 0, (L, n_stages)
    k = L // n_stages

    full = functional_state(model)

    def sub(prefix):
        pl = len(prefix)
        return {kk[pl:]: jnp.array(v) for kk, v in full.items()
                if kk.startswith(prefix)}

    emb_p = sub("bert.embeddings.")
    layer_states = [sub(f"bert.encoder.layers.{i}.") for i in range(L)]
    # stack: leaf (n_stages, k, ...)
    block_p = {
        kk: jnp.stack([jnp.stack([layer_states[st * k + j][kk]
                                  for j in range(k)])
                       for st in range(n_stages)])
        for kk in layer_states[0]}
    last_p = {"pooler": sub("bert.pooler."), "cls": sub("cls.")}
    # weight tie: cls.decoder_weight IS the embedding table; carry it in
    # last_p explicitly so the head stage has it
    last_p["cls"]["decoder_weight"] = emb_p["word_embeddings.weight"]

    embeddings, enc_layer0 = model.bert.embeddings, \
        model.bert.encoder.layers[0]
    pooler, cls_head = model.bert.pooler, model.cls

    def first_fn(p, aux):
        out, _ = functional_call(embeddings, p, aux["input_ids"],
                                 aux["token_type_ids"])
        return out

    def block_fn(p, h, aux):
        am = (aux["attention_mask"] != 0)[:, None, None, :]

        def one(h, sl):
            out, _ = functional_call(enc_layer0, sl, h, am)
            return out, None

        h, _ = jax.lax.scan(one, h, p)
        return h

    def last_fn(p, h, aux):
        pooled, _ = functional_call(pooler, p["pooler"], h)
        (mlm, nsp), _ = functional_call(
            cls_head, p["cls"], h, pooled,
            masked_positions=aux["masked_positions"])
        return {"mlm": mlm, "nsp": nsp}

    from ..parallel.pipeline import gpipe_model

    run = gpipe_model(mesh, first_fn, block_fn, last_fn,
                      num_microbatches, axis=axis, dp_axis=dp_axis,
                      remat_stages=remat_stages)
    criterion = BertPretrainingCriterion(cfg.vocab_size)

    def loss_fn(params, batch):
        emb_p, block_p, last_p = params
        aux = {kk: batch[kk] for kk in
               ("input_ids", "token_type_ids", "attention_mask",
                "masked_positions")}
        outs = run(emb_p, block_p, last_p, aux)
        from ..nn.layer.layers import Tensor as _T

        with jax.named_scope("loss"):
            return criterion(_T(outs["mlm"]), _T(outs["nsp"]),
                             _T(batch["masked_labels"]),
                             _T(batch["nsp_labels"]))._value

    lr = learning_rate

    @jax.jit
    def step(state, batch):
        params = state["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        g_emb, g_block, g_last = grads
        # tied table: sum embedding-stage and decoder-head gradients
        tied = g_emb["word_embeddings.weight"] \
            + g_last["cls"]["decoder_weight"]
        g_emb = dict(g_emb, **{"word_embeddings.weight": tied})
        e_p, b_p, l_p = params
        with jax.named_scope("optimizer"):
            new_e = {kk: v - lr * g_emb[kk] for kk, v in e_p.items()}
            new_b = {kk: v - lr * g_block[kk] for kk, v in b_p.items()}
            new_l = {
                grp: {kk: v - lr * g_last[grp][kk]
                      for kk, v in l_p[grp].items()}
                for grp in l_p}
        new_l["cls"]["decoder_weight"] = new_e["word_embeddings.weight"]
        return {"params": (new_e, new_b, new_l)}, loss

    return step, {"params": (emb_p, block_p, last_p)}
