"""JoyAI-LLM-Flash autoregressive training with its MTP module against
the plain reference (benchmark/reference/joyai_flash.py — the one the
benchmark's `correct` uses): the step, the latent attention layer, the
sigmoid router with its selection bias, the share test that ties a
chip's share to the whole layer, the MTP module's shift and order, and
the kept plan under recomputation."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.models import joyai_flash as M
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import joyai_flash as R  # noqa: E402


def _close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


def _params(model, bias=0.0, seed=0):
    """The model's functional state; selection biases seeded draws from
    U(-bias, bias)."""
    params = {k: jnp.array(v) for k, v in functional_state(model).items()}
    rng = np.random.default_rng(seed)
    for k in M.bias_names(params):
        params[k] = jnp.asarray(rng.uniform(-bias, bias, params[k].shape),
                                jnp.float32)
    return params


def _split(params):
    fixed = {k: params[k] for k in M.bias_names(params)}
    return {k: v for k, v in params.items() if k not in fixed}, fixed


# -- the step against the reference ------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """One float32 loss-and-gradient pass of a tiny model whose
    attention runs the flash kernels in interpret mode at the published
    head widths (a pair of 192-wide q/k heads over 128-wide v heads:
    the packed layout), with the reference's losses, logits and
    gradients on the same weights and non-zero selection biases."""
    mp = pytest.MonkeyPatch()
    mp.setattr(A, "_flash_ok", lambda q, k: True)
    mp.setattr(A, "flash_attention", functools.partial(
        A.flash_attention, interpret=True))
    try:
        paddle_tpu.seed(3)
        cfg = M.JoyAIFlashConfig.tiny(
            num_attention_heads=2, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, experts_held=(2, 4),
            num_experts_per_tok=3, recompute=True, vocab_size=64)
        model = M.JoyAIFlashForCausalLMWithMTP(cfg)
        batch = M.fake_batch(cfg, 2, 40, seed=5)
        params = _params(model, bias=0.05)
        train, fixed = _split(params)
        loss_fn = M.build_loss(model, bf16=False, probe=8)
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn({**p, **fixed}, batch), has_aux=True))(train)
        config = dataclasses.asdict(cfg)
        ref = R.forward(config, params, batch)
        ref_grads = R.grads(config, params, batch)
        return dict(cfg=cfg, batch=batch, loss=loss, aux=aux, grads=grads,
                    params=params, ref=ref, ref_grads=ref_grads)
    finally:
        mp.undo()


def test_both_losses_match_reference(trained):
    t = trained
    _close(t["aux"]["ce"], t["ref"]["ce"], 1e-5)
    _close(t["aux"]["mtp_ce"], t["ref"]["mtp_ce"], 1e-5)
    _close(t["loss"], t["ref"]["loss"], 1e-5)
    lam = t["cfg"].mtp_loss_weight
    _close(t["loss"], t["aux"]["ce"] + lam * t["aux"]["mtp_ce"], 1e-6)


@pytest.mark.parametrize("head", ["logits", "mtp_logits"])
def test_probe_logits_match_reference(trained, head):
    pos = M.probe_positions(40, 8)
    assert len(pos) == 8 and pos[0] == 0 and pos[-1] == 37
    got = trained["aux"]["probe_logits" if head == "logits"
                         else "mtp_probe_logits"]
    _close(got, np.asarray(trained["ref"][head])[:, pos], 2e-4)


def test_routing_counts_and_loads(trained):
    aux, ref, cfg = trained["aux"], trained["ref"], trained["cfg"]
    experts, stats, load = (np.asarray(aux[k]) for k in (
        "moe_experts", "moe_stats", "moe_load"))
    assert experts.shape == (3, 80, 3) and load.shape == (3, 8)
    first, count = cfg.experts_held
    for layer in range(3):
        assert (np.sort(experts[layer], 1)
                == np.sort(np.asarray(ref["experts"][layer]), 1)).all()
        by_expert = np.bincount(experts[layer].reshape(-1), minlength=8)
        assert (load[layer] == by_expert).all()
        assert (stats[layer, :count] == by_expert[first:first + count]).all()
        assert stats[layer, -2] == 80 * 3
        assert stats[layer, -1] == by_expert[first:first + count].sum()


def test_every_trained_leaf_has_a_gradient_and_no_bias_has(trained):
    assert set(trained["grads"]) == set(trained["ref_grads"])
    assert not any(k.endswith(M.BIAS_LEAF) for k in trained["grads"])
    assert len(M.bias_names(trained["params"])) == 3
    for k, g in trained["grads"].items():
        assert float(jnp.abs(g).max()) > 0, k


_LEAVES = [
    "model.embed_tokens.weight", "lm_head.weight", "model.norm.weight",
    "model.layers.0.self_attn.q_a_proj.weight",
    "model.layers.0.self_attn.q_a_layernorm.weight",
    "model.layers.0.mlp.down_proj.weight",
    "model.layers.1.self_attn.q_b_proj.weight",
    "model.layers.1.self_attn.kv_a_proj_with_mqa.weight",
    "model.layers.1.self_attn.kv_a_layernorm.weight",
    "model.layers.2.self_attn.kv_b_proj.weight",
    "model.layers.2.self_attn.o_proj.weight",
    "model.layers.1.moe.gate_weight", "model.layers.2.moe.w_gate",
    "model.layers.2.moe.w_down",
    "model.layers.1.moe.shared_experts.down_proj.weight",
    "mtp.eh_proj.weight", "mtp.enorm.weight", "mtp.hnorm.weight",
    "mtp.norm.weight", "mtp.block.moe.gate_weight",
    "mtp.block.self_attn.kv_b_proj.weight", "mtp.block.moe.w_up",
]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_matches_reference(trained, leaf):
    got, want = trained["grads"][leaf], trained["ref_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    _close(got / scale, want / scale, 2e-4)


def test_all_gradients_match_reference(trained):
    for leaf, want in trained["ref_grads"].items():
        scale = float(jnp.abs(want).max())
        _close(trained["grads"][leaf] / scale, want / scale, 5e-4)


def test_bf16_step_trains_and_moves_the_biases():
    paddle_tpu.seed(1)
    cfg = M.JoyAIFlashConfig.tiny(recompute=True)
    model = M.JoyAIFlashForCausalLMWithMTP(cfg)
    step, state = M.build_train_step(model, weight_decay=0.01)
    assert set(state["m"]) == set(state["params"]) - set(
        M.bias_names(state["params"]))
    batch = M.fake_batch(cfg, 2, 24, seed=2)
    losses = []
    for _ in range(4):
        state, loss, aux = step(state, batch, jnp.float32(1e-2))
        losses.append(float(loss))
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert set(aux) == {"ce", "mtp_ce", "moe_stats", "moe_load"}
    for k in M.bias_names(state["params"]):
        b = np.asarray(state["params"][k])
        assert np.abs(b).max() > 0 and np.abs(b).max() <= 4e-3 + 1e-7


def test_step_moves_each_bias_against_its_own_layers_load():
    paddle_tpu.seed(2)
    cfg = M.JoyAIFlashConfig.tiny(bias_update_rate=0.5)
    model = M.JoyAIFlashForCausalLMWithMTP(cfg)
    step, state = M.build_train_step(model, bf16=False)
    names = M.bias_names(state["params"])
    assert names == ["model.layers.1.moe." + M.BIAS_LEAF,
                     "model.layers.2.moe." + M.BIAS_LEAF,
                     "mtp.block.moe." + M.BIAS_LEAF]
    state, _, aux = step(state, M.fake_batch(cfg, 2, 16), jnp.float32(0.0))
    load = np.asarray(aux["moe_load"], np.float32)
    for i, k in enumerate(names):
        want = 0.5 * np.sign(load[i].mean() - load[i])
        _close(state["params"][k], want, 1e-7)


def test_config_refuses_what_is_not_built():
    with pytest.raises(NotImplementedError):
        M.JoyAIFlashConfig.tiny(rope_scaling={"type": "yarn", "factor": 4})
    with pytest.raises(NotImplementedError):
        M.JoyAIFlashConfig.tiny(topk_method="group_limited_greedy")
    with pytest.raises(NotImplementedError):
        M.JoyAIFlashConfig.tiny(num_nextn_predict_layers=2)
    with pytest.raises(NotImplementedError):
        M.JoyAIFlashForCausalLMWithMTP(M.JoyAIFlashConfig.tiny(n_group=2))
    cfg = M.JoyAIFlashConfig.tiny(first_k_dense_replace=2)
    assert [cfg.is_sparse(i) for i in range(3)] == [False, False, True]


# -- the latent attention layer -------------------------------------------------

def _attn_case(seed=4, **kw):
    paddle_tpu.seed(seed)
    args = dict(embed_dim=32, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
                rope_theta=3.2e7)
    args.update(kw)
    layer = nn.LatentAttention(**args)
    x = jnp.asarray(np.random.RandomState(seed).randn(2, 20, 32),
                    jnp.float32)
    cfg = {"num_attention_heads": args["num_heads"],
           "qk_nope_head_dim": args["qk_nope_head_dim"],
           "qk_rope_head_dim": args["qk_rope_head_dim"],
           "v_head_dim": args["v_head_dim"],
           "kv_lora_rank": args["kv_lora_rank"], "rms_norm_eps": 1e-6,
           "rope_theta": args["rope_theta"]}
    return layer, x, cfg


def test_latent_attention_matches_reference():
    layer, x, cfg = _attn_case()
    params = {"a." + k: v for k, v in functional_state(layer).items()}
    assert set(functional_state(layer)) == {
        n + ".weight" for n in (
            "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
            "kv_a_layernorm", "kv_b_proj", "o_proj")}
    out = layer(paddle_tpu.to_tensor(x), np.arange(20))
    with jax.default_matmul_precision("highest"):
        want = R._attention(cfg, params, "a.", x, jnp.arange(20))
    _close(out.numpy(), want, 2e-5)


def test_latent_attention_is_causal_and_shares_one_rotated_key_head():
    layer, x, _ = _attn_case()
    assert layer.kv_a_proj_with_mqa.weight.shape == [32, 16 + 8]
    assert layer.kv_b_proj.weight.shape == [16, 4 * (16 + 12)]
    assert layer.o_proj.weight.shape == [4 * 12, 32]
    pos = np.arange(20)
    full = layer(paddle_tpu.to_tensor(x), pos).numpy()
    cut = layer(paddle_tpu.to_tensor(x[:, :11]), pos[:11]).numpy()
    _close(full[:, :11], cut, 1e-5)        # a position sees no later one


def test_interleaved_rotation_gives_the_reference_scores():
    """The helper sorts the pairs' lanes into halves and leaves them
    so; the reference rotates the pairs in place.  Every q . k agrees,
    and depends on the distance alone."""
    from paddle_tpu.nn import functional as F

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 12, 3, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 12, 1, 8), jnp.float32)
    pos = np.arange(12)
    qr, kr = F.rotary_embedding(paddle_tpu.to_tensor(q),
                                paddle_tpu.to_tensor(k), pos, 3.2e7,
                                interleaved=True)
    got = jnp.einsum("bqhd,bkhd->bhqk", qr.numpy(),
                     jnp.broadcast_to(kr.numpy(), (1, 12, 3, 8)))
    want = jnp.einsum(
        "bqhd,bkhd->bhqk", R._rope_interleaved(q, jnp.arange(12), 3.2e7),
        jnp.broadcast_to(R._rope_interleaved(k, jnp.arange(12), 3.2e7),
                         (1, 12, 3, 8)))
    _close(got, want, 1e-5)
    same = jnp.broadcast_to(q[:, :1], q.shape)
    sr, _ = F.rotary_embedding(paddle_tpu.to_tensor(same),
                               paddle_tpu.to_tensor(same), pos, 1e4,
                               interleaved=True)
    s = jnp.einsum("qd,kd->qk", sr.numpy()[0, :, 0], sr.numpy()[0, :, 0])
    _close(s[2, 5], s[6, 9], 1e-5)
    # the default convention is untouched: rotate-half
    q2, _ = F.rotary_embedding(paddle_tpu.to_tensor(q),
                               paddle_tpu.to_tensor(k), pos, 1e4)
    assert not np.allclose(q2.numpy(), qr.numpy())


# -- the sigmoid router with its selection bias -----------------------------

def _router_case(t=64, h=16, n=12, seed=7):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(t, h), jnp.float32),
            jnp.asarray(rng.randn(h, n) * 0.5, jnp.float32))


def test_sigmoid_router_weights_are_scores_renormalised_and_scaled():
    x, wr = _router_case()
    experts, weights = moe.route_top_k(x, wr, 3, scoring="sigmoid",
                                       scale=2.5)
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    own = np.argsort(-s, axis=1)[:, :3]
    assert (np.sort(np.asarray(experts), 1) == np.sort(own, 1)).all()
    at = np.take_along_axis(s, np.asarray(experts), 1)
    _close(weights, 2.5 * at / at.sum(1, keepdims=True), 1e-6)
    _close(np.asarray(weights).sum(1), 2.5, 1e-5)
    _, plain = moe.route_top_k(x, wr, 3, renormalize=False,
                               scoring="sigmoid")
    _close(plain, at, 1e-6)


def test_selection_bias_changes_the_choice_and_never_the_weights():
    x, wr = _router_case()
    bias = jnp.zeros((12,)).at[5].set(10.0).at[0].set(-10.0)
    experts, weights = moe.route_top_k(x, wr, 3, scoring="sigmoid",
                                       bias=bias, scale=2.5)
    experts = np.asarray(experts)
    assert (experts == 5).any(1).all() and not (experts == 0).any()
    plain = np.asarray(moe.route_top_k(x, wr, 3, scoring="sigmoid")[0])
    assert not (plain == 5).any(1).all()
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    at = np.take_along_axis(s, experts, 1)      # the scores, not s + b
    _close(weights, 2.5 * at / at.sum(1, keepdims=True), 1e-6)


def test_no_gradient_reaches_the_selection_bias():
    x, wr = _router_case()
    bias = jnp.asarray(np.random.RandomState(8).randn(12) * 0.1, jnp.float32)

    def f(bias, wr):
        _, w = moe.route_top_k(x, wr, 3, scoring="sigmoid", bias=bias,
                               renormalize=False)
        return jnp.sum(w * w)

    db, dw = jax.grad(f, argnums=(0, 1))(bias, wr)
    assert float(jnp.abs(db).max()) == 0.0
    assert float(jnp.abs(dw).max()) > 0.0


def test_softmax_router_is_what_it_was():
    x, wr = _router_case()
    experts, weights = moe.route_top_k(x, wr, 3)
    p = np.asarray(jax.nn.softmax(x @ wr, axis=-1))
    at = np.take_along_axis(p, np.asarray(experts), 1)
    _close(weights, at / at.sum(1, keepdims=True), 1e-6)
    with pytest.raises(ValueError):
        moe.route_top_k(x, wr, 3, scoring="tanh")


@pytest.mark.parametrize("load,want", [
    ([4, 0, 2, 2], [-1, 1, 0, 0]),
    ([1, 1, 1, 1], [0, 0, 0, 0]),
    ([0, 0, 0, 8], [1, 1, 1, -1]),
])
def test_bias_update_sign_rule(load, want):
    bias = jnp.asarray([0.5, -0.5, 0.0, 0.25], jnp.float32)
    new = moe.update_selection_bias(bias, jnp.asarray(load, jnp.int32),
                                    1e-3)
    _close(new - bias, 1e-3 * np.asarray(want, np.float32), 1e-7)


def test_router_load_counts_every_output():
    experts = jnp.asarray([[0, 3], [3, 5], [3, 0]], jnp.int32)
    assert list(np.asarray(moe.router_load(experts, 6))) == [2, 0, 0, 3, 0,
                                                             1]


@pytest.mark.parametrize("kw", [dict(n_group=2), dict(topk_group=2),
                                dict(n_group=8, topk_group=4)])
def test_group_limited_routing_raises(kw):
    with pytest.raises(NotImplementedError):
        nn.RoutedMoE(16, 8, 8, 2, scoring="sigmoid", **kw)


def test_routed_moe_layer_with_bias_and_shared_expert_matches_reference():
    paddle_tpu.seed(9)
    layer = nn.RoutedMoE(16, 8, 8, 3, held=(2, 4), scoring="sigmoid",
                         routed_scaling_factor=2.5, selection_bias=True,
                         n_shared_experts=1)
    state = functional_state(layer)
    assert "e_score_correction_bias" in state
    assert "shared_experts.down_proj.weight" in state
    state["e_score_correction_bias"] = jnp.asarray(
        np.random.RandomState(9).uniform(-0.2, 0.2, 8), jnp.float32)
    x = jnp.asarray(np.random.RandomState(10).randn(2, 12, 16), jnp.float32)
    (out, stats, experts, load), _ = functional_call(layer, state, x)
    cfg = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5}
    params = {"m." + k: v for k, v in state.items()}
    with jax.default_matmul_precision("highest"):
        want, ref_experts, _ = R.moe_layer(cfg, params, "m.",
                                           x.reshape(-1, 16), (2, 4))
    _close(out.reshape(-1, 16), want, 2e-5)
    assert (np.sort(experts, 1) == np.sort(ref_experts, 1)).all()
    assert int(load.sum()) == 24 * 3 and stats.shape == (4 + 2,)
    # the softmax layer keeps its three outputs
    plain = nn.RoutedMoE(16, 8, 8, 3)
    assert len(plain(paddle_tpu.to_tensor(x))) == 3


# -- the share test -------------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_equal_the_whole_layer():
    """Every chip of the group computes its own routed experts' part
    and, alike, the shared expert: the routed parts of all the shares
    plus the shared expert counted ONCE are the uncut layer."""
    rng = np.random.RandomState(11)
    t, h, f, n, k, shares = 48, 16, 8, 32, 4, 16
    per = n // shares
    w = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
    full = {"m.gate_weight": w(h, n),
            "m.e_score_correction_bias": w(n) * 0.2,
            "m.w_gate": w(n, h, f), "m.w_up": w(n, h, f),
            "m.w_down": w(n, f, h),
            "m.shared_experts.gate_proj.weight": w(h, f),
            "m.shared_experts.up_proj.weight": w(h, f),
            "m.shared_experts.down_proj.weight": w(f, h)}
    x = w(t, h)
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        whole, _, _ = R.moe_layer(cfg, full, "m.", x, (0, n))
        shared = R._gated_ffn(
            cfg, x, *(full[f"m.shared_experts.{p}_proj.weight"]
                      for p in ("gate", "up", "down")))
    total, visits = shared, 0
    for i in range(shares):
        held = (i * per, per)
        params = {"wr": full["m.gate_weight"],
                  "br": full["m.e_score_correction_bias"],
                  **{a: full[b][held[0]:held[0] + per] for a, b in (
                      ("wg", "m.w_gate"), ("wu", "m.w_up"),
                      ("wd", "m.w_down"))}}
        part, stats, _ = moe.routed_moe_local(
            params, x, k, held=held, scoring="sigmoid", scale=2.5)
        total = total + part
        visits += int(stats[-1])
    assert visits == t * k              # every visit computed exactly once
    _close(total, whole, 2e-5)


# -- the MTP module ---------------------------------------------------------------

def _mtp_case():
    paddle_tpu.seed(12)
    cfg = M.JoyAIFlashConfig.tiny(num_hidden_layers=2)
    model = M.JoyAIFlashForCausalLMWithMTP(cfg)
    return cfg, model, _params(model)


def test_mtp_targets_are_shifted_by_two_and_leave_out_what_has_none():
    """Changing the LAST token moves the main loss (it is t_{i+1} of
    position S - 2) and the MTP loss (t_{i+2} of position S - 3);
    changing the first moves neither's targets, only inputs."""
    cfg, model, params = _mtp_case()
    loss_fn = jax.jit(M.build_loss(model, bf16=False))
    ids = np.asarray(M.fake_batch(cfg, 1, 12, seed=1)["input_ids"])
    config = dataclasses.asdict(cfg)
    for edit in (lambda a: a, lambda a: np.concatenate(
            [a[:, :-1], (a[:, -1:] + 1) % cfg.vocab_size], 1)):
        batch = {"input_ids": edit(ids)}
        _, aux = loss_fn(params, batch)
        ref = R.forward(config, params, batch)
        _close(aux["ce"], ref["ce"], 1e-5)
        _close(aux["mtp_ce"], ref["mtp_ce"], 1e-5)
    # by hand from the reference's logits: S - 1 and S - 2 positions
    logp = jax.nn.log_softmax(ref["logits"][0], -1)
    mtp_logp = jax.nn.log_softmax(ref["mtp_logits"][0], -1)
    tok = batch["input_ids"][0]
    _close(ref["ce"], -np.mean([logp[i, tok[i + 1]] for i in range(11)]),
           1e-5)
    _close(ref["mtp_ce"],
           -np.mean([mtp_logp[i, tok[i + 2]] for i in range(10)]), 1e-5)


def test_eh_proj_reads_the_embedding_first():
    """Swapping the two halves of W_eh's rows is swapping the order of
    the concatenation: the module's output changes, and equals the
    reference's only in the released code's order."""
    cfg, model, params = _mtp_case()
    batch = M.fake_batch(cfg, 1, 10, seed=3)
    w = params["mtp.eh_proj.weight"]
    h = cfg.hidden_size
    assert w.shape == (2 * h, h)
    swapped = {**params, "mtp.eh_proj.weight": jnp.concatenate(
        [w[h:], w[:h]], axis=0)}
    loss_fn = jax.jit(M.build_loss(model, bf16=False))
    config = dataclasses.asdict(cfg)
    a, b = (loss_fn(p, batch)[1]["mtp_ce"] for p in (params, swapped))
    assert abs(float(a) - float(b)) > 1e-4
    _close(a, R.forward(config, params, batch)["mtp_ce"], 1e-5)
    # the embedding half multiplies RMSNorm_e(Emb(t_{i+1})): zeroing it
    # makes the module blind to the next token
    blind = {**params, "mtp.eh_proj.weight": w.at[:h].set(0.0)}
    other = {"input_ids": np.asarray(batch["input_ids"]).copy()}
    other["input_ids"][0, 5] = (other["input_ids"][0, 5] + 1) % 96
    (hid_a, mtp_a, *_), _ = functional_call(model, blind, batch["input_ids"])
    (hid_b, mtp_b, *_), _ = functional_call(model, blind, other["input_ids"])
    _close(mtp_a[:, :4], mtp_b[:, :4], 1e-6)   # position 4 reads t_5


def test_lambda_weighs_the_second_loss():
    cfg, model, params = _mtp_case()
    batch = M.fake_batch(cfg, 1, 10, seed=4)
    loss, aux = M.build_loss(model, bf16=False)(params, batch)
    _close(loss, aux["ce"] + 0.3 * aux["mtp_ce"], 1e-6)
    model.config.mtp_loss_weight = 0.0
    loss0, aux0 = M.build_loss(model, bf16=False)(params, batch)
    _close(loss0, aux0["ce"], 1e-7)


def test_shared_embedding_and_head():
    _, model, params = _mtp_case()
    assert not any(k.startswith("mtp.") and ("embed" in k or "lm_head" in k)
                   for k in params)
    assert {"mtp.enorm.weight", "mtp.hnorm.weight", "mtp.eh_proj.weight",
            "mtp.norm.weight"} <= set(params)


# -- the kept plan under recomputation -----------------------------------------

def _count_primitive(jaxpr, name):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitive(sub, name)
    return n


class TestPlanKeptOverRecomputation:
    def _grad(self, recompute):
        paddle_tpu.seed(13)
        cfg = M.JoyAIFlashConfig.tiny(recompute=recompute)
        model = M.JoyAIFlashForCausalLMWithMTP(cfg)
        params = _params(model, bias=0.05, seed=1)
        train, fixed = _split(params)
        loss_fn = M.build_loss(model, bf16=False)
        batch = M.fake_batch(cfg, 2, 16, seed=6)
        return jax.grad(lambda p: loss_fn({**p, **fixed}, batch)[0]), train

    def test_gradient_holds_one_sort_and_one_top_k_a_layer(self):
        grad, train = self._grad(True)
        jaxpr = jax.make_jaxpr(grad)(train).jaxpr
        assert _count_primitive(jaxpr, "sort") == 3
        assert _count_primitive(jaxpr, "top_k") == 3

    def test_gradients_bit_equal_to_no_recomputation(self):
        a, train = self._grad(True)
        b, _ = self._grad(False)
        ga, gb = jax.jit(a)(train), jax.jit(b)(train)
        for k in ga:
            _close(ga[k], gb[k], 1e-6)


def test_record_moe_stats_feeds_the_new_counters():
    from paddle_tpu import profiler

    before = profiler.get_int_stats()
    stats = np.array([[3, 1, 8, 4], [2, 2, 8, 4]])
    load = np.array([[3, 1, 2, 2], [2, 2, 1, 3]])
    M.record_moe_stats(stats, load, bias_updates=2)
    after = profiler.get_int_stats()
    delta = lambda n: after.get(n, 0) - before.get(n, 0)
    assert delta("moe_router_rows_total") == 16
    assert delta("moe_router_rows_max_total") == 6
    assert delta("moe_bias_updates_total") == 2
    assert delta("moe_rows_routed_total") == 16
    assert delta("moe_rows_held_total") == 8
    assert delta("moe_dropped_total") == 0
