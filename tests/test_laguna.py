"""Laguna: the model against the plain reference (benchmark/reference/
laguna.py — the one the benchmark's `correct` uses) on seeded weights,
the three published lists honoured layer by layer, the share test that
ties a chip's share to the whole layer, the attention layer's gate,
window and two rotations against a softmax written out, the two
elementwise passes around the flash kernels (ops/pallas/attn_edge.py,
interpret mode) against their XLA statements, the train step and the
counters."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import nn, profiler
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.models import laguna as M
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import attn_edge as X
from paddle_tpu.ops.pallas import attn_edge as E

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import laguna as R  # noqa: E402

SEQ = 40

GRAD_LEAVES = ("model.layers.3.self_attn.g_proj.weight",
               "model.layers.4.self_attn.g_proj.weight",
               "model.layers.3.self_attn.q_proj.weight",
               "model.layers.4.self_attn.q_proj.weight",
               "model.layers.0.self_attn.k_proj.weight",
               "model.layers.2.self_attn.v_proj.weight",
               "model.layers.1.moe.gate_weight",
               "model.layers.4.moe.w_down",
               "model.layers.2.moe.shared_experts.down_proj.weight",
               "model.layers.0.mlp.up_proj.weight",
               "model.embed_tokens.weight", "lm_head.weight",
               "model.norm.weight")


def _reference_config(cfg):
    return {**dataclasses.asdict(cfg), "router_width": cfg.num_experts}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def compared():
    """The tiny model (5 layers as the benchmark's: full + dense, three
    window layers, a full one, the four with experts; a share of 4 of 8
    experts) and the reference on the same weights and batch, float32."""
    paddle_tpu.seed(3)
    # weights ten times the published range: attention far from uniform,
    # so that positions and the window's edge matter to the result
    cfg = M.LagunaConfig.tiny(experts_held=(2, 4), initializer_range=0.2)
    model = M.LagunaForCausalLM(cfg)
    params = dict(functional_state(model))
    batch = M.fake_batch(cfg, 2, SEQ, seed=5)
    loss_fn = M.build_loss(model, bf16=False, probe=SEQ - 1)
    loss, aux = loss_fn(params, batch)
    grads = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
    config = _reference_config(cfg)
    ref = R.forward(config, params, batch)
    return {"cfg": cfg, "config": config, "params": params, "batch": batch,
            "loss": loss, "aux": aux, "grads": grads, "ref": ref,
            "ref_grads": R.grads(config, params, batch,
                                 list(ref["experts"]))}


def test_loss_and_logits_match_reference(compared):
    c = compared
    assert abs(float(c["loss"]) - float(c["ref"]["loss"])) < 1e-5
    pos = M.probe_positions(SEQ, SEQ - 1)
    assert R.rel_rms(c["aux"]["probe_logits"],
                     np.asarray(c["ref"]["logits"])[:, pos]) < 1e-5


def test_routing_matches_reference(compared):
    c = compared
    for got, want in zip(np.asarray(c["aux"]["moe_experts"]),
                         c["ref"]["experts"]):
        np.testing.assert_array_equal(np.sort(got, 1),
                                      np.sort(np.asarray(want), 1))
    assert len(c["ref"]["experts"]) == 4


@pytest.mark.parametrize("leaf", GRAD_LEAVES)
def test_gradient_matches_reference(compared, leaf):
    assert _rel(compared["grads"][leaf], compared["ref_grads"][leaf]) < 2e-5


def test_all_gradients_match_reference(compared):
    c = compared
    assert set(c["ref_grads"]) == set(c["params"])
    worst = max((_rel(c["grads"][k], g), k)
                for k, g in c["ref_grads"].items())
    assert worst[0] < 5e-5, worst


@pytest.mark.parametrize("control,by", [
    ({"window_lower_bound": False}, "logits"),
    ({"rope_angle_dtype": "bfloat16"}, "logits"),
    ({"gate_dtype": "bfloat16"}, "logits")])
def test_reference_controls_move_the_reading(compared, control, by):
    """The reference's control readings are not the reference: each
    moves the logits by far more than the model's own distance from
    it."""
    c = compared
    low = R.forward({**c["config"], **control}, c["params"], c["batch"],
                    list(c["ref"]["experts"]))
    assert R.rel_rms(low["logits"], c["ref"]["logits"]) > 1e-4


# -- the three published lists ------------------------------------------------

def test_layers_follow_the_three_published_lists():
    cfg = M.LagunaConfig(num_hidden_layers=9)
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48, 64, 64,
                                                 64, 48)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 8
    # lists of the published 40 are cut to the layers that run
    long = M.LagunaConfig(
        num_hidden_layers=5, layer_types=list(cfg.layer_types) * 5,
        mlp_layer_types=["dense"] + ["sparse"] * 39,
        num_attention_heads_per_layer=[48, 64, 64, 64] * 10)
    assert len(long.layer_types) == len(long.mlp_layer_types) == 5
    with pytest.raises(ValueError, match="lists 3 layers of 5"):
        M.LagunaConfig(num_hidden_layers=5, layer_types=["full_attention"] * 3)
    with pytest.raises(ValueError, match="layer kinds"):
        M.LagunaConfig(num_hidden_layers=2,
                       layer_types=["full_attention", "linear_attention"])


def test_model_builds_each_layer_from_its_entries():
    # any order of kinds, not the published period alone
    cfg = M.LagunaConfig.tiny(
        layer_types=("sliding_attention", "full_attention",
                     "full_attention", "sliding_attention",
                     "sliding_attention"),
        num_attention_heads_per_layer=(8, 6, 2, 4, 8),
        mlp_layer_types=("sparse", "dense", "sparse", "dense", "sparse"))
    model = M.LagunaForCausalLM(cfg)
    for i, layer in enumerate(model.model.layers):
        attn = layer.self_attn
        assert attn.num_heads == cfg.num_attention_heads_per_layer[i]
        assert attn.q_proj.weight.shape == [32, attn.num_heads * 16]
        assert attn.g_proj.weight.shape == [32, attn.num_heads]
        assert attn.k_proj.weight.shape == [32, 2 * 16]
        window = cfg.layer_types[i] == "sliding_attention"
        assert attn.window == (8 if window else None)
        assert (attn._inv_freq is None) == window
        assert attn._rotary_dim == (16 if window else 8)
        assert hasattr(layer, "moe") == (cfg.mlp_layer_types[i] == "sparse")
        assert hasattr(layer, "mlp") == (cfg.mlp_layer_types[i] == "dense")
    params = dict(functional_state(model))
    batch = M.fake_batch(cfg, 1, 24, seed=1)
    loss, _ = M.build_loss(model, bf16=False)(params, batch)
    ref = R.forward(_reference_config(cfg), params, batch)
    assert abs(float(loss) - float(ref["loss"])) < 1e-5


def test_published_config_counts_33_4_billion_parameters():
    """The per-head gate's form is what makes the count the catalog's
    33.4B (an element-wise gate would read 34.1B)."""
    cfg = M.LagunaConfig()
    e, d, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    total = 2 * cfg.vocab_size * e + e
    for i in range(cfg.num_hidden_layers):
        h = cfg.num_attention_heads_per_layer[i]
        total += 2 * e * h * d + 2 * e * kv * d + e * h + 2 * e
        if cfg.is_sparse(i):
            total += e * cfg.num_experts + 3 * e * cfg.moe_intermediate_size \
                * (cfg.num_experts + 1)
        else:
            total += 3 * e * cfg.intermediate_size
    assert round(total / 1e9, 2) == 33.44
    elementwise = total + sum(
        e * h * (d - 1) for h in cfg.num_attention_heads_per_layer)
    assert round(elementwise / 1e9, 1) == 34.1


def test_refuses_what_the_config_cannot_say():
    for kw in ({"gating": "elementwise"}, {"hidden_act": "gelu"},
               {"attention_bias": True}, {"tie_word_embeddings": True},
               {"moe_apply_router_weight_on_input": True}):
        with pytest.raises(NotImplementedError):
            M.LagunaConfig.tiny(**kw)
    rope = M._published_rope()
    rope["full_attention"]["rope_type"] = "llama3"
    with pytest.raises(NotImplementedError, match="llama3"):
        M.LagunaForCausalLM(M.LagunaConfig.tiny(rope_parameters=rope))


def test_rope_scaling_still_raises_in_the_other_decoders():
    from paddle_tpu.models import joyai_flash, kimi_linear

    scaling = {"rope_type": "yarn", "factor": 4}
    with pytest.raises(NotImplementedError):
        joyai_flash.JoyAIFlashConfig.tiny(rope_scaling=scaling)
    with pytest.raises(NotImplementedError):
        kimi_linear.KimiLinearConfig.tiny(rope_scaling=scaling)


# -- the share test ----------------------------------------------------------

@pytest.mark.parametrize("index", [1, 4])
def test_shares_and_what_every_chip_computes_once_equal_the_layer(index):
    """An expert layer of a 32-expert tiny model cut into 16 shares of 2
    experts (the deployment's 16 chips a layer): the routed parts the
    shares give, with attention, router and the shared expert counted
    once, add up to the uncut reference's layer output — for a window
    layer and for a full one."""
    paddle_tpu.seed(11)
    cfg = M.LagunaConfig.tiny(num_experts=32, num_experts_per_tok=4)
    whole = M.LagunaDecoderLayer(cfg, index)
    params = {f"model.layers.{index}." + k: jnp.array(v)
              for k, v in functional_state(whole).items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)),
                    jnp.float32)
    pos = np.arange(24, dtype=np.int32)
    want, _, _ = R._layer(_reference_config(cfg), params, index, x,
                          (0, 32), None)
    routed_sum, once = 0.0, None
    for share in range(16):
        held = (2 * share, 2)
        part = M.LagunaDecoderLayer(
            dataclasses.replace(cfg, experts_held=held), index)
        state = dict(functional_state(whole))
        for name in ("moe.w_gate", "moe.w_up", "moe.w_down"):
            state[name] = state[name][held[0]:held[0] + 2]
        (out, _), _ = functional_call(part, state, x, pos)
        # what every chip computes alike: x + attention + shared expert
        (alone, _), _ = functional_call(
            part, {**state, "moe.w_down": jnp.zeros_like(
                state["moe.w_down"])}, x, pos)
        routed_sum = routed_sum + (out - alone)
        once = alone
    np.testing.assert_allclose(np.asarray(once + routed_sum),
                               np.asarray(want), atol=2e-5)


# -- the attention layer ------------------------------------------------------

def _plain_attention(layer, x, positions):
    """nn.GatedWindowAttention as a softmax written out in float64 over
    its own weights."""
    w = {k: np.asarray(v, np.float64)
         for k, v in functional_state(layer).items()}
    x = np.asarray(x, np.float64)
    b, s, _ = x.shape
    h, hkv, d = layer.num_heads, layer.num_kv_heads, layer.head_dim
    q = (x @ w["q_proj.weight"]).reshape(b, s, h, d)
    k = (x @ w["k_proj.weight"]).reshape(b, s, hkv, d)
    v = (x @ w["v_proj.weight"]).reshape(b, s, hkv, d)
    r = layer._rotary_dim
    inv = layer._inv_freq if layer._inv_freq is not None else \
        layer._theta ** (-np.arange(0, r, 2) / r)
    ang = np.asarray(positions, np.float64)[:, None] * np.asarray(
        inv, np.float64)
    cos, sin = (f(ang)[None, :, None, :] * layer._amplitude
                for f in (np.cos, np.sin))

    def rot(a):
        a1, a2 = a[..., :r // 2], a[..., r // 2:r]
        return np.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin,
                               a[..., r:]], -1)

    q, k = rot(q), rot(k)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = j <= i
    if layer.window is not None:
        seen &= j > i - layer.window
    out = np.zeros((b, s, h, d))
    for head in range(h):
        kv = head // (h // hkv)
        sc = np.einsum("bqd,bkd->bqk", q[:, :, head], k[:, :, kv]) / np.sqrt(d)
        sc = np.where(seen, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, :, head] = np.einsum("bqk,bkd->bqd",
                                    p / p.sum(-1, keepdims=True), v[:, :, kv])
    if layer.g_proj is not None:
        out *= (1 / (1 + np.exp(-(x @ w["g_proj.weight"]))))[..., None]
    return out.reshape(b, s, h * d) @ w["o_proj.weight"]


@pytest.mark.parametrize("kind,heads,window,gate", [
    ("full_attention", 6, None, True), ("sliding_attention", 8, 5, True),
    ("sliding_attention", 4, 64, True), ("full_attention", 2, None, False)])
def test_attention_layer_is_the_softmax_written_out(kind, heads, window,
                                                    gate):
    paddle_tpu.seed(7)
    rope = M.LagunaConfig.tiny().rope_parameters[kind]
    layer = nn.GatedWindowAttention(32, heads, 2, 16, window=window,
                                    rope=rope, gate=gate)
    assert (layer.g_proj is None) == (not gate)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 20, 32)),
                    jnp.float32)
    pos = np.arange(20, dtype=np.int32)
    got = layer(paddle_tpu.to_tensor(x), pos)
    np.testing.assert_allclose(np.asarray(got._value),
                               _plain_attention(layer, x, pos), atol=2e-5)


def test_a_row_in_a_window_layer_sees_its_window_alone():
    paddle_tpu.seed(8)
    layer = nn.GatedWindowAttention(
        32, 4, 2, 16, window=6,
        rope=M.LagunaConfig.tiny().rope_parameters["sliding_attention"])
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 24, 32)).astype(np.float32)
    y = x.copy()
    y[0, :10] = rng.normal(size=(10, 32))       # rows 0-9 change
    pos = np.arange(24, dtype=np.int32)
    a, b = (np.asarray(layer(paddle_tpu.to_tensor(jnp.asarray(t)), pos)._value)
            for t in (x, y))
    # row i sees rows i-5 ... i: rows from 15 on see none of rows 0-9
    np.testing.assert_allclose(a[0, 15:], b[0, 15:], atol=1e-6)
    assert np.abs(a[0, 10:15] - b[0, 10:15]).max() > 1e-4


def test_gate_logits_stay_float32_under_bfloat16():
    paddle_tpu.seed(9)
    layer = nn.GatedWindowAttention(32, 4, 2, 16, window=None)
    x = paddle_tpu.to_tensor(jnp.ones((1, 8, 32), jnp.bfloat16))
    assert layer.g_proj(x)._value.dtype == jnp.float32


# -- the passes around the flash kernels ---------------------------------------

def _stat(name):
    return profiler.get_int_stats().get(name, 0)


def _ulps(a, b):
    """The largest distance between two bfloat16 arrays in units in the
    last place of the larger of each pair — where a sum of products
    cancels, in float32's at the operands' size (XLA's CPU fusions
    contract a multiply and an add into one rounding; the kernels'
    statements, interpreted, do not)."""
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    size = np.maximum(np.maximum(np.abs(a), np.abs(b)), 2.0 ** -12)
    return float((np.abs(a - b) / 2.0 ** (np.floor(np.log2(size)) - 7)).max())


_ROTATIONS = {
    "plain": dict(theta=1e4),
    "yarn_half": dict(theta=5e5, rotary_dim=64, amplitude=1.4159,
                      inv_freq=F.yarn_inv_freq(64, 5e5, 64.0, 4096)),
}


def _xla_rope(q, k, pos, **kw):
    return tuple(t._value for t in F.rotary_embedding(q, k, pos, **kw))


@pytest.mark.parametrize("rows", [64, 72], ids=["tiles", "ragged"])
@pytest.mark.parametrize("heads", [64, 48])
@pytest.mark.parametrize("rotation", list(_ROTATIONS))
def test_edge_kernels_match_the_xla_statement(rotation, heads, rows,
                                              monkeypatch):
    """`rope` and `head_gate` through the kernels (interpret mode, the
    row tile set to 32 so that a pass crosses tiles and, at 72 rows,
    pads one) at the cell's head counts over 8 kv heads of 128: values and
    pull-backs of bfloat16 operands within one unit in the last place of
    `F.rotary_embedding` / the gate's statement, the gate logits'
    float32 gradient to 1e-5."""
    monkeypatch.setattr(E, "ROW_TILE", 32)
    kw = _ROTATIONS[rotation]
    rng = np.random.default_rng(heads + rows)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q, k = draw(1, rows, heads, 128), draw(1, rows, 8, 128)
    dq, dk = draw(*q.shape), draw(*k.shape)
    pos = np.arange(rows, dtype=np.int32) * 37      # angles that wrap
    got, pull = jax.vjp(lambda q, k: E.rope(
        q, k, pos, interpret=True, **kw), q, k)
    want, pull_xla = jax.vjp(lambda q, k: _xla_rope(q, k, pos, **kw), q, k)
    for a, b in zip(got + pull((dq, dk)), want + pull_xla((dq, dk))):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _ulps(a, b) <= 1
    g = jnp.asarray(rng.normal(size=(1, rows, heads)) * 3, jnp.float32)
    y, pull = jax.vjp(lambda o, g: E.head_gate(o, g, interpret=True), q, g)
    y_xla, pull_xla = jax.vjp(X.head_gate, q, g)
    (do, dg), (do_xla, dg_xla) = pull(dq), pull_xla(dq)
    assert _ulps(y, y_xla) <= 1 and _ulps(do, do_xla) <= 1
    assert dg.dtype == jnp.float32
    assert _rel(dg, dg_xla) < 1e-5


def _edge_counts(before):
    return tuple(_stat(n) - before[n] for n in (
        "attn_edge_fused_total", "attn_edge_fallback_total"))


def test_edge_counters_and_what_the_kernels_refuse():
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q, k, g = draw(1, 32, 4, 128), draw(1, 32, 2, 128), draw(1, 32, 4)
    pos = np.arange(32, dtype=np.int32)
    now = lambda: {n: _stat(n) for n in (
        "attn_edge_fused_total", "attn_edge_fallback_total",
        "rope_partial_total")}
    # a layer of 128-wide heads: one rotation, one gate
    before = now()
    jax.jit(lambda q, k: E.rope(q, k, pos, rotary_dim=64,
                                interpret=True)).lower(q, k)
    jax.jit(lambda o, g: E.head_gate(o, g, interpret=True)).lower(q, g)
    assert _edge_counts(before) == (2, 0)
    # the partial rotation is counted on this path as F.rotary_embedding
    # counts it on its own
    assert _stat("rope_partial_total") - before["rope_partial_total"] == 1
    # 64-wide heads, and interleaved pairs at 128: the XLA statement,
    # counted as refused, bit for bit
    narrow = lambda a: a.reshape(a.shape[:2] + (-1, 64))
    before = now()
    got = E.rope(narrow(q), narrow(k), pos, interpret=True)
    y = E.head_gate(narrow(q), jnp.tile(g, 2), interpret=True)
    assert _edge_counts(before) == (0, 2)
    for a, b in zip(got, _xla_rope(narrow(q), narrow(k), pos)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(X.head_gate(narrow(q), jnp.tile(g, 2))))
    before = now()
    got = E.rope(q, k, pos, interleaved=True, interpret=True)
    assert _edge_counts(before) == (0, 1)
    for a, b in zip(got, _xla_rope(q, k, pos, interleaved=True)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # off the TPU and not asked to interpret: the XLA path, uncounted
    before = now()
    E.rope(q, k, pos)
    E.head_gate(q, g)
    assert _edge_counts(before) == (0, 0)


# -- the train step and the counters ------------------------------------------

def test_bf16_step_trains_and_counts():
    paddle_tpu.seed(1)
    cfg = M.LagunaConfig.tiny(recompute=True, experts_held=(0, 4))
    model = M.LagunaForCausalLM(cfg)
    names = ("rope_yarn_total", "rope_partial_total")
    before = {n: _stat(n) for n in names}
    step, state = M.build_train_step(model, weight_decay=0.01, probe=4)
    batch = M.fake_batch(cfg, 2, 32, seed=2)
    losses = []
    for _ in range(4):
        state, loss, aux = step(state, jax.device_put(batch),
                                jnp.float32(2e-3))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05
    # the two full layers rotate half a head by YaRN's frequencies
    assert {n: _stat(n) - before[n] for n in names} == {
        "rope_yarn_total": 2, "rope_partial_total": 2}
    stats = np.asarray(aux["moe_stats"])
    assert stats.shape == (4, 4 + 2)
    assert (stats[:, -2] == 2 * 32 * cfg.num_experts_per_tok).all()
    assert aux["probe_logits"].shape == (2, 4, cfg.vocab_size)
    assert aux["moe_experts"].shape == (4, 64, 2)
    rows = {n: _stat(n) for n in ("moe_rows_routed_total",
                                  "moe_rows_held_total",
                                  "moe_dropped_total")}
    M.record_moe_stats(stats)
    assert _stat("moe_rows_routed_total") - rows["moe_rows_routed_total"] \
        == int(stats[:, -2].sum())
    assert _stat("moe_rows_held_total") - rows["moe_rows_held_total"] \
        == int(stats[:, :-2].sum())
    assert _stat("moe_dropped_total") == rows["moe_dropped_total"]
    # no selection bias: nothing but gradients moves the state
    assert not [k for k in state["params"] if "e_score_correction" in k]


def test_scopes_of_a_traced_step():
    """The names the benchmark's per-layer readers look for."""
    paddle_tpu.seed(2)
    cfg = M.LagunaConfig.tiny(recompute=True)
    model = M.LagunaForCausalLM(cfg)
    step, state = M.build_train_step(model)
    batch = M.fake_batch(cfg, 1, 16)
    text = step.lower(state, batch, jnp.float32(1e-3)).as_text(
        debug_info=True)
    for path in ("layers/1/self_attn/q_proj", "layers/1/self_attn/k_proj",
                 "layers/1/self_attn/v_proj", "layers/1/self_attn/g_proj",
                 "layers/1/self_attn/rope", "layers/1/self_attn/gate",
                 "layers/1/self_attn/o_proj", "layers/4/self_attn/rope",
                 "layers/4/moe/router", "layers/4/moe/shared_experts",
                 "layers/0/mlp"):
        assert path in text, path
