"""SDAR-MoE block-diffusion training against the plain reference
(benchmark/reference/sdar_moe.py — the one the benchmark's `correct`
uses), the dropless expert layer, the share test that ties a chip's
share to the whole layer, and the small parts the model brings."""

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
from paddle_tpu.models import sdar_moe as M
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import sdar_moe as R  # noqa: E402


def _close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


# -- the step against the reference ------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """One float32 step of a tiny model whose attention runs the flash
    kernels in interpret mode (grouped instances: D = 128), with the
    reference's loss, logits and gradients on the same weights."""
    mp = pytest.MonkeyPatch()
    mp.setattr(A, "_flash_ok", lambda q, k: True)
    mp.setattr(A, "flash_attention", functools.partial(
        A.flash_attention, interpret=True))
    try:
        paddle_tpu.seed(3)
        cfg = M.SdarMoeConfig.tiny(
            head_dim=128, experts_held=(2, 4), num_experts_per_tok=3,
            recompute=True, vocab_size=64)
        model = M.SdarMoeForBlockDiffusion(cfg)
        batch = M.fake_batch(cfg, 2, 40, seed=5)
        loss_fn = M.build_blockdiff_loss(model, bf16=False, probe=8)
        params = {k: jnp.array(v)
                  for k, v in functional_state(model).items()}
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch)
        config = dataclasses.asdict(cfg)
        ref = R.forward(config, params, batch)
        ref_grads = R.grads(config, params, batch)
        return dict(cfg=cfg, batch=batch, loss=loss, aux=aux, grads=grads,
                    ref=ref, ref_grads=ref_grads, params=params)
    finally:
        mp.undo()


def test_loss_and_mean_ce_match_reference(trained):
    _close(trained["loss"], trained["ref"]["loss"], 1e-5)
    _close(trained["aux"]["ce"], trained["ref"]["ce"], 1e-5)


def test_masked_position_logits_match_reference(trained):
    pos, valid = M.probe_positions(trained["batch"]["masked"], 8)
    ref = np.take_along_axis(np.asarray(trained["ref"]["logits"]),
                             pos[..., None], axis=1)
    assert valid.sum() > 8
    _close(np.asarray(trained["aux"]["probe_logits"])[valid], ref[valid],
           2e-5)


def test_routing_and_counts(trained):
    cfg, aux = trained["cfg"], trained["aux"]
    for got, ref in zip(aux["moe_experts"], trained["ref"]["experts"]):
        assert (np.sort(got, 1) == np.sort(ref, 1)).all()
    stats = np.asarray(aux["moe_stats"])
    rows = 2 * 2 * 40
    assert (stats[:, -2] == rows * cfg.num_experts_per_tok).all()
    # every held visit was computed: nothing dropped
    assert (stats[:, :-2].sum(1) == stats[:, -1]).all()
    first, count = cfg.experts_held
    for layer, e in enumerate(trained["ref"]["experts"]):
        e = np.asarray(e)
        want = [(e == first + i).sum() for i in range(count)]
        assert stats[layer, :-2].tolist() == want


_LEAVES = ["lm_head.weight", "model.embed_tokens.weight",
           "model.norm.weight"] + [
    f"model.layers.{i}.{n}" for i in (0, 1) for n in (
        "input_layernorm.weight", "post_attention_layernorm.weight",
        "self_attn.q_proj.weight", "self_attn.k_proj.weight",
        "self_attn.v_proj.weight", "self_attn.out_proj.weight",
        "self_attn.q_norm.weight", "self_attn.k_norm.weight",
        "moe.gate_weight", "moe.w_gate", "moe.w_up", "moe.w_down")]


def test_every_leaf_has_a_gradient(trained):
    assert sorted(trained["grads"]) == sorted(_LEAVES)


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_matches_reference(trained, leaf):
    got, want = trained["grads"][leaf], trained["ref_grads"][leaf]
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0
    _close(np.asarray(got) / scale, np.asarray(want) / scale, 2e-4)


def test_bf16_step_trains():
    paddle_tpu.seed(0)
    cfg = M.SdarMoeConfig.tiny(experts_held=(0, 4), recompute=True)
    model = M.SdarMoeForBlockDiffusion(cfg)
    step, state = M.build_blockdiff_train_step(model)
    batch = M.fake_batch(cfg, 2, 16, seed=1)
    losses = []
    for _ in range(4):
        state, loss, aux = step(state, batch, jnp.float32(3e-3))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert abs(float(aux["ce"]) - np.log(cfg.vocab_size)) < 1.0
    assert "probe_logits" not in aux


def test_dense_layers_follow_the_config():
    cfg = M.SdarMoeConfig.tiny(num_hidden_layers=3, mlp_only_layers=(1,))
    model = M.SdarMoeForBlockDiffusion(cfg)
    kinds = [layer.sparse for layer in model.model.layers]
    assert kinds == [True, False, True]
    batch = M.fake_batch(cfg, 1, 8, seed=2)
    params = {k: jnp.array(v) for k, v in functional_state(model).items()}
    loss, aux = M.build_blockdiff_loss(model, bf16=False)(params, batch)
    ref = R.forward(dataclasses.asdict(cfg), params, batch)
    _close(loss, ref["loss"], 1e-5)
    assert aux["moe_stats"].shape == (2, cfg.num_experts + 2)


def test_batch_recipe():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 50, (3, 22), dtype=np.int32)
    b = M.make_blockdiff_batch(ids, 4, 63, rng)
    assert (b["noisy_ids"][b["masked"]] == 63).all()
    assert (b["noisy_ids"][~b["masked"]] == ids[~b["masked"]]).all()
    inv_t = b["inv_t"]
    assert (inv_t >= 1.0).all() and (inv_t <= 1e3).all()
    # one t a block
    for blk in range(5):
        chunk = inv_t[:, 4 * blk:4 * blk + 4]
        assert (chunk == chunk[:, :1]).all()


# -- the expert layer ----------------------------------------------------------

def _ref_layer(p, x, top_k, held, given=None):
    cfg = {"num_experts_per_tok": top_k, "norm_topk_prob": True}
    names = {"gate_weight": p["wr"], "w_gate": p["wg"], "w_up": p["wu"],
             "w_down": p["wd"]}
    with jax.default_matmul_precision("highest"):
        return R.moe_layer(cfg, names, "", x, held, given)[0]


def test_dropless_under_skewed_routing():
    """One expert takes half the rows (four times a fair share and
    more than a chunk): every visit is computed."""
    n, k, t = 12, 2, 96
    p = moe.init_routed_moe_params(0, n, 16, 24)
    x = jnp.asarray(np.random.RandomState(1).normal(size=(t, 16)),
                    jnp.float32)
    rng = np.random.RandomState(2)
    experts = np.stack([np.where(np.arange(t) % 2 == 0, 5,
                                 rng.randint(0, 5, t)),
                        rng.randint(6, 12, t)], axis=1).astype(np.int32)
    weights = jnp.asarray(rng.dirichlet([1, 1], t), jnp.float32)
    out, stats, _ = moe.routed_moe_local(
        p, x, k, routing=(jnp.asarray(experts), weights), chunk=32)
    want = jnp.zeros_like(x)
    for j in range(k):
        for e in range(n):
            y = (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])) @ p["wd"][e]
            want = want + jnp.where((experts[:, j] == e)[:, None],
                                    y * weights[:, j:j + 1], 0)
    _close(out, want, 1e-5)
    stats = np.asarray(stats)
    assert stats[5] == t // 2 and stats[:-2].max() == t // 2
    assert stats[-2] == t * k and stats[-1] == t * k     # none dropped


def test_held_share_leaves_out_absent_experts():
    p = moe.init_routed_moe_params(0, 16, 16, 24)
    x = jnp.asarray(np.random.RandomState(1).normal(size=(50, 16)),
                    jnp.float32)
    share = {"wr": p["wr"], **{k: p[k][4:8] for k in ("wg", "wu", "wd")}}
    out, stats, experts = moe.routed_moe_local(share, x, 4, held=(4, 4),
                                               chunk=64)
    _close(out, _ref_layer(share, x, 4, (4, 4)), 1e-5)
    experts = np.asarray(experts)
    assert np.asarray(stats)[-1] == ((experts >= 4) & (experts < 8)).sum()


def test_layer_gradients_match_dense():
    p = moe.init_routed_moe_params(0, 8, 16, 24)
    x = jnp.asarray(np.random.RandomState(1).normal(size=(40, 16)),
                    jnp.float32)
    f = lambda p, x: jnp.sum(jnp.sin(
        moe.routed_moe_local(p, x, 3, chunk=32)[0]))
    g = lambda p, x: jnp.sum(jnp.sin(_ref_layer(p, x, 3, (0, 8))))
    got, want = jax.grad(f, (0, 1))(p, x), jax.grad(g, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 2e-5)


class TestShares:
    """32 experts in 4 shares of 8: the partial outputs of the four
    shares add up to the uncut reference's layer output, and the
    `ep_axis` path on 4 virtual devices gives the same sum."""

    N, K, H, F, T = 32, 4, 16, 24, 64

    @pytest.fixture(scope="class")
    def setup(self):
        p = moe.init_routed_moe_params(7, self.N, self.H, self.F)
        x = jnp.asarray(np.random.RandomState(8).normal(
            size=(self.T, self.H)), jnp.float32)
        return p, x, _ref_layer(p, x, self.K, (0, self.N))

    def test_four_partial_outputs_add_up(self, setup):
        p, x, whole = setup
        total, computed = jnp.zeros_like(x), 0
        for s in range(4):
            share = {"wr": p["wr"], **{
                k: p[k][8 * s:8 * s + 8] for k in ("wg", "wu", "wd")}}
            out, stats, _ = moe.routed_moe_local(
                share, x, self.K, held=(8 * s, 8), chunk=64)
            total, computed = total + out, computed + int(stats[-1])
        _close(total, whole, 1e-5)
        assert computed == self.T * self.K

    def test_expert_parallel_exchange_gives_the_same_sum(self, setup):
        from jax.sharding import Mesh, PartitionSpec as P

        p, x, whole = setup
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        spec = {"wr": P(), "wg": P("ep"), "wu": P("ep"), "wd": P("ep")}

        def local(p, x):
            out, stats, _ = moe.routed_moe_local(p, x, self.K,
                                                 ep_axis="ep", chunk=64)
            return out, stats[None]

        out, stats = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(spec, P("ep")),
            out_specs=(P("ep"), P("ep")), check_vma=False))(p, x)
        _close(out, whole, 1e-5)
        assert int(np.asarray(stats)[:, -1].sum()) == self.T * self.K

    def test_expert_parallel_gradients(self, setup):
        from jax.sharding import Mesh, PartitionSpec as P

        p, x, _ = setup
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        spec = {"wr": P(), "wg": P("ep"), "wu": P("ep"), "wd": P("ep")}
        sharded = jax.shard_map(
            lambda p, x: moe.routed_moe_local(p, x, self.K, ep_axis="ep",
                                              chunk=64)[0],
            mesh=mesh, in_specs=(spec, P("ep")), out_specs=P("ep"),
            check_vma=False)
        got = jax.grad(lambda p, x: jnp.sum(jnp.sin(sharded(p, x))),
                       (0, 1))(p, x)
        want = jax.grad(lambda p, x: jnp.sum(jnp.sin(_ref_layer(
            p, x, self.K, (0, self.N)))), (0, 1))(p, x)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            _close(a, b, 2e-5)


def test_routed_moe_layer_matches_function():
    paddle_tpu.seed(1)
    layer = nn.RoutedMoE(16, 24, 8, 2, held=(2, 4))
    x = jnp.asarray(np.random.RandomState(0).normal(size=(2, 10, 16)),
                    jnp.float32)
    (out, stats, experts), _ = functional_call(
        layer, functional_state(layer), x)
    p = {"wr": layer.gate_weight._value, "wg": layer.w_gate._value,
         "wu": layer.w_up._value, "wd": layer.w_down._value}
    _close(out.reshape(20, 16), _ref_layer(p, x.reshape(20, 16), 2, (2, 4)),
           1e-5)
    assert stats.shape == (6,) and experts.shape == (20, 2)


# -- the visit plan and the walk ------------------------------------------------

def _plan_case(name):
    """(local expert ids by visit, count, chunk, rows a visit)."""
    rng = np.random.RandomState(11)
    return {
        # 96 visits in chunks of 40: 24 of padding
        "random_padded": (rng.randint(0, 6, 96), 5, 40, 3),
        "random_unpadded": (rng.randint(0, 6, 96), 5, 32, 3),
        "one_chunk": (rng.randint(0, 6, 30), 5, 64, 2),
        "all_on_one_held_expert": (np.full(64, 2), 4, 24, 4),
        "none_held": (np.full(64, 4), 4, 24, 4),
        "one_expert_held": (rng.randint(0, 2, 50), 1, 16, 1),
    }[name]


@pytest.mark.parametrize("sort", ["packed", "two_operand"])
@pytest.mark.parametrize("case", [
    "random_padded", "random_unpadded", "one_chunk",
    "all_on_one_held_expert", "none_held", "one_expert_held"])
def test_visit_plan_is_the_stable_sort(case, sort, monkeypatch):
    """Both forms of the plan, bit for bit: the stable argsort of the
    expert ids, the rows and ids gathered in that order, the weights
    left by visit; padding visits are absent, of row 0, and own the
    padded weights' indices."""
    local, count, chunk, per_row = _plan_case(case)
    m = len(local)
    weights = np.random.RandomState(12).rand(m).astype(np.float32)
    if sort == "two_operand":
        monkeypatch.setattr(moe, "_packed_key_bits", lambda c, v: None)
    before = paddle_tpu.profiler.get_int_stats().get(
        f"moe_plan_{sort}_total", 0)
    (w, order, tok, eid, n_valid), chunk = jax.jit(
        lambda l, w: moe._visit_plan(l, w, count, chunk, per_row))(
        jnp.asarray(local, jnp.int32), jnp.asarray(weights))
    assert paddle_tpu.profiler.get_int_stats()[
        f"moe_plan_{sort}_total"] == before + 1
    want = np.argsort(local, kind="stable")
    pad = -m % chunk
    assert len(order) == m + pad and (m + pad) % chunk == 0
    assert all(a.dtype == jnp.int32 for a in (order, tok, eid, n_valid))
    np.testing.assert_array_equal(order[:m], want)
    np.testing.assert_array_equal(tok[:m], want // per_row)
    np.testing.assert_array_equal(eid[:m], local[want])
    np.testing.assert_array_equal(order[m:], np.arange(m, m + pad))
    assert (np.asarray(tok[m:]) == 0).all()
    assert (np.asarray(eid[m:]) == count).all()
    np.testing.assert_array_equal(w[:m], weights)
    assert (np.asarray(w[m:]) == 0).all()
    assert int(n_valid) == (local < count).sum()


@pytest.mark.parametrize("count,visits,bits", [
    (16, 262144, 18),           # the sdar cell's layer
    (16, 262145, 19),
    (128, 1, 1),
    (127, 1 << 24, 24),         # the largest key is 2**31 - 1
    (128, 1 << 24, None),       # one expert more: int32 overflows
    (16, (1 << 27) + 1, None),
    (3, 1 << 29, 29),
    (4, 1 << 29, None),
])
def test_plan_form_follows_the_shapes(count, visits, bits):
    """The packed key is taken exactly where (absent id << bits) |
    (visits - 1) fits int32; nothing is allocated to find out."""
    assert moe._packed_key_bits(count, visits) == bits
    if bits is not None:
        assert visits - 1 < 1 << bits
        assert (count << bits) | (visits - 1) <= np.iinfo(np.int32).max


@pytest.mark.parametrize("n_valid,bodies", [
    (0, 0), (1, 1), (31, 1), (32, 1), (33, 2), (64, 2), (65, 3), (96, 3)])
def test_walk_visits_the_chunks_that_hold_work(n_valid, bodies):
    starts = jax.jit(lambda n: moe._walk(
        n, 32, (jnp.int32(0), jnp.full((3,), -1, jnp.int32)),
        lambda c, start: (c[0] + 1, c[1].at[c[0]].set(start))))(
        jnp.int32(n_valid))
    assert int(starts[0]) == bodies
    assert starts[1].tolist() == [0, 32, 64][:bodies] + [-1] * (3 - bodies)


def test_no_held_visit_is_an_empty_walk(monkeypatch):
    """Every visit lands on an expert held elsewhere: no chunk is
    walked, the output is zero, and so are the gradients (finite: the
    walk's carries start at zero)."""
    t, k = 40, 2
    p = moe.init_routed_moe_params(0, 8, 16, 24, held=(0, 4))
    x = jnp.asarray(np.random.RandomState(1).normal(size=(t, 16)),
                    jnp.float32)
    rng = np.random.RandomState(2)
    experts = jnp.asarray(rng.randint(4, 8, (t, k)), jnp.int32)
    weights = jnp.asarray(rng.dirichlet([1, 1], t), jnp.float32)
    walked = []
    walk = moe._walk

    def counting(n_valid, chunk, carry, active):
        walked.append((n_valid + chunk - 1) // chunk)
        return walk(n_valid, chunk, carry, active)

    def f(p, x, weights):
        out, stats, _ = moe.routed_moe_local(
            p, x, k, held=(0, 4), routing=(experts, weights), chunk=16)
        return jnp.sum(out * jnp.cos(x)), (out, stats)

    monkeypatch.setattr(moe, "_walk", counting)
    grads, (out, stats) = jax.grad(f, (0, 1, 2), has_aux=True)(
        p, x, weights)
    assert [int(n) for n in walked] == [0, 0]     # forward, backward
    assert not np.asarray(out).any()
    assert np.asarray(stats).tolist() == [0, 0, 0, 0, t * k, 0]
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all() and not np.asarray(g).any()


def _count_primitive(jaxpr, name):
    """Equations of primitive `name` in `jaxpr` and every jaxpr its
    equations carry (checkpoint, custom_vjp, while, pjit bodies)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitive(sub, name)
    return n


class TestPlanBuiltOnce:
    """One tiny decoder layer under the model's own per-layer
    `jax.checkpoint`: the policy keeps the expert layer's visit plan,
    so the gradient sorts once, and recomputing changes no bit."""

    def _grads(self, recompute):
        paddle_tpu.seed(4)
        cfg = M.SdarMoeConfig.tiny(num_hidden_layers=1, experts_held=(2, 4),
                                   recompute=recompute)
        model = M.SdarMoeModel(cfg)
        params = {k: jnp.array(v)
                  for k, v in functional_state(model).items()}
        ids = jnp.asarray(np.random.RandomState(5).randint(0, 96, (2, 24)),
                          jnp.int32)

        def loss(params):
            (hidden, _), _ = functional_call(model, params, ids,
                                             np.arange(24, dtype=np.int32))
            return jnp.sum(jnp.sin(hidden))

        return jax.grad(loss), params

    def test_gradient_holds_one_sort_a_layer(self):
        grad, params = self._grads(recompute=True)
        stats = paddle_tpu.profiler.get_int_stats
        before = stats().get("moe_plan_packed_total", 0), stats().get(
            "moe_plan_two_operand_total", 0)
        jaxpr = jax.make_jaxpr(grad)(params).jaxpr
        assert _count_primitive(jaxpr, "sort") == 1
        # nor a second choice of experts: the router's backward reads
        # the forward pass's ids, as the kept plan does
        assert _count_primitive(jaxpr, "top_k") == 1
        assert _count_primitive(jaxpr, "remat2") >= 1     # jax.checkpoint
        assert (stats()["moe_plan_packed_total"],
                stats().get("moe_plan_two_operand_total", 0)) \
            == (before[0] + 1, before[1])

    def test_a_checkpoint_without_the_policy_sorts_twice(self):
        """What a model that wraps the layer in a plain `jax.checkpoint`
        gets: the behaviour before the plan had a name."""
        p = moe.init_routed_moe_params(0, 8, 16, 24)
        x = jnp.ones((12, 16), jnp.float32)
        f = jax.checkpoint(lambda p, x: jnp.sum(
            moe.routed_moe_local(p, x, 2, chunk=8)[0]))
        assert _count_primitive(
            jax.make_jaxpr(jax.grad(f))(p, x).jaxpr, "sort") == 2

    def test_gradients_bit_equal_to_no_recomputation(self):
        grad, params = self._grads(recompute=True)
        plain, _ = self._grads(recompute=False)
        got, want = jax.jit(grad)(params), jax.jit(plain)(params)
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=k)


# -- the small parts -----------------------------------------------------------

def test_rms_norm():
    paddle_tpu.seed(0)
    layer = nn.RMSNorm(12, epsilon=1e-6)
    x = np.random.RandomState(0).normal(size=(3, 5, 12)).astype(np.float32)
    layer.weight.set_value(np.linspace(0.5, 1.5, 12).astype(np.float32))
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.linspace(0.5, 1.5, 12)
    _close(layer(nn.layer.layers.Tensor(x)).numpy(), want, 1e-5)
    # the result keeps the input's dtype, the statistics are float32
    y = nn.functional.rms_norm(jnp.asarray(x, jnp.bfloat16))
    assert y._value.dtype == jnp.bfloat16


class TestRotary:
    def _qk(self, s=9, h=2, d=16):
        r = np.random.RandomState(0)
        return (jnp.asarray(r.normal(size=(1, s, h, d)), jnp.float32),
                jnp.asarray(r.normal(size=(1, s, h, d)), jnp.float32))

    def test_rotation_preserves_norms(self):
        q, k = self._qk()
        rq, rk = nn.functional.rotary_embedding(q, k, np.arange(9), 1e4)
        _close(jnp.linalg.norm(rq._value, axis=-1),
               jnp.linalg.norm(q, axis=-1), 1e-5)
        _close(rk._value[:, 0], k[:, 0], 1e-6)      # position 0: identity

    def test_scores_depend_on_relative_position_only(self):
        q, k = self._qk(s=1)
        rot = lambda x, pos: nn.functional.rotary_embedding(
            x, x, np.array([pos]), 1e4)[0]._value
        score = lambda m, n: float(jnp.sum(rot(q, m) * rot(k, n)))
        assert abs(score(7, 3) - score(104, 100)) < 1e-3
        assert abs(score(7, 3) - score(7, 4)) > 1e-3

    def test_matches_reference_convention(self):
        q, k = self._qk()
        rq, _ = nn.functional.rotary_embedding(q, k, np.arange(9), 1e6)
        _close(rq._value, R._rope(q, jnp.arange(9), 1e6), 1e-6)


def test_gated_ffn():
    paddle_tpu.seed(0)
    layer = nn.GatedFFN(8, 20)
    x = np.random.RandomState(0).normal(size=(4, 8)).astype(np.float32)
    g, u, d = (getattr(layer, n).weight.numpy() for n in (
        "gate_proj", "up_proj", "down_proj"))
    silu = lambda z: z / (1 + np.exp(-z))
    _close(layer(nn.layer.layers.Tensor(x)).numpy(),
           (silu(x @ g) * (x @ u)) @ d, 1e-5)
    assert layer.gate_proj.bias is None


def test_grouped_query_attention_matches_repeated_kv_mha():
    """GQA(8 heads, 2 kv heads) == plain attention over the same
    projections with every kv head repeated 4 times."""
    paddle_tpu.seed(0)
    layer = nn.GroupedQueryAttention(32, 8, 2, head_dim=8, qk_norm=True,
                                     rope_theta=1e4)
    layer.eval()
    x = jnp.asarray(np.random.RandomState(0).normal(size=(2, 11, 32)),
                    jnp.float32)
    pos = np.arange(11)
    out, _ = functional_call(layer, functional_state(layer), x, pos,
                             is_causal=True)
    w = {n: getattr(layer, n).weight._value for n in (
        "q_proj", "k_proj", "v_proj", "out_proj")}
    q = (x @ w["q_proj"]).reshape(2, 11, 8, 8)
    k = (x @ w["k_proj"]).reshape(2, 11, 2, 8)
    v = (x @ w["v_proj"]).reshape(2, 11, 2, 8)
    q = R._rope(R._rms_norm(q, layer.q_norm.weight._value, 1e-6),
                jnp.arange(11), 1e4)
    k = R._rope(R._rms_norm(k, layer.k_norm.weight._value, 1e-6),
                jnp.arange(11), 1e4)
    ref = A._xla_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                           is_causal=True)
    _close(out, ref.reshape(2, 11, 64) @ w["out_proj"], 1e-5)
