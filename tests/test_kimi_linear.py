"""Kimi Linear: the chunked gated delta-rule scan (interpret-mode
kernels: their chunk-local half against its float32 statement, the
whole and its hand-written backward) against the recurrence a token
at a time, the model against the plain reference (benchmark/reference/
kimi_linear.py — the one the benchmark's `correct` uses), the share
test that ties a chip's share to the whole layer, latent attention
without a query latent and without rotation, the layer's two
elementwise passes around the scan (ops/pallas/kda_edge.py, interpret
mode) against their XLA statement, and the counters."""

import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import nn, profiler
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.models import kimi_linear as M
from paddle_tpu.nn.functional import kda as X
from paddle_tpu.ops.pallas import kda as K
from paddle_tpu.ops.pallas import kda_edge as E

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import kimi_linear as R  # noqa: E402

SCALE = 128 ** -0.5
OPERANDS = ("q", "k", "v", "g", "beta")


def _operands(b, s, h, d, seed=0, g_min=-0.5):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(rng.normal(size=(b, s, h, d))) for _ in range(2))
    v = rng.normal(size=(b, s, h, d))
    g = rng.uniform(g_min, 0.0, size=(b, s, h, d))
    beta = rng.uniform(0.05, 0.95, size=(b, s, h))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the scan against the recurrence -----------------------------------------

def _out_and_grads(scan, args, seed=9):
    w = jnp.asarray(np.random.default_rng(seed).normal(size=args[2].shape),
                    jnp.float32)
    out, vjp = jax.vjp(scan, *args)
    return out, vjp(w)


@functools.lru_cache(maxsize=None)
def _scan_and_recurrence(s, g_min, heads, batch):
    """Outputs and all five operands' gradients of the chunked scan
    (interpret-mode kernels) and of the recurrence, float32."""
    args = _operands(batch, s, heads, 128, seed=s, g_min=g_min)
    return (_out_and_grads(lambda *a: K.kda_attention(*a, interpret=True),
                           args),
            _out_and_grads(lambda *a: X.recurrent(*a, SCALE), args))


# S = 64, 192, a length that is no multiple of 64; strong decay (g down
# to -5 a token: -320 cumulated in a chunk); one head, an odd count (a
# head a grid step), 8 (two a grid step); batch 1 and 2
CASES = [(64, -0.5, 2, 2), (192, -0.5, 8, 1), (100, -0.5, 3, 2),
         (128, -5.0, 2, 2), (128, -0.5, 1, 2)]


@pytest.mark.parametrize("s,g_min,heads,batch", CASES)
def test_chunked_scan_output_matches_recurrence(s, g_min, heads, batch):
    (out, _), (ref, _) = _scan_and_recurrence(s, g_min, heads, batch)
    assert out.shape == ref.shape == (batch, s, heads, 128)
    assert bool(jnp.isfinite(out).all())
    assert _rel(out, ref) < 1e-5


@pytest.mark.parametrize("operand", range(5), ids=OPERANDS)
@pytest.mark.parametrize("s,g_min,heads,batch", CASES)
def test_chunked_scan_gradient_matches_recurrence(s, g_min, heads, batch,
                                                  operand):
    (_, got), (_, want) = _scan_and_recurrence(s, g_min, heads, batch)
    assert bool(jnp.isfinite(got[operand]).all())
    assert _rel(got[operand], want[operand]) < 2e-5


# -- the kernels' chunk-local half against its float32 statement -------------

LOCAL = ("W", "U0", "Qg", "Kg", "Aqk", "d")


def _kernel_local(q, k, v, g, beta):
    """W, U0, Qg, Kg, Aqk, d of every chunk and head as the kernels'
    body makes them in VMEM (ops/pallas/kda.py: `_chunk_matmuls`,
    `_chunk_local`), through a thin interpret-mode harness, in
    `X._local`'s layouts."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    n = s // X.CHUNK

    def body(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u0_ref, qg_ref,
             kg_ref, aqk_ref, d_ref, g_scr, k_scr):
        x = K._chunk_local(K._chunk_matmuls(
            0, 1, q_ref, k_ref, v_ref, g_ref, beta_ref,
            jnp.zeros((d, d), jnp.float32), g_scr, k_scr, SCALE))
        w_ref[0] = K._dot(x.t, x.bk, K._NN)
        u0_ref[0] = x.u                 # T (bv - bk S) at S = 0
        qg_ref[0], kg_ref[0] = x.qg, x.kg
        aqk_ref[0, 0, 0], d_ref[0, 0, 0] = x.aqk, x.d

    rows, beta_rows, _, _ = K._specs(n, h, 1, False)
    at = lambda shape: pl.BlockSpec((1, 1, 1) + shape,
                                    lambda b, i, c: (b, c, i, 0, 0))
    flat = lambda a: a.reshape(b, s, -1)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w, u0, qg, kg, aqk, dd = pl.pallas_call(
        body, grid=(b, h, n), in_specs=[rows] * 4 + [beta_rows],
        out_specs=[rows] * 4 + [at((X.CHUNK, X.CHUNK)), at((1, d))],
        out_shape=[f32(b, s, h * d)] * 4 + [f32(b, n, h, X.CHUNK, X.CHUNK),
                                            f32(b, n, h, 1, d)],
        scratch_shapes=[pltpu.VMEM((X.CHUNK, d), jnp.float32)] * 2,
        interpret=True)(flat(q), flat(k), flat(v), flat(g), beta)
    cut = lambda a: a.reshape(b, n, X.CHUNK, h, d)
    return cut(w), cut(u0), cut(qg), cut(kg), aqk, dd[:, :, :, 0]


def _cut(args):
    b, s = args[0].shape[:2]
    return tuple(a.reshape((b, s // X.CHUNK, X.CHUNK) + a.shape[2:])
                 for a in args)


@functools.lru_cache(maxsize=None)
def _local_both(g_min):
    args = _operands(2, 128, 3, 128, seed=7, g_min=g_min)
    return _kernel_local(*args), X._local(*_cut(args), SCALE)


@pytest.mark.parametrize("g_min", [-0.5, -5.0])
@pytest.mark.parametrize("quantity", range(6), ids=LOCAL)
def test_kernel_chunk_local_matches_its_float32_statement(quantity, g_min):
    got, want = _local_both(g_min)
    assert got[quantity].shape == want[quantity].shape
    assert bool(jnp.isfinite(got[quantity]).all())
    # (at -5 a token d underflows to 0 in both)
    assert float(jnp.linalg.norm(got[quantity] - want[quantity])) \
        <= 1e-5 * float(jnp.linalg.norm(want[quantity]))


def _chunked_oracle(q, k, v, g, beta):
    """The chunked form in XLA: `X._local` and the three lines that need
    the state, a `lax.scan` over chunks — autodiff of it is `jax.vjp(
    _local)` composed with the lines the backward kernel transposes."""
    b, s, h, d = q.shape
    local = X._local(*_cut((q, k, v, g, beta)), SCALE)
    ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def chunk(state, x):                            # state (B, H, dk, dv)
        w, u0, qg, kg, aqk, dd = x
        u = u0 - ein("bchk,bhkv->bchv", w, state)
        o = ein("bchk,bhkv->bchv", qg, state) + ein("bhij,bjhv->bihv", aqk, u)
        return dd[..., None] * state + ein("bchk,bchv->bhkv", kg, u), o

    _, o = jax.lax.scan(chunk, jnp.zeros((b, h, d, d), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in local))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, h, d)


@functools.lru_cache(maxsize=None)
def _kernel_and_oracle():
    args = _operands(1, 192, 2, 128, seed=11, g_min=-1.0)
    return (_out_and_grads(lambda *a: K.kda_attention(*a, interpret=True),
                           args), _out_and_grads(_chunked_oracle, args))


@pytest.mark.parametrize("operand", range(5), ids=OPERANDS)
def test_hand_written_backward_matches_autodiff_of_the_chunked_form(operand):
    """dq, dk, dv, dg, dbeta of `kda_bwd` (three chunks: dS is carried)
    against `jax.vjp` through `_local` and the walk's lines."""
    (out, got), (ref, want) = _kernel_and_oracle()
    assert _rel(out, ref) < 1e-5
    assert _rel(got[operand], want[operand]) < 2e-5


@functools.lru_cache(maxsize=None)
def _repeated_keys():
    """Two keys a head, each repeated over the whole sequence, beta ->
    1, hardly any decay: I + A is then as far from the identity as it
    gets, and a finite Neumann product of A is wrong."""
    q, k, v, g, beta = _operands(1, 128, 2, 128, seed=13, g_min=-0.01)
    k = jnp.broadcast_to(k[:, :2], (1, 64, 2, 2, 128)).reshape(1, 128, 2, 128)
    args = (q, k, v, g, jnp.full_like(beta, 0.999))
    return (_out_and_grads(lambda *a: K.kda_attention(*a, interpret=True),
                           args),
            _out_and_grads(lambda *a: X.recurrent(*a, SCALE), args))


@pytest.mark.parametrize("what", range(6), ids=("out",) + OPERANDS)
def test_repeated_keys_with_beta_near_one(what):
    (out, got), (ref, want) = _repeated_keys()
    a, b = ((out,) + got)[what], ((ref,) + want)[what]
    assert bool(jnp.isfinite(a).all())
    assert _rel(a, b) < 1e-4


def test_inverse_of_unit_lower_triangular():
    rng = np.random.default_rng(0)
    a = jnp.asarray(np.tril(0.1 * rng.normal(size=(3, 64, 64)), -1),
                    jnp.float32)
    t = X.inv_unit_lower(a)
    np.testing.assert_allclose(
        np.asarray(t @ (jnp.eye(64) + a)), np.broadcast_to(np.eye(64),
                                                           (3, 64, 64)),
        atol=2e-4)
    # its hand-written cotangent against autodiff through a solve
    w = jnp.asarray(rng.normal(size=(3, 64, 64)), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(X.inv_unit_lower(a) * w))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(64) + a) * w))(a)
    assert _rel(jnp.tril(got, -1), jnp.tril(want, -1)) < 1e-3


def test_exponents_never_positive_under_strong_decay():
    """The kernels' chunk-local quantities stay finite and bounded at -5
    a token (-320 cumulated): no exponent above 0 is ever formed."""
    local = _kernel_local(*_operands(1, 128, 2, 128, seed=3, g_min=-5.0))
    assert all(bool(jnp.isfinite(a).all()) for a in local)
    # |Aqk_ij| <= scale |q_i| |k_j|, the decay a contraction
    assert float(jnp.abs(local[4]).max()) <= SCALE * 1.0001
    # Qg, Kg and d are decays of q and k: never above them
    assert float(jnp.abs(local[3]).max()) <= 1.0001
    assert float(local[5].max()) <= 1.0


def test_short_conv_is_causal_and_depthwise():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    y = np.asarray(X.short_conv(x, taps))
    want = np.zeros_like(y)
    for t in range(9):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(taps[i]) * np.asarray(x[:, t - 3 + i])
    np.testing.assert_allclose(y, want, atol=1e-6)


# -- counters ----------------------------------------------------------------

def _delta(before, name):
    return profiler.get_int_stats().get(name, 0) - before.get(name, 0)


def test_counters_count_what_was_traced():
    args = _operands(1, 130, 2, 128)
    small = _operands(1, 10, 2, 16)
    before = profiler.get_int_stats()
    jax.jit(lambda *a: K.kda_attention(*a, interpret=True)).lower(*args)
    assert (_delta(before, "kda_chunked_total"),
            _delta(before, "kda_chunks_total"),
            _delta(before, "kda_fallback_total")) == (1, 3, 0)
    # a head width the kernels refuse: the recurrence, counted
    before = profiler.get_int_stats()
    out = K.kda_attention(*small, interpret=True)
    assert (_delta(before, "kda_chunked_total"),
            _delta(before, "kda_fallback_total")) == (0, 1)
    assert _rel(out, X.recurrent(*small, 16 ** -0.5)) < 1e-6
    # off the TPU and not asked to interpret: the XLA path, uncounted
    before = profiler.get_int_stats()
    K.kda_attention(*args)
    assert (_delta(before, "kda_chunked_total"),
            _delta(before, "kda_fallback_total")) == (0, 0)


# -- the layer's edge: one pass before the scan, one after -------------------

PRE_IN = ("q_raw", "k_raw", "v_raw", "f", "q_conv1d", "k_conv1d",
          "v_conv1d", "dt_bias", "A_log")
PRE_OUT = ("q", "k", "v", "g")
POST_IN = ("o", "gate", "o_norm")
# (batch, tokens, heads, row tile): three tiles with four heads a grid
# step's worth of one; a length that is no multiple of the tile, two
# heads; three heads (a head a grid step), a tile of two loop steps
EDGE_CASES = [(1, 96, 1, 32), (2, 100, 2, 32), (1, 64, 3, 64)]


def _edge_operands(b, s, h, seed=0):
    rng = np.random.default_rng(seed)
    w = h * 128
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pre = (draw(b, s, w), draw(b, s, w), draw(b, s, w), draw(b, s, w) - 2.0,
           draw(4, w) / 2, draw(4, w) / 2, draw(4, w) / 2, draw(w) - 3.0,
           jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32)))
    return pre, (draw(b, s, w), draw(b, s, w), draw(128))


@functools.lru_cache(maxsize=None)
def _pre_both(b, s, h, tile):
    """(outputs, the nine cotangents) of `kda_pre` through the kernels
    and of its XLA statement, float32."""
    pre, _ = _edge_operands(b, s, h)
    w = tuple(jnp.asarray(np.random.default_rng(5 + i).normal(
        size=pre[0].shape), jnp.float32) for i in range(4))
    both = []
    for fn in (functools.partial(E.kda_pre, interpret=True, tile=tile),
               X.edge_pre):
        out, vjp = jax.vjp(fn, *pre)
        both.append((out, vjp(w)))
    return both


@functools.lru_cache(maxsize=None)
def _post_both(b, s, h, tile):
    _, post = _edge_operands(b, s, h)
    w = jnp.asarray(np.random.default_rng(9).normal(size=post[0].shape),
                    jnp.float32)
    both = []
    for fn in (lambda *a: E.kda_post(*a, 1e-5, interpret=True, tile=tile),
               lambda *a: X.edge_post(*a, 1e-5)):
        out, vjp = jax.vjp(fn, *post)
        both.append((out,) + vjp(w))
    return both


@pytest.mark.parametrize("out", range(4), ids=PRE_OUT)
@pytest.mark.parametrize("case", EDGE_CASES)
def test_fused_pre_output_matches_its_xla_statement(case, out):
    (got, _), (want, _) = _pre_both(*case)
    assert got[out].shape == want[out].shape
    assert got[out].dtype == want[out].dtype
    assert _rel(got[out], want[out]) < 1e-6


@pytest.mark.parametrize("operand", range(9), ids=PRE_IN)
@pytest.mark.parametrize("case", EDGE_CASES)
def test_fused_pre_cotangent_matches_its_xla_statement(case, operand):
    """The hand-written backward: the four raw operands, the three tap
    matrices, dt_bias and A_log (its lanes summed to heads)."""
    (_, got), (_, want) = _pre_both(*case)
    assert got[operand].shape == want[operand].shape
    assert _rel(got[operand], want[operand]) < 1e-5


@pytest.mark.parametrize("what", range(4), ids=("out",) + POST_IN)
@pytest.mark.parametrize("case", EDGE_CASES)
def test_fused_post_matches_its_xla_statement(case, what):
    got, want = _post_both(*case)
    assert got[what].shape == want[what].shape
    assert _rel(got[what], want[what]) < 1e-5


@pytest.mark.parametrize("operand", range(3), ids=PRE_IN[:3])
def test_fused_pre_backward_reaches_into_the_tile_before(operand):
    """A cotangent that is non-zero only in the first rows of ONE tile
    (the third of four): the convolution's pull-back reaches 3 rows
    ahead, so the raw operand's gradient lands in the last 3 rows of
    the tile BEFORE (carried there in VMEM by the backward walk), and
    nowhere earlier."""
    tile, first = 32, 64
    pre, _ = _edge_operands(1, 128, 2, seed=3)
    w = [jnp.zeros_like(pre[0]) for _ in range(4)]
    w[operand] = w[operand].at[:, first:first + 2].set(1.0)
    grads = []
    for fn in (functools.partial(E.kda_pre, interpret=True, tile=tile),
               X.edge_pre):
        grads.append(jax.vjp(fn, *pre)[1](tuple(w))[operand])
    got, want = grads
    assert _rel(got, want) < 1e-5
    before = np.abs(np.asarray(got[0, first - 3:first]))
    assert before.min(axis=-1).max() > 0        # rows 61..63 of tile 1
    assert float(jnp.abs(got[0, :first - 3]).max()) == 0.0
    assert float(jnp.abs(got[0, first + 2:]).max()) == 0.0


@pytest.mark.parametrize("token", [32, 45])
def test_fused_pre_forward_is_causal(token):
    """A change at token t (a tile's first row; a row inside one)
    leaves every earlier row of q, k, v, g as it was, bit for bit, and
    moves row t."""
    pre, _ = _edge_operands(1, 96, 1, seed=4)
    moved = tuple(a.at[:, token].add(1.0) for a in pre[:4]) + pre[4:]
    run = functools.partial(E.kda_pre, interpret=True, tile=32)
    for a, b in zip(run(*pre), run(*moved)):
        assert bool((a[:, :token] == b[:, :token]).all())
        assert float(jnp.abs(a[:, token] - b[:, token]).max()) > 0


def test_edge_counters_and_the_width_the_kernels_refuse():
    pre, post = _edge_operands(1, 40, 2)
    narrow_pre = tuple(a[..., :128] for a in pre[:8]) + (pre[8],)
    narrow_post = (post[0][..., :128], post[1][..., :128], post[2][:64])
    before = profiler.get_int_stats()
    jax.jit(functools.partial(E.kda_pre, interpret=True)).lower(*pre)
    jax.jit(lambda *a: E.kda_post(*a, 1e-5, interpret=True)).lower(*post)
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (2, 0)
    # heads of 64 channels: the XLA statement, counted as refused
    before = profiler.get_int_stats()
    got = E.kda_pre(*narrow_pre, interpret=True)
    y = E.kda_post(*narrow_post, 1e-5, interpret=True)
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (0, 2)
    for a, b in zip(got, X.edge_pre(*narrow_pre)):
        assert _rel(a, b) < 1e-6
    assert _rel(y, X.edge_post(*narrow_post, 1e-5)) < 1e-6
    # off the TPU and not asked to interpret: the XLA path, uncounted
    before = profiler.get_int_stats()
    E.kda_pre(*narrow_pre)
    E.kda_pre(*pre)
    E.kda_post(*post, 1e-5)
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (0, 0)


# -- the model against the reference -----------------------------------------

def _params(model, bias=0.0, seed=0):
    params = {k: jnp.array(v) for k, v in functional_state(model).items()}
    rng = np.random.default_rng(seed)
    for k in M.bias_names(params):
        params[k] = jnp.asarray(rng.uniform(-bias, bias, params[k].shape),
                                jnp.float32)
    return params


def _reference_config(cfg):
    c = dataclasses.asdict(cfg)
    return {**c, "n_routed_experts": c["num_experts"],
            "num_experts_per_tok": c["num_experts_per_token"],
            "norm_topk_prob": c["moe_renormalize"]}


@pytest.fixture(scope="module")
def trained():
    """One float32 loss-and-gradient pass of a tiny model (KDA, KDA,
    latent, KDA; the dense FFN first) whose scans run the chunked
    kernels in interpret mode at the published head width, with the
    reference's loss, logits and gradients on the same weights and
    non-zero selection biases."""
    mp = pytest.MonkeyPatch()
    mp.setattr(K, "kda_attention", functools.partial(K.kda_attention,
                                                     interpret=True))
    before = profiler.get_int_stats()
    try:
        paddle_tpu.seed(3)
        cfg = M.KimiLinearConfig.tiny(
            experts_held=(2, 4), num_experts_per_token=3, recompute=True,
            vocab_size=64)
        model = M.KimiLinearForCausalLM(cfg)
        batch = M.fake_batch(cfg, 2, 40, seed=5)
        params = _params(model, bias=0.05)
        fixed = {k: params[k] for k in M.bias_names(params)}
        train = {k: v for k, v in params.items() if k not in fixed}
        loss_fn = M.build_loss(model, bf16=False, probe=8)
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn({**p, **fixed}, batch), has_aux=True))(train)
        config = _reference_config(cfg)
        ref = R.forward(config, params, batch,
                        probe=M.probe_positions(40, 8))
        ref_grads = R.grads(config, params, batch)
    finally:
        mp.undo()
    return dict(cfg=cfg, loss=loss, aux=aux, grads=grads, ref=ref,
                ref_grads=ref_grads, params=params,
                chunked=_delta(before, "kda_chunked_total"))


def test_loss_and_logits_match_reference(trained):
    assert abs(float(trained["loss"]) - float(trained["ref"]["loss"])) < 1e-5
    np.testing.assert_allclose(np.asarray(trained["aux"]["probe_logits"]),
                               np.asarray(trained["ref"]["logits"]),
                               atol=2e-5)
    # 3 KDA layers, all chunked (counted where traced: a recomputed
    # forward replays the trace)
    assert trained["chunked"] == 3


def test_routing_matches_reference(trained):
    for got, want in zip(trained["aux"]["moe_experts"],
                         trained["ref"]["experts"]):
        assert (np.sort(np.asarray(got), 1)
                == np.sort(np.asarray(want), 1)).all()
    assert np.asarray(trained["aux"]["moe_load"]).sum(1).tolist() \
        == [2 * 40 * 3] * 3


# the leaves the benchmark's `correct` compares, and a few beside them
_LEAVES = ["model.layers.3.self_attn.k_proj.weight",
           "model.layers.3.self_attn.f_b_proj.weight",
           "model.layers.3.self_attn.A_log",
           "model.layers.3.self_attn.dt_bias",
           "model.layers.3.self_attn.k_conv1d.weight",
           "model.layers.3.self_attn.b_proj.weight",
           "model.layers.3.self_attn.o_norm.weight",
           "model.layers.2.self_attn.kv_b_proj.weight",
           "model.layers.2.self_attn.q_proj.weight",
           "model.layers.3.moe.w_down", "model.layers.1.moe.gate_weight",
           "model.layers.0.self_attn.v_conv1d.weight",
           "model.embed_tokens.weight"]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_matches_reference(trained, leaf):
    assert _rel(trained["grads"][leaf], trained["ref_grads"][leaf]) < 2e-4


def test_all_gradients_match_reference_and_no_bias_has_one(trained):
    assert set(trained["grads"]) == set(trained["ref_grads"])
    assert not any(k.endswith(M.BIAS_LEAF) for k in trained["grads"])
    worst = max(_rel(trained["grads"][k], trained["ref_grads"][k])
                for k in trained["grads"])
    assert worst < 2e-4


def test_layer_kinds_follow_the_published_lists():
    cfg = M.KimiLinearConfig()
    kinds = [cfg.kind(i) for i in range(27)]
    assert kinds.count("kda") == 20 and kinds.count("mla") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert not cfg.is_sparse(0) and all(cfg.is_sparse(i)
                                        for i in range(1, 27))
    with pytest.raises(ValueError, match="neither"):
        M.KimiLinearConfig.tiny(num_hidden_layers=5)


def test_bf16_step_trains_moves_the_biases_and_spares_the_taps():
    paddle_tpu.seed(4)
    cfg = M.KimiLinearConfig.tiny(experts_held=(0, 4), recompute=True)
    model = M.KimiLinearForCausalLM(cfg)
    step, state = M.build_train_step(model, weight_decay=0.5)
    biases = M.bias_names(state["params"])
    assert len(biases) == 3
    before = {k: np.asarray(v) for k, v in state["params"].items()}
    batch = M.fake_batch(cfg, 2, 24, seed=1)
    losses = []
    for _ in range(3):
        state, loss, aux = step(state, batch, jnp.float32(3e-3))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(float(jnp.abs(state["params"][k]).max()) > 0 for k in biases)
    # A_log and dt_bias stay float32 in the working copy (scope `cast`)
    # no weight decay on the taps: at learning rate r and decay 0.5 a
    # decayed matrix moves by at least r * 0.5 * |w| beyond Adam's step
    tap = "model.layers.0.self_attn.k_conv1d.weight"
    moved = np.abs(np.asarray(state["params"][tap]) - before[tap]).max()
    assert moved <= 3 * 3e-3 * 1.01


# -- the share test ----------------------------------------------------------

def test_four_shares_and_what_every_chip_computes_once_equal_the_layer():
    """A sparse KDA layer of a 32-expert tiny model cut into 4 shares of
    8 experts: the routed parts the shares give, with attention (KDA),
    router and shared expert counted once, add up to the uncut
    reference's layer output; and the same for the latent layer."""
    for kind, index in (("kda", 1), ("mla", 2)):
        paddle_tpu.seed(11)
        cfg = M.KimiLinearConfig.tiny(num_experts=32,
                                      num_experts_per_token=4)
        whole = M.KimiLinearDecoderLayer(cfg, kind, True)
        params = {f"model.layers.{index}." + k: jnp.array(v)
                  for k, v in functional_state(whole).items()}
        x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)),
                        jnp.float32)
        config = _reference_config(cfg)
        want, _, _ = R._layer(config, params, f"model.layers.{index}.", x,
                              (0, 32), None)
        routed_sum, once = 0.0, None
        for share in range(4):
            held = (8 * share, 8)
            part_cfg = dataclasses.replace(cfg, experts_held=held)
            part = M.KimiLinearDecoderLayer(part_cfg, kind, True)
            state = dict(functional_state(whole))
            for name in ("moe.w_gate", "moe.w_up", "moe.w_down"):
                state[name] = state[name][held[0]:held[0] + 8]
            (out, _), _ = functional_call(part, state, x)
            # what every chip computes alike: x + attention + shared
            (alone, _), _ = functional_call(
                part, {**state, "moe.w_down": jnp.zeros_like(
                    state["moe.w_down"])}, x)
            routed_sum = routed_sum + (out - alone)
            once = alone
        np.testing.assert_allclose(np.asarray(once + routed_sum),
                                   np.asarray(want), atol=2e-5)


# -- latent attention without a query latent and without rotation ------------

def _plain_latent(layer, x, causal):
    """nn.LatentAttention(q_lora_rank=None, use_rope=False) as a plain
    softmax over its own weights."""
    w = {k: np.asarray(v, np.float64)
         for k, v in functional_state(layer).items()}
    x = np.asarray(x, np.float64)
    b, s, _ = x.shape
    h, nope, rope, vd, rank = (layer.num_heads, layer.nope, layer.rope,
                               layer.v_dim, layer.kv_lora_rank)
    q = (x @ w["q_proj.weight"]).reshape(b, s, h, nope + rope)
    kv_a = x @ w["kv_a_proj_with_mqa.weight"]
    c = kv_a[..., :rank]
    c = c / np.sqrt((c ** 2).mean(-1, keepdims=True) + 1e-5) \
        * w["kv_a_layernorm.weight"]
    kv = (c @ w["kv_b_proj.weight"]).reshape(b, s, h, nope + vd)
    k = np.concatenate([kv[..., :nope], np.broadcast_to(
        kv_a[:, :, None, rank:], (b, s, h, rope))], -1)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    if causal:
        scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", p, kv[..., nope:]).reshape(b, s, -1)
    return o @ w["o_proj.weight"]


@pytest.mark.parametrize("causal", [True, False])
def test_latent_attention_without_latent_or_rotation_is_a_plain_softmax(
        causal):
    paddle_tpu.seed(5)
    layer = nn.LatentAttention(32, 4, None, 16, 16, 8, 16, epsilon=1e-5,
                               use_rope=False)
    names = {n for n, _ in layer.named_parameters()}
    assert "q_proj.weight" in names
    assert not {n for n in names if n.startswith(("q_a_", "q_b_"))}
    x = np.random.default_rng(6).normal(size=(2, 12, 32)).astype(np.float32)
    out = layer(paddle_tpu.to_tensor(x), None, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out._value),
                               _plain_latent(layer, x, causal), atol=2e-5)


def test_position_free_layer_commutes_with_a_permutation_of_positions():
    """use_rope=False, is_causal=False: permuting the positions permutes
    the output and changes nothing else — the layer reads no position."""
    paddle_tpu.seed(7)
    layer = nn.LatentAttention(32, 4, None, 16, 16, 8, 16, use_rope=False)
    x = np.random.default_rng(8).normal(size=(1, 10, 32)).astype(np.float32)
    perm = np.random.default_rng(9).permutation(10)
    run = lambda a: np.asarray(layer(paddle_tpu.to_tensor(a), None,
                                     is_causal=False)._value)
    np.testing.assert_allclose(run(x[:, perm]), run(x)[:, perm], atol=1e-5)
    # with rotation the same permutation does change the output
    rotating = nn.LatentAttention(32, 4, None, 16, 16, 8, 16, use_rope=True)
    pos = np.arange(10, dtype=np.int32)
    rot = lambda a: np.asarray(rotating(paddle_tpu.to_tensor(a), pos,
                                        is_causal=False)._value)
    assert np.abs(rot(x[:, perm]) - rot(x)[:, perm]).max() > 1e-4


def test_kda_layer_scopes_and_parameters():
    paddle_tpu.seed(1)
    layer = nn.KimiDeltaAttention(32, 2, 16)
    assert [n for n, _ in layer.named_sublayers()] == [
        "q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d", "v_conv1d",
        "f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "g_b_proj", "kda_core",
        "o_norm", "o_proj"]
    assert sorted(n for n, _ in layer.named_parameters()) == sorted(
        [f"{n}.weight" for n in (
            "q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d", "v_conv1d",
            "f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "g_b_proj",
            "o_norm", "o_proj")] + ["A_log", "dt_bias"])
    # the two passes around the scan run under scopes of their own,
    # forward and backward, outside every projection's
    state = functional_state(layer)
    loss = lambda p, x: jnp.sum(functional_call(layer, p, x)[0])
    text = jax.jit(jax.grad(loss)).lower(
        state, jnp.ones((1, 8, 32))).compile().as_text()
    scopes = set(re.findall(r"kimideltaattention\)*/(\w+)", text))
    assert {"kda_pre", "kda_core", "kda_post", "q_proj", "f_b_proj",
            "b_proj", "g_b_proj", "o_proj"} <= scopes
    assert not scopes & {"q_conv1d", "k_conv1d", "v_conv1d", "o_norm"}
    assert not re.search(r"kda_(pre|post)/\w+_proj", text)
    a = np.exp(np.asarray(layer.A_log._value))
    assert a.min() >= 1 and a.max() <= 16
    dt = np.log1p(np.exp(np.asarray(layer.dt_bias._value)))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    # published sizes: 39.52 M parameters a layer
    count = lambda e, h, d: (4 * e * h * d + 2 * (e * d + d * h * d) + e * h
                             + 3 * 4 * h * d + h + h * d + d)
    assert sum(int(np.prod(p.shape)) for _, p in layer.named_parameters()) \
        == count(32, 2, 16)
    assert abs(count(2304, 32, 128) / 1e6 - 39.52) < 0.01
