"""Qwen3-Next: the chunked scan kernels' Gated DeltaNet forms
(interpret mode: a decay that is one scalar a head, two value heads over
one query/key head) against the per-channel kernel fed the broadcast
decay and repeated q and k, and against the recurrence a token at a
time; the SiLU-gated head norm pass; the pass before the scan
(`kda_edge.gdn_pre`'s kernels against its XLA statement, and the tiny
step on them against the XLA path); `nn.GatedDeltaNet`, the
element-wise-gated attention with zero-centred QK norms, the gated
shared expert and the zero-centred RMSNorm against the plain reference
(benchmark/reference/qwen3_next.py — the one the benchmark's `correct`
uses); the model's loss, logits and gradients against it; the share
test that ties a chip's share to the whole layer; and the tiny Kimi
Linear and Laguna train steps' programs, unchanged by the new forms."""

import dataclasses
import functools
import hashlib
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
from paddle_tpu.models import qwen3_next as M
from paddle_tpu.nn.functional import kda as X
from paddle_tpu.ops.pallas import kda as K
from paddle_tpu.ops.pallas import kda_edge as E

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.reference import qwen3_next as R  # noqa: E402

SCALE = 128 ** -0.5
OPERANDS = ("q", "k", "v", "g", "beta")


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _delta(before, name):
    return profiler.get_int_stats().get(name, 0) - before.get(name, 0)


# -- the scan's Gated DeltaNet forms -----------------------------------------

def _operands(b, s, hk, hv, g_min, head_decay=True, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(rng.normal(size=(b, s, hk, 128))) for _ in range(2))
    v = rng.normal(size=(b, s, hv, 128))
    g = rng.uniform(g_min, 0.0, size=(b, s, hv) + ((128,) * (not head_decay)))
    beta = rng.uniform(0.05, 0.95, size=(b, s, hv))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _per_channel(q, k, v, g, beta):
    """The same recurrence in the per-channel form the Kimi instances
    take: the decay broadcast over a head's lanes, q and k repeated to
    the value heads."""
    group = v.shape[2] // q.shape[2]
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], g.shape + (128,))
    return (jnp.repeat(q, group, 2), jnp.repeat(k, group, 2), v, g, beta)


@functools.lru_cache(maxsize=None)
def _three_ways(b, s, hk, hv, g_min, head_decay):
    """Outputs and the five operands' gradients of (the new form's
    kernels, the per-channel kernels on the expanded operands, the
    recurrence on them), interpret mode, float32."""
    args = _operands(b, s, hk, hv, g_min, head_decay, seed=s)
    w = jnp.asarray(np.random.default_rng(9).normal(size=args[2].shape),
                    jnp.float32)
    out = []
    for fn in (lambda *a: K.kda_attention(*a, interpret=True),
               lambda *a: K.kda_attention(*_per_channel(*a), interpret=True),
               lambda *a: X.recurrent(*_per_channel(*a), SCALE)):
        o, vjp = jax.vjp(fn, *args)
        out.append((o, vjp(w)))
    return out


# (batch, seq, key heads, value heads, g_min, a decay a head): the cell's
# form over two chunks; batch 2 and a length no multiple of 64 with
# strong decay (-20 a token, the released initialisation's range);
# grouped heads under a per-channel decay; a decay a head, one group;
# four chunks of a decay near 0, where exp(G_i - G_j) is near 1 for
# every pair and a score the masks should drop would show
CASES = [(1, 128, 1, 2, -0.5, True), (2, 100, 1, 2, -20.0, True),
         (1, 64, 1, 2, -0.5, False), (1, 64, 2, 2, -0.5, True),
         (1, 256, 1, 2, -1e-3, True)]
IDS = ["grouped-head-decay", "padded-strong-decay", "grouped-channel-decay",
       "ungrouped-head-decay", "near-zero-decay"]


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_new_forms_equal_the_per_channel_kernel_and_the_recurrence(case):
    (out, grads), (alike, alike_grads), (ref, ref_grads) = _three_ways(*case)
    assert out.shape == ref.shape == (case[0], case[1], case[3], 128)
    # a per-channel decay over grouped heads: the same float32 arithmetic
    # a value head, only the sums of a key head's two value heads (dq,
    # dk) taken in another order.  A decay a head takes its scores in
    # the scalar form (exp(G_i - G_j) over q k^T and k k^T, G cumulated
    # once a step for all heads), other float32 arithmetic than the
    # per-channel kernel's: held to it as to the recurrence
    same, same_grads = (1e-5, 5e-5) if case[5] else (1e-6, 1e-5)
    assert _rel(out, alike) < same
    assert _rel(out, ref) < 1e-5
    for i in range(5):
        assert bool(jnp.isfinite(grads[i]).all()), OPERANDS[i]
        assert grads[i].shape == _operands(*case)[i].shape
        assert _rel(grads[i], alike_grads[i]) < same_grads, OPERANDS[i]
        assert _rel(grads[i], ref_grads[i]) < 5e-5, OPERANDS[i]


def test_scalar_scores_are_counted_where_the_decay_is_a_head_s():
    """Traced: a differentiated instance with a decay a head builds its
    forward and its backward call in the scalar form (2); a recomputed
    forward builds one more; the Kimi form builds none."""
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a)), argnums=(0, 1, 2))
    scan = lambda *a: K.kda_attention(*a, interpret=True)
    for args, f, n in ((_operands(1, 64, 1, 2, -0.5), scan, 2),
                       (_operands(1, 64, 1, 2, -0.5), jax.checkpoint(scan), 3),
                       (_operands(1, 64, 2, 2, -0.5, head_decay=False), scan,
                        0)):
        before = profiler.get_int_stats()
        jax.make_jaxpr(grad(f))(*args)
        assert _delta(before, "kda_scalar_scores_total") == n


def test_counters_and_a_ratio_the_kernels_do_not_group():
    """Traced: a decay a head and a pair of value heads a key head are
    counted; three value heads a key head repeat q and k in HBM first
    (counted) and still give the recurrence."""
    before = profiler.get_int_stats()
    jax.jit(lambda *a: K.kda_attention(*a, interpret=True)).lower(
        *_operands(1, 64, 2, 4, -0.5))
    assert _delta(before, "kda_head_decay_total") == 1
    assert _delta(before, "kda_grouped_heads_total") == 1
    assert _delta(before, "kda_group_repeat_total") == 0
    args = _operands(1, 64, 1, 3, -0.5)
    before = profiler.get_int_stats()
    out = K.kda_attention(*args, interpret=True)
    assert _delta(before, "kda_group_repeat_total") == 1
    assert _delta(before, "kda_grouped_heads_total") == 0
    assert _rel(out, X.recurrent(*_per_channel(*args), SCALE)) < 1e-5
    # the Kimi form counts none of them
    before = profiler.get_int_stats()
    jax.jit(lambda *a: K.kda_attention(*a, interpret=True)).lower(
        *_operands(1, 64, 2, 2, -0.5, head_decay=False))
    assert all(_delta(before, n) == 0 for n in (
        "kda_head_decay_total", "kda_grouped_heads_total",
        "kda_group_repeat_total"))
    assert _delta(before, "kda_chunked_total") == 1
    with pytest.raises(ValueError, match="no whole group"):
        K.kda_attention(*_operands(1, 64, 2, 3, -0.5), interpret=True)


def test_kernel_names_tell_the_forms_apart():
    text = lambda case: str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(K.kda_attention(*a, interpret=True)),
        argnums=(0, 1, 2, 3, 4)))(*_operands(*case)))
    gdn = text((1, 64, 1, 2, -0.5, True))
    kimi = text((1, 64, 2, 2, -0.5, False))
    assert "name=gdn_fwd" in gdn and "name=gdn_bwd" in gdn
    assert "kda_fwd" not in gdn and "kda_bwd" not in gdn
    assert "name=kda_fwd" in kimi and "gdn_" not in kimi
    # no name the Kimi and Laguna readers take for their own
    assert not re.search(r"name=(flash_|kda_)", gdn)


# -- the SiLU-gated head norm ------------------------------------------------

@pytest.mark.parametrize("what", ["forward", "do", "dgate", "dweight"])
def test_silu_gated_head_norm_matches_its_xla_statement(what):
    rng = np.random.default_rng(4)
    o, gate = (jnp.asarray(rng.normal(size=(2, 40, 256)), jnp.float32)
               for _ in range(2))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(128,)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    runs = []
    for fn in (lambda o, g, w: E.kda_post(o, g, w, 1e-6, interpret=True,
                                          tile=32, activation="silu"),
               lambda o, g, w: X.edge_post(o, g, w, 1e-6, "silu")):
        y, vjp = jax.vjp(fn, o, gate, w)
        runs.append((y,) + vjp(dy))
    at = ["forward", "do", "dgate", "dweight"].index(what)
    assert _rel(runs[0][at], runs[1][at]) < 1e-5
    if what == "forward":     # SiLU, not sigmoid
        sig = X.edge_post(o, gate, w, 1e-6)
        assert _rel(runs[1][0], sig) > 0.1


def test_silu_instances_are_named_and_counted_apart():
    o = jnp.ones((1, 32, 128), jnp.float32)
    post = lambda act: str(jax.make_jaxpr(jax.grad(lambda o: jnp.sum(
        E.kda_post(o, o, jnp.ones((128,)), 1e-6, interpret=True, tile=32,
                   activation=act))))(o))
    assert "name=gdn_post_fwd" in post("silu")
    assert "name=gdn_post_bwd" in post("silu")
    assert "name=kda_post_fwd" in post("sigmoid")
    assert "gdn_post" not in post("sigmoid")
    with pytest.raises(ValueError, match="sigmoid or silu"):
        E.kda_post(o, o, jnp.ones((128,)), 1e-6, activation="gelu")


# -- the work before the scan ------------------------------------------------

PRE_OUT = ("q", "k", "v", "g", "beta", "z")
PRE_IN = ("qkvz", "ba", "taps", "dt_bias", "a_log")
# (batch, tokens, key heads, value heads, row tile): two value heads a key
# head over three tiles; as many value as key heads, batch 2, a length no
# multiple of the tile; two key heads a forward grid step and two a
# backward one, padded
PRE_CASES = [(1, 96, 1, 2, 32), (2, 100, 2, 2, 32), (1, 80, 2, 4, 32)]
PRE_IDS = ["grouped", "ungrouped-padded", "two-heads-a-step"]


def _pre_operands(b, s, hk, hv, d=128, seed=0):
    rng = np.random.default_rng(seed)
    width = (2 * hk + hv) * d
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (draw(b, s, width + hv * d), draw(b, s, 2 * hv), draw(4, width) / 2,
            draw(hv), jnp.log(jnp.asarray(rng.uniform(1, 16, hv), jnp.float32)))


def _pre_xla(qkvz, ba, taps, dt_bias, a_log, key_heads):
    width = taps.shape[1]
    return X.gdn_pre(qkvz[..., :width], ba, taps, dt_bias, a_log,
                     key_heads) + (qkvz[..., width:],)


@functools.lru_cache(maxsize=None)
def _pre_both(b, s, hk, hv, tile):
    """(outputs, the five cotangents) of `kda_edge.gdn_pre` through the
    kernels and of its XLA statement, float32."""
    args = _pre_operands(b, s, hk, hv)
    both = []
    for fn in (functools.partial(E.gdn_pre, key_heads=hk, interpret=True,
                                 tile=tile),
               functools.partial(_pre_xla, key_heads=hk)):
        out, vjp = jax.vjp(fn, *args)
        w = tuple(jnp.asarray(np.random.default_rng(7 + i).normal(
            size=a.shape), a.dtype) for i, a in enumerate(out))
        both.append((out, vjp(w)))
    return both


@pytest.mark.parametrize("out", range(6), ids=PRE_OUT)
@pytest.mark.parametrize("case", PRE_CASES, ids=PRE_IDS)
def test_gdn_pre_output_matches_its_xla_statement(case, out):
    (got, _), (want, _) = _pre_both(*case)
    assert got[out].shape == want[out].shape
    assert got[out].dtype == want[out].dtype
    assert _rel(got[out], want[out]) < 1e-6


@pytest.mark.parametrize("operand", range(5), ids=PRE_IN)
@pytest.mark.parametrize("case", PRE_CASES, ids=PRE_IDS)
def test_gdn_pre_cotangent_matches_its_xla_statement(case, operand):
    """The hand-written backward: the projection's cotangent [dq~ | dk~
    | dv~ | dz] as one array, the taps; and XLA's for ba, dt_bias and
    A_log beside it."""
    (_, got), (_, want) = _pre_both(*case)
    assert got[operand].shape == want[operand].shape
    assert _rel(got[operand], want[operand]) < 1e-5


@pytest.mark.parametrize("part", range(3), ids=PRE_OUT[:3])
def test_gdn_pre_backward_reaches_into_the_tile_before(part):
    """A cotangent of q, k or v that is non-zero only in the first rows
    of ONE tile (the third of four): the convolution's pull-back lands
    in the last 3 rows of the tile BEFORE (carried in VMEM by the
    backward walk) and nowhere earlier, in that part's lanes alone."""
    tile, first, hk = 32, 64, 1
    args = _pre_operands(1, 128, hk, 2, seed=3)
    fns = (functools.partial(E.gdn_pre, key_heads=hk, interpret=True,
                             tile=tile),
           functools.partial(_pre_xla, key_heads=hk))
    grads = []
    for fn in fns:
        out, vjp = jax.vjp(fn, *args)
        w = [jnp.zeros_like(a) for a in out]
        w[part] = w[part].at[:, first:first + 2].set(1.0)
        grads.append(vjp(tuple(w))[0])
    got, want = grads
    assert _rel(got, want) < 1e-5
    lanes = slice(part * 128, (part + 1) * 128 if part < 2 else 512)
    before = np.abs(np.asarray(got[0, first - 3:first, lanes]))
    assert before.min(axis=-1).max() > 0        # rows 61..63 of tile 1
    assert float(jnp.abs(got[0, :first - 3]).max()) == 0.0
    assert float(jnp.abs(got[0, first + 2:]).max()) == 0.0
    rest = np.asarray(got[0]).copy()
    rest[:, lanes] = 0.0
    assert float(np.abs(rest).max()) == 0.0


def test_gdn_pre_kernels_named_counted_and_refused():
    """`gdn_pre_fwd` / `gdn_pre_bwd` under jitted names that none of the
    benchmark's kernel readers take for the scan's or the flash
    kernels'; an instance counts once; heads of 64 channels take the
    XLA statement, counted as refused; off the TPU without `interpret`,
    the XLA path, uncounted."""
    args = _pre_operands(1, 40, 1, 2)
    fused = functools.partial(E.gdn_pre, key_heads=1, interpret=True)
    loss = lambda *a: sum(jnp.sum(jnp.square(x)) for x in fused(*a))
    before = profiler.get_int_stats()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        *args))
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (1, 0)
    assert "name=gdn_pre_fwd" in text and "name=gdn_pre_bwd" in text
    assert "name=_gdn_pre_forward" in text
    assert "name=_gdn_pre_backward" in text
    assert not re.search(r"_(gdn|kda|flash)_(forward|backward)\b", text)
    # heads of 64 channels: the XLA statement, counted as refused
    narrow = _pre_operands(1, 40, 1, 2, d=64)
    before = profiler.get_int_stats()
    got = E.gdn_pre(*narrow, 1, interpret=True)
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (0, 1)
    for a, b in zip(got, _pre_xla(*narrow, 1)):
        assert a.shape == b.shape and _rel(a, b) < 1e-6
    before = profiler.get_int_stats()
    E.gdn_pre(*args, 1)
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (0, 0)


# -- the layers against the reference ----------------------------------------

_CFG = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, rms_norm_eps=1e-6, rope_theta=1e7,
            partial_rotary_factor=0.25, linear_num_key_heads=1,
            linear_num_value_heads=2, linear_key_head_dim=128,
            linear_value_head_dim=128, num_experts_per_tok=3,
            norm_topk_prob=True, full_attention_interval=4)


def _x(shape=(2, 24, 32), seed=2):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _perturbed(layer, names, seed=0):
    """The layer's state with the zero-centred norms' weights moved off
    zero, so that (1 + w) and w give different results."""
    state = {k: jnp.array(v) for k, v in functional_state(layer).items()}
    rng = np.random.default_rng(seed)
    for k in names:
        state[k] = state[k] + jnp.asarray(
            rng.uniform(-0.3, 0.3, state[k].shape), jnp.float32)
    return state


def _against_reference(layer, state, ref, *inputs):
    def run(p):
        out = functional_call(layer, p, *inputs)[0]
        return out[0] if isinstance(out, tuple) else out

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            lambda p: jnp.sum(jnp.sin(run(p))))(state)
        want, want_grads = jax.value_and_grad(
            lambda p: jnp.sum(jnp.sin(ref(p))))(state)
    assert abs(float(got) - float(want)) < 1e-4 * max(1.0, abs(float(want)))
    for k in want_grads:
        assert _rel(got_grads[k], want_grads[k]) < 1e-4, k


def test_gated_delta_net_matches_reference():
    paddle_tpu.seed(1)
    layer = nn.GatedDeltaNet(32, 1, 2)
    state = _perturbed(layer, ["norm.weight"])
    x = _x()
    _against_reference(layer, state,
                       lambda p: R._gdn(_CFG, p, "", x), x)


def test_element_gated_attention_matches_reference():
    paddle_tpu.seed(2)
    layer = nn.GatedWindowAttention(
        32, 4, 2, 16, rope={"rope_theta": 1e7, "partial_rotary_factor": 0.25},
        gate="element", qk_norm=True, norm_offset=True)
    assert "g_proj" not in dict(layer.named_sublayers())
    assert layer.q_proj.weight.shape == [32, 2 * 4 * 16]
    state = _perturbed(layer, ["q_norm.weight", "k_norm.weight"])
    x, pos = _x(), np.arange(24, dtype=np.int32)
    _against_reference(layer, state,
                       lambda p: R._attention(_CFG, p, "", x), x, pos)


def test_gated_shared_expert_matches_reference():
    paddle_tpu.seed(3)
    layer = nn.RoutedMoE(32, 24, 8, 3, held=(2, 4), n_shared_experts=1,
                         shared_gate=True)
    assert layer.shared_expert_gate.weight.shape == [32, 1]
    state = {k: jnp.array(v) for k, v in functional_state(layer).items()}
    x = _x((40, 32))
    cfg = {**_CFG, "router_width": 8}
    _against_reference(
        layer, state, lambda p: R.moe_layer(cfg, p, "", x, (2, 4))[0], x)
    with pytest.raises(ValueError, match="without a shared expert"):
        nn.RoutedMoE(32, 24, 8, 3, shared_gate=True)


def test_zero_centred_norm_and_the_plain_one():
    x = _x((3, 16))
    plain, centred = nn.RMSNorm(16), nn.RMSNorm(16, zero_centred=True)
    assert float(jnp.abs(centred.weight._value).max()) == 0.0
    assert float(jnp.abs(plain.weight._value - 1).max()) == 0.0
    w = jnp.asarray(np.random.default_rng(1).uniform(-0.5, 0.5, 16),
                    jnp.float32)
    run = lambda layer, w: functional_call(layer, {"weight": w}, x)[0]
    np.testing.assert_allclose(np.asarray(run(centred, w)),
                               np.asarray(R._norm0(x, w, 1e-6)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(run(centred, w)),
                               np.asarray(run(plain, 1 + w)), atol=1e-6)


# -- the model against the reference -----------------------------------------

def _reference_config(cfg):
    return {**dataclasses.asdict(cfg), "router_width": cfg.num_experts}


def _tiny_step():
    """A tiny model (three Gated DeltaNet layers, one full), its float32
    parameters with the norms moved off their start and the decay's
    rates drawn from (0.05, 1), a batch, and one jitted loss-and-gradient
    pass over them: (config, params, batch, loss, aux, grads)."""
    paddle_tpu.seed(3)
    cfg = M.Qwen3NextConfig.tiny(
        experts_held=(2, 4), num_experts_per_tok=3, recompute=True,
        vocab_size=64)
    model = M.Qwen3NextForCausalLM(cfg)
    params = {k: jnp.array(v) for k, v in functional_state(model).items()}
    rng = np.random.default_rng(0)
    for k in params:
        if k.endswith("layernorm.weight") or k.endswith(
                ("q_norm.weight", "k_norm.weight", "model.norm.weight")):
            params[k] = params[k] + jnp.asarray(
                rng.uniform(-0.3, 0.3, params[k].shape), jnp.float32)
        elif k.endswith("A_log"):
            params[k] = jnp.log(jnp.asarray(
                rng.uniform(0.05, 1.0, params[k].shape), jnp.float32))
    batch = M.fake_batch(cfg, 2, 40, seed=5)
    loss_fn = M.build_loss(model, bf16=False, probe=8)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, batch)
    return cfg, params, batch, loss, aux, grads


@pytest.fixture(scope="module")
def trained():
    """One float32 loss-and-gradient pass of a tiny model (three Gated
    DeltaNet layers, one full) whose scans run the chunked kernels in
    interpret mode at the published head width, against the reference's
    loss, logits and gradients on the same weights.  The decay's rates
    are drawn from (0.05, 1) and not from the released (0, 16): at that
    range's strong decay a token's dg is a cancelling sum at float32's
    floor, where the kernels and the recurrence round apart (the kernel
    tests above cover g down to -20 a token)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(K, "kda_attention", functools.partial(K.kda_attention,
                                                     interpret=True))
    before = profiler.get_int_stats()
    try:
        cfg, params, batch, loss, aux, grads = _tiny_step()
        config = _reference_config(cfg)
        ref = R.forward(config, params, batch,
                        probe=M.probe_positions(40, 8))
        ref_grads = R.grads(config, params, batch)
    finally:
        mp.undo()
    return dict(cfg=cfg, loss=loss, aux=aux, grads=grads, ref=ref,
                ref_grads=ref_grads,
                decay=_delta(before, "kda_head_decay_total"),
                grouped=_delta(before, "kda_grouped_heads_total"),
                scalar=_delta(before, "kda_scalar_scores_total"))


def test_loss_and_logits_match_reference(trained):
    assert abs(float(trained["loss"]) - float(trained["ref"]["loss"])) < 1e-5
    np.testing.assert_allclose(np.asarray(trained["aux"]["probe_logits"]),
                               np.asarray(trained["ref"]["logits"]),
                               atol=2e-5)
    # 3 GDN layers, all on the kernels' new forms (counted where traced);
    # their scores in the scalar form in each call built: a forward, its
    # recomputation and a backward a layer, as the cell's 6 + 3 calls
    assert trained["decay"] == trained["grouped"] == 3
    assert trained["scalar"] == 9


def test_routing_matches_reference(trained):
    for got, want in zip(trained["aux"]["moe_experts"],
                         trained["ref"]["experts"]):
        assert (np.sort(np.asarray(got), 1)
                == np.sort(np.asarray(want), 1)).all()
    assert np.asarray(trained["aux"]["moe_stats"]).shape[0] == 4


# the leaves the benchmark's `correct` compares, and a few beside them
_LEAVES = ["model.layers.2.linear_attn.A_log",
           "model.layers.2.linear_attn.dt_bias",
           "model.layers.2.linear_attn.in_proj_ba.weight",
           "model.layers.2.linear_attn.conv1d.weight",
           "model.layers.2.linear_attn.in_proj_qkvz.weight",
           "model.layers.0.linear_attn.norm.weight",
           "model.layers.3.self_attn.q_proj.weight",
           "model.layers.3.self_attn.q_norm.weight",
           "model.layers.3.moe.shared_expert_gate.weight",
           "model.layers.3.moe.w_down", "model.layers.0.moe.gate_weight",
           "model.layers.1.input_layernorm.weight",
           "model.embed_tokens.weight"]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_matches_reference(trained, leaf):
    assert _rel(trained["grads"][leaf], trained["ref_grads"][leaf]) < 2e-4


def test_all_gradients_match_reference(trained):
    assert set(trained["grads"]) == set(trained["ref_grads"])
    worst = max(_rel(trained["grads"][k], trained["ref_grads"][k])
                for k in trained["grads"])
    assert worst < 2e-4


def test_step_on_the_pre_scan_kernels_equals_the_xla_path(trained):
    """The same tiny step with `gdn_pre`'s kernels taken (interpret
    mode): its loss, logits and every gradient equal the XLA path's
    (`trained`) to float32 rounding, and each of the three layers
    counts one fused instance."""
    mp = pytest.MonkeyPatch()
    mp.setattr(K, "kda_attention", functools.partial(K.kda_attention,
                                                     interpret=True))
    mp.setattr(E, "gdn_pre", functools.partial(E.gdn_pre, interpret=True))
    before = profiler.get_int_stats()
    try:
        _, _, _, loss, aux, grads = _tiny_step()
    finally:
        mp.undo()
    assert (_delta(before, "kda_edge_fused_total"),
            _delta(before, "kda_edge_fallback_total")) == (3, 0)
    assert abs(float(loss) - float(trained["loss"])) < 1e-6
    np.testing.assert_allclose(np.asarray(aux["probe_logits"]),
                               np.asarray(trained["aux"]["probe_logits"]),
                               atol=1e-5)
    assert grads.keys() == trained["grads"].keys()
    for k in grads:
        assert _rel(grads[k], trained["grads"][k]) < 1e-5, k


def test_layer_kinds_follow_the_published_interval():
    cfg = M.Qwen3NextConfig()
    kinds = [cfg.kind(i) for i in range(48)]
    assert kinds.count("linear_attention") == 36
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == list(range(3, 48, 4))
    assert all(cfg.is_sparse(i) for i in range(48))
    assert not M.Qwen3NextConfig.tiny(mlp_only_layers=[1]).is_sparse(1)


def test_bf16_step_trains_keeps_the_decay_float32_and_spares_the_taps():
    paddle_tpu.seed(4)
    cfg = M.Qwen3NextConfig.tiny(experts_held=(0, 4), recompute=True)
    model = M.Qwen3NextForCausalLM(cfg)
    step, state = M.build_train_step(model, weight_decay=0.5)
    before = {k: np.asarray(v) for k, v in state["params"].items()}
    batch = M.fake_batch(cfg, 2, 24, seed=1)
    losses = []
    for _ in range(3):
        state, loss, aux = step(state, batch, jnp.float32(3e-3))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # the decay's rate and step stay float32 in the working copy
    assert all(k.endswith(M._FLOAT32_LEAVES) for k in before
               if k.endswith(("A_log", "dt_bias")))
    # no weight decay on the taps: at learning rate r and decay 0.5 a
    # decayed matrix moves by at least r * 0.5 * |w| beyond Adam's step
    tap = "model.layers.0.linear_attn.conv1d.weight"
    moved = np.abs(np.asarray(state["params"][tap]) - before[tap]).max()
    assert moved <= 3 * 3e-3 * 1.01


# -- the share test ----------------------------------------------------------

def test_four_shares_and_what_every_chip_computes_once_equal_the_layer():
    """A Gated DeltaNet layer and a full layer of a 32-expert tiny model
    cut into 4 shares of 8 experts: the routed parts the shares give,
    with the mixer, router and gated shared expert counted once, add up
    to the uncut reference's layer output."""
    for index in (0, 3):
        paddle_tpu.seed(11)
        cfg = M.Qwen3NextConfig.tiny(num_experts=32, num_experts_per_tok=4)
        whole = M.Qwen3NextDecoderLayer(cfg, index)
        params = {f"model.layers.{index}." + k: jnp.array(v)
                  for k, v in functional_state(whole).items()}
        x = _x((2, 24, 32))
        config = _reference_config(cfg)
        with jax.default_matmul_precision("highest"):
            want, _, _ = R._layer(config, params, index, x, (0, 32), None)
        routed_sum, once = 0.0, None
        pos = np.arange(24, dtype=np.int32)
        for share in range(4):
            held = (8 * share, 8)
            part = M.Qwen3NextDecoderLayer(
                dataclasses.replace(cfg, experts_held=held), index)
            state = dict(functional_state(whole))
            for name in ("moe.w_gate", "moe.w_up", "moe.w_down"):
                state[name] = state[name][held[0]:held[0] + 8]
            (out, _), _ = functional_call(part, state, x, pos)
            # what every chip computes alike: x + mixer + gated shared
            (alone, _), _ = functional_call(
                part, {**state, "moe.w_down": jnp.zeros_like(
                    state["moe.w_down"])}, x, pos)
            routed_sum = routed_sum + (out - alone)
            once = alone
        np.testing.assert_allclose(np.asarray(once + routed_sum),
                                   np.asarray(want), atol=2e-5)


def test_gated_delta_net_scopes_and_parameters():
    paddle_tpu.seed(1)
    layer = nn.GatedDeltaNet(32, 1, 2)
    assert [n for n, _ in layer.named_sublayers()] == [
        "in_proj_qkvz", "in_proj_ba", "conv1d", "gdn_core", "norm",
        "out_proj"]
    shapes = {n: tuple(p.shape) for n, p in layer.named_parameters()}
    assert shapes == {"in_proj_qkvz.weight": (32, 2 * 128 + 2 * 256),
                      "in_proj_ba.weight": (32, 4),
                      "conv1d.weight": (4, 2 * 128 + 256),
                      "A_log": (2,), "dt_bias": (2,), "norm.weight": (128,),
                      "out_proj.weight": (256, 32)}
    state = functional_state(layer)
    loss = lambda p, x: jnp.sum(functional_call(layer, p, x)[0])
    text = jax.jit(jax.grad(loss)).lower(
        state, jnp.ones((1, 8, 32))).compile().as_text()
    scopes = set(re.findall(r"gateddeltanet\)*/(\w+)", text))
    assert {"gdn_pre", "gdn_core", "gdn_post", "in_proj_qkvz", "in_proj_ba",
            "out_proj"} <= scopes
    a = np.exp(np.asarray(layer.A_log._value))
    assert a.min() >= 0 and a.max() <= 16
    assert np.asarray(layer.dt_bias._value).tolist() == [1.0, 1.0]
    # published sizes: 33.72 M parameters a layer
    count = lambda e, hk, hv, d: (e * (2 * hk * d + 2 * hv * d) + e * 2 * hv
                                  + 4 * (2 * hk * d + hv * d) + 2 * hv + d
                                  + hv * d * e)
    assert sum(int(np.prod(p.shape)) for _, p in layer.named_parameters()) \
        == count(32, 1, 2, 128)
    assert abs(count(2048, 16, 32, 128) / 1e6 - 33.72) < 0.01


# -- the programs of the accepted cells --------------------------------------

# sha256 (first 16 hex digits) of the tiny train steps' jaxprs with the
# Pallas kernels' path taken — `_common.on_tpu` forced, as on a chip; the
# flash kernels' compile probes fail off the chip, so their XLA path —,
# object addresses stripped: the program the accepted Kimi Linear and
# Laguna cells run, as it was before the Gated DeltaNet forms and options
# were added to the kernels and layers they share
_PROGRAMS = {"kimi_linear": "0d6ffb66efd9ea09", "laguna": "980f185006a5d8b1",
             "laguna_head128": "162b7e6315690c42"}


def _program_hash(build):
    from paddle_tpu.ops.pallas import _common

    mp = pytest.MonkeyPatch()
    mp.setattr(_common, "on_tpu", lambda: True)
    try:
        step, state, batch = build()
        text = str(jax.make_jaxpr(step)(state, batch, jnp.float32(1e-3)))
    finally:
        mp.undo()
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _kimi():
    from paddle_tpu.models import kimi_linear

    paddle_tpu.seed(0)
    m = kimi_linear.KimiLinearForCausalLM(
        kimi_linear.KimiLinearConfig.tiny(recompute=True))
    step, state = kimi_linear.build_train_step(m, probe=4)
    return step, state, kimi_linear.fake_batch(m.config, 1, 64)


def _laguna(**kw):
    from paddle_tpu.models import laguna

    paddle_tpu.seed(0)
    m = laguna.LagunaForCausalLM(laguna.LagunaConfig.tiny(**kw))
    step, state = laguna.build_train_step(m, probe=4)
    return step, state, laguna.fake_batch(
        m.config, 1, 256 if kw.get("head_dim") else 64)


@pytest.mark.parametrize("cell,build", [
    ("kimi_linear", _kimi),
    ("laguna", lambda: _laguna(recompute=True)),
    ("laguna_head128", lambda: _laguna(head_dim=128))])
def test_accepted_cells_programs_are_unchanged(cell, build):
    assert _program_hash(build) == _PROGRAMS[cell]
