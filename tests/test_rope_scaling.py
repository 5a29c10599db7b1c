"""Rotary positions on part of a head and YaRN's scaled frequencies
(`F.rotary_embedding(rotary_dim=, inv_freq=, amplitude=)`,
`F.yarn_inv_freq`), at the published values of the configuration that
brought them (poolside Laguna-XS.2's full-attention layers), and the
call sites that were there before, bit for bit."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from paddle_tpu import profiler
from paddle_tpu.fluid.dygraph.varbase import Tensor

# rope_parameters["full_attention"] of the published config.json
YARN = dict(dim=64, base=500000.0, factor=64.0, original_max=4096,
            beta_fast=64.0, beta_slow=1.0)


def _qk(seed=0, b=2, s=48, h=3, d=128, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return tuple(Tensor(rng.standard_normal((b, s, n, d)).astype(dtype))
                 for n in (h, 1))


def _value(t):
    return np.asarray(t._value)


class TestYarnInverseFrequencies:
    def test_correction_range_at_the_published_values(self):
        c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (
            2 * math.log(5e5))
        assert (math.floor(c(64)), math.ceil(c(1))) == (5, 16)

    def test_unscaled_blended_and_interpolated_pairs(self):
        inv = F.yarn_inv_freq(**YARN)
        plain = 5e5 ** (-2.0 * np.arange(32) / 64)
        assert inv.dtype == np.float32 and inv.shape == (32,)
        # pairs 0-5 keep their frequency, 16-31 turn 64 x slower, the
        # ten between blend linearly
        np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
        np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
        ramp = (np.arange(6, 16) - 5) / 11
        np.testing.assert_allclose(
            inv[6:16], plain[6:16] * ((1 - ramp) + ramp / 64), rtol=1e-6)
        assert np.all(np.diff(inv) < 0)

    def test_the_amplitude_the_config_states(self):
        assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672,
                                                       rel=1e-12)

    @pytest.mark.parametrize("factor,orig,beta_fast", [
        (8.0, 2048, 32.0), (4.0, 8192, 32.0), (64.0, 16, 64.0)])
    def test_against_the_formula_written_out(self, factor, orig, beta_fast):
        dim, base = 32, 10000.0
        got = F.yarn_inv_freq(dim, base, factor, orig, beta_fast, 1.0)
        c = lambda r: dim * math.log(orig / (r * 2 * math.pi)) / (
            2 * math.log(base))
        low, high = max(math.floor(c(beta_fast)), 0), min(
            math.ceil(c(1.0)), dim - 1)
        high = high + 0.001 if low == high else high
        p = np.arange(dim // 2)
        ramp = np.clip((p - low) / (high - low), 0, 1)
        want = base ** (-2.0 * p / dim) * ((1 - ramp) + ramp / factor)
        np.testing.assert_allclose(got, want, rtol=2e-6)


class TestPartialRotation:
    def test_lanes_past_rotary_dim_are_untouched(self):
        q, k = _qk()
        pos = np.arange(48, dtype=np.int32)
        before = profiler.get_int_stats().get("rope_partial_total", 0)
        rq, rk = F.rotary_embedding(q, k, pos, 5e5, rotary_dim=64)
        assert profiler.get_int_stats()["rope_partial_total"] == before + 1
        for new, old in ((rq, q), (rk, k)):
            np.testing.assert_array_equal(_value(new)[..., 64:],
                                          _value(old)[..., 64:])
            assert not np.allclose(_value(new)[:, 1:, :, :64],
                                   _value(old)[:, 1:, :, :64])
        # the rotated lanes are a whole-head rotation of a 64-wide head
        nq, nk = (Tensor(_value(t)[..., :64]) for t in (q, k))
        wq, wk = F.rotary_embedding(nq, nk, pos, 5e5)
        np.testing.assert_array_equal(_value(rq)[..., :64], _value(wq))
        np.testing.assert_array_equal(_value(rk)[..., :64], _value(wk))

    def test_yarn_rotation_by_hand(self):
        q, k = _qk(seed=1, s=40)
        pos = np.arange(40, dtype=np.int32) * 400    # up to 15,600
        inv = F.yarn_inv_freq(**YARN)
        amp = 1.4158883083359672
        rq, _ = F.rotary_embedding(q, k, pos, 5e5, rotary_dim=64,
                                   inv_freq=inv, amplitude=amp)
        x = _value(q).astype(np.float64)
        ang = pos[:, None].astype(np.float64) * inv.astype(np.float64)
        cos, sin = (f(ang)[None, :, None, :] * amp for f in (np.cos, np.sin))
        x1, x2 = x[..., :32], x[..., 32:64]
        want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               x[..., 64:]], axis=-1)
        # float32 angles of up to 15,600 rad carry 1e-3 rad
        np.testing.assert_allclose(_value(rq), want, atol=2e-2)
        np.testing.assert_allclose(_value(rq)[:, :3], want[:, :3], atol=1e-4)

    def test_amplitude_scales_the_rotated_lanes_only(self):
        q, k = _qk(seed=2)
        pos = np.arange(48, dtype=np.int32)
        one, _ = F.rotary_embedding(q, k, pos, 1e4, rotary_dim=64)
        two, _ = F.rotary_embedding(q, k, pos, 1e4, rotary_dim=64,
                                    amplitude=2.0)
        np.testing.assert_allclose(_value(two)[..., :64],
                                   2 * _value(one)[..., :64], rtol=1e-6)
        np.testing.assert_array_equal(_value(two)[..., 64:],
                                      _value(one)[..., 64:])

    def test_scores_depend_on_the_distance_alone(self):
        """q_i . k_j after the rotation is a function of i - j: shifting
        every position by a constant leaves the scores."""
        q, k = _qk(seed=3, b=1, s=16, h=1)
        inv = F.yarn_inv_freq(**YARN)
        scores = []
        for shift in (0, 1000):
            pos = np.arange(16, dtype=np.int32) + shift
            rq, rk = F.rotary_embedding(q, k, pos, 5e5, rotary_dim=64,
                                        inv_freq=inv, amplitude=1.4)
            scores.append(np.einsum("bqhd,bkhd->bqk", _value(rq),
                                    _value(rk)))
        np.testing.assert_allclose(scores[0], scores[1], atol=2e-2)


class TestOldCallSites:
    @pytest.mark.parametrize("interleaved", [False, True])
    @pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
    def test_bit_equal_to_the_rotation_written_out(self, interleaved, dtype):
        """What `rotary_embedding(q, k, positions, theta, interleaved)`
        computed before it knew a partial width: the same arithmetic in
        the same order, so the same bits."""
        q, k = _qk(seed=4, d=64)
        if dtype == "bfloat16":
            q, k = (Tensor(t._value.astype(jnp.bfloat16)) for t in (q, k))
        pos = np.arange(48, dtype=np.int32)

        def old(x):
            d = x.shape[-1]
            inv = 1e6 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ang = jnp.asarray(pos).astype(jnp.float32)[..., None] * inv
            cos = jnp.cos(ang[None])[:, :, None, :]
            sin = jnp.sin(ang[None])[:, :, None, :]
            xf = x.astype(jnp.float32)
            x1, x2 = (xf[..., 0::2], xf[..., 1::2]) if interleaved \
                else jnp.split(xf, 2, axis=-1)
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin],
                                   axis=-1).astype(x.dtype)

        rq, rk = F.rotary_embedding(q, k, pos, 1e6, interleaved=interleaved)
        np.testing.assert_array_equal(_value(rq), np.asarray(old(q._value)))
        np.testing.assert_array_equal(_value(rk), np.asarray(old(k._value)))

    def test_a_full_width_rotary_dim_is_the_plain_rotation(self):
        q, k = _qk(seed=5)
        pos = np.arange(48, dtype=np.int32)
        a, _ = F.rotary_embedding(q, k, pos, 1e4)
        b, _ = F.rotary_embedding(q, k, pos, 1e4, rotary_dim=128)
        np.testing.assert_array_equal(_value(a), _value(b))
