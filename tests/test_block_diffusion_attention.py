"""Grouped-query attention and the block-diffusion mask in the flash
kernels (interpret mode), against `_xla_attention` with the dense
mask: forward and all three gradients, at lengths that are no multiple
of the tile, block lengths 4 and 32, on the grouped (packed, D = 128)
instances and on the head-batched ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops.pallas import attention as A


def _qkv(seed, b, s, h, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype))


def _grads(f, q, k, v):
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


def _check(flash, oracle, q, k, v, tol=2e-5):
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(oracle(q, k, v)),
                               atol=tol, rtol=tol)
    for a, b in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=10 * tol, rtol=10 * tol)


class TestMaskFamily:
    @pytest.mark.parametrize("seq,block", [(8, 4), (12, 4), (64, 32)])
    def test_dense_is_the_definition(self, seq, block):
        m = A.BlockDiffusionMask(seq, block).dense()
        for i in range(2 * seq):
            for j in range(2 * seq):
                bi, bj = (i % seq) // block, (j % seq) // block
                if i < seq:
                    want = bj == bi if j < seq else bj < bi
                else:
                    want = j >= seq and bj <= bi
                assert m[i, j] == want, (i, j)
        # S^2 + B S of the (2S)^2 pairs are live
        assert m.sum() == seq * seq + block * seq

    def test_tile_tables(self):
        mask = A.BlockDiffusionMask(4096, 4)
        live, k_fetch, q_fetch = mask.tiles(8192, 8192, 512, 512)
        assert live.sum() == 80 and live.size == 256
        assert mask.tiles(8192, 8192, 256, 256)[0].sum() == 288
        # a dead step keeps the last live tile of its row / column
        for table, lv in ((k_fetch, live), (q_fetch, live.T)):
            for a in range(lv.shape[0]):
                for b in range(lv.shape[1]):
                    assert lv[a, table[a, b]]
                    if lv[a, b]:
                        assert table[a, b] == b


class TestGroupedQuery:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grouped_kernels_match_repeated_kv(self, causal):
        """D = 128 on the packed layout: the kernels read 2 kv heads
        for 8 query heads; S = 200 pads to two 128-tiles."""
        q, k, v = _qkv(0, 2, 200, 8, 2, 128)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, is_causal=causal, block_q=128, block_k=128,
            interpret=True)
        oracle = lambda q, k, v: A._xla_attention(q, k, v,
                                                  is_causal=causal)
        _check(flash, oracle, q, k, v)

    def test_other_widths_repeat_kv(self):
        q, k, v = _qkv(1, 1, 96, 4, 2, 64)
        flash = lambda q, k, v: A.flash_attention(q, k, v, interpret=True)
        _check(flash, lambda q, k, v: A._xla_attention(q, k, v), q, k, v)


class TestBlockDiffusionKernels:
    @pytest.mark.parametrize("seq,block", [(200, 4), (160, 32)])
    @pytest.mark.parametrize("h,hkv,d", [(8, 2, 128), (4, 4, 64)])
    def test_against_dense_mask(self, seq, block, h, hkv, d):
        """2 * seq rows on 128-tiles (400 -> 512, 320 -> 384): live,
        dead and padded tiles all occur."""
        mask = A.BlockDiffusionMask(seq, block)
        q, k, v = _qkv(2, 2, 2 * seq, h, hkv, d)
        before = profiler.get_int_stats()
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, block_mask=mask, block_q=128, block_k=128,
            interpret=True)
        oracle = lambda q, k, v: A._xla_attention(q, k, v, mask=mask)
        _check(flash, oracle, q, k, v)
        after = profiler.get_int_stats()
        delta = lambda n: after.get(n, 0) - before.get(n, 0)
        assert delta("flash_block_mask_total") > 0
        assert 0 < delta("flash_tiles_live_total") \
            < delta("flash_tiles_total")
        assert delta("flash_fallback_total") == 0

    def test_key_bias_and_mask_compose(self):
        mask = A.BlockDiffusionMask(64, 4)
        q, k, v = _qkv(3, 2, 128, 4, 4, 64)
        bias = jnp.where(jnp.arange(128)[None, :] % 7 == 3,
                         A.DEFAULT_MASK_VALUE, 0.0) * jnp.ones((2, 1))
        # keep every row a live column: never bias out the diagonal
        bias = bias.at[:, ::7].set(0.0)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, key_bias=bias, block_mask=mask, interpret=True)
        dense = jnp.asarray(mask.dense())[None, None]
        oracle = lambda q, k, v: A._xla_attention(
            q, k, v, mask=jnp.where(dense, bias[:, None, None, :],
                                    A.DEFAULT_MASK_VALUE))
        _check(flash, oracle, q, k, v)

    def test_wrong_length_is_refused(self):
        q, k, v = _qkv(4, 1, 64, 2, 2, 64)
        with pytest.raises(ValueError, match="block_mask covers"):
            A.flash_attention(q, k, v, interpret=True,
                              block_mask=A.BlockDiffusionMask(16, 4))
