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
        cls, k_fetch, q_fetch = mask.tiles(8192, 8192, 512, 512)
        live = cls != 0
        assert live.sum() == 80 and live.size == 256
        assert (mask.tiles(8192, 8192, 256, 256)[0] != 0).sum() == 288
        # a dead step keeps the last live tile of its row / column
        for table, lv in ((k_fetch, live), (q_fetch, live.T)):
            for a in range(lv.shape[0]):
                for b in range(lv.shape[1]):
                    assert lv[a, table[a, b]]
                    if lv[a, b]:
                        assert table[a, b] == b

    @pytest.mark.parametrize("seq,block,block_q,block_k", [
        (384, 4, 128, 128),     # 768 rows: whole tiles, no padding
        (330, 4, 128, 128),     # 660 -> 768: the halves meet mid-tile
        (200, 4, 128, 256),     # 400 -> 512: too short for a full tile
        (160, 32, 64, 128),     # 320 -> 384, block 32
        (1024, 4, 256, 512),    # the cell's tile shape
    ])
    def test_tile_classes_are_the_dense_mask(self, seq, block, block_q,
                                             block_k):
        """0 dead: no pair live; 1 partial: both kinds; 2 full: every
        pair live — over the padded rows and columns, where a padding
        row sees nothing and a padding column is seen by nothing, so a
        tile that touches padding is never full."""
        mask = A.BlockDiffusionMask(seq, block)
        n_rows = -(-mask.rows // block_q) * block_q
        n_cols = -(-mask.rows // block_k) * block_k
        dense = np.zeros((n_rows, n_cols), bool)
        dense[:mask.rows, :mask.rows] = mask.dense()
        cls = mask.tiles(n_rows, n_cols, block_q, block_k)[0]
        assert cls.shape == (n_rows // block_q, n_cols // block_k)
        assert cls.dtype == np.int32
        for iq in range(cls.shape[0]):
            for ik in range(cls.shape[1]):
                t = dense[iq * block_q:(iq + 1) * block_q,
                          ik * block_k:(ik + 1) * block_k]
                want = 2 if t.all() else 1 if t.any() else 0
                assert cls[iq, ik] == want, (iq, ik)
                if (iq + 1) * block_q > mask.rows \
                        or (ik + 1) * block_k > mask.rows:
                    assert cls[iq, ik] != 2, (iq, ik)

    def test_tile_classes_of_the_sdar_cell(self):
        """`sdar_30b_a3b.blockdiff_s4096`: 2 x 4096 rows on (256, 512)
        tiles: 160 of 512 live, 112 of them full; of the 48 partial, 16
        noisy x noisy tiles hold 4 live columns a row."""
        mask = A.BlockDiffusionMask(4096, 4)
        cls = mask.tiles(8192, 8192, 256, 512)[0]
        assert cls.size == 512
        assert ((cls != 0).sum(), (cls == 2).sum(), (cls == 1).sum()) \
            == (160, 112, 48)
        noisy = cls[:16, :8]
        assert (noisy == 1).sum() == 16 and (noisy == 2).sum() == 0
        dense = mask.dense()
        assert dense[:256, :512].mean() == 4 / 512


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


def _all_live_partial(tiles):
    """`BlockDiffusionMask.tiles` as it was before a tile had a class:
    every live tile runs the masked body."""
    def patched(self, *a):
        cls, k_fetch, q_fetch = tiles(self, *a)
        return np.minimum(cls, 1), k_fetch, q_fetch
    return patched


def _assert_same_bits(got, want):
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


class TestMaskOnlyWhereItCanChangeATile:
    """The backward kernels run a full tile without the code mask and
    no kernel adds an all-zero key bias: `where(True, s, .)` is `s` and
    `s + 0.0` is `s`, so every output is the masked, biased kernels' to
    the bit."""

    @pytest.fixture
    def run(self, monkeypatch):
        """`run(q, k, v, **flash_attention kwargs)` -> ([out, lse, dq,
        dk, dv], the `biased` the kernels were built with).

        The kernels are jitted on static arguments that a patched
        `tiles` does not change, so no traced instance may outlive a
        call.  The interpreter's arithmetic is compiled without XLA's
        fusion pass: which elementwise operations the CPU backend fuses
        into a row sum decides the order it adds in, so two programs
        that differ by an identity would else differ in last bits that
        are the CPU compiler's, not the kernels'."""
        forward = A._flash_forward
        seen = {}

        def spy(*a, **kw):
            out, lse = forward(*a, **kw)
            seen.update(lse=lse, biased=kw["biased"])
            return out, lse

        monkeypatch.setattr(A, "_flash_forward", spy)

        def run(q, k, v, **kw):
            def f(q, k, v):
                out, vjp = jax.vjp(lambda q, k, v: A.flash_attention(
                    q, k, v, block_q=128, block_k=128, interpret=True,
                    **kw), q, k, v)
                w = jax.random.normal(jax.random.PRNGKey(9), out.shape)
                return (out, seen["lse"], *vjp(w))

            jax.clear_caches()
            got = jax.jit(f).lower(q, k, v).compile(compiler_options={
                "xla_disable_hlo_passes": "fusion"})(q, k, v)
            jax.clear_caches()
            return [np.asarray(x) for x in got], seen["biased"]

        return run

    @pytest.mark.parametrize("seq", [384, 330])
    @pytest.mark.parametrize("h,hkv,d", [(8, 2, 128), (4, 4, 64)])
    def test_full_tiles_unmasked_bit_equal(self, run, monkeypatch, seq,
                                           h, hkv, d):
        """768 rows on 128-tiles (660 pad to them): dead, partial and
        full tiles, against the same call with every live tile classed
        partial."""
        mask = A.BlockDiffusionMask(seq, 4)
        cls = mask.tiles(768, 768, 128, 128)[0]
        assert (cls == 2).any() and (cls == 1).any() and (cls == 0).any()
        q, k, v = _qkv(5, 1, 2 * seq, h, hkv, d)
        got, _ = run(q, k, v, block_mask=mask)
        monkeypatch.setattr(A.BlockDiffusionMask, "tiles",
                            _all_live_partial(A.BlockDiffusionMask.tiles))
        assert (mask.tiles(768, 768, 128, 128)[0] == 2).sum() == 0
        want, _ = run(q, k, v, block_mask=mask)
        _assert_same_bits(got, want)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("h,hkv,d", [(8, 2, 128), (4, 4, 64)])
    def test_zero_key_bias_not_added_bit_equal(self, run, masked, h, hkv,
                                               d):
        """No key bias and no key to pad: the kernels lose the bias
        line; an all-zero bias passed in keeps it.  Same bits."""
        mask = A.BlockDiffusionMask(128, 4) if masked else None
        q, k, v = _qkv(6, 2, 256, h, hkv, d)
        got, biased = run(q, k, v, block_mask=mask)
        assert biased is False
        want, biased = run(q, k, v, block_mask=mask,
                           key_bias=jnp.zeros((2, 256), jnp.float32))
        assert biased is True
        _assert_same_bits(got, want)

    def test_padded_keys_keep_the_bias(self, run):
        """Padding keys are hidden through the bias: it stays."""
        q, k, v = _qkv(7, 1, 200, 4, 4, 64)
        assert run(q, k, v)[1] is True

    def test_tile_counters(self):
        """384 + 384 rows, block 4, on 128-tiles: 36 tiles a head, 15
        live (3 noisy x noisy, 3 + 3 on the two block-causal diagonals,
        3 + 3 below them), those 6 full."""
        mask = A.BlockDiffusionMask(384, 4)
        q, k, v = _qkv(8, 1, 768, 2, 2, 64)
        before = profiler.get_int_stats()
        A.flash_attention(q, k, v, block_mask=mask, block_q=128,
                          block_k=128, interpret=True)
        after = profiler.get_int_stats()
        delta = lambda n: after.get(n, 0) - before.get(n, 0)
        assert (delta("flash_tiles_full_total"),
                delta("flash_tiles_live_total"),
                delta("flash_tiles_total"),
                delta("flash_block_mask_total")) == (6, 15, 36, 1)
