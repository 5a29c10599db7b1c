"""A causal sliding window in the flash kernels (interpret mode on the
CPU mesh): the three kernels against `_xla_attention` with a dense
band, forward and gradients, over windows below, at and above the tile
edge, rows that are no multiple of it, grouped heads (groups of 8 and
of 6: the two a decoder with 64 and 48 query heads over 8 key/value
heads has) and key padding; the band grid's geometry and `_WindowTiles`
against the dense mask's own classes; and Mosaic's verdict on the
band-grid instances at the sizes a 16k training step has."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops.pallas import attention as A


def _qkv(seed, b, s, h, hkv, d):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    return mk(h), mk(hkv), mk(hkv)


def _dense_band(s, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) & (j > i - window)


def _stat(name):
    return profiler.get_int_stats().get(name, 0)


# (rows, window, tile edge): under, at and over the tile; rows that are
# no multiple of it; a window that is no multiple of it
_SHAPES = [(512, 64, 128), (512, 128, 128), (512, 200, 128),
           (512, 256, 128), (300, 96, 128), (640, 384, 256),
           (384, 1, 128)]


class TestWindowKernels:
    @pytest.mark.parametrize("s,window,block", _SHAPES)
    def test_forward_matches_dense_band(self, s, window, block):
        q, k, v = _qkv(0, 1, s, 2, 2, 128)
        want = A._xla_attention(q, k, v, is_causal=True, window=window)
        # the oracle's band is the mask written out
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(128)
        probs = jax.nn.softmax(jnp.where(
            _dense_band(s, window)[None, None], logits, -jnp.inf), -1)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(jnp.einsum(
                "bhqk,bkhd->bqhd", probs, v)), rtol=1e-5, atol=1e-5)
        got = A.flash_attention(q, k, v, is_causal=True, window=window,
                                block_q=block, block_k=block,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("s,window,block", _SHAPES)
    @pytest.mark.parametrize("h,hkv", [(2, 2), (8, 1), (6, 1)])
    def test_gradients_match_dense_band(self, s, window, block, h, hkv):
        if (h, hkv) != (8, 1) and (s, window, block) not in (
                _SHAPES[2], _SHAPES[4], _SHAPES[5]):
            pytest.skip("the plain and group-6 layouts at three shapes")
        q, k, v = _qkv(1, 1, s, h, hkv, 128)
        w = jnp.asarray(np.random.RandomState(2).randn(*q.shape),
                        jnp.float32)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

        flash = lambda q, k, v: A.flash_attention(
            q, k, v, is_causal=True, window=window, block_q=block,
            block_k=block, interpret=True)
        oracle = lambda q, k, v: A._xla_attention(
            q, k, v, is_causal=True, window=window)
        got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-3, atol=5e-3, err_msg=name)

    @pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
    def test_unequal_tile_edges(self, block_q, block_k):
        q, k, v = _qkv(3, 2, 700, 4, 2, 128)
        w = jnp.asarray(np.random.RandomState(4).randn(*q.shape),
                        jnp.float32)
        flash = lambda q, k, v: jnp.sum(w * A.flash_attention(
            q, k, v, is_causal=True, window=300, block_q=block_q,
            block_k=block_k, interpret=True))
        oracle = lambda q, k, v: jnp.sum(w * A._xla_attention(
            q, k, v, is_causal=True, window=300))
        np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v),
                                   rtol=1e-3)
        for g, r in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                        jax.grad(oracle, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-3, atol=5e-3)

    def test_key_padding_beside_the_window(self):
        s, window = 512, 160
        q, k, v = _qkv(5, 2, s, 4, 2, 128)
        lens = np.array([400, 130])
        keep = jnp.asarray(np.arange(s)[None, :] < lens[:, None])
        bias = jnp.where(keep, 0.0, A.DEFAULT_MASK_VALUE)
        want = A._xla_attention(q, k, v, mask=keep[:, None, None, :],
                                is_causal=True, window=window)
        got = A.flash_attention(q, k, v, key_bias=bias, is_causal=True,
                                window=window, block_q=128, block_k=128,
                                interpret=True)
        # rows past a sequence's length see padding alone in their
        # window: their values are whatever a mean over masked keys is
        for i, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(got[i, :n]),
                                       np.asarray(want[i, :n]),
                                       rtol=2e-3, atol=2e-3)

    def test_dropout_bits_agree_between_passes(self):
        q, k, v = _qkv(6, 1, 384, 2, 2, 64)
        f = lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, is_causal=True, window=100, dropout_p=0.2,
            dropout_seed=7, block_q=128, block_k=128, interpret=True))
        # a directional derivative of the dropped-out function agrees
        # with its gradient only if both passes drew the same bits
        g = jax.grad(f)(q, k, v)
        dq = jnp.asarray(np.random.RandomState(8).randn(*q.shape),
                         jnp.float32)
        eps = 1e-2
        fd = (f(q + eps * dq, k, v) - f(q - eps * dq, k, v)) / (2 * eps)
        np.testing.assert_allclose(float(fd), float(jnp.sum(g * dq)),
                                   rtol=2e-2)

    def test_window_over_the_rows_is_plain_causal(self):
        q, k, v = _qkv(9, 1, 256, 2, 2, 128)
        lower = lambda window: A.flash_attention(
            q, k, v, is_causal=True, window=window, interpret=True)
        before = _stat("flash_window_total")
        jaxpr = lambda window: str(jax.make_jaxpr(
            lambda q, k, v: A.flash_attention(
                q, k, v, is_causal=True, window=window,
                interpret=True))(q, k, v))
        assert jaxpr(256) == jaxpr(4096) == jaxpr(None)
        assert _stat("flash_window_total") == before
        np.testing.assert_array_equal(np.asarray(lower(256)),
                                      np.asarray(lower(None)))
        assert jaxpr(255) != jaxpr(None)

    def test_refuses_what_a_window_is_not_for(self):
        q, k, v = _qkv(10, 1, 256, 2, 2, 128)
        with pytest.raises(ValueError, match="window"):
            A.flash_attention(q, k, v, window=64, interpret=True)
        with pytest.raises(ValueError, match="window"):
            A.flash_attention(q, k[:, :128], v[:, :128], is_causal=True,
                              window=64, interpret=True)
        with pytest.raises(ValueError, match="window"):
            A.flash_attention(q, k, v, is_causal=True, window=0,
                              interpret=True)


class TestBandGeometry:
    @pytest.mark.parametrize("rows,window,block_q,block_k", [
        (16384, 512, 256, 256), (16384, 512, 512, 512),
        (16384, 512, 256, 512), (1024, 200, 128, 128),
        (1024, 100, 256, 128), (768, 300, 128, 256), (512, 1, 128, 128)])
    def test_classes_and_band_against_the_dense_mask(self, rows, window,
                                                     block_q, block_k):
        cls, k_fetch, q_fetch = A._WindowTiles(window).tiles(
            rows, rows, block_q, block_k)
        nq, nk = cls.shape
        if rows <= 1024:
            m = _dense_band(rows, window).reshape(nq, block_q, nk, block_k)
            want = m.any(axis=(1, 3)).astype(int) + m.all(axis=(1, 3))
            np.testing.assert_array_equal(cls, want)
        # the band of every q tile is its live tiles, in order, and the
        # grid's inner axis is as long as the longest
        lo, hi = A._band_k(np.arange(nq), block_q, block_k, window, nk, np)
        lo_q, hi_q = A._band_q(np.arange(nk), block_q, block_k, window, nq,
                               np)
        for i in range(nq):
            np.testing.assert_array_equal(np.flatnonzero(cls[i]),
                                          np.arange(lo[i], hi[i] + 1))
        for j in range(nk):
            np.testing.assert_array_equal(np.flatnonzero(cls[:, j]),
                                          np.arange(lo_q[j], hi_q[j] + 1))
        k_steps, q_steps = A._band_lengths(window, nq, nk, block_q, block_k)
        assert k_steps == (cls != 0).sum(1).max()
        assert q_steps == (cls != 0).sum(0).max()
        assert k_steps <= (window + block_q - 2) // block_k + 2
        # a step's class as the kernels compute it
        for i, j in [(0, 0), (nq - 1, nk - 1), (nq // 2, max(
                lo[nq // 2], 0)), (nq // 2, hi[nq // 2])]:
            tile, (live, full) = A._band_step(i, j - lo[i], True, block_q,
                                              block_k, window, (nq, nk))
            assert (int(tile), int(live), int(live and full)) == (
                j, int(cls[i, j] != 0), int(cls[i, j] == 2))

    def test_the_16k_instance_by_tile_shape(self):
        """What ISSUE 38 reckons with: at 16,384 rows and a window of
        512 a head's rectangle has 63 live tiles of 1,024 at (512, 512),
        none full; (256, 256) has 189 of 4,096, 63 of them full."""
        for edge, live, full, steps in ((512, 63, 0, 64),
                                        (256, 189, 63, 192)):
            cls = A._WindowTiles(512).tiles(16384, 16384, edge, edge)[0]
            assert ((cls != 0).sum(), (cls == 2).sum()) == (live, full)
            n = 16384 // edge
            assert n * A._band_lengths(512, n, n, edge, edge)[0] == steps

    def test_counters(self):
        q, k, v = _qkv(11, 1, 1024, 8, 1, 128)
        names = ("flash_window_total", "flash_window_grid_steps_total",
                 "flash_window_tiles_live_total", "flash_tiles_live_total",
                 "flash_tiles_full_total", "flash_tiles_total")
        before = {n: _stat(n) for n in names}
        A.flash_attention(q, k, v, is_causal=True, window=256,
                          block_q=128, block_k=128, interpret=True)
        delta = {n: _stat(n) - before[n] for n in names}
        # 8 q tiles x a band of 3; the first two bands are 1 and 2 long
        assert delta == {"flash_window_total": 1,
                         "flash_window_grid_steps_total": 24,
                         "flash_window_tiles_live_total": 21,
                         "flash_tiles_live_total": 21,
                         "flash_tiles_full_total": 7,
                         "flash_tiles_total": 64}


class TestMosaicAcceptsTheBandGrid:
    """The band-grid instances at the sizes of a 16k training step, put
    to Mosaic for a v5e topology without a chip (as
    tests/test_pallas_attention.py: TestMosaicAcceptsForV5e)."""

    @pytest.fixture
    def v5e(self):
        from jax.experimental import topologies
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from paddle_tpu.ops.pallas import _common

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - libtpu held by another process
            pytest.skip(f"topology AOT unavailable: {e}")
        before = _stat("flash_fallback_total")
        try:
            with _common.compile_target(NamedSharding(
                    Mesh(np.array(topo.devices[:1]), ("d",)), P())):
                yield
        finally:
            A._EXACT_PROBE_CACHE.clear()
        assert _stat("flash_fallback_total") == before

    @pytest.mark.parametrize("heads,block_h,window", [
        (64, 8, 512), (48, 6, None)])
    def test_grouped_instances_at_16k(self, v5e, heads, block_h, window):
        edge = (256, 256) if window else (256, 512)
        assert A._probe_exact(
            (heads, 16384, 128), (heads, 16384, 128), heads, True, 0.0,
            jnp.bfloat16, block_h, *edge, 0, packed=True, kv_heads=8,
            biased=False, window=window)
