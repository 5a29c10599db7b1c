"""The flash kernels with value heads narrower than the query/key heads
(latent attention: 192 over 128) and with a plain causal mask taken
through the tile classes: forward and backward against the XLA path,
the causal class table, bit-equality with the unclassed causal kernel,
the counters, and Mosaic's verdict on the `joyai_llm_flash` cell's
instances for a described v5e."""

import functools
import os

# bit-equality of two interpreted kernels needs XLA:CPU's fusion pass
# off: what is fused into a row sum decides the order it adds in
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_disable_hlo_passes=fusion").strip()

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops.pallas import attention as A


def _qkv(rng, b, sq, sk, h, d, dv, dtype=jnp.float32):
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32), dtype)
    return mk(b, sq, h, d), mk(b, sk, h, d), mk(b, sk, h, dv)


def _flash(**kw):
    return functools.partial(A.flash_attention, interpret=True, **kw)


# -- v width != q/k width ------------------------------------------------------

@pytest.mark.parametrize("sq,sk,h,d,dv,causal", [
    (128, 128, 2, 192, 128, True),      # the model's widths: a head pair
    (300, 300, 4, 192, 128, True),      # two pairs a step, padded rows
    (256, 256, 3, 192, 128, True),      # an odd head count: merged
    (128, 256, 2, 320, 128, False),     # 5 lane blocks a pair
    (200, 200, 4, 48, 32, True),        # no block multiple
    (130, 250, 2, 96, 64, False),       # cross lengths, no mask
    (100, 228, 3, 24, 40, True),        # v wider than q/k, causal offset
])
def test_split_value_width_matches_xla(sq, sk, h, d, dv, causal):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng, 2, sq, sk, h, d, dv)
    flash = _flash(is_causal=causal, block_q=128, block_k=128)
    xla = functools.partial(A._xla_attention, is_causal=causal)
    out = flash(q, k, v)
    assert out.shape == (2, sq, h, dv)
    np.testing.assert_allclose(out, xla(q, k, v), rtol=2e-5, atol=2e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.square(f(*a)))
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_split_value_width_with_key_padding_and_dropout_runs():
    """The key bias and the in-kernel dropout are layout-blind: same
    bits forward and backward with a narrower v."""
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, 2, 128, 128, 2, 48, 32)
    kb = jnp.where(jnp.arange(128)[None, :] < 100, 0.0, -1e9) \
        * jnp.ones((2, 1))
    out = _flash(key_bias=kb)(q, k, v)
    mask = (jnp.arange(128) < 100)[None, None, None, :]
    np.testing.assert_allclose(
        out, A._xla_attention(q, k, v, mask=mask), rtol=2e-5, atol=2e-5)
    drop = _flash(dropout_p=0.3, dropout_seed=jnp.int32(7))
    g = jax.grad(lambda *a: jnp.sum(drop(*a)), argnums=(0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in g)
    assert g[2].shape == v.shape


def test_split_value_counter_counts_instances():
    rng = np.random.RandomState(2)
    before = profiler.get_int_stats().get("flash_split_value_total", 0)
    q, k, v = _qkv(rng, 1, 128, 128, 2, 48, 32)
    _flash()(q, k, v)
    _flash()(q, k, k)           # equal widths: not counted
    after = profiler.get_int_stats().get("flash_split_value_total", 0)
    assert after - before == 1


def test_bf16_latent_widths_close_to_xla():
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, 1, 256, 256, 2, 192, 128, jnp.bfloat16)
    out = _flash(is_causal=True)(q, k, v).astype(jnp.float32)
    ref = A._xla_attention(q, k, v, is_causal=True).astype(jnp.float32)
    assert float(jnp.abs(out - ref).max()) < 0.05


# -- the causal tile classes ---------------------------------------------------

@pytest.mark.parametrize("n,block,full,live", [
    (8192, 512, 120, 136),      # the cell: 16 x 16 tiles a head
    (1024, 256, 6, 10),
    (512, 512, 0, 1),
])
def test_causal_class_counts(n, block, full, live):
    cls, k_fetch, q_fetch = A._CausalTiles(0).tiles(n, n, block, block)
    assert cls.shape == (n // block, n // block)
    assert int((cls == 2).sum()) == full
    assert int((cls != 0).sum()) == live
    # below the diagonal full, on it partial, above it dead
    nt = n // block
    for iq in range(nt):
        for ik in range(nt):
            assert cls[iq, ik] == (2 if ik < iq else 1 if ik == iq else 0)
    # a dead step keeps the last live tile in VMEM: nothing is fetched
    assert (k_fetch == np.minimum(np.arange(nt)[None, :],
                                  np.arange(nt)[:, None])).all()
    assert (q_fetch == np.maximum(np.arange(nt)[None, :],
                                  np.arange(nt)[:, None])).all()


@pytest.mark.parametrize("sq,sk,bq,bk", [(256, 384, 128, 128),
                                         (384, 256, 128, 128),
                                         (512, 512, 256, 128)])
def test_causal_classes_agree_with_the_dense_mask(sq, sk, bq, bk):
    offset = sk - sq
    cls = A._CausalTiles(offset).tiles(sq, sk, bq, bk)[0]
    dense = np.arange(sk)[None, :] <= np.arange(sq)[:, None] + offset
    tiles = dense.reshape(sq // bq, bq, sk // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    # a q tile with a row that sees no key keeps every tile (the row's
    # result is the mean over all keys, as the XLA path's)
    blind = ~dense.any(axis=1).reshape(sq // bq, bq).all(axis=1) \
        & ~dense.any(axis=1).reshape(sq // bq, bq).any(axis=1)
    blind = ~dense.reshape(sq // bq, bq, sk).any(axis=2).all(axis=1)
    for iq in range(sq // bq):
        for ik in range(sk // bk):
            want = 2 if every[iq, ik] else 1 if some[iq, ik] else 0
            if blind[iq]:
                want = max(want, 1)
            assert cls[iq, ik] == want, (iq, ik)


@pytest.mark.parametrize("sq,sk,dv", [(200, 200, 32), (256, 384, 48),
                                      (300, 200, 48), (384, 384, 32)])
def test_classed_causal_kernel_is_bit_equal_to_the_unclassed(sq, sk, dv,
                                                             monkeypatch):
    """Dead tiles add exact zeros and a full tile's select returns its
    input: skipping both changes no bit, forward or backward."""
    rng = np.random.RandomState(4)
    q, k, v = _qkv(rng, 2, sq, sk, 2, 48, dv)
    flash = _flash(is_causal=True, block_q=128, block_k=128)
    loss = lambda *a: jnp.sum(jnp.square(flash(*a)))

    def run():
        jax.clear_caches()
        return flash(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    out, grads = run()
    monkeypatch.setattr(A, "_tiler", lambda mask, causal, offset: mask)
    plain_out, plain_grads = run()
    jax.clear_caches()
    assert bool((out == plain_out).all())
    for g, p in zip(grads, plain_grads):
        assert bool((g == p).all())


def test_causal_instances_feed_the_tile_counters():
    rng = np.random.RandomState(5)
    q, k, v = _qkv(rng, 1, 512, 512, 2, 48, 32)
    before = profiler.get_int_stats()
    _flash(is_causal=True, block_q=128, block_k=128)(q, k, v)
    after = profiler.get_int_stats()
    delta = lambda n: after.get(n, 0) - before.get(n, 0)
    assert (delta("flash_tiles_full_total"), delta("flash_tiles_live_total"),
            delta("flash_tiles_total")) == (6, 10, 16)
    assert delta("flash_block_mask_total") == 0


def test_causal_kernel_skips_dead_tiles(monkeypatch):
    """The forward kernel's k/v index map asks for the diagonal tile on
    every dead step: the pipeline moves nothing for them."""
    cls, k_fetch, _ = A._CausalTiles(0).tiles(512, 512, 128, 128)
    for iq in range(4):
        assert list(k_fetch[iq]) == [min(ik, iq) for ik in range(4)]
    tables, arrays, specs, fetch = A._mask_operands(
        A._CausalTiles(0), None, 512, 512, 128, 128, "qk")
    assert len(tables) == 2 and arrays == [] and specs == []
    assert int(fetch(1, 3, tables)) == 1


def test_block_mask_still_goes_by_its_own_table():
    """With a block mask the table is the mask's, causal or not."""
    mask = A.BlockDiffusionMask(128, 4)
    assert A._tiler(mask, True, 0) is mask
    assert A._tiler(None, False, 0) is None
    assert A._tiler(None, True, 3) == A._CausalTiles(3)


# -- Mosaic's verdict on the cell's instances ----------------------------------

@pytest.fixture
def v5e():
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas import _common

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - libtpu held by another process
        pytest.skip(f"topology AOT unavailable: {e}")
    before = profiler.get_int_stats()
    try:
        with _common.compile_target(NamedSharding(
                Mesh(np.array(topo.devices[:1]), ("d",)), P())):
            yield
    finally:
        A._EXACT_PROBE_CACHE.clear()
    after = profiler.get_int_stats()
    assert after.get("flash_fallback_total", 0) == \
        before.get("flash_fallback_total", 0)


def test_mosaic_accepts_the_latent_attention_instances(v5e):
    """The `joyai_llm_flash.ar_mtp_s8192` instances: 2 x 8192 rows, 32
    heads of 192 (q, k) over 128 (v), bfloat16, causal by tile class on
    (512, 512) tiles, no key bias — forward and both backward kernels,
    at the head block flash_attention() takes there — and the generic
    probe that lets the dispatcher reach them (`_flash_ok`: a merged
    pair of heads 192 wide in q, k AND v, so an accumulator row is one
    and a half vregs)."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16)
    try:
        assert A._flash_ok(q, q)
    finally:
        A._PROBE_CACHE.clear()
    assert A._probe_exact((64, 8192, 192), (64, 8192, 192), 32, True, 0.0,
                          jnp.bfloat16, 4, 512, 512, 0, packed=True,
                          kv_heads=32, biased=False, v_dim=128)
