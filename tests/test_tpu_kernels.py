"""Real-hardware (non-interpret) Pallas kernel tests — the TPU lane.

Every other kernel test runs `interpret=True` on CPU, which proves the
arithmetic and nothing about Mosaic: a kernel can pass all of them and
fail lowering for every input shape on the chip (BENCH_r02).  This lane
runs the kernels through the actual Mosaic compiler, on the chip, in
one process (after `python chip_smoke.py`, the second command there):

    PADDLE_TPU_TEST_LANE=1 python -m pytest tests -m tpu

Oracles: `_xla_attention` for the flash kernels
(tests/test_pallas_attention.py validates it against NumPy in interpret
mode; here it runs on the same chip) and `_dense_paged_attention` for
the ragged paged kernel.  The last test fails the lane if any kernel
gave way to its XLA path on the way (`flash_fallback_total`,
`serving_ragged_fallback_total`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas.attention import (
    _xla_attention,
    flash_attention,
)

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(jax.default_backend() != "tpu",
                       reason="needs a real TPU backend "
                              "(PADDLE_TPU_TEST_LANE=1)"),
]


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla_on_tpu(causal):
    q, k, v = (_rand((2, 256, 4, 64), s) for s in (0, 1, 2))
    out = flash_attention(q, k, v, is_causal=causal)
    ref = _xla_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_key_padding_bias_on_tpu():
    q, k, v = (_rand((2, 256, 4, 64), s) for s in (3, 4, 5))
    kb = jnp.where(jnp.arange(256)[None, :] < 200, 0.0, -1e9)
    kb = jnp.broadcast_to(kb, (2, 256)).astype(jnp.float32)
    out = flash_attention(q, k, v, key_bias=kb)
    ref = _xla_attention(q, k, v, mask=kb[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_grads_match_xla_on_tpu():
    q, k, v = (_rand((2, 256, 4, 64), s) for s in (6, 7, 8))

    def loss(att):
        return lambda q, k, v: jnp.sum(att(q, k, v, is_causal=True) ** 2)

    g = jax.grad(loss(lambda q, k, v, **kw: flash_attention(q, k, v, **kw)),
                 argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(loss(lambda q, k, v, **kw: _xla_attention(q, k, v, **kw)),
                 argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch on TPU")


def test_bf16_dropout_lowers_and_runs():
    q, k, v = (_rand((2, 256, 4, 64), s, jnp.bfloat16) for s in (9, 10, 11))
    out = flash_attention(q, k, v, dropout_p=0.1, dropout_seed=3)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, dropout_p=0.1, dropout_seed=3).astype(jnp.float32)))(q)
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


def test_odd_shapes_via_padding_shim():
    q = _rand((2, 300, 4, 64), 12)
    k = _rand((2, 333, 4, 64), 13)
    v = _rand((2, 333, 4, 64), 14)
    out = flash_attention(q, k, v)
    ref = _xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_bert_seq512_shape_regression():
    """The exact (B, S) = (·, 512) family that crashed in BENCH_r02."""
    q, k, v = (_rand((2, 512, 4, 64), s, jnp.bfloat16)
               for s in (15, 16, 17))
    kb = jnp.where(jnp.arange(512)[None, :] < 400, 0.0, -1e9)
    kb = jnp.broadcast_to(kb, (2, 512)).astype(jnp.float32)
    out = flash_attention(q, k, v, key_bias=kb, dropout_p=0.1,
                          dropout_seed=1)
    assert out.shape == (2, 512, 4, 64)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


def test_flash_pair_at_bench_shape():
    """The BERT-base step's own instance — (B*H, S, D) = (384, 512, 64)
    bf16, key-padding bias, dropout 0.1 — forward and both backward
    kernels against an XLA oracle that applies the SAME keep mask
    (the in-kernel RNG is a pure hash of absolute coordinates)."""
    b, s, h, d, p_drop, seed = 32, 512, 12, 64, 0.1, 5
    q, k, v, g = (_rand((b, s, h, d), i, jnp.bfloat16)
                  for i in (20, 21, 22, 23))
    lens = np.random.RandomState(24).randint(s // 2, s + 1, (b,))
    kb = jnp.where(jnp.arange(s)[None, :] < lens[:, None], 0.0,
                   -1e9).astype(jnp.float32)
    keep = A._keep_mask3(jnp.int32(seed), 0, 0, 0, b * h, s, s,
                         p_drop).reshape(b, h, s, s)

    def oracle(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
        probs = jax.nn.softmax(logits + kb[:, None, None, :], axis=-1)
        probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def kernel(q, k, v):
        return flash_attention(q, k, v, key_bias=kb, dropout_p=p_drop,
                               dropout_seed=seed).astype(jnp.float32)

    gf = g.astype(jnp.float32)
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a) * gf), argnums=(0, 1, 2))(q, k, v)
    ref, rgrads = jax.value_and_grad(
        lambda *a: jnp.sum(oracle(*a) * gf), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(oracle(q, k, v)),
                               atol=3e-2, rtol=3e-2)
    for name, a, r in zip("qkv", grads, rgrads):
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(r.astype(jnp.float32)), atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch at the bench shape")
    rungs = {key[6] for key, ok in A._EXACT_PROBE_CACHE.items()
             if ok and key[0] == (b * h, s, d)}
    print(f"flash head-block rung Mosaic accepted: {sorted(rungs)}")
    assert rungs


@pytest.mark.parametrize("b,s,h,d", [
    (128, 128, 12, 64),     # bert_base.pretrain_s128's instance
    (8, 512, 4, 128),       # one head a 128-lane block
])
def test_flash_pair_packed_at_bench_shapes(b, s, h, d):
    """The packed (B, S, H*D) instances beside the s512 one above:
    bf16, key-padding bias, dropout 0.1, forward and both backward
    kernels against the same-keep-mask oracle; each traced instance
    counts in `flash_packed_layout_total`."""
    p_drop, seed = 0.1, 7
    q, k, v, g = (_rand((b, s, h, d), i, jnp.bfloat16)
                  for i in (30, 31, 32, 33))
    lens = np.random.RandomState(34).randint(s // 2, s + 1, (b,))
    kb = jnp.where(jnp.arange(s)[None, :] < lens[:, None], 0.0,
                   -1e9).astype(jnp.float32)
    keep = A._keep_mask3(jnp.int32(seed), 0, 0, 0, b * h, s, s,
                         p_drop).reshape(b, h, s, s)

    def oracle(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
        probs = jax.nn.softmax(logits + kb[:, None, None, :], axis=-1)
        probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def kernel(q, k, v):
        return flash_attention(q, k, v, key_bias=kb, dropout_p=p_drop,
                               dropout_seed=seed).astype(jnp.float32)

    gf = g.astype(jnp.float32)
    before = profiler.get_int_stats().get("flash_packed_layout_total", 0)
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a) * gf), argnums=(0, 1, 2))(q, k, v)
    assert profiler.get_int_stats()["flash_packed_layout_total"] \
        == before + 1
    ref, rgrads = jax.value_and_grad(
        lambda *a: jnp.sum(oracle(*a) * gf), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(oracle(q, k, v)),
                               atol=3e-2, rtol=3e-2)
    for name, a, r in zip("qkv", grads, rgrads):
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(r.astype(jnp.float32)), atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch at {(b, s, h, d)}")


def test_merged_shapes_on_tpu():
    """Shapes outside the packed rule (odd head count at D = 64, a head
    dim that fills no lane block) keep the merged (B*H, S, D) kernels:
    parity with XLA, and no packed instance counted."""
    before = profiler.get_int_stats().get("flash_packed_layout_total", 0)
    for h, d in ((3, 64), (4, 80)):
        q, k, v = (_rand((2, 256, h, d), s) for s in (40, 41, 42))
        out = flash_attention(q, k, v, is_causal=True)
        ref = _xla_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)
    assert profiler.get_int_stats().get(
        "flash_packed_layout_total", 0) == before


# -- ragged paged attention (serving decode / chunked prefill) --------------

def _paged_case(lengths, t, dtype, seed=0):
    """tests/test_fast_decode.py's ragged layout (random pool, scratch
    page 0 included, length-0 rows on the scratch page) at serving
    sizes: page 16, 8 heads x 64."""
    from test_fast_decode import _paged_case as case

    q, kp, vp, rows, lens = case(lengths, t=t, page_size=16, heads=8,
                                 dim=64, seed=seed)
    return q.astype(dtype), kp.astype(dtype), vp.astype(dtype), rows, lens


def _assert_paged_parity(q, kp, vp, rows, lens, qpos=None):
    t, d = q.shape[1], q.shape[-1]
    if qpos is None:
        qpos = lens[:, None] - t + jnp.arange(t, dtype=jnp.int32)[None, :]
    out = A.paged_attention(q, kp, vp, rows, lens, q_positions=qpos)
    ref = A._dense_paged_attention(q, kp, vp, rows, lens, qpos,
                                   1.0 / (d ** 0.5))
    out, ref = (np.asarray(x.astype(jnp.float32)) for x in (out, ref))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_parity_on_tpu(dtype):
    """T == 1 over ragged lengths: one token, an exact page multiple,
    several pages, and a length-0 lane that only sees the scratch
    page."""
    _assert_paged_parity(*_paged_case([1, 16, 75, 0, 33, 128, 5, 64],
                                      1, dtype))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_chunk_parity_on_tpu(dtype):
    """T == a prefill chunk at explicit absolute positions (the chunk
    step's call): the third 64-token chunk of a prompt, causal inside
    the chunk."""
    q, kp, vp, rows, lens = _paged_case([192], 64, dtype, seed=1)
    qpos = (128 + jnp.arange(64, dtype=jnp.int32))[None, :]
    _assert_paged_parity(q, kp, vp, rows, lens, qpos)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_causal_tail_parity_on_tpu(dtype):
    """T > 1 at the default positions: the T newest tokens, causally
    masked among themselves."""
    _assert_paged_parity(*_paged_case([40, 9, 100], 8, dtype, seed=2))


def test_dataloader_workers_feed_executor_on_tpu():
    """One `DataLoader(num_workers=2)` epoch into the Executor from the
    process that holds the chip: the worker processes are forked from
    a parent with the TPU runtime loaded, stay host-side, and must
    neither hang nor crash."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.io import DataLoader, Dataset

    class Rows(Dataset):
        def __len__(self):
            return 64

        def __getitem__(self, i):
            r = np.random.RandomState(i)
            return r.randn(16).astype(np.float32), \
                r.randn(1).astype(np.float32)

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard(), \
            scope_guard(Scope()):
        x = fluid.data("x", [-1, 16], "float32")
        y = fluid.data("y", [-1, 1], "float32")
        loss = fluid.layers.reduce_mean(
            fluid.layers.loss.square_error_cost(
                fluid.layers.fc(x, 1), y))
        fluid.optimizer.SGD(0.01).minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        loader = DataLoader(Rows(), batch_size=8, num_workers=2,
                            timeout=120)
        assert loader.use_process_workers
        losses = [exe.run(main, feed={"x": xb, "y": yb},
                          fetch_list=[loss])[0] for xb, yb in loader]
    assert len(losses) == 8
    assert all(np.isfinite(np.asarray(v)).all() for v in losses)


def test_launcher_recognises_the_tpu_host(monkeypatch):
    """The launcher refuses several workers per TPU host; it has to
    tell, without touching JAX, that this machine is one."""
    from paddle_tpu.distributed import launch_utils

    import glob

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    seen = {n: launch_utils._vfio_group_vendors(n.rsplit("/", 1)[1])
            for n in glob.glob("/dev/vfio/[0-9]*")}
    assert launch_utils.on_tpu_host(), \
        (glob.glob("/dev/accel*"), seen)


def test_routed_moe_packed_plan_on_tpu():
    """The dropless expert layer through XLA:TPU's sort, grouped
    matmuls and dynamic-trip-count walk, under the checkpoint policy
    that keeps its visit plan: forward and gradients against the
    gather-free dense oracle, nothing dropped, and the plan counted
    under `moe_plan_packed_total` (one packed int32 key a visit)."""
    from paddle_tpu.parallel import moe

    n, held, k, t, h, f = 32, (8, 8), 4, 2048, 256, 128
    p = moe.init_routed_moe_params(0, n, h, f, held=held)
    x = _rand((t, h), 50)

    def layer(p, x):
        out, stats, experts = moe.routed_moe_local(p, x, k, held=held,
                                                   chunk=1024)
        return jnp.sum(jnp.sin(out)), (out, stats, experts)

    def oracle(p, x, experts):
        _, weights = moe.route_top_k(x, p["wr"], k)
        out = jnp.zeros_like(x)
        for e in range(held[1]):
            y = (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])) @ p["wd"][e]
            w = jnp.sum(jnp.where(experts == held[0] + e, weights, 0), 1)
            out = out + y * w[:, None]
        return jnp.sum(jnp.sin(out)), out

    before = profiler.get_int_stats()
    remat = jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(
            "moe_plan"))
    with jax.default_matmul_precision("highest"):
        grads, (out, stats, experts) = jax.jit(jax.grad(
            remat, (0, 1), has_aux=True))(p, x)
        want, ref = jax.jit(jax.grad(oracle, (0, 1), has_aux=True))(
            p, x, experts)
    after = profiler.get_int_stats()
    assert after["moe_plan_packed_total"] \
        == before.get("moe_plan_packed_total", 0) + 1
    assert after.get("moe_plan_two_operand_total", 0) \
        == before.get("moe_plan_two_operand_total", 0)
    stats = np.asarray(stats)
    assert stats[:-2].sum() == stats[-1] > 0 and stats[-2] == t * k
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=5e-3)


def test_block_diffusion_mask_grouped_on_tpu():
    """The masked kernels at the SDAR cell's head shape (8 query heads
    a key/value head, D = 128) through Mosaic, against `_xla_attention`
    with the dense mask: 2 x 1024 rows on (256, 512) tiles hold dead
    tiles (skipped), partial ones (masked from the codes) and full ones
    (in the backward kernels the same body without the mask); no key
    bias, no padded key, so the kernels are built without the bias
    add."""
    mask = A.BlockDiffusionMask(1024, 4)
    q = _rand((2, 2048, 16, 128), 60, jnp.bfloat16)
    k, v = (_rand((2, 2048, 2, 128), s, jnp.bfloat16) for s in (61, 62))
    w = _rand((2, 2048, 16, 128), 63, jnp.bfloat16)
    before = profiler.get_int_stats()

    def out_and_grads(attention):
        def f(q, k, v):
            out, vjp = jax.vjp(attention, q, k, v)
            return out, vjp(w)
        return jax.jit(f)(q, k, v)

    out, got = out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, block_mask=mask))
    ref, want = out_and_grads(
        lambda q, k, v: _xla_attention(q, k, v, mask=mask))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a / np.abs(b).max(),
                                   b / np.abs(b).max(), atol=2e-2)
    after = profiler.get_int_stats()
    delta = lambda n: after.get(n, 0) - before.get(n, 0)
    calls = delta("flash_block_mask_total")
    assert calls > 0
    assert (delta("flash_tiles_full_total"),
            delta("flash_tiles_live_total"),
            delta("flash_tiles_total")) == (4 * calls, 16 * calls,
                                            32 * calls)


@pytest.mark.parametrize("heads", [4, 3])
def test_latent_attention_heads_on_tpu(heads):
    """Latent attention's head shape (q/k heads of 192 over v heads of
    128, causal) through Mosaic, against `_xla_attention`: an even head
    count takes the packed operands (a head pair on three lane blocks),
    an odd one the merged ones; 1200 rows pad to (512, 512) tiles of
    which the causal table skips the dead ones and masks the diagonal
    ones."""
    q, k = (_rand((2, 1200, heads, 192), s, jnp.bfloat16) for s in (70, 71))
    v, w = (_rand((2, 1200, heads, 128), s, jnp.bfloat16) for s in (72, 73))
    before = profiler.get_int_stats()

    def out_and_grads(attention):
        def f(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: attention(q, k, v, is_causal=True), q, k, v)
            return out, vjp(w)
        return jax.jit(f)(q, k, v)

    out, got = out_and_grads(flash_attention)
    ref, want = out_and_grads(_xla_attention)
    assert out.shape == (2, 1200, heads, 128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a / np.abs(b).max(),
                                   b / np.abs(b).max(), atol=2e-2)
    after = profiler.get_int_stats()
    delta = lambda n: after.get(n, 0) - before.get(n, 0)
    calls = delta("flash_split_value_total")
    assert calls > 0
    assert delta("flash_packed_layout_total") == (calls if heads == 4 else 0)
    assert (delta("flash_tiles_full_total"),
            delta("flash_tiles_live_total"),
            delta("flash_tiles_total")) == (3 * calls, 6 * calls, 9 * calls)


@pytest.mark.parametrize("cell,h,hkv,d,dv,kw,pieces", [
    ("sdar_30b_a3b.blockdiff_s4096", 8, 1, 128, 128,
     dict(mask=A.BlockDiffusionMask(4096, 4)), 8),
    ("joyai_llm_flash.ar_mtp_s8192", 4, 4, 192, 128,
     dict(is_causal=True), 4),
])
def test_forward_body_at_the_moe_cells_shapes(cell, h, hkv, d, dv, kw,
                                              pieces):
    """The forward kernel's grid step as the two MoE cells run it — the
    cell's rows (8,192), tiles and heads a step (one key/value group of
    8 heads on (256, 512) tiles under the block-diffusion mask; 4 heads
    of 192 over 128 on (512, 512) causal tiles), one head block of it —
    through Mosaic against `_xla_attention`; the body walked the step's
    heads one at a time (`flash_fwd_pieces_total`)."""
    q = _rand((1, 8192, h, d), 80, jnp.bfloat16)
    k = _rand((1, 8192, hkv, d), 81, jnp.bfloat16)
    v = _rand((1, 8192, hkv, dv), 82, jnp.bfloat16)
    before = profiler.get_int_stats().get("flash_fwd_pieces_total", 0)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, is_causal=kw.get("is_causal", False),
        block_mask=kw.get("mask")))(q, k, v)
    assert profiler.get_int_stats()["flash_fwd_pieces_total"] \
        == before + pieces, cell
    ref = jax.jit(lambda q, k, v: _xla_attention(q, k, v, **kw))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def _kda_operands(b, s, h, d, seed):
    rng = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (jnp.asarray(unit(rng.randn(b, s, h, d)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    g = jnp.asarray(-rng.uniform(0.001, 1.0, (b, s, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, s, h)), jnp.float32)
    return q, k, v, g, beta


def _kda_out_and_grads(scan, args, w):
    def f(*a):
        out, vjp = jax.vjp(lambda *a: scan(*a).astype(jnp.float32), *a)
        return out, vjp(w)
    return jax.jit(f)(*args)


def _assert_kda_close(got, want):
    (out, grads), (ref, ref_grads) = got, want
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    for a, b_ in zip(grads, ref_grads):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        assert np.linalg.norm(a - b_) <= 2e-2 * np.linalg.norm(b_)


@pytest.mark.parametrize("heads", [4, 3])
def test_kda_scan_against_the_recurrence_on_tpu(heads):
    """The chunked gated delta-rule scan (ops/pallas/kda.py: `kda_fwd`,
    `kda_bwd` through Mosaic, the chunk-local half made in VMEM inside
    them, the backward written by hand) at the Kimi cell's head shape —
    128-wide heads, bfloat16 q / k / v, float32 gate — against the
    recurrence a token at a time on the same chip, outputs and all
    five operands' gradients; a length that is no multiple of 64,
    decays down to -1 a token; two heads a grid step and (3 heads) one."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas.kda import kda_attention

    b, s, d = 1, 1000, 128
    args = _kda_operands(b, s, heads, d, 90)
    w = _rand((b, s, heads, d), 91)
    before = profiler.get_int_stats()
    got = _kda_out_and_grads(kda_attention, args, w)
    after = profiler.get_int_stats()
    assert after.get("kda_chunked_total", 0) \
        == before.get("kda_chunked_total", 0) + 1
    assert after.get("kda_chunks_total", 0) \
        == before.get("kda_chunks_total", 0) + 16
    _assert_kda_close(got, _kda_out_and_grads(
        lambda *a: X.recurrent(*a, d ** -0.5), args, w))


def test_kda_scan_at_the_cell_shape_on_tpu():
    """One call of each kernel with all 32 heads at 16,384 tokens (the
    Kimi cell's instance: 8,192 chunk-heads a call, nothing of a chunk
    through HBM but the operands and the entering states): the first
    1,024 tokens' outputs, and every gradient under a cotangent that is
    zero beyond them, equal the recurrence's on that prefix — and the
    gradients beyond it are exactly zero."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas.kda import kda_attention

    b, s, h, d, n = 1, 16384, 32, 128, 1024
    args = _kda_operands(b, s, h, d, 92)
    w = _rand((b, s, h, d), 93).at[:, n:].set(0.0)
    out, grads = _kda_out_and_grads(kda_attention, args, w)
    assert bool(jnp.isfinite(out).all())
    for grad in grads:
        assert float(jnp.abs(grad[:, n:].astype(jnp.float32)).max()) == 0.0
    prefix = tuple(a[:, :n] for a in args)
    _assert_kda_close((out[:, :n], tuple(a[:, :n] for a in grads)),
                      _kda_out_and_grads(
                          lambda *a: X.recurrent(*a, d ** -0.5), prefix,
                          w[:, :n]))


@pytest.mark.parametrize("tokens,key_heads", [(16384, 16), (1000, 1)])
def test_gdn_forms_against_the_per_channel_kernels_on_tpu(tokens, key_heads):
    """The scan's Gated DeltaNet instances (`gdn_fwd` / `gdn_bwd`: a
    decay a value head, two value heads over each key head) at the
    Qwen3-Next cell's shape — 1 x 16,384, 16 key heads over 32 value
    heads of 128, decays down to -20 a token — and at a length that is
    no multiple of 64, against the Kimi instances (`kda_fwd` /
    `kda_bwd`) fed the decay broadcast to the lanes and q and k repeated
    to the value heads; and the first 1,000 tokens against the
    recurrence.  The instances take their scores in the scalar form
    (exp(G_i - G_j) over q k^T and k k^T; `kda_scalar_scores_total`, 2
    a differentiated instance): other float32 arithmetic than the Kimi
    kernels', so the bfloat16 outputs agree to bfloat16 rounding, as the
    bfloat16 gradients do, and the float32 dg, dbeta nearly to float32
    rounding."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas.kda import kda_attention

    b, d, hk = 1, 128, key_heads
    hv = 2 * hk
    rng = np.random.RandomState(95)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (jnp.asarray(unit(rng.randn(b, tokens, hk, d)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, tokens, hv, d), jnp.bfloat16)
    g = jnp.asarray(-rng.uniform(0.0, 20.0, (b, tokens, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, tokens, hv)), jnp.float32)
    args = (q, k, v, g, beta)
    w = _rand((b, tokens, hv, d), 96)

    def expand(q, k, v, g, beta):
        return (jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
                jnp.broadcast_to(g[..., None], g.shape + (d,)), beta)

    before = profiler.get_int_stats()
    out, grads = _kda_out_and_grads(kda_attention, args, w)
    after = profiler.get_int_stats()
    for name in ("kda_head_decay_total", "kda_grouped_heads_total"):
        assert after.get(name, 0) == before.get(name, 0) + 1
    assert after.get("kda_scalar_scores_total", 0) \
        == before.get("kda_scalar_scores_total", 0) + 2
    assert after.get("kda_group_repeat_total", 0) \
        == before.get("kda_group_repeat_total", 0)
    alike, alike_grads = _kda_out_and_grads(
        lambda *a: kda_attention(*expand(*a)), args, w)
    f32 = lambda a: np.asarray(a, np.float32)
    rel = lambda a, b_: np.linalg.norm(f32(a) - f32(b_)) / np.linalg.norm(
        f32(b_))
    assert rel(out, alike) < 1e-2
    for i, (a, b_) in enumerate(zip(grads, alike_grads)):
        assert a.dtype == args[i].dtype and a.shape == args[i].shape
        assert rel(a, b_) < (1e-2 if a.dtype == jnp.bfloat16 else 1e-4), i
    n = min(tokens, 1000)
    prefix = tuple(a[:, :n] for a in args)
    _assert_kda_close(_kda_out_and_grads(kda_attention, prefix, w[:, :n]),
                      _kda_out_and_grads(
                          lambda *a: X.recurrent(*expand(*a), d ** -0.5),
                          prefix, w[:, :n]))


def test_silu_gated_head_norm_against_its_xla_statement_on_tpu():
    """`gdn_post_fwd` / `gdn_post_bwd` (the gated head norm with SiLU)
    at the Qwen3-Next cell's shape — 1 x 16,384 x 32 heads of 128,
    bfloat16 — against the XLA statement on the same chip."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas import kda_edge as E

    _, post, cot = _edge_operands_tpu(16384, 32, 97)

    def run(fn):
        return jax.jit(lambda *a: jax.vjp(fn, *a)[1](cot[-1]) + (fn(*a),))(
            *post)

    got = run(lambda o, g, w: E.kda_post(o, g, w, 1e-6, activation="silu"))
    want = run(lambda o, g, w: X.edge_post(o, g, w, 1e-6, "silu"))
    f32 = lambda a: np.asarray(a, np.float32)
    for a, b_ in zip(got, want):
        assert a.dtype == b_.dtype and a.shape == b_.shape
        assert np.linalg.norm(f32(a) - f32(b_)) <= 2e-2 * np.linalg.norm(
            f32(b_))


def _edge_operands_tpu(s, heads, seed):
    rng = np.random.RandomState(seed)
    w = heads * 128
    rows = lambda shift=0.0: jnp.asarray(rng.randn(1, s, w) + shift,
                                         jnp.bfloat16)
    pre = (rows(), rows(), rows(), rows(-2.0),
           *(jnp.asarray(rng.uniform(-0.5, 0.5, (4, w)), jnp.bfloat16)
             for _ in range(3)),
           jnp.asarray(rng.randn(w) - 3.0, jnp.float32),
           jnp.asarray(np.log(rng.uniform(1, 16, heads)), jnp.float32))
    post = (rows(), rows(), jnp.asarray(1 + 0.1 * rng.randn(128),
                                        jnp.bfloat16))
    cot = tuple(rows() for _ in range(3)) + (
        jnp.asarray(rng.randn(1, s, w), jnp.float32), rows())
    return pre, post, cot


@pytest.mark.parametrize("tokens,heads", [(16384, 32), (1000, 3)])
def test_kda_edge_passes_against_their_xla_statement_on_tpu(tokens, heads):
    """The two elementwise passes around the scan (ops/pallas/
    kda_edge.py: `kda_pre_fwd` / `kda_pre_bwd`, `kda_post_fwd` /
    `kda_post_bwd` through Mosaic) at the Kimi cell's shape — 1 x
    16,384 x 32 heads of 128, bfloat16 operands, float32 g — and at a
    length that is no multiple of the row tile with a head a grid step,
    against their XLA statement (nn/functional/kda.py) on the same
    chip: the five outputs and all twelve cotangents; and the two
    counters."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas import kda_edge as E

    pre, post, cot = _edge_operands_tpu(tokens, heads, 94)

    def both(pre_fn, post_fn):
        def f(pre, post):
            out, vjp = jax.vjp(lambda pre, post: (
                *pre_fn(*pre), post_fn(*post, 1e-5)), pre, post)
            return out, vjp(cot)
        return jax.jit(f)(pre, post)

    before = profiler.get_int_stats()
    out, (d_pre, d_post) = both(E.kda_pre, E.kda_post)
    after = profiler.get_int_stats()
    assert after.get("kda_edge_fused_total", 0) \
        == before.get("kda_edge_fused_total", 0) + 2
    assert after.get("kda_edge_fallback_total", 0) \
        == before.get("kda_edge_fallback_total", 0)
    ref, (r_pre, r_post) = both(X.edge_pre, X.edge_post)
    f32 = lambda a: np.asarray(a, np.float32)
    for a, b_ in zip(out, ref):
        assert a.dtype == b_.dtype
        np.testing.assert_allclose(f32(a), f32(b_), atol=2e-2, rtol=2e-2)
    for a, b_ in zip(d_pre + d_post, r_pre + r_post):
        assert a.dtype == b_.dtype and a.shape == b_.shape
        assert np.linalg.norm(f32(a) - f32(b_)) <= 2e-2 * np.linalg.norm(
            f32(b_))


@pytest.mark.parametrize("tokens,key_heads", [(16384, 16), (1000, 1)])
def test_gdn_pre_pass_against_its_xla_statement_on_tpu(tokens, key_heads):
    """Gated DeltaNet's pass before its scan (`gdn_pre_fwd` /
    `gdn_pre_bwd` through Mosaic, reading q~, k~, v~ in place from the
    projection's whole output) at the Qwen3-Next cell's shape — 1 x
    16,384, 16 key heads under 32 value heads of 128, bfloat16, 4 taps
    — and at a length that is no multiple of the row tile with one key
    head, against its XLA statement on the same chip: the six outputs
    and the five cotangents within bfloat16 rounding; one fused
    instance counted."""
    from paddle_tpu.nn.functional import kda as X
    from paddle_tpu.ops.pallas import kda_edge as E

    rng = np.random.RandomState(98)
    hk, hv, d = key_heads, 2 * key_heads, 128
    width = (2 * hk + hv) * d
    qkvz = jnp.asarray(rng.randn(1, tokens, width + hv * d), jnp.bfloat16)
    ba = jnp.asarray(rng.randn(1, tokens, 2 * hv), jnp.bfloat16)
    taps = jnp.asarray(rng.uniform(-0.5, 0.5, (4, width)), jnp.float32)
    dt_bias = jnp.asarray(rng.randn(hv), jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1, 16, hv)), jnp.float32)
    args = (qkvz, ba, taps, dt_bias, a_log)

    def xla(qkvz, *rest):
        return X.gdn_pre(qkvz[..., :width], *rest, hk) + (qkvz[..., width:],)

    shapes = [((1, tokens, h, d), jnp.bfloat16) for h in (hk, hk, hv)] + [
        ((1, tokens, hv), jnp.float32)] * 2 + [
        ((1, tokens, hv * d), jnp.bfloat16)]
    cot = tuple(jnp.asarray(rng.randn(*shape), dtype)
                for shape, dtype in shapes)

    def run(fn):
        def f(*a):
            out, vjp = jax.vjp(fn, *a)
            return out, vjp(cot)
        return jax.jit(f)(*args)

    before = profiler.get_int_stats()
    out, grads = run(lambda *a: E.gdn_pre(*a, hk))
    after = profiler.get_int_stats()
    assert after.get("kda_edge_fused_total", 0) \
        == before.get("kda_edge_fused_total", 0) + 1
    assert after.get("kda_edge_fallback_total", 0) \
        == before.get("kda_edge_fallback_total", 0)
    ref, ref_grads = run(xla)
    f32 = lambda a: np.asarray(a, np.float32)
    for a, b_ in zip(out + grads, ref + ref_grads):
        assert a.dtype == b_.dtype and a.shape == b_.shape
        assert np.linalg.norm(f32(a) - f32(b_)) <= 2e-2 * np.linalg.norm(
            f32(b_))


def _attend_and_grads(attend, q, k, v, w):
    def f(q, k, v, w):      # w an operand: a closed-over one is a constant
        out, vjp = jax.vjp(lambda *a: attend(*a).astype(jnp.float32),
                           q, k, v)
        return out, vjp(w)
    return jax.jit(f)(q, k, v, w)


def _assert_attention_close(got, want, what):
    (out, grads), (ref, ref_grads) = got, want
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(out), f32(ref), atol=2e-2, rtol=2e-2,
                               err_msg=what)
    for a, b_, name in zip(grads, ref_grads, "qkv"):
        assert np.linalg.norm(f32(a) - f32(b_)) <= 2e-2 * np.linalg.norm(
            f32(b_)), (what, name)


def test_window_flash_at_the_cell_shape_on_tpu():
    """The three kernels with a window of 512 at the Laguna cell's
    instance — 1 x 16,384 rows, 64 query heads in groups of 8 over 8
    key/value heads of 128, grids that walk the band — through Mosaic
    against `_xla_attention` with a dense band.  The oracle cannot hold
    16,384^2 scores a head, and need not: rows 7,000 ... 8,535 (no tile's
    edge) see keys 6,489 ... 8,535 alone, so their outputs, and every
    gradient under a cotangent that is zero outside them, equal the
    oracle's on that segment (a causal offset of 511 rows: its band is
    the same band) — and the gradients outside it are exactly zero."""
    s, h, hkv, d, window = 16384, 64, 8, 128, 512
    lo, hi = 7000, 8536
    first = lo - window + 1
    q = _rand((1, s, h, d), 100, jnp.bfloat16)
    k = _rand((1, s, hkv, d), 101, jnp.bfloat16)
    v = _rand((1, s, hkv, d), 102, jnp.bfloat16)
    rows = (jnp.arange(s) >= lo) & (jnp.arange(s) < hi)
    w = jnp.where(rows[None, :, None, None], _rand((1, s, h, d), 103), 0.0)
    names = ("flash_window_total", "flash_window_grid_steps_total",
             "flash_window_tiles_live_total")
    before = {n: profiler.get_int_stats().get(n, 0) for n in names}
    out, grads = _attend_and_grads(
        lambda q, k, v: flash_attention(q, k, v, is_causal=True,
                                        window=window), q, k, v, w)
    delta = {n: profiler.get_int_stats().get(n, 0) - before[n]
             for n in names}
    # (256, 256) tiles: 64 q tiles x a band of 3, the first two shorter
    assert delta == {"flash_window_total": 1,
                     "flash_window_grid_steps_total": 192,
                     "flash_window_tiles_live_total": 189}
    assert bool(jnp.isfinite(out).all())
    dq, dk, dv = grads
    zero = lambda a: float(jnp.abs(a.astype(jnp.float32)).max()) == 0.0
    assert zero(dq[:, :lo]) and zero(dq[:, hi:])
    for g in (dk, dv):
        assert zero(g[:, :first]) and zero(g[:, hi:])
    _assert_attention_close(
        (out[:, lo:hi], (dq[:, lo:hi], dk[:, first:hi], dv[:, first:hi])),
        _attend_and_grads(
            lambda q, k, v: _xla_attention(q, k, v, is_causal=True,
                                           window=window),
            q[:, lo:hi], k[:, first:hi], v[:, first:hi], w[:, lo:hi]),
        "window 512, groups of 8")


def test_grouped_causal_flash_in_groups_of_six_on_tpu():
    """The full layers' instance of the Laguna cell — 48 query heads in
    groups of 6 over 8 key/value heads, a rung of the head-block ladder
    no other configuration takes — at 16,384 rows through Mosaic: the
    first 1,024 rows' outputs, and every gradient under a cotangent that
    is zero beyond them, equal `_xla_attention`'s on that prefix."""
    s, h, hkv, d, n = 16384, 48, 8, 128, 1024
    q = _rand((1, s, h, d), 110, jnp.bfloat16)
    k = _rand((1, s, hkv, d), 111, jnp.bfloat16)
    v = _rand((1, s, hkv, d), 112, jnp.bfloat16)
    w = _rand((1, s, h, d), 113).at[:, n:].set(0.0)
    before = profiler.get_int_stats().get("flash_fwd_pieces_total", 0)
    out, grads = _attend_and_grads(
        lambda q, k, v: flash_attention(q, k, v, is_causal=True),
        q, k, v, w)
    # the whole group a grid step: 6 heads walked one at a time
    assert profiler.get_int_stats()["flash_fwd_pieces_total"] == before + 6
    assert bool(jnp.isfinite(out).all())
    for g in grads:
        assert float(jnp.abs(g[:, n:].astype(jnp.float32)).max()) == 0.0
    _assert_attention_close(
        (out[:, :n], tuple(g[:, :n] for g in grads)),
        _attend_and_grads(
            lambda q, k, v: _xla_attention(q, k, v, is_causal=True),
            q[:, :n], k[:, :n], v[:, :n], w[:, :n]),
        "causal, groups of 6")


@pytest.mark.parametrize("rows,window", [(3000, 200), (1024, 512),
                                         (2048, 2047)])
def test_window_flash_small_shapes_on_tpu(rows, window):
    """Windows under, at twice and far over the tile's edge, rows that
    are no multiple of it, with key padding beside the band."""
    h, hkv, d = 16, 2, 128
    q = _rand((2, rows, h, d), 120, jnp.bfloat16)
    k = _rand((2, rows, hkv, d), 121, jnp.bfloat16)
    v = _rand((2, rows, hkv, d), 122, jnp.bfloat16)
    w = _rand((2, rows, h, d), 123)
    lens = np.array([rows, rows - 300])
    keep = jnp.asarray(np.arange(rows)[None, :] < lens[:, None])
    w = jnp.where(keep[:, :, None, None], w, 0.0)
    bias = jnp.where(keep, 0.0, A.DEFAULT_MASK_VALUE)
    got = _attend_and_grads(
        lambda q, k, v: flash_attention(q, k, v, key_bias=bias,
                                        is_causal=True, window=window),
        q, k, v, w)
    want = _attend_and_grads(
        lambda q, k, v: _xla_attention(q, k, v,
                                       mask=keep[:, None, None, :],
                                       is_causal=True, window=window),
        q, k, v, w)
    real = keep[:, :, None, None]
    _assert_attention_close(
        (jnp.where(real, got[0], 0.0), got[1]),
        (jnp.where(real, want[0], 0.0), want[1]),
        f"rows {rows}, window {window}")


@pytest.mark.parametrize("tokens,heads,rotation", [
    (16384, 64, "plain"), (16384, 48, "yarn_half"), (1000, 3, "yarn_half")])
def test_attn_edge_passes_against_their_xla_statement_on_tpu(tokens, heads,
                                                             rotation):
    """The two elementwise passes around the flash kernels (ops/pallas/
    attn_edge.py: `rope_fwd` / `rope_bwd`, `head_gate_fwd` /
    `head_gate_bwd` through Mosaic) at the Laguna cell's two instances —
    1 x 16,384 x 64 heads of 128 with the plain whole-head rotation, 48
    with YaRN's on 64 of the 128 lanes, over 8 kv heads — and at a length
    that is no multiple of the row tile with a head a grid step, against
    `F.rotary_embedding` and the gate's XLA statement on the same chip:
    values, pull-backs, and the two counters."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.nn.functional import attn_edge as X
    from paddle_tpu.ops.pallas import attn_edge as E

    kw = dict(theta=1e4) if rotation == "plain" else dict(
        theta=5e5, rotary_dim=64, amplitude=1.4159,
        inv_freq=F.yarn_inv_freq(64, 5e5, 64.0, 4096))
    kv = 8 if heads % 8 == 0 else 1
    q = _rand((1, tokens, heads, 128), 130, jnp.bfloat16)
    k = _rand((1, tokens, kv, 128), 131, jnp.bfloat16)
    g = 3 * _rand((1, tokens, heads), 132)
    cot = (_rand(q.shape, 133, jnp.bfloat16), _rand(k.shape, 134,
                                                    jnp.bfloat16),
           _rand(q.shape, 135, jnp.bfloat16))
    pos = np.arange(tokens, dtype=np.int32)

    def both(rope, gate):
        def f(q, k, g, cot):
            out, vjp = jax.vjp(lambda q, k, g: (
                *rope(q, k, pos, **kw), gate(q, g)), q, k, g)
            return out, vjp(cot)
        return jax.jit(f)(q, k, g, cot)

    names = ("attn_edge_fused_total", "attn_edge_fallback_total")
    before = [profiler.get_int_stats().get(n, 0) for n in names]
    got = both(E.rope, E.head_gate)
    after = [profiler.get_int_stats().get(n, 0) for n in names]
    assert [a - b for a, b in zip(after, before)] == [2, 0]
    want = both(lambda *a, **kw: tuple(
        t._value for t in F.rotary_embedding(*a, **kw)), X.head_gate)
    f32 = lambda a: np.asarray(a, np.float32)
    for a, b_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        assert a.dtype == b_.dtype and a.shape == b_.shape
        # one rounding to bfloat16 on either side; the gate logits'
        # float32 gradient sums 128 lanes in another order
        np.testing.assert_allclose(f32(a), f32(b_), atol=2e-2, rtol=2e-2)
        assert np.linalg.norm(f32(a) - f32(b_)) <= 4e-3 * np.linalg.norm(
            f32(b_))


def test_no_kernel_gave_way():
    """Runs last: nothing above (and no other tpu-marked test before
    it) may have pushed a kernel onto its XLA path."""
    stats = profiler.get_int_stats()
    assert stats.get("flash_fallback_total", 0) == 0
    assert stats.get("serving_ragged_fallback_total", 0) == 0
    assert stats.get("kda_fallback_total", 0) == 0
    assert stats.get("kda_edge_fallback_total", 0) == 0
    assert stats.get("kda_edge_fused_total", 0) > 0
    assert stats.get("attn_edge_fallback_total", 0) == 0
    assert stats.get("attn_edge_fused_total", 0) > 0
    assert stats.get("flash_window_total", 0) > 0


def test_packed_layout_engaged():
    """The H x 64 and D = 128 instances above took the kernels on the
    projections' own (B, S, H*D) layout, not the merged one."""
    assert profiler.get_int_stats().get("flash_packed_layout_total", 0) > 0
