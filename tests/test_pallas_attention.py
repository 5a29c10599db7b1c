"""Flash-attention kernel tests (interpret mode on the CPU mesh).

Covers the round-2 kernel upgrades: in-kernel key-padding bias,
in-kernel counter-based dropout (bit-exact fwd/bwd agreement), the
padding shim for non-block-multiple shapes, and the Pallas backward
kernels vs autodiff-through-XLA oracle gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import attention as A


def _rand_qkv(rng, b=2, sq=128, sk=128, h=2, d=64):
    mk = lambda s: jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    return mk(sq), mk(sk), mk(sk)


class TestFlashForward:
    def test_causal_oracle(self):
        rng = np.random.RandomState(0)
        q, k, v = _rand_qkv(rng)
        ref = A._xla_attention(q, k, v, is_causal=True)
        out = A.flash_attention(q, k, v, is_causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_unaligned_lengths_padding_shim(self):
        """ADVICE round-1 #1: non-block-multiple seq lens must not read
        garbage K/V columns."""
        rng = np.random.RandomState(1)
        q, k, v = _rand_qkv(rng, sq=100, sk=75, d=48)
        ref = A._xla_attention(q, k, v)
        out = A.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_cross_attention_causal_offset(self):
        rng = np.random.RandomState(2)
        q, k, v = _rand_qkv(rng, sq=64, sk=160)
        ref = A._xla_attention(q, k, v, is_causal=True)
        out = A.flash_attention(q, k, v, is_causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_key_padding_bias_in_kernel(self):
        rng = np.random.RandomState(3)
        b, sk = 2, 128
        q, k, v = _rand_qkv(rng, b=b, sk=sk)
        lens = np.array([100, 57])
        bool_mask = jnp.asarray(np.arange(sk)[None, :] < lens[:, None])
        bias = jnp.where(bool_mask, 0.0, A.DEFAULT_MASK_VALUE)
        ref = A._xla_attention(q, k, v,
                               mask=bool_mask[:, None, None, :])
        out = A.flash_attention(q, k, v, key_bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_dispatcher_mask_reduction(self):
        m4 = jnp.zeros((2, 1, 1, 128), jnp.float32)
        assert A._mask_as_key_bias(m4, 2, 128) is not None
        m_bool = jnp.ones((2, 128), jnp.bool_)
        kb = A._mask_as_key_bias(m_bool, 2, 128)
        assert kb is not None and kb.dtype == jnp.float32
        # per-query masks are NOT expressible as key bias
        dense = jnp.zeros((2, 1, 128, 128), jnp.float32)
        assert A._mask_as_key_bias(dense, 2, 128) is None
        per_head = jnp.zeros((2, 4, 1, 128), jnp.float32)
        assert A._mask_as_key_bias(per_head, 2, 128) is None


def _grads(fn, q, k, v):
    """dq, dk, dv of `fn` under a non-uniform cotangent (exercises all
    grad paths)."""
    def loss(q, k, v):
        out = fn(q, k, v)
        w = jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape)
        return jnp.sum(out * w) / out.size
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_vs_oracle(self, causal):
        rng = np.random.RandomState(4)
        q, k, v = _rand_qkv(rng, b=1, sq=128, sk=128, h=2, d=64)
        g_ref = _grads(
            lambda q, k, v: A._xla_attention(q, k, v, is_causal=causal),
            q, k, v)
        g_out = _grads(
            lambda q, k, v: A.flash_attention(q, k, v, is_causal=causal,
                                              interpret=True),
            q, k, v)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    def test_grads_unaligned_with_bias(self):
        rng = np.random.RandomState(5)
        b, sk = 2, 90
        q, k, v = _rand_qkv(rng, b=b, sq=70, sk=sk, d=32)
        lens = np.array([88, 41])
        bool_mask = jnp.asarray(np.arange(sk)[None, :] < lens[:, None])
        bias = jnp.where(bool_mask, 0.0, A.DEFAULT_MASK_VALUE)
        g_ref = _grads(
            lambda q, k, v: A._xla_attention(
                q, k, v, mask=bool_mask[:, None, None, :]), q, k, v)
        g_out = _grads(
            lambda q, k, v: A.flash_attention(q, k, v, key_bias=bias,
                                              interpret=True), q, k, v)
        for a, b_ in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)


class TestFlashDropout:
    """The in-kernel RNG is a pure function of absolute coordinates, so
    an XLA oracle applying the *same* keep mask must match bit-for-bit
    in expectation AND gradient."""

    def _oracle_with_keep(self, q, k, v, keep, p_drop):
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def _keep_for(self, seed, b, h, sq, sk, p_drop):
        """Reconstruct the kernel's keep mask with the same hash."""
        seed_arr = jnp.asarray([seed], jnp.int32)
        rows = []
        for bh in range(b * h):
            rows.append(A._keep_mask(seed_arr[0], bh, 0, 0, sq, sk, p_drop))
        m = jnp.stack(rows).reshape(b, h, sq, sk)
        return m

    def test_dropout_matches_masked_oracle(self):
        rng = np.random.RandomState(6)
        p_drop = 0.3
        b, sq, sk, h, d = 1, 128, 128, 2, 64
        q, k, v = _rand_qkv(rng, b=b, sq=sq, sk=sk, h=h, d=d)
        keep = self._keep_for(7, b, h, sq, sk, p_drop)

        out = A.flash_attention(q, k, v, dropout_p=p_drop, dropout_seed=7,
                                interpret=True)
        ref = self._oracle_with_keep(q, k, v, keep, p_drop)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_dropout_grads_match_masked_oracle(self):
        rng = np.random.RandomState(7)
        p_drop = 0.25
        b, sq, sk, h, d = 1, 128, 128, 1, 32
        q, k, v = _rand_qkv(rng, b=b, sq=sq, sk=sk, h=h, d=d)
        keep = self._keep_for(11, b, h, sq, sk, p_drop)

        def l_kernel(q, k, v):
            return jnp.sum(A.flash_attention(
                q, k, v, dropout_p=p_drop, dropout_seed=11,
                interpret=True) ** 2)

        def l_oracle(q, k, v):
            return jnp.sum(self._oracle_with_keep(q, k, v, keep,
                                                  p_drop) ** 2)

        g_k = jax.grad(l_kernel, argnums=(0, 1, 2))(q, k, v)
        g_o = jax.grad(l_oracle, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_k, g_o):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)

    def test_dropout_rate_and_determinism(self):
        keep = np.asarray(A._keep_mask(jnp.int32(3), 0, 0, 0, 256, 256, 0.4))
        assert abs(keep.mean() - 0.6) < 0.02
        keep2 = np.asarray(A._keep_mask(jnp.int32(3), 0, 0, 0, 256, 256, 0.4))
        np.testing.assert_array_equal(keep, keep2)
        keep3 = np.asarray(A._keep_mask(jnp.int32(4), 0, 0, 0, 256, 256, 0.4))
        assert (keep != keep3).any()
        # block-layout independence: bits at offset == slice of full mask
        sub = np.asarray(A._keep_mask(jnp.int32(3), 0, 128, 64, 128, 128, 0.4))
        np.testing.assert_array_equal(sub, keep[128:, 64:192])


def _stat(name):
    from paddle_tpu import profiler

    return profiler.get_int_stats().get(name, 0)


def _packed_count():
    return _stat("flash_packed_layout_total")


class TestPackedLayout:
    """Where a grid step's heads fill whole 128-lane blocks (head pairs
    at D = 64, heads at D % 128 == 0) the kernels read q/k/v and write
    o/dq/dk/dv as (B, S, H*D), the projections' own layout: same
    results as the XLA oracle and as the merged (B*H, S, D) path, no
    transpose around the calls, the same dropout bits."""

    @pytest.fixture
    def merged(self, monkeypatch):
        """Force the merged operand layout for any shape."""
        def force():
            monkeypatch.setattr(A, "_packs", lambda h, d: False)
        return force

    @pytest.mark.parametrize("h,d", [(12, 64), (4, 64), (4, 128)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads(self, h, d, causal, merged):
        rng = np.random.RandomState(20 + h + d)
        q, k, v = _rand_qkv(rng, b=2, sq=128, sk=128, h=h, d=d)
        assert A._packs(h, d)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, is_causal=causal, interpret=True)
        oracle = lambda q, k, v: A._xla_attention(q, k, v,
                                                  is_causal=causal)
        before = _packed_count()
        out, g_out = flash(q, k, v), _grads(flash, q, k, v)
        assert _packed_count() == before + 2
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=1e-3, atol=1e-3)
        for a, b in zip(g_out, _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        merged()
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(flash(q, k, v)),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(g_out, _grads(flash, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        assert _packed_count() == before + 2

    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
    def test_cross_attention_causal_offset(self, h, d):
        rng = np.random.RandomState(31)
        q, k, v = _rand_qkv(rng, sq=128, sk=256, h=h, d=d)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, is_causal=True, interpret=True)
        oracle = lambda q, k, v: A._xla_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=1e-3, atol=1e-3)
        for a, b in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
    def test_unaligned_lengths_with_key_bias(self, h, d):
        """The padding shim pads the sequence axis of (B, S, H*D)."""
        rng = np.random.RandomState(32)
        b, sq, sk = 2, 70, 90
        q, k, v = _rand_qkv(rng, b=b, sq=sq, sk=sk, h=h, d=d)
        lens = np.array([88, 41])
        keep = jnp.asarray(np.arange(sk)[None, :] < lens[:, None])
        bias = jnp.where(keep, 0.0, A.DEFAULT_MASK_VALUE)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, key_bias=bias, interpret=True)
        oracle = lambda q, k, v: A._xla_attention(
            q, k, v, mask=keep[:, None, None, :])
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=1e-3, atol=1e-3)
        for a, b_ in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("h,d", [(4, 64), (12, 64), (2, 128)])
    def test_dropout_bits_are_keep_mask3_at_absolute_head(self, h, d):
        """The packed grid passes bh0 = b*H + first head of the step:
        the keep mask is `_keep_mask3` over (b*H + h, q, k), the bits
        the merged grid draws."""
        rng = np.random.RandomState(33)
        b, s, p_drop, seed = 2, 128, 0.3, 13
        q, k, v = _rand_qkv(rng, b=b, sq=s, sk=s, h=h, d=d)
        keep = A._keep_mask3(jnp.int32(seed), 0, 0, 0, b * h, s, s,
                             p_drop).reshape(b, h, s, s)

        def oracle(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
            probs = jax.nn.softmax(logits, axis=-1)
            probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        flash = lambda q, k, v: A.flash_attention(
            q, k, v, dropout_p=p_drop, dropout_seed=seed, interpret=True)
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=1e-3, atol=1e-3)
        for a, b_ in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("h,d", [(3, 64), (2, 32), (2, 80)])
    def test_other_shapes_stay_merged(self, h, d):
        """Odd head counts at D = 64 and head dims that fill no
        128-lane block keep the merged path, and still match."""
        assert not A._packs(h, d)
        rng = np.random.RandomState(34)
        q, k, v = _rand_qkv(rng, b=1, sq=128, sk=128, h=h, d=d)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, is_causal=True, interpret=True)
        oracle = lambda q, k, v: A._xla_attention(q, k, v, is_causal=True)
        before = _packed_count()
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=1e-3, atol=1e-3)
        for a, b in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        assert _packed_count() == before

    def test_no_rank4_transpose_around_the_kernels(self):
        """Two BERT-width attention blocks (projections -> flash ->
        out projection), forward and backward: the jaxpr holds the six
        kernel calls and no transpose of a (B, S, H, D) operand."""
        b, s, h, d = 2, 128, 12, 64
        rng = np.random.RandomState(35)
        x = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
        ws = [jnp.asarray(rng.randn(4, h * d, h * d) / 28, jnp.float32)
              for _ in range(2)]

        def blocks(x, ws):
            for w in ws:
                q, k, v = ((x @ w[i]).reshape(b, s, h, d)
                           for i in range(3))
                o = A.flash_attention(q, k, v, dropout_p=0.1,
                                      dropout_seed=3, interpret=True)
                x = x + o.reshape(b, s, h * d) @ w[3]
            return jnp.sum(x * x)

        seen = {"pallas_call": 0, "rank4_transpose": 0}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    seen["pallas_call"] += 1
                    continue            # the kernel body is Mosaic's
                if eqn.primitive.name == "transpose" and any(
                        getattr(v.aval, "ndim", 0) == 4
                        for v in eqn.invars):
                    seen["rank4_transpose"] += 1
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(jax.grad(blocks, argnums=(0, 1)))(x, ws).jaxpr)
        assert seen == {"pallas_call": 6, "rank4_transpose": 0}

    def test_packed_head_block_ladder(self):
        """Packed rungs hold whole 128-lane blocks (head pairs at
        D = 64) and at most the heads `max_h` allows; the merged ladder
        is what it was."""
        assert A._block_h_ladder(12) == [6, 4, 3, 2, 1]
        assert A._block_h_ladder(12, 64, 4) == [4, 2]
        assert A._block_h_ladder(12, 64, 64) == [6, 4, 2]
        assert A._block_h_ladder(6, 64, 4) == [2]
        assert A._block_h_ladder(8, 64, 8) == [8, 4, 2]
        assert A._block_h_ladder(4, 128, 4) == [4, 2, 1]
        assert A._block_h_ladder(3, 128, 4) == [3, 1]


def _pieces_count():
    return _stat("flash_fwd_pieces_total")


class TestForwardWalksHeads:
    """The forward body walks a step's heads one at a time with the row
    statistics lane-replicated: against a dense oracle (out, lse and
    the three gradients through the backward kernels, which read that
    lse), for every operand layout the one body serves and every mask
    it composes, at 2 x 2 tiles with more than one head a step — more
    than one piece a step, more than one online-softmax update a
    row."""

    LAYOUTS = {                 # h, hkv, d, dv, heads a step
        "merged": (3, 3, 64, 64, 3),
        "merged_192": (3, 3, 192, 192, 3),  # the probe's: 1.5 vregs a row
        "packed_128": (4, 4, 128, 128, 4),
        "packed_64_pairs": (4, 4, 64, 64, 4),
        "packed_192_over_128_pairs": (4, 4, 192, 128, 4),
        "grouped": (8, 2, 128, 128, 4),
    }
    S, VALID, P_DROP, SEED = 256, 200, 0.25, 13

    def _oracle(self, kind, h, hkv):
        s = self.S
        seen = np.ones((s, s), bool)
        if kind == "causal":
            seen = np.tril(seen)
        if kind == "block_mask":
            seen = A.BlockDiffusionMask(s // 2, 4).dense()
        bias = np.where(np.arange(s) < self.VALID, 0.0,
                        A.DEFAULT_MASK_VALUE) if kind == "key_bias" \
            else np.zeros(s)
        keep = jnp.stack([A._keep_mask(jnp.int32(self.SEED), n, 0, 0, s, s,
                                       self.P_DROP) for n in range(h)]) \
            if kind == "dropout" else None

        def logits(q, k):
            k = jnp.repeat(k, h // hkv, axis=2)
            x = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
            return jnp.where(seen, x + bias, A.DEFAULT_MASK_VALUE)

        def out(q, k, v):
            probs = jax.nn.softmax(logits(q, k), axis=-1)
            if keep is not None:
                probs = jnp.where(keep, probs / (1.0 - self.P_DROP), 0.0)
            return jnp.einsum("bhqk,bkhd->bqhd", probs,
                              jnp.repeat(v, h // hkv, axis=2))

        lse = lambda q, k: jax.nn.logsumexp(logits(q, k), axis=-1)
        return out, lse

    @pytest.mark.parametrize("kind", ["plain", "causal", "block_mask",
                                      "key_bias", "dropout"])
    @pytest.mark.parametrize("layout", list(LAYOUTS))
    def test_out_lse_and_grads(self, layout, kind, monkeypatch):
        h, hkv, d, dv, block_h = self.LAYOUTS[layout]
        if layout == "grouped" and kind == "dropout":
            block_h = 8     # dropout repeats the kv heads in HBM first
        rng = np.random.RandomState(len(layout) + len(kind))
        mk = lambda n, w: jnp.asarray(rng.randn(1, self.S, n, w),
                                      jnp.float32)
        q, k, v = mk(h, d), mk(hkv, d), mk(hkv, dv)
        kw = dict(
            is_causal=kind == "causal",
            block_mask=A.BlockDiffusionMask(self.S // 2, 4)
            if kind == "block_mask" else None,
            key_bias=jnp.where(jnp.arange(self.S) < self.VALID, 0.0,
                               A.DEFAULT_MASK_VALUE)[None]
            if kind == "key_bias" else None,
            dropout_p=self.P_DROP if kind == "dropout" else 0.0,
            dropout_seed=self.SEED)
        forward, seen = A._flash_forward, {}

        def spy(*a, **kws):
            out, lse = forward(*a, **kws)
            seen.update(lse=lse, block_h=kws["block_h"])
            return out, lse

        monkeypatch.setattr(A, "_flash_forward", spy)
        flash = lambda q, k, v: A.flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True, **kw)
        before = _pieces_count()
        out = flash(q, k, v)
        assert seen["block_h"] == block_h > 1
        assert _pieces_count() == before + block_h
        oracle, oracle_lse = self._oracle(kind, h, hkv)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(oracle(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(seen["lse"]).reshape(h, self.S),
            np.asarray(oracle_lse(q, k))[0], rtol=2e-5, atol=2e-5)
        for a, b in zip(_grads(flash, q, k, v), _grads(oracle, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("cell,b,s,h,hkv,d,dv,kw,pieces", [
        ("bert_base.pretrain_s512", 32, 512, 12, 12, 64, 64,
         dict(dropout_p=0.1, key_bias=True), 4),
        ("bert_base.pretrain_s128", 128, 128, 12, 12, 64, 64,
         dict(dropout_p=0.1, key_bias=True), 6),
        ("sdar_30b_a3b.blockdiff_s4096", 4, 8192, 32, 4, 128, 128,
         dict(block_mask=A.BlockDiffusionMask(4096, 4)), 8),
        ("joyai_llm_flash.ar_mtp_s8192", 2, 8192, 32, 32, 192, 128,
         dict(is_causal=True), 4),
    ])
    def test_pieces_a_step_at_the_cells_shapes(self, cell, b, s, h, hkv, d,
                                               dv, kw, pieces):
        """Trace only (`eval_shape`): `flash_fwd_pieces_total` gains
        the heads a grid step walks, at the first rung of each cell's
        head-block ladder (the rung the chip takes there)."""
        kw = dict(kw)
        if kw.pop("key_bias", False):
            kw["key_bias"] = jax.ShapeDtypeStruct((b, s), jnp.float32)
        x = lambda n, w: jax.ShapeDtypeStruct((b, s, n, w), jnp.bfloat16)
        statics = {n: kw.pop(n) for n in ("dropout_p", "block_mask",
                                          "is_causal") if n in kw}
        before = _pieces_count()
        jax.eval_shape(
            lambda q, k, v, **dyn: A.flash_attention(
                q, k, v, interpret=True, **statics, **dyn),
            x(h, d), x(hkv, d), x(hkv, dv), **kw)
        assert _pieces_count() == before + pieces, cell


class TestMosaicAcceptsForV5e:
    """Interpret mode proves the arithmetic and nothing about Mosaic.
    libtpu compiles for a v5e TOPOLOGY on a host without a chip, so
    the compile probes — pointed at it with `compile_target` — get
    Mosaic's real accept/refuse verdict here, in tier-1.  (Whether an
    accepted kernel computes the right thing on hardware is the TPU
    lane's question, tests/test_tpu_kernels.py.)"""

    @pytest.fixture
    def v5e(self):
        from jax.experimental import topologies
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from paddle_tpu import profiler
        from paddle_tpu.ops.pallas import _common

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - libtpu held by another process
            pytest.skip(f"topology AOT unavailable: {e}")
        before = profiler.get_int_stats()
        try:
            with _common.compile_target(NamedSharding(
                    Mesh(np.array(topo.devices[:1]), ("d",)), P())):
                yield
        finally:
            for cache in (A._PROBE_CACHE, A._EXACT_PROBE_CACHE,
                          A._RAGGED_PROBE_CACHE):
                cache.clear()
        after = profiler.get_int_stats()
        for name in ("flash_fallback_total",
                     "serving_ragged_fallback_total"):
            assert after.get(name, 0) == before.get(name, 0), name

    @pytest.mark.parametrize("b,t,h,d,page,dtype", [
        (8, 1, 8, 64, 16, jnp.bfloat16),    # decode, every slot
        (1, 64, 8, 64, 16, jnp.bfloat16),   # one prefill chunk
        (4, 1, 12, 64, 128, jnp.float32),
        (3, 8, 16, 128, 16, jnp.bfloat16),  # causal tail, B > 1
    ])
    def test_ragged_paged_kernel(self, v5e, b, t, h, d, page, dtype):
        assert A._probe_ragged((b, t, h, d), (33, page, h, d), (b, 4),
                               dtype, page, 0.125)

    def test_flash_pair(self, v5e):
        """The generic fwd+bwd probe, and the exact instance at the
        bench blocks (512, 512), bf16, dropout, at a small head block
        (which larger rung Mosaic accepts is printed by chip_smoke.py
        and the TPU lane)."""
        q = jax.ShapeDtypeStruct((2, 512, 4, 64), jnp.bfloat16)
        assert A._flash_ok(q, q)
        assert A._probe_exact((8, 512, 64), (8, 512, 64), 4, False, 0.1,
                              jnp.bfloat16, 2, 512, 512, 0)
        # the packed (B, S, H*D) instances of the two BERT cells, at
        # the rung flash_attention() tries first there
        assert A._probe_exact((24, 512, 64), (24, 512, 64), 12, False,
                              0.1, jnp.bfloat16, 4, 512, 512, 0,
                              packed=True)
        assert A._probe_exact((24, 128, 64), (24, 128, 64), 12, False,
                              0.1, jnp.bfloat16, 6, 128, 128, 0,
                              packed=True)

    @pytest.mark.parametrize("bh,sq,sk,d,heads,block_h,causal", [
        (8, 512, 512, 128, 4, 4, False),    # one head a lane block
        (16, 256, 512, 64, 8, 8, True),     # wmt: cross, causal offset
        (12, 512, 512, 64, 6, 2, False),    # dp2 x mp2: 6 local heads
    ])
    def test_flash_pair_packed(self, v5e, bh, sq, sk, d, heads, block_h,
                               causal):
        assert A._probe_exact((bh, sq, d), (bh, sk, d), heads, causal,
                              0.1, jnp.bfloat16, block_h,
                              min(sq, 512), min(sk, 512), sk - sq,
                              packed=True)


    def test_flash_pair_grouped_block_mask(self, v5e):
        """The `sdar_30b_a3b.blockdiff_s4096` instances: 32 query heads
        on 4 key/value heads at D = 128, 2 x 4096 rows under the
        block-diffusion mask (tile-class tables as scalar prefetch; in
        the backward kernels a masked and an unmasked copy of the tile
        body), the whole group
        of 8 heads a step on (256, 512) tiles — the rung
        flash_attention() tries first there — and, as the cell passes
        no key bias and pads no key, without the bias add."""
        mask = A.BlockDiffusionMask(4096, 4)
        assert A._probe_exact((32, 8192, 128), (32, 8192, 128), 32, False,
                              0.0, jnp.bfloat16, 8, 256, 512, 0,
                              packed=True, kv_heads=4, block_mask=mask,
                              biased=False)
        # grouped heads alone (causal), and the mask on plain heads
        assert A._probe_exact((16, 1024, 128), (16, 1024, 128), 16, True,
                              0.0, jnp.bfloat16, 4, 512, 512, 0,
                              packed=True, kv_heads=4)
        assert A._probe_exact((24, 1024, 64), (24, 1024, 64), 12, False,
                              0.1, jnp.bfloat16, 4, 512, 512, 0,
                              packed=True, kv_heads=12,
                              block_mask=A.BlockDiffusionMask(512, 32))


    @pytest.mark.parametrize("tokens,heads", [(16384, 32), (256, 3)])
    def test_kda_scan_kernels(self, v5e, tokens, heads):
        """The Kimi cell's scan (ops/pallas/kda.py) at the cell's shape:
        one `kda_fwd` and one `kda_bwd` call with all 32 heads of 128
        over 16,384 tokens — (64, 128) blocks of the projections' own
        layout, the chunk-local half (cumulated gate, scores, the
        inverse by substitution and merges) and the hand-written
        backward in VMEM, two heads a grid step, the state in VMEM over
        the chunk axis, matmuls at full float32 precision — compiled
        whole for a v5e; and an odd head count (a head a grid step)."""
        from paddle_tpu.ops.pallas import _common
        from paddle_tpu.ops.pallas.kda import kda_attention

        put = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=_common._COMPILE_TARGET)
        args = (put((1, tokens, heads, 128), jnp.bfloat16),) * 3 + (
            put((1, tokens, heads, 128), jnp.float32),
            put((1, tokens, heads), jnp.float32))
        loss = lambda *a: jnp.sum(kda_attention(*a).astype(jnp.float32))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert sum("_kda_forward" in c for c in calls) == 1
        assert sum("_kda_backward" in c for c in calls) == 1

    @pytest.mark.parametrize("tokens,key_heads", [(16384, 16), (1000, 1)])
    def test_gdn_scan_kernels(self, v5e, tokens, key_heads):
        """The Qwen3-Next cell's scan at the cell's shape — 1 x 16,384,
        16 key heads under 32 value heads of 128, a decay a value head:
        `gdn_fwd` / `gdn_bwd` with the scores in the scalar form (G
        cumulated once a step for all heads on a (64, 32) block, a
        (64, 64) exponential a head, [k; q] k^T once a key head, dg and
        dbeta laid out as rows by one matmul) — compiled whole for a
        v5e; and one key head over a length no multiple of 64 ((64, 2)
        blocks of g and beta)."""
        from paddle_tpu import profiler
        from paddle_tpu.ops.pallas import _common
        from paddle_tpu.ops.pallas.kda import kda_attention

        put = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=_common._COMPILE_TARGET)
        hk, hv = key_heads, 2 * key_heads
        args = (put((1, tokens, hk, 128), jnp.bfloat16),) * 2 + (
            put((1, tokens, hv, 128), jnp.bfloat16),
            put((1, tokens, hv), jnp.float32),
            put((1, tokens, hv), jnp.float32))
        loss = lambda *a: jnp.sum(kda_attention(*a).astype(jnp.float32))
        before = profiler.get_int_stats()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
        assert profiler.get_int_stats().get("kda_scalar_scores_total", 0) \
            == before.get("kda_scalar_scores_total", 0) + 2
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert sum("_gdn_forward" in c for c in calls) == 1
        assert sum("_gdn_backward" in c for c in calls) == 1
        assert not any("_kda_forward" in c or "_kda_backward" in c
                       for c in calls)

    @pytest.mark.parametrize("tokens,heads", [(16384, 32), (512, 3)])
    def test_kda_edge_kernels(self, v5e, tokens, heads):
        """The Kimi cell's two elementwise passes around the scan
        (ops/pallas/kda_edge.py) at the cell's shape, 1 x 16,384 x 32
        heads of 128: `kda_pre_fwd`, `kda_pre_bwd`, `kda_post_fwd`,
        `kda_post_bwd` — (256, 512) blocks of the projections' own
        layout walked 32 rows at a time, the rows before a tile as a
        second operand, row shifts as sublane rotations — compiled for a
        v5e; and an odd head count (a head a grid step).  None of the
        four calls is named as the scan's or the flash kernels' are."""
        from paddle_tpu.ops.pallas import _common, kda_edge

        put = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=_common._COMPILE_TARGET)
        width = heads * 128
        rows = put((1, tokens, width), jnp.bfloat16)
        taps = put((4, width), jnp.bfloat16)
        pre = (rows,) * 4 + (taps,) * 3 + (put((width,), jnp.float32),
                                           put((heads,), jnp.float32))
        post = (rows, rows, put((128,), jnp.bfloat16))

        def loss(pre, post):
            q, k, v, g = kda_edge.kda_pre(*pre)
            y = kda_edge.kda_post(*post, 1e-5)
            return sum(jnp.sum(a.astype(jnp.float32)) for a in (q, k, v, g, y))

        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            pre, post).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        for fn in ("_pre_forward", "_pre_backward", "_post_forward",
                   "_post_backward"):
            assert sum(fn in c for c in calls) == 1, fn
        assert not any(fn in c for c in calls for fn in (
            "_kda_forward", "_kda_backward", "_flash_forward",
            "_flash_backward"))


    @pytest.mark.parametrize("tokens,key_heads", [(16384, 16), (512, 1)])
    def test_gdn_pre_kernels(self, v5e, tokens, key_heads):
        """The Qwen3-Next cell's pass before its scan (`kda_edge.gdn_pre`)
        at the cell's shape, 1 x 16,384, 16 key heads under 32 value
        heads of 128: `gdn_pre_fwd` on (256, 512 | 512 | 1,024) blocks
        of q~, k~, v~ read in place from the projection's output,
        `gdn_pre_bwd` on (256, 1,024) blocks of all its lanes, the
        cotangents' index maps held where a block is not theirs —
        compiled for a v5e; and one key head (128-lane blocks).  The
        projection's cotangent is the backward call's own output: no
        concatenate at its edge; neither call is named as the scan's or
        the flash kernels' are."""
        from paddle_tpu.ops.pallas import _common, kda_edge

        put = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=_common._COMPILE_TARGET)
        hk, hv = key_heads, 2 * key_heads
        width = (2 * hk + hv) * 128
        args = (put((1, tokens, width + hv * 128), jnp.bfloat16),
                put((1, tokens, 2 * hv), jnp.bfloat16),
                put((4, width), jnp.float32), put((hv,), jnp.float32),
                put((hv,), jnp.float32))

        def loss(*a):
            return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in kda_edge.gdn_pre(*a, hk))

        text = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
            *args).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        for fn in ("_gdn_pre_forward", "_gdn_pre_backward"):
            assert sum(fn in c for c in calls) == 1, fn
        assert not any(fn in c for c in calls for fn in (
            "_gdn_forward", "_gdn_backward", "_kda_forward",
            "_kda_backward", "_flash_forward", "_flash_backward"))
        assert " concatenate(" not in text

    @pytest.mark.parametrize("tokens,heads,rotary_dim", [
        (16384, 64, 128), (16384, 48, 64), (512, 3, 64)])
    def test_attn_edge_kernels(self, v5e, tokens, heads, rotary_dim):
        """The Laguna cell's two elementwise passes around its flash
        kernels (ops/pallas/attn_edge.py) at the cell's shapes, 1 x
        16,384 x 64 heads of 128 rotated whole and x 48 rotated on 64 of
        128 lanes, over 8 kv heads: `rope_fwd`, `rope_bwd` (q's call and
        k's), `head_gate_fwd`, `head_gate_bwd` — (256, 1024) blocks of
        the projections' own layout, the rotation's halves swapped by
        lane rotations, the gate's per-head broadcast and lane sums as
        matmuls with a 0/1 matrix — compiled for a v5e; and an odd head
        count (a head a grid step).  None of the calls is named as the
        flash kernels are: the benchmark tells those by `_flash_`."""
        from paddle_tpu.ops.pallas import _common, attn_edge

        put = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=_common._COMPILE_TARGET)
        kv = 8 if heads % 8 == 0 else 1
        q = put((1, tokens, heads, 128), jnp.bfloat16)
        k = put((1, tokens, kv, 128), jnp.bfloat16)
        g = put((1, tokens, heads), jnp.float32)
        pos = np.arange(tokens, dtype=np.int32)

        def loss(q, k, g):
            qr, kr = attn_edge.rope(q, k, pos, 5e5, rotary_dim=rotary_dim)
            y = attn_edge.head_gate(qr, g)
            return sum(jnp.sum(a.astype(jnp.float32)) for a in (kr, y))

        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, k, g).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        for name, n in (("rope_fwd", 2), ("rope_bwd", 2),
                        ("head_gate_fwd", 1), ("head_gate_bwd", 1)):
            assert sum(f"/{name}/" in c for c in calls) == n, name
        assert len(calls) == 6
        assert not any("_flash_" in c or "/flash_" in c for c in calls)


def test_flash_per_shard_matches_unsharded():
    """`sharded_attention_scope`'s kernel path: flash attention under
    shard_map over (batch, heads) equals the unsharded kernel — the
    split is exact, attention being independent per batch element and
    head (GSPMD cannot partition a Mosaic call, so on a multi-chip mesh
    this is the only way the kernels run)."""
    from jax.sharding import Mesh

    rng = np.random.RandomState(8)
    q, k, v = _rand_qkv(rng, b=4, sq=128, sk=128, h=2, d=32)
    kb = jnp.where(jnp.arange(128)[None, :] < 100, 0.0, -1e9)
    kb = jnp.broadcast_to(kb, (4, 128)).astype(jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    out = A._flash_per_shard((mesh, "dp", "mp"), q, k, v, kb, True, None,
                             0.0, None, interpret=True)
    ref = A.flash_attention(q, k, v, key_bias=kb, is_causal=True,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # dropout: the shard's position is folded into the seed, so the two
    # dp shards (same local coordinates) draw different masks
    same = jnp.concatenate([q[:2], q[:2]])
    drop = A._flash_per_shard((mesh, "dp", None), same, same, same, None,
                              False, None, 0.5, jnp.asarray([3]),
                              interpret=True)
    assert not np.allclose(np.asarray(drop[:2]), np.asarray(drop[2:]))
