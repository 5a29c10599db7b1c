"""The transformer's dense FFN (nn/functional/dense_ffn.py): forward and
gradients against a plain jax.numpy oracle, the hash dropout mask against
a numpy oracle of the same hash, and the two branches of
nn/layer/transformer.py:_dense_ffn_block against each other.

Reference counterpart: the CUDA fused_feedforward operator family
(/root/reference/paddle/fluid/operators/fused/fused_feedforward_op.cu:1).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.nn.functional.dense_ffn import dense_ffn

ACTIVATIONS = ("gelu", "gelu_tanh", "relu")


def _params(T=256, H=128, F=256, seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(T, H), dtype),
            jnp.asarray(r.randn(H, F) * 0.05, dtype),
            jnp.asarray(r.randn(F) * 0.01, dtype),
            jnp.asarray(r.randn(F, H) * 0.05, dtype),
            jnp.asarray(r.randn(H) * 0.01, dtype))


def _ref(x, w1, b1, w2, b2, activation="gelu", keep=None, p=0.0):
    # "gelu" is the EXACT erf form (the repo's GELU()/F.gelu default)
    act = {"gelu": lambda v: jax.nn.gelu(v, approximate=False),
           "gelu_tanh": lambda v: jax.nn.gelu(v, approximate=True),
           "relu": jax.nn.relu}[activation]
    h = act(x @ w1 + b1)
    if keep is not None:
        h = jnp.where(keep, h / (1.0 - p), 0.0)
    return h @ w2 + b2


def _keep_oracle(seed, rows, cols, p):
    """lowbias32 on (row, column, seed) in numpy: keep where hash >= p."""
    with np.errstate(over="ignore"):
        r = np.arange(rows, dtype=np.uint32)[:, None]
        c = np.arange(cols, dtype=np.uint32)[None, :]
        x = (r * np.uint32(0x9E3779B1)) ^ (c * np.uint32(0x85EBCA77))
        x = x ^ (np.uint32(seed) * np.uint32(0x165667B1))
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x7FEB352D)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(0x846CA68B)
        x = x ^ (x >> np.uint32(16))
    return x >= np.uint32(min(int(p * 2 ** 32), 2 ** 32 - 1))


def _assert_grads_close(got, want, atol):
    for name, a, b in zip(("dx", "dw1", "db1", "dw2", "db2"), got, want):
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=atol,
                                   err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ACTIVATIONS)
def test_forward_matches_oracle(activation, dtype):
    args = _params(dtype=dtype)
    out = dense_ffn(*args, activation=activation)
    want = _ref(*(a.astype(jnp.float32) for a in args),
                activation=activation)
    assert out.dtype == dtype and out.shape == (256, 128)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want),
        atol=2e-5 if dtype == jnp.float32 else 0.15)


@pytest.mark.parametrize("activation", ACTIVATIONS)
def test_gradients_match_oracle(activation):
    args = _params()
    got = jax.grad(lambda a: jnp.sum(
        dense_ffn(*a, activation=activation) ** 2))(args)
    want = jax.grad(lambda a: jnp.sum(
        _ref(*a, activation=activation) ** 2))(args)
    _assert_grads_close(got, want, atol=3e-6)


@pytest.mark.parametrize("p", [0.1, 0.5])
def test_dropout_matches_hash_oracle(p):
    """The mask is the hash of absolute (row, column) and the seed, and
    about p of the hidden units drop."""
    args = _params(seed=1)
    seed = jnp.asarray([1234], jnp.int32)
    out = dense_ffn(*args, dropout_p=p, dropout_seed=seed)
    keep = _keep_oracle(1234, 256, 256, p)
    assert abs(1.0 - keep.mean() - p) < 0.01
    want = _ref(*args, keep=jnp.asarray(keep), p=p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5)
    # no seed given is seed 0, not another mask
    np.testing.assert_array_equal(
        np.asarray(dense_ffn(*args, dropout_p=p)),
        np.asarray(dense_ffn(*args, dropout_p=p,
                             dropout_seed=jnp.zeros((1,), jnp.int32))))


def test_dropout_gradients_consistent():
    """The backward pass regenerates the forward's mask."""
    args = _params(seed=2)
    seed = jnp.asarray([77], jnp.int32)
    p = 0.25
    keep = jnp.asarray(_keep_oracle(77, 256, 256, p))
    got = jax.grad(lambda a: jnp.sum(
        dense_ffn(*a, dropout_p=p, dropout_seed=seed) ** 2))(args)
    want = jax.grad(lambda a: jnp.sum(_ref(*a, keep=keep, p=p) ** 2))(args)
    _assert_grads_close(got, want, atol=5e-6)


def test_leading_dims():
    """(B, S, H) is flattened to rows and restored; a row count that no
    tile divides takes the same path."""
    x, w1, b1, w2, b2 = _params(T=300)
    out = dense_ffn(x.reshape(3, 100, 128), w1, b1, w2, b2,
                    activation="relu")
    assert out.shape == (3, 100, 128)
    np.testing.assert_allclose(
        np.asarray(out).reshape(300, 128),
        np.asarray(_ref(x, w1, b1, w2, b2, activation="relu")), atol=2e-5)


@pytest.mark.parametrize("activation", ACTIVATIONS)
def test_encoder_layer_ffn_equals_layer_by_layer(activation):
    """_dense_ffn_block's one-call branch equals its layer-by-layer
    branch, linear2(act(linear1(x))), in eval mode."""
    import paddle_tpu.nn as nn
    from paddle_tpu.fluid.dygraph import guard, to_variable
    from paddle_tpu.nn.layer.transformer import _dense_ffn_block

    with guard():
        layer = nn.TransformerEncoderLayer(
            32, 4, 64, dropout=0.1,
            activation="relu" if activation == "relu" else "gelu")
        if activation == "gelu_tanh":
            layer.activation = nn.GELU(approximate=True)
        layer.eval()
        x = to_variable(np.random.RandomState(0).randn(2, 10, 32)
                        .astype("float32"))
        one_call = _dense_ffn_block(layer, x)
        by_layer = layer.linear2(layer.activation(layer.linear1(x)))
        assert one_call.shape == [2, 10, 32]
        np.testing.assert_allclose(one_call.numpy(), by_layer.numpy(),
                                   atol=1e-5)
