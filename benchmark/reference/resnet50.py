"""Plain reference for bottleneck ResNets: forward pass and loss.

Straightforward `jax.numpy`/`lax` in float32 with
`jax.default_matmul_precision("highest")`: He et al. 2015, Table 1,
with the stride of a stage on the convolution `config["stride_on"]`
names, batch normalisation in training mode (statistics of the batch
itself, biased variance) as a training step uses it, a softmax
classifier and the mean cross-entropy.  NCHW, OIHW.

It is fed the system's own seeded weights under the Program's variable
names, which count up in creation order (`conv2d_<i>.w_0`,
`batch_norm_<i>.w_0` scale / `.b_0` shift, `fc_0.w_0` (in, out) /
`.b_0`); a bottleneck creates 1x1, 3x3, 1x1 and then its projection
shortcut, as `models/resnet.py` does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Relative difference of the loss and relative RMS difference of the
# class probabilities, system against reference.
#
# The system is a float32 Program, and on a TPU XLA runs a float32
# convolution in bf16 passes by default: each product carries a
# rounding error near 2^-8, each of the 53 convolutions adds about
# that much to the activations, batch norm renormalises, and the
# errors add like a random walk to a few percent on the logits.  The
# untrained classifier is far from uniform (first loss 7 to 8.6 against
# ln 1000 = 6.9), so that shows as 0.08 to 0.12 on the probabilities
# and up to 0.011 on the loss (my chip runs, PR 22, 22 samples of 8
# images).  The tolerances are about 2.5 times the largest seen: a
# convolution in fp8 (2^-4 per product) or a layer left out would
# fail them.  There is no floor here: the configuration states
# float32, and a closer match is not a different configuration.
LOSS_TOLERANCE = 3e-2
PROBS_TOLERANCE = 3e-1


class _Weights:
    """Hands out the Program's variables in creation order."""

    def __init__(self, params):
        self.p = params
        self.n = 0

    def conv_bn(self):
        i, self.n = self.n, self.n + 1
        return (self.p[f"conv2d_{i}.w_0"], self.p[f"batch_norm_{i}.w_0"],
                self.p[f"batch_norm_{i}.b_0"])


def _conv_bn(x, weights, stride, eps, relu):
    w, scale, shift = weights
    pad = (w.shape[-1] - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    mean = y.mean((0, 2, 3), keepdims=True)
    var = jnp.square(y - mean).mean((0, 2, 3), keepdims=True)
    y = ((y - mean) / jnp.sqrt(var + eps) * scale[None, :, None, None]
         + shift[None, :, None, None])
    return jax.nn.relu(y) if relu else y


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _forward(stage_blocks, width, stride_on_1x1, eps, params, image, label):
    with jax.default_matmul_precision("highest"):
        w = _Weights(params)
        x = _conv_bn(image, w.conv_bn(), 2, eps, True)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, repeats in enumerate(stage_blocks):
            mid = width * 2 ** stage
            for i in range(repeats):
                stride = 2 if i == 0 and stage > 0 else 1
                s1, s3 = (stride, 1) if stride_on_1x1 else (1, stride)
                y = _conv_bn(x, w.conv_bn(), s1, eps, True)
                y = _conv_bn(y, w.conv_bn(), s3, eps, True)
                y = _conv_bn(y, w.conv_bn(), 1, eps, False)
                if x.shape[1] != 4 * mid or stride != 1:
                    x = _conv_bn(x, w.conv_bn(), stride, eps, False)
                x = jax.nn.relu(x + y)
        logits = x.mean((2, 3)) @ params["fc_0.w_0"] + params["fc_0.b_0"]
        probs = jax.nn.softmax(logits, axis=-1)
        nll = -jnp.log(jnp.take_along_axis(probs, label, axis=-1))
        return nll.mean(), probs


def forward(config: dict, params: dict, image, label):
    """`(loss, class probabilities (batch, classes))` in float32;
    `label` is (batch, 1)."""
    if config["block"] != "bottleneck":
        raise ValueError("the reference builds bottleneck ResNets only")
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    return _forward(tuple(config["stage_blocks"]), config["width"],
                    config["stride_on"] == "1x1",
                    float(config["batch_norm"]["epsilon"]), params,
                    jnp.asarray(image, jnp.float32),
                    jnp.asarray(label, jnp.int32))


def compare(loss: float, probs: np.ndarray, ref_loss: float,
            ref_probs: np.ndarray) -> dict:
    diff = float(np.sqrt(np.mean(np.square(probs - ref_probs)))
                 / np.sqrt(np.mean(np.square(ref_probs))))
    loss_diff = abs(loss - ref_loss) / abs(ref_loss)
    return {
        "ok": bool(diff < PROBS_TOLERANCE and loss_diff < LOSS_TOLERANCE),
        "probs_rel_rms": diff, "loss_rel": loss_diff,
        "loss": loss, "reference_loss": ref_loss,
    }
