"""Plain reference for BERT pretraining: forward pass and loss.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")` (on a TPU a float32 matmul
otherwise runs in bf16 passes): no kernel, no cast, no dropout.  It
follows Devlin et al. 2018 as released (`modeling.py` of
google-research/bert): post-layer-norm encoder, erf GELU, MLM head
(dense + GELU + layer norm, decoder tied to the word embedding, plus a
bias) at the masked positions, NSP head on the tanh-pooled first token.
It is fed the system's own seeded weights under the system's parameter
names; a Linear weight there is (in, out).

Departure of the system, not of this file: its layer norms use epsilon
1e-5 where the published model uses 1e-12 (kept here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Relative RMS difference of the MLM logits, system against reference,
# and relative difference of the loss.
#
# The system computes in bfloat16 (8 significant bits, rounding error
# 2^-9 = 2e-3 per value) over float32 master weights; through 12 layers
# with layer norms the errors add like a random walk.  The v5e measured
# 0.0108 to 0.0112 on the logits and up to 2.6e-4 on the loss (my chip
# runs, PR 22, 9 samples of 2 sequences): LOGITS_TOLERANCE is 3.6 times
# that, and a step in fp8 or int8 (errors of 3e-2 and more per value)
# would fail it.  The loss is a weak witness — an untrained model's
# log-softmax over 30522 words hardly moves with its logits — and is
# held to a round 2e-3.  LOGITS_FLOOR is the other side: logits that
# leave a bf16 matmul carry at least the rounding of the output itself
# (1e-3 of their RMS), so a difference under the floor means the system
# did NOT compute in bfloat16 where the configuration says it does, and
# what is being timed is not the stated configuration.
LOGITS_TOLERANCE = 4e-2
LOGITS_FLOOR = 1e-4
LOSS_TOLERANCE = 2e-3


def _layer_norm(x, weight, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _linear(x, p, name):
    return x @ p[name + ".weight"] + p[name + ".bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def _log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - jnp.log(jnp.exp(x).sum(-1, keepdims=True))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward(heads, layers, eps, p, b):
    with jax.default_matmul_precision("highest"):
        ids = b["input_ids"]
        batch, seq = ids.shape
        x = (p["bert.embeddings.word_embeddings.weight"][ids]
             + p["bert.embeddings.position_embeddings.weight"][
                 jnp.arange(seq)][None]
             + p["bert.embeddings.token_type_embeddings.weight"][
                 b["token_type_ids"]])
        x = _layer_norm(x, p["bert.embeddings.layer_norm.weight"],
                        p["bert.embeddings.layer_norm.bias"], eps)
        hidden = x.shape[-1]
        head_dim = hidden // heads
        # padded keys get no weight; padded queries still attend
        key_bias = jnp.where(b["attention_mask"] != 0, 0.0,
                             -jnp.inf)[:, None, None, :]

        def split(y):
            return y.reshape(batch, seq, heads, head_dim).transpose(
                0, 2, 1, 3)

        for i in range(layers):
            pre = f"bert.encoder.layers.{i}."
            q = split(_linear(x, p, pre + "self_attn.q_proj"))
            k = split(_linear(x, p, pre + "self_attn.k_proj"))
            v = split(_linear(x, p, pre + "self_attn.v_proj"))
            scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(head_dim)
            probs = jax.nn.softmax(scores + key_bias, axis=-1)
            ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(
                batch, seq, hidden)
            x = _layer_norm(x + _linear(ctx, p, pre + "self_attn.out_proj"),
                            p[pre + "norm1.weight"], p[pre + "norm1.bias"],
                            eps)
            ffn = _linear(_gelu(_linear(x, p, pre + "linear1")), p,
                          pre + "linear2")
            x = _layer_norm(x + ffn, p[pre + "norm2.weight"],
                            p[pre + "norm2.bias"], eps)

        pooled = jnp.tanh(_linear(x[:, 0], p, "bert.pooler.dense"))
        at_masked = jnp.take_along_axis(
            x, b["masked_positions"][..., None], axis=1)
        t = _layer_norm(_gelu(_linear(at_masked, p, "cls.transform")),
                        p["cls.layer_norm.weight"],
                        p["cls.layer_norm.bias"], eps)
        mlm = (t @ p["bert.embeddings.word_embeddings.weight"].T
               + p["cls.decoder_bias"])
        nsp = _linear(pooled, p, "cls.seq_relationship")
        mlm_nll = -jnp.take_along_axis(
            _log_softmax(mlm), b["masked_labels"][..., None], axis=-1)
        nsp_nll = -jnp.take_along_axis(
            _log_softmax(nsp), b["nsp_labels"][..., None], axis=-1)
        return mlm_nll.mean() + nsp_nll.mean(), mlm


def forward(config: dict, params: dict, batch: dict):
    """`(loss, MLM logits (batch, masked, vocab))` in float32."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    return _forward(config["num_attention_heads"],
                    config["num_hidden_layers"],
                    float(config["layer_norm_eps"]), params, batch)


def compare(loss: float, logits: np.ndarray, ref_loss: float,
            ref_logits: np.ndarray) -> dict:
    diff = float(np.sqrt(np.mean(np.square(logits - ref_logits)))
                 / np.sqrt(np.mean(np.square(ref_logits))))
    loss_diff = abs(loss - ref_loss) / abs(ref_loss)
    return {
        "ok": bool(LOGITS_FLOOR < diff < LOGITS_TOLERANCE
                   and loss_diff < LOSS_TOLERANCE),
        "logits_rel_rms": diff, "loss_rel": loss_diff,
        "loss": loss, "reference_loss": ref_loss,
    }
