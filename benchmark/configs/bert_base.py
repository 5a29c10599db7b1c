"""Builder for BERT pretraining configurations (`"builder": "bert_base"`).

Builds the system under test exactly as a user of the functional path
does — `paddle_tpu.models.bert.build_pretrain_step(model, bf16=True)`,
one jitted step a call (the way `chip_smoke.bert_base_step` proved on
the chip) — draws the cell's batches, and checks the system's forward
pass against `benchmark/reference/bert_base.py` before the window.

The batch recipe is the benchmark's own copy of `bert.fake_batch`'s,
so that a change to the program cannot change the traffic: real
lengths uniform in [seq * min_length_share, seq] behind a key-padding
mask, sorted masked positions, random ids, labels and segment ids.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops
from benchmark.reference import bert_base as reference

_MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "intermediate_size", "hidden_act",
               "hidden_dropout_prob", "attention_probs_dropout_prob",
               "max_position_embeddings", "type_vocab_size",
               "initializer_range")


def make_batch(config: dict, batch: int, seq: int, masked: int,
               min_length_share: float, rng: np.random.Generator) -> dict:
    """One host batch.  int32: what the ids are on the device (x64 is
    off), so the copy moves the bytes a real loader would."""
    lens = rng.integers(max(1, int(seq * min_length_share)), seq + 1,
                        (batch,))
    return {
        "input_ids": rng.integers(0, config["vocab_size"], (batch, seq),
                                  dtype=np.int32),
        "attention_mask": (np.arange(seq)[None, :]
                           < lens[:, None]).astype(np.int32),
        "token_type_ids": rng.integers(0, config["type_vocab_size"],
                                       (batch, seq), dtype=np.int32),
        "masked_positions": np.sort(rng.integers(
            0, seq, (batch, masked), dtype=np.int32), axis=1),
        "masked_labels": rng.integers(0, config["vocab_size"],
                                      (batch, masked), dtype=np.int32),
        "nsp_labels": rng.integers(0, 2, (batch,), dtype=np.int32),
    }


class BertSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns its loss without waiting, `fetch` brings a
    loss to the host, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        import paddle_tpu
        from paddle_tpu.models import bert

        if chips != 1:
            raise ValueError("the bert_base builder drives one chip")
        t = traffic
        self.spans = spans
        self.items_per_step = t["batch"] * t["seq"]
        self.flops_per_item = flops.bert_train_flops_per_token(
            config, t["batch"], t["seq"], t["masked"])
        self.untrained_loss = math.log(config["vocab_size"]) + math.log(2.0)
        self.first_loss_band = config["first_loss_band"]
        layers = config["num_hidden_layers"]
        self.kernels = {
            "flash_" + k: {"flops": c["flops"] * layers,
                           "bytes": c["bytes"] * layers}
            for k, c in flops.flash_attention_cost(
                t["batch"], config["num_attention_heads"], t["seq"],
                config["hidden_size"] // config["num_attention_heads"]
            ).items()}

        def draw(i, batch):
            return make_batch(config, batch, t["seq"], t["masked"],
                              t["min_length_share"],
                              np.random.default_rng([seed, i]))

        with spans.span("setup.pool"):
            self.pool = [draw(i, t["batch"])
                         for i in range(t["pool_batches"])]
        with spans.span("setup.model"):
            paddle_tpu.seed(seed)
            self._model = bert.BertForPretraining(
                bert.BertConfig(**{k: config[k] for k in _MODEL_KEYS}))
            step, self._state = bert.build_pretrain_step(
                self._model,
                bf16=config["training"]["activations"] == "bfloat16",
                weight_decay=config["training"]["weight_decay"])
            self._lr = jnp.float32(config["training"]["learning_rate"])
        with spans.span("setup.lower"):
            lowered = step.lower(self._state, jax.device_put(self.pool[0]),
                                 self._lr)
        with spans.span("setup.compile"):
            self._compiled = lowered.compile()
            self.memory_analysis = _memory_analysis(self._compiled)
            self.kernel_ops = _mosaic_calls(self._compiled)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference(
                config, draw(len(self.pool), t["reference_sample"]))

    # -- the loop's interface ---------------------------------------------
    def step(self, batch):
        with self.spans.span("bench.feed"):
            on_device = jax.device_put(batch)
        with self.spans.span("bench.dispatch"):
            self._state, loss = self._compiled(self._state, on_device,
                                               self._lr)
        return loss

    def fetch(self, loss) -> float:
        return float(loss)

    def sync(self) -> None:
        jax.block_until_ready(self._state)

    def close(self) -> None:
        self._state = self._compiled = None

    # -- checks ------------------------------------------------------------
    def checks(self, counters_now: dict, first_loss: float) -> dict:
        """Conditions of `correct` that belong to this configuration."""
        out = {"reference_matches": self.reference["ok"],
               "flash_fallback_total_is_0":
                   counters_now.get("flash_fallback_total", 0) == 0}
        if jax.devices()[0].platform == "tpu":
            layers = len(self._model.bert.encoder.layers)
            kinds = list(self.kernel_ops.values())
            out["flash_kernels_in_executable"] = (
                kinds.count("flash_fwd") >= layers
                and kinds.count("flash_bwd") >= 2 * layers)
        return out

    def _compare_with_reference(self, config, sample) -> dict:
        """The system's own forward pass (its model, its bf16 cast, its
        attention kernel, its criterion; dropout off) against the plain
        float32 reference, on the same seeded weights and sample."""
        from paddle_tpu.jit import functional_call
        from paddle_tpu.models import bert
        from paddle_tpu.nn.layer.layers import Tensor

        model = self._model
        criterion = bert.BertPretrainingCriterion(config["vocab_size"])
        bf16 = config["training"]["activations"] == "bfloat16"

        def system_forward(params, b):
            if bf16:
                params = {k: v.astype(jnp.bfloat16)
                          if v.dtype == jnp.float32 else v
                          for k, v in params.items()}
            mask = (b["attention_mask"] != 0)[:, None, None, :]
            (mlm, nsp), _ = functional_call(
                model, params, b["input_ids"], b["token_type_ids"],
                attention_mask=mask,
                masked_positions=b["masked_positions"])
            loss = criterion(Tensor(mlm), Tensor(nsp),
                             Tensor(b["masked_labels"]),
                             Tensor(b["nsp_labels"]))._value
            return loss, mlm

        sample = jax.device_put(sample)
        params = self._state["params"]
        model.eval()                     # dropout off, read at trace time
        try:
            loss, mlm = jax.jit(system_forward)(params, sample)
        finally:
            model.train()
        ref_loss, ref_mlm = reference.forward(config, params, sample)
        return reference.compare(float(loss), np.asarray(mlm, np.float32),
                                 float(ref_loss), np.asarray(ref_mlm))


def _memory_analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    if m is None:
        return {}
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(m, k)}


def _mosaic_calls(compiled) -> dict:
    """`{HLO instruction name: "flash_fwd" | "flash_bwd"}` for the
    Mosaic calls of the executable, told apart by the call's `op_name`
    metadata (the kernels carry no name of their own yet; PERF.md lists
    a stable `jax.named_scope` for the `tracing` PR).  The device trace
    names its events by HLO instruction."""
    import re

    out = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not name or not op:
            continue
        if "_flash_backward" in op.group(1):
            out[name.group(1)] = "flash_bwd"
        elif "_flash_forward" in op.group(1):
            out[name.group(1)] = "flash_fwd"
    return out


def build(config, traffic, chips, seed, spans) -> BertSystem:
    return BertSystem(config, traffic, chips, seed, spans)
