"""Builder for SDAR-MoE block-diffusion training (`"builder":
"sdar_moe"`).

Builds the system under test as a user of the functional path does —
`paddle_tpu.models.sdar_moe.build_blockdiff_train_step(model)`, one
jitted step a call — draws the cell's batches, and decides `correct`
on the timed step's OWN outputs: before the warm-up the compiled step
runs once on the first pool batch at learning rate 0 (the weights stay
as seeded; the Adam moments are zeroed again), and its loss, its
logits at the probed masked positions and the experts its routers
chose are compared with `benchmark/reference/sdar_moe.py`, computed a
sequence at a time on the same weights and given the same experts.
The first warm-up step then repeats that batch at the real rate.

The batch recipe is the benchmark's own copy of
`sdar_moe.make_blockdiff_batch`'s, so that a change to the program
cannot change the traffic: token ids uniform over the vocabulary slice
without its last id (the mask id), one t ~ U(0, 1] a block of
`block_length`, each token of the block masked with probability t.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops_sdar_moe as flops
from benchmark.reference import sdar_moe as reference

_T_MIN = 1e-3
# leaves whose gradient on one sequence is compared with the
# reference's: a router, a held expert's down projection, a per-head
# query norm, the embedding
_GRAD_LEAVES = ("model.layers.0.moe.gate_weight",
                "model.layers.{last}.moe.w_down",
                "model.layers.0.self_attn.q_norm.weight",
                "model.embed_tokens.weight")


def make_batch(config: dict, batch: int, seq: int,
               rng: np.random.Generator) -> dict:
    """One host batch.  int32 ids, float32 1/t, bool flags: what they
    are on the device."""
    block = config["assumed"]["block_length"]
    mask_id = config["vocab_size"] - 1
    clean = rng.integers(0, mask_id, (batch, seq), dtype=np.int32)
    blocks = -(-seq // block)
    t = np.clip(1.0 - rng.random((batch, blocks)), _T_MIN, 1.0)
    t = np.repeat(t, block, axis=1)[:, :seq]
    masked = rng.random((batch, seq)) < t
    return {"clean_ids": clean,
            "noisy_ids": np.where(masked, mask_id, clean).astype(np.int32),
            "masked": masked,
            "inv_t": (1.0 / t).astype(np.float32)}


def model_config(config: dict):
    from paddle_tpu.models import sdar_moe

    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_act", "rms_norm_eps", "rope_theta", "attention_bias",
            "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
            "tie_word_embeddings", "vocab_size")
    return sdar_moe.SdarMoeConfig(
        **{k: config[k] for k in keys},
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        block_length=config["assumed"]["block_length"],
        initializer_range=config["assumed"]["initializer_range"],
        recompute="recompute" in config)


def condition_weights(model, config: dict) -> None:
    """Rescales the initializer's draws as `assumed.seeded_weights` of
    the configuration file says (absent: the draws stay as they are).

    The checkpoint this cell continues from was trained under a
    load-balancing loss, whose minimiser is a uniform expert load; the
    source's initializer alone (normal(0, 0.02) everywhere), untrained,
    gives the opposite — from the second layer on every row of a
    sequence picks the same 8 of the 128 experts, and whether this chip
    holds them is a lottery of the seed that sets both the step's time
    and its FLOP count (PERF.md §6, PR 28: the readings).  Four
    conventions, each one number in the file, bring seeded weights to
    that loss's target; they were chosen by the balance of the load in
    a float32 forward pass on the CPU, not by any timing:

    * token embedding rows x `embedding_multiplier` (sqrt(hidden_size),
      the Transformer's own convention): a token's row is its own and
      not its context's mean;
    * the projections that write into the residual stream (attention
      output, expert down) / `residual_projection_divisor` (sqrt(2 x
      the published depth), GPT-2's convention): what rows share does
      not double with every layer;
    * q- and k-norm scales x `qk_norm_scale`: attention logits spread
      enough to prefer some keys, as trained attention does, so that a
      mask row takes after the context its own position picks;
    * the mask token's row x `mask_row_multiplier`: it states no
      content."""
    spec = config["assumed"].get("seeded_weights")
    if not spec:
        return
    rows = model.model.embed_tokens.weight
    mask_id = config["vocab_size"] - 1
    mask_row = rows._value[mask_id] * spec["mask_row_multiplier"]
    rows._value = (rows._value * spec["embedding_multiplier"]
                   ).at[mask_id].set(mask_row)
    for layer in model.model.layers:
        attn = layer.self_attn
        for scale in (attn.q_norm.weight, attn.k_norm.weight):
            scale._value = scale._value * spec["qk_norm_scale"]
        for w in (attn.out_proj.weight, layer.moe.w_down):
            w._value = w._value / spec["residual_projection_divisor"]


def build_model(config: dict, seed: int):
    """The model with the weights a run of `seed` starts from: the one
    path to them, for the system and for
    `benchmark/tests/precision_readings.py`."""
    import paddle_tpu
    from paddle_tpu.models import sdar_moe

    paddle_tpu.seed(seed)
    model = sdar_moe.SdarMoeForBlockDiffusion(model_config(config))
    condition_weights(model, config)
    return model


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it: the router's width
    under `num_experts`, the assumed block length beside it."""
    return {**config, "num_experts": config["router_width"],
            "block_length": config["assumed"]["block_length"]}


class SdarMoeSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns (mean CE, expert count vectors) without
    waiting, `fetch` brings them to the host and feeds the program's
    `moe_*` counters, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        from paddle_tpu.models import sdar_moe

        if chips != 1:
            raise ValueError("the sdar_moe builder drives one chip")
        t = traffic
        self.spans = spans
        self._config, self._traffic = config, traffic
        self._sdar = sdar_moe
        self.items_per_step = t["batch"] * t["seq"]
        self.untrained_loss = math.log(config["vocab_size"])
        self.first_loss_band = config["first_loss_band"]
        self._held_visits, self._fetched = 0.0, 0

        def draw(i):
            return make_batch(config, t["batch"], t["seq"],
                              np.random.default_rng([seed, i]))

        with spans.span("setup.pool"):
            self.pool = [draw(i) for i in range(t["pool_batches"])]
        self._masked_mean = float(np.mean(
            [b["masked"].sum() for b in self.pool]))
        layers = config["num_hidden_layers"]
        self.kernels = {"flash_" + k: {"flops": c["flops"] * layers,
                                       "bytes": c["bytes"] * layers}
                        for k, c in flops.block_flash_cost(
                            config, t["batch"], t["seq"]).items()}
        with spans.span("setup.model"):
            self._model = build_model(config, seed)
            step, self._state = sdar_moe.build_blockdiff_train_step(
                self._model,
                bf16=config["training"]["activations"] == "bfloat16",
                weight_decay=config["training"]["weight_decay"],
                probe=t["probe"], take_weights=True)
            self._lr = jnp.float32(config["training"]["learning_rate"])
        with spans.span("setup.lower"):
            lowered = step.lower(self._state, jax.device_put(self.pool[0]),
                                 self._lr)
        with spans.span("setup.compile"):
            self._compiled = lowered.compile()
            self.memory_analysis = _memory_analysis(self._compiled)
            self.kernel_ops = _mosaic_calls(self._compiled)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference()

    # -- what the metric readers read ---------------------------------------
    @property
    def held_visits_per_layer_step(self) -> float:
        """Mean visits that landed on held experts, a step and layer,
        over the steps fetched so far; the share 1/8 expects before."""
        if self._fetched:
            return self._held_visits / self._fetched
        c = self._config
        return (2 * self.items_per_step * c["num_experts_per_tok"]
                * c["num_experts"] / c["router_width"])

    @property
    def flops_per_item(self) -> float:
        t = self._traffic
        return flops.train_flops_per_token(
            self._config, t["batch"], t["seq"],
            self.held_visits_per_layer_step, self._masked_mean)

    # -- the loop's interface ---------------------------------------------
    def step(self, batch):
        with self.spans.span("bench.feed"):
            on_device = jax.device_put(batch)
        with self.spans.span("bench.dispatch"):
            self._state, _, aux = self._compiled(self._state, on_device,
                                                 self._lr)
        return aux["ce"], aux["moe_stats"]

    def fetch(self, handle) -> float:
        ce, stats = jax.device_get(handle)
        self._sdar.record_moe_stats(stats)
        self._held_visits += float(stats[:, :-2].sum()) / stats.shape[0]
        self._fetched += 1
        return float(ce)

    def sync(self) -> None:
        jax.block_until_ready(self._state)

    def close(self) -> None:
        self._state = self._compiled = None

    # -- checks ------------------------------------------------------------
    def checks(self, counters_now: dict, first_loss: float) -> dict:
        """Conditions of `correct` that belong to this configuration."""
        ref = self.reference
        out = {"reference_matches": ref["ok"],
               "routing_differs_only_at_near_ties":
                   ref["routing"]["all_near_ties"],
               "first_loss_is_the_compared_one":
                   abs(first_loss - ref["ce"]) <= 1e-6 * abs(ref["ce"]),
               "moe_dropped_total_is_0":
                   counters_now.get("moe_dropped_total", 0) == 0
                   and counters_now.get("moe_rows_held_total", 0) > 0,
               "flash_fallback_total_is_0":
                   counters_now.get("flash_fallback_total", 0) == 0}
        if "gradients" in ref:
            out["gradients_match"] = ref["gradients"]["ok"]
        if jax.devices()[0].platform == "tpu":
            layers = self._config["num_hidden_layers"]
            kinds = list(self.kernel_ops.values())
            passes = 2 if "recompute" in self._config else 1
            out["masked_flash_kernels_in_executable"] = (
                kinds.count("flash_fwd") == passes * layers
                and kinds.count("flash_bwd") == 2 * layers
                and counters_now.get("flash_block_mask_total", 0) >= layers)
            out["grouped_matmuls_in_executable"] = \
                kinds.count("grouped_matmul") >= 9 * layers
        return out

    def _compare_with_reference(self) -> dict:
        """The compiled step's own loss, probe logits and routing on
        the first pool batch (learning rate 0) against the reference,
        a sequence at a time."""
        t, config = self._traffic, reference_config(self._config)
        batch = self.pool[0]
        n = t["reference_sample"]
        if n != t["batch"]:
            raise ValueError("reference_sample must be the whole batch: "
                             "the step's loss is the batch's")
        self._state, loss, aux = self._compiled(
            self._state, jax.device_put(batch), jnp.float32(0.0))
        for moments in (self._state["m"], self._state["v"]):
            for k in list(moments):     # a leaf at a time: no second copy
                moments[k] = jnp.zeros_like(moments[k])
        self._state["t"] = jnp.int32(0)
        params = self._state["params"]
        loss, ce = float(loss), float(aux["ce"])
        logits = np.asarray(aux["probe_logits"])
        experts = np.asarray(aux["moe_experts"])       # (L, B * 2S, k)
        rows = 2 * t["seq"]
        pos, valid = self._sdar.probe_positions(batch["masked"], t["probe"])

        ref_logits, weighted, plain, count = [], 0.0, 0.0, 0
        differ, gaps = [], []
        for i in range(n):
            one = {k: v[i:i + 1] for k, v in batch.items()}
            routing = [jnp.asarray(e[i * rows:(i + 1) * rows])
                       for e in experts]
            ref = reference.forward(config, params, one, routing)
            m = int(one["masked"].sum())
            weighted += float(ref["loss"]) * m
            plain += float(ref["ce"]) * m
            count += m
            ref_logits.append(np.asarray(ref["logits"])[0][pos[i]])
            for layer, probs in enumerate(ref["probs"]):
                probs = np.asarray(probs)
                k = routing[layer].shape[1]
                own = np.argpartition(-probs, k - 1, axis=1)[:, :k]
                agree = reference.routing_agreement(
                    np.asarray(routing[layer]), own, probs)
                differ.append(agree["differ_share"])
                gaps.append(agree["max_gap"])
        ref_logits = np.stack(ref_logits)
        out = reference.compare(loss, logits[valid], weighted / count,
                                ref_logits[valid])
        out["ce"], out["reference_ce"] = ce, plain / count
        out["probed_positions"] = int(valid.sum())
        out["routing"] = {"differ_share_mean": float(np.mean(differ)),
                          "differ_share_max": float(np.max(differ)),
                          "max_gap": float(np.max(gaps)),
                          "all_near_ties": bool(
                              np.max(gaps) <= reference.NEAR_TIE)}
        if t.get("grad_check"):
            out["gradients"] = self._compare_gradients(config, params)
        return out

    def _compare_gradients(self, config, params) -> dict:
        """Gradients of the named leaves on the first sequence of the
        first pool batch: the system's loss function (its cast, its
        kernels, its expert layer) against the reference's `jax.grad`,
        given the same experts."""
        names = [n.format(last=config["num_hidden_layers"] - 1)
                 for n in _GRAD_LEAVES]
        one = {k: v[:1] for k, v in self.pool[0].items()}
        got, routing = system_gradients(
            self._model, params, names, one,
            bf16=config["training"]["activations"] == "bfloat16")
        want = reference.grads(config, params, one, routing, wrt=names,
                               remat=True)
        return reference.compare_gradients(got, want)


def system_gradients(model, params, names, batch, bf16):
    """`jax.grad` of the program's own loss (`build_blockdiff_loss`)
    with respect to the leaves `names` on `batch` -> (gradients, the
    experts every layer's router chose)."""
    from paddle_tpu.models import sdar_moe

    loss_fn = sdar_moe.build_blockdiff_loss(model, bf16=bf16, probe=1)

    def system(leaves, rest, batch):
        return jax.grad(lambda l: loss_fn({**rest, **l}, batch),
                        has_aux=True)(leaves)

    leaves = {k: params[k] for k in names}
    rest = {k: v for k, v in params.items() if k not in leaves}
    got, aux = jax.jit(system)(leaves, rest, jax.device_put(batch))
    return got, [jnp.asarray(e) for e in np.asarray(aux["moe_experts"])]


def _memory_analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    if m is None:
        return {}
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(m, k)}


def _mosaic_calls(compiled) -> dict:
    """`{HLO instruction name: "flash_fwd" | "flash_bwd" |
    "grouped_matmul"}` for the Mosaic calls of the executable: the
    flash kernels by the jitted function in the call's `op_name`, the
    grouped matmuls XLA makes of `jax.lax.ragged_dot` by theirs
    (`ragged-dot-none`).  The device trace names its events by HLO
    instruction."""
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
        elif op.group(1).startswith("ragged-dot-none"):
            out[name.group(1)] = "grouped_matmul"
    return out


def build(config, traffic, chips, seed, spans) -> SdarMoeSystem:
    return SdarMoeSystem(config, traffic, chips, seed, spans)
