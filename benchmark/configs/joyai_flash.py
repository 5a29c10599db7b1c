"""Builder for JoyAI-LLM-Flash autoregressive training with its
multi-token-prediction module (`"builder": "joyai_flash"`).

Builds the system under test as a user of the functional path does —
`paddle_tpu.models.joyai_flash.build_train_step(model)`, one jitted
step a call — draws the cell's batches, and decides `correct` on the
timed step's OWN outputs: before the warm-up the compiled step runs
once on the first pool batch at learning rate 0 with seeded selection
biases (`assumed.comparison_selection_bias`).  Its two cross-entropies,
both heads' logits at the probed positions and the experts its routers
chose are compared with `benchmark/reference/joyai_flash.py`, computed
a sequence at a time on the same weights and given the same experts;
the gradients are the step's too — after one step from zero moments
Adam's first moment is (1 - beta1) x the gradient, whatever the rate —
and are compared leaf by leaf with the reference's `jax.grad`.  Then
the moments and the biases are zeros again, and the first warm-up step
repeats that batch at the real rate.

The batch recipe is the benchmark's own: one unpadded document a
sequence, token ids uniform over the vocabulary slice; the targets are
the same sequence shifted by one and by two, made by the step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs.sdar_moe import _memory_analysis, _mosaic_calls
from benchmark.lib import flops_joyai as flops
from benchmark.reference import joyai_flash as reference

_BETA1 = 0.9
# leaves whose gradient is compared with the reference's: the two
# low-rank projections the latent attention adds, a held routed
# expert's down projection, the shared expert's, the MTP module's
# projection, a router
_GRAD_LEAVES = ("model.layers.{last}.self_attn.kv_b_proj.weight",
                "model.layers.0.self_attn.q_a_proj.weight",
                "model.layers.{last}.moe.w_down",
                "model.layers.1.moe.shared_experts.down_proj.weight",
                "mtp.eh_proj.weight",
                "model.layers.1.moe.gate_weight")
# a fair router lands held / routed (16 / 256 = 1/16) of the visits on
# this chip's experts; the window's share has to lie within this
# factor of it (the seeded weights' readings: PERF.md §6)
_HELD_SHARE_BAND = (0.8, 1.25)


def make_batch(config: dict, batch: int, seq: int,
               rng: np.random.Generator) -> dict:
    """One host batch.  int32 ids: what they are on the device."""
    return {"input_ids": rng.integers(0, config["vocab_size"], (batch, seq),
                                      dtype=np.int32)}


def model_config(config: dict):
    from paddle_tpu.models import joyai_flash

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "moe_layer_freq", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "scoring_func", "topk_method", "num_nextn_predict_layers",
            "hidden_act", "rms_norm_eps", "rope_theta", "rope_interleave",
            "rope_scaling", "attention_bias", "tie_word_embeddings")
    assumed = config["assumed"]
    return joyai_flash.JoyAIFlashConfig(
        **{k: config[k] for k in keys},
        n_routed_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        initializer_range=assumed["initializer_range"],
        mtp_loss_weight=assumed["mtp_loss_weight"],
        bias_update_rate=assumed["bias_update_rate"],
        recompute="recompute" in config)


def condition_weights(model, config: dict) -> None:
    """Rescales the initializer's draws as `assumed.seeded_weights` of
    the configuration file says (absent: the draws stay as they are).

    A trained router of this family spreads its rows: the selection
    bias's update drives every expert's load to the mean.  The
    source's initializer alone, untrained, does the opposite — after
    the first attention layer a row's state is mostly a causal mean of
    values, nearly the same for every row of a sequence, so most rows
    pick the same 8 experts, and whether this chip holds them is a
    lottery of the seed that sets both the step's time and its FLOP
    count (PERF.md §6, PR 28 and PR 32: the readings).  Two
    conventions, each one number in the file:

    * token embedding rows x `embedding_multiplier` (sqrt(hidden_size),
      the Transformer's own convention): a token's row is its own and
      not its context's mean;
    * the projections that write into the residual stream (attention
      output, dense / routed / shared expert down) /
      `residual_projection_divisor` (sqrt(2 x the published depth),
      GPT-2's convention): what rows share does not double with every
      layer."""
    spec = config["assumed"].get("seeded_weights")
    if not spec:
        return
    rows = model.model.embed_tokens.weight
    rows._value = rows._value * spec["embedding_multiplier"]
    for layer in list(model.model.layers) + [model.mtp.block]:
        down = [layer.self_attn.o_proj.weight]
        if layer.sparse:
            down += [layer.moe.w_down,
                     layer.moe.shared_experts.down_proj.weight]
        else:
            down.append(layer.mlp.down_proj.weight)
        for w in down:
            w._value = w._value / spec["residual_projection_divisor"]


def build_model(config: dict, seed: int):
    """The model with the weights a run of `seed` starts from: the one
    path to them, for the system and for the scripts under
    benchmark/tests."""
    import paddle_tpu
    from paddle_tpu.models import joyai_flash

    paddle_tpu.seed(seed)
    model = joyai_flash.JoyAIFlashForCausalLMWithMTP(model_config(config))
    condition_weights(model, config)
    return model


def comparison_biases(config: dict, names, seed: int) -> dict:
    """The selection biases of the compared step: seeded draws from
    U(-a, a), a = `assumed.comparison_selection_bias`."""
    a = config["assumed"].get("comparison_selection_bias", 0.0)
    rng = np.random.default_rng([seed, 2 ** 20])
    return {k: rng.uniform(-a, a, (config["router_width"],)).astype(
        np.float32) for k in names}     # on the host: the step donates


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it: the router's width
    under `n_routed_experts`, the assumed lambda beside it."""
    return {**config, "n_routed_experts": config["router_width"],
            "mtp_loss_weight": config["assumed"]["mtp_loss_weight"]}


def grad_leaves(config: dict) -> list:
    return [n.format(last=config["num_hidden_layers"] - 1)
            for n in _GRAD_LEAVES]


class JoyAIFlashSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns (loss, count vectors, loads) without
    waiting, `fetch` brings them to the host and feeds the program's
    `moe_*` counters, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        from paddle_tpu.models import joyai_flash

        if chips != 1:
            raise ValueError("the joyai_flash builder drives one chip")
        t = traffic
        self.spans = spans
        self._config, self._traffic, self._seed = config, traffic, seed
        self._joyai = joyai_flash
        self.items_per_step = t["batch"] * t["seq"]
        self.untrained_loss = (
            1 + config["assumed"]["mtp_loss_weight"]) * math.log(
            config["vocab_size"])
        self.first_loss_band = config["first_loss_band"]
        self._held_visits, self._fetched = 0.0, 0

        def draw(i):
            return make_batch(config, t["batch"], t["seq"],
                              np.random.default_rng([seed, i]))

        with spans.span("setup.pool"):
            self.pool = [draw(i) for i in range(t["pool_batches"])]
        layers = flops.attention_layers(config)
        self.kernels = {"flash_" + k: {"flops": c["flops"] * layers,
                                       "bytes": c["bytes"] * layers}
                        for k, c in flops.mla_flash_cost(
                            config, t["batch"], t["seq"]).items()}
        with spans.span("setup.model"):
            self._model = build_model(config, seed)
            step, self._state = joyai_flash.build_train_step(
                self._model,
                bf16=config["training"]["activations"] == "bfloat16",
                weight_decay=config["training"]["weight_decay"],
                probe=t["probe"], take_weights=True)
            self._biases = joyai_flash.bias_names(self._state["params"])
            self._lr = jnp.float32(config["training"]["learning_rate"])
        with spans.span("setup.lower"):
            lowered = step.lower(self._state, jax.device_put(self.pool[0]),
                                 self._lr)
        with spans.span("setup.compile"):
            self._compiled = lowered.compile()
            self.memory_analysis = _memory_analysis(self._compiled)
            self.kernel_ops = _mosaic_calls(self._compiled)
            self.router_sort_keys = _router_sort_keys(self._compiled)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference()

    # -- what the metric readers read ---------------------------------------
    @property
    def held_visits_per_layer_step(self) -> float:
        """Mean visits that landed on held experts, a step and expert
        layer, over the steps fetched so far; the share 1/16 expects
        before."""
        if self._fetched:
            return self._held_visits / self._fetched
        c = self._config
        return (self.items_per_step * c["num_experts_per_tok"]
                * c["n_routed_experts"] / c["router_width"])

    @property
    def flops_per_item(self) -> float:
        t = self._traffic
        return flops.train_flops_per_token(
            self._config, t["batch"], t["seq"],
            self.held_visits_per_layer_step)

    # -- the loop's interface ---------------------------------------------
    def step(self, batch):
        with self.spans.span("bench.feed"):
            on_device = jax.device_put(batch)
        with self.spans.span("bench.dispatch"):
            self._state, loss, aux = self._compiled(self._state, on_device,
                                                    self._lr)
        return loss, aux["moe_stats"], aux["moe_load"]

    def fetch(self, handle) -> float:
        loss, stats, load = jax.device_get(handle)
        self._joyai.record_moe_stats(stats, load,
                                     bias_updates=len(self._biases))
        self._held_visits += float(stats[:, :-2].sum()) / stats.shape[0]
        self._fetched += 1
        return float(loss)

    def sync(self) -> None:
        jax.block_until_ready(self._state)

    def close(self) -> None:
        self._state = self._compiled = None

    # -- checks ------------------------------------------------------------
    def checks(self, counters_now: dict, first_loss: float) -> dict:
        """Conditions of `correct` that belong to this configuration."""
        ref, config = self.reference, self._config
        routed = counters_now.get("moe_rows_routed_total", 0)
        share = (counters_now.get("moe_rows_held_total", 0) / max(routed, 1)
                 * config["router_width"] / config["n_routed_experts"])
        out = {"reference_matches": ref["ok"],
               "routing_differs_only_at_near_ties":
                   ref["routing"]["all_near_ties"],
               "gradients_match": ref["gradients"]["ok"],
               # the same batch and weights; the compared step's seeded
               # selection biases move the routing, not the untrained loss
               "first_loss_near_the_compared_one":
                   abs(first_loss - ref["loss"]) <= 1e-2 * abs(ref["loss"]),
               "moe_dropped_total_is_0":
                   counters_now.get("moe_dropped_total", 0) == 0
                   and counters_now.get("moe_rows_held_total", 0) > 0,
               "router_counts_every_visit":
                   counters_now.get("moe_router_rows_total", 0) == routed
                   and counters_now.get("moe_bias_updates_total", 0) > 0,
               "held_share_near_held_over_routed":
                   _HELD_SHARE_BAND[0] < share < _HELD_SHARE_BAND[1],
               "flash_fallback_total_is_0":
                   counters_now.get("flash_fallback_total", 0) == 0}
        if jax.devices()[0].platform == "tpu":
            attn = flops.attention_layers(config)
            kinds = list(self.kernel_ops.values())
            passes = 2 if "recompute" in config else 1
            out["latent_flash_kernels_in_executable"] = (
                kinds.count("flash_fwd") == passes * attn
                and kinds.count("flash_bwd") == 2 * attn
                and counters_now.get("flash_split_value_total", 0) >= attn
                and counters_now.get("flash_tiles_live_total", 0)
                < counters_now.get("flash_tiles_total", 0))
            out["grouped_matmuls_in_executable"] = \
                kinds.count("grouped_matmul") >= 9 * flops.sparse_layers(
                    config)
            out["sigmoid_routers_traced"] = counters_now.get(
                "moe_sigmoid_router_total", 0) >= flops.sparse_layers(config)
            # no comparison of results tells a bfloat16 router from the
            # noise of bfloat16 activations (PERF.md §6: the control reads
            # what the system reads), so the executable is asked: every
            # router's top-k sorts float32 scores
            out["routers_choose_in_float32"] = (
                len(self.router_sort_keys) >= flops.sparse_layers(config)
                and set(self.router_sort_keys) == {"f32"})
        return out

    def _compare_with_reference(self) -> dict:
        """The compiled step's own losses, probe logits, routing and
        gradients on the first pool batch (learning rate 0, seeded
        selection biases) against the reference, a sequence at a
        time."""
        t, config = self._traffic, reference_config(self._config)
        batch = self.pool[0]
        n = t["batch"]
        if t["reference_sample"] != n:
            raise ValueError("reference_sample must be the whole batch: "
                             "the step's loss is the batch's")
        state = self._state
        seeded = comparison_biases(self._config, self._biases, self._seed)
        state["params"].update({k: jnp.asarray(v)
                                for k, v in seeded.items()})
        state, loss, aux = self._compiled(state, jax.device_put(batch),
                                          jnp.float32(0.0))
        self._state = state
        names = grad_leaves(self._config) if t.get("grad_check") else []
        got_grads = {k: np.asarray(state["m"][k]) / (1 - _BETA1)
                     for k in names}
        for moments in (state["m"], state["v"]):
            for k in list(moments):     # a leaf at a time: no second copy
                moments[k] = jnp.zeros_like(moments[k])
        state["t"] = jnp.int32(0)
        # the reference reads the biases the step read, not the moved ones
        params = {**state["params"], **seeded}
        for k in self._biases:
            state["params"][k] = jnp.zeros_like(seeded[k])
        seq = t["seq"]
        pos = self._joyai.probe_positions(seq, t["probe"])
        experts = np.asarray(aux["moe_experts"])        # (L, B * S, k)
        want = {"ce": 0.0, "mtp_ce": 0.0, "logits": [], "mtp_logits": []}
        want_grads = {k: 0.0 for k in names}
        differ, gaps = [], []
        k_top = config["num_experts_per_tok"]
        for i in range(n):
            one = {k: v[i:i + 1] for k, v in batch.items()}
            routing = [jnp.asarray(e[i * seq:(i + 1) * seq])
                       for e in experts]
            ref = reference.forward(config, params, one, routing)
            for key in ("ce", "mtp_ce"):
                want[key] += float(ref[key]) / n
            for key in ("logits", "mtp_logits"):
                want[key].append(np.asarray(ref[key])[0][pos])
            for layer, choose_by in enumerate(ref["choose_by"]):
                choose_by = np.asarray(choose_by)
                own = np.argpartition(-choose_by, k_top - 1,
                                      axis=1)[:, :k_top]
                agree = reference.routing_agreement(
                    np.asarray(routing[layer]), own, choose_by)
                differ.append(agree["differ_share"])
                gaps.append(agree["max_gap"])
            del ref
            if names:
                g = reference.grads(config, params, one, routing, wrt=names,
                                    remat=True)
                for k in names:     # equal counts a sequence: the mean
                    want_grads[k] = want_grads[k] + np.asarray(g[k]) / n
        out = reference.compare(
            {"ce": float(aux["ce"]), "mtp_ce": float(aux["mtp_ce"]),
             "logits": np.asarray(aux["probe_logits"]),
             "mtp_logits": np.asarray(aux["mtp_probe_logits"])},
            {**want, "logits": np.stack(want["logits"]),
             "mtp_logits": np.stack(want["mtp_logits"])})
        out["loss"] = float(loss)
        out["probed_positions"] = int(n * len(pos))
        out["routing"] = {"differ_share_mean": float(np.mean(differ)),
                          "differ_share_max": float(np.max(differ)),
                          "max_gap": float(np.max(gaps)),
                          "all_near_ties": bool(
                              np.max(gaps) <= reference.NEAR_TIE)}
        out["gradients"] = reference.compare_gradients(got_grads,
                                                       want_grads)
        return out


def _router_sort_keys(compiled) -> list:
    """The dtype of the scores each router's top-k sorts (XLA lowers
    `lax.top_k` to a sort of (score, index) on a TPU): one entry an
    expert layer, by the `…/moe/router/top_k` in the `op_name`."""
    import re

    return re.findall(r'= \((\w+)\[[^\n]*? sort\([^\n]*'
                      r'op_name="[^"]*moe/router/top_k', compiled.as_text())


def build(config, traffic, chips, seed, spans) -> JoyAIFlashSystem:
    # before anything of this configuration touches the chip: a program
    # without the model fails here, at once
    from paddle_tpu.models import joyai_flash  # noqa: F401

    return JoyAIFlashSystem(config, traffic, chips, seed, spans)
