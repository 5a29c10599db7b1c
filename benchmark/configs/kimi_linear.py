"""Builder for Kimi Linear autoregressive training (`"builder":
"kimi_linear"`).

Builds the system under test as a user of the functional path does —
`paddle_tpu.models.kimi_linear.build_train_step(model)`, one jitted
step a call — draws the cell's batches, and decides `correct` on the
timed step's OWN outputs, as benchmark/configs/joyai_flash.py does:
before the warm-up the compiled step runs once on the first pool batch
at learning rate 0 with seeded selection biases
(`assumed.comparison_selection_bias`).  Its cross-entropy, its logits at
the probed positions and the experts its routers chose are compared
with `benchmark/reference/kimi_linear.py` — the recurrence a token at a
time, a sequence at a time, on the same weights and given the same
experts; the gradients are the step's too (Adam's first moment after
one step from zero moments is (1 - beta1) x the gradient) and are
compared leaf by leaf with the reference's `jax.grad`.  Then the
moments and the biases are zeros again, and the first warm-up step
repeats that batch at the real rate.

The batch recipe is the benchmark's own: one unpadded document a
sequence, token ids uniform over the vocabulary slice; the targets are
the same sequence shifted by one, made by the step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs.joyai_flash import (_HELD_SHARE_BAND,
                                           _router_sort_keys,
                                           comparison_biases, make_batch)
from benchmark.configs.sdar_moe import _memory_analysis, _mosaic_calls
from benchmark.lib import flops_joyai
from benchmark.lib import flops_kimi_linear as flops
from benchmark.reference import kimi_linear as reference

_BETA1 = 0.9
# leaves whose gradient is compared with the reference's: of the last
# KDA layer a large projection, the decay's up-projection, its
# per-head rate, the k convolution's taps and the beta projection
# (every operand of the scan but v); the latent layer's up-projection;
# a held routed expert's down projection; a router
_GRAD_LEAVES = ("model.layers.{kda}.self_attn.k_proj.weight",
                "model.layers.{kda}.self_attn.f_b_proj.weight",
                "model.layers.{kda}.self_attn.A_log",
                "model.layers.{kda}.self_attn.k_conv1d.weight",
                "model.layers.{kda}.self_attn.b_proj.weight",
                "model.layers.{mla}.self_attn.kv_b_proj.weight",
                "model.layers.{last}.moe.w_down",
                "model.layers.1.moe.gate_weight")


def model_config(config: dict):
    from paddle_tpu.models import kimi_linear

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "mla_use_nope",
            "linear_attn_config", "num_experts_per_token",
            "num_shared_experts", "first_k_dense_replace", "moe_layer_freq",
            "num_expert_group", "topk_group", "use_grouped_topk",
            "moe_renormalize", "moe_router_activation_func",
            "routed_scaling_factor", "num_nextn_predict_layers",
            "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
            "tie_word_embeddings", "model_max_length", "model_type")
    assumed = config["assumed"]
    return kimi_linear.KimiLinearConfig(
        **{k: config[k] for k in keys},
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        initializer_range=assumed["initializer_range"],
        bias_update_rate=assumed["bias_update_rate"],
        recompute="recompute" in config)


def condition_weights(model, config: dict) -> None:
    """Rescales the initializer's draws as `assumed.seeded_weights` of
    the configuration file says (absent: the draws stay as they are);
    the two conventions of benchmark/configs/joyai_flash.py:
    `condition_weights`, by the same two numbers."""
    spec = config["assumed"].get("seeded_weights")
    if not spec:
        return
    rows = model.model.embed_tokens.weight
    rows._value = rows._value * spec["embedding_multiplier"]
    for layer in model.model.layers:
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
    from paddle_tpu.models import kimi_linear

    paddle_tpu.seed(seed)
    model = kimi_linear.KimiLinearForCausalLM(model_config(config))
    condition_weights(model, config)
    return model


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it: the router's keys
    under the names benchmark/reference/joyai_flash.py's `route` has."""
    return {**config, "n_routed_experts": config["router_width"],
            "num_experts_per_tok": config["num_experts_per_token"],
            "norm_topk_prob": config["moe_renormalize"],
            "n_group": config["num_expert_group"]}


def grad_leaves(config: dict) -> list:
    kinds = flops.layer_kinds(config)
    last = lambda kind: len(kinds) - 1 - kinds[::-1].index(kind)
    return [n.format(kda=last("kda"), mla=last("mla"), last=len(kinds) - 1)
            for n in _GRAD_LEAVES]


class _Text:
    """An executable's text, rendered once, for the readers that ask
    `as_text()` of it."""

    def __init__(self, compiled):
        self._text = compiled.as_text()

    def as_text(self) -> str:
        return self._text


def _kernel_calls(compiled) -> dict:
    """`_mosaic_calls` plus the scan's kernels, by the jitted function
    in the call's `op_name`."""
    import re

    out = _mosaic_calls(compiled)
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if name and op:
            for fn, kind in (("_kda_forward", "kda_fwd"),
                             ("_kda_backward", "kda_bwd")):
                if fn in op.group(1):
                    out[name.group(1)] = kind
    return out


class KimiLinearSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns (loss, count vectors, loads) without
    waiting, `fetch` brings them to the host and feeds the program's
    `moe_*` counters, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        from paddle_tpu.models import kimi_linear

        if chips != 1:
            raise ValueError("the kimi_linear builder drives one chip")
        t = traffic
        self.spans = spans
        self._config, self._traffic, self._seed = config, traffic, seed
        self._kimi = kimi_linear
        self.items_per_step = t["batch"] * t["seq"]
        self.untrained_loss = math.log(config["vocab_size"])
        self.first_loss_band = config["first_loss_band"]
        self._held_visits, self._fetched = 0.0, 0

        def draw(i):
            return make_batch(config, t["batch"], t["seq"],
                              np.random.default_rng([seed, i]))

        with spans.span("setup.pool"):
            self.pool = [draw(i) for i in range(t["pool_batches"])]
        lin = config["linear_attn_config"]
        kda, mla = flops.kda_layers(config), flops.latent_layers(config)
        scale = lambda cost, n: {"flops": cost["flops"] * n,
                                 "bytes": cost["bytes"] * n}
        self.kernels = {
            **{"flash_" + k: scale(c, mla)
               for k, c in flops_joyai.mla_flash_cost(
                   config, t["batch"], t["seq"]).items()},
            **{"kda_core_" + k: scale(c, kda)
               for k, c in flops.kda_core_cost(
                   t["batch"], t["seq"], lin["num_heads"], lin["head_dim"],
                   lin["head_dim"]).items()}}
        with spans.span("setup.model"):
            self._model = build_model(config, seed)
            step, self._state = kimi_linear.build_train_step(
                self._model,
                bf16=config["training"]["activations"] == "bfloat16",
                weight_decay=config["training"]["weight_decay"],
                probe=t["probe"], take_weights=True)
            self._biases = kimi_linear.bias_names(self._state["params"])
            self._lr = jnp.float32(config["training"]["learning_rate"])
        with spans.span("setup.lower"):
            lowered = step.lower(self._state, jax.device_put(self.pool[0]),
                                 self._lr)
        with spans.span("setup.compile"):
            self._compiled = lowered.compile()
            self.memory_analysis = _memory_analysis(self._compiled)
            text = _Text(self._compiled)
            self.kernel_ops = _kernel_calls(text)
            self.router_sort_keys = _router_sort_keys(text)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference()

    # -- what the metric readers read ---------------------------------------
    @property
    def held_visits_per_layer_step(self) -> float:
        """Mean visits that landed on held experts, a step and expert
        layer, over the steps fetched so far; the share 8 / 256 expects
        before."""
        if self._fetched:
            return self._held_visits / self._fetched
        c = self._config
        return (self.items_per_step * c["num_experts_per_token"]
                * c["num_experts"] / c["router_width"])

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
        self._kimi.record_moe_stats(stats, load,
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
                 * config["router_width"] / config["num_experts"])
        out = {"reference_matches": ref["ok"],
               "routing_differs_only_at_near_ties":
                   ref["routing"]["all_near_ties"],
               "gradients_match": ref["gradients"]["ok"],
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
                   counters_now.get("flash_fallback_total", 0) == 0,
               "kda_fallback_total_is_0":
                   counters_now.get("kda_fallback_total", 0) == 0}
        if jax.devices()[0].platform == "tpu":
            kda, mla = flops.kda_layers(config), flops.latent_layers(config)
            sparse = flops.sparse_layers(config)
            kinds = list(self.kernel_ops.values())
            passes = 2 if "recompute" in config else 1
            chunked = counters_now.get("kda_chunked_total", 0)
            out["kda_scan_kernels_in_executable"] = (
                kinds.count("kda_fwd") == passes * kda
                and kinds.count("kda_bwd") == kda
                and chunked > 0 and chunked % kda == 0
                and counters_now.get("kda_chunks_total", 0) > 0)
            out["latent_flash_kernels_in_executable"] = (
                kinds.count("flash_fwd") == passes * mla
                and kinds.count("flash_bwd") == 2 * mla
                and counters_now.get("flash_split_value_total", 0) >= mla
                and counters_now.get("flash_tiles_live_total", 0)
                < counters_now.get("flash_tiles_total", 0))
            out["grouped_matmuls_in_executable"] = \
                kinds.count("grouped_matmul") >= 9 * sparse
            out["sigmoid_routers_traced"] = counters_now.get(
                "moe_sigmoid_router_total", 0) >= sparse
            out["routers_choose_in_float32"] = (
                len(self.router_sort_keys) >= sparse
                and set(self.router_sort_keys) == {"f32"})
        return out

    def _compare_with_reference(self) -> dict:
        """The compiled step's own loss, probe logits, routing and
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
        pos = self._kimi.probe_positions(seq, t["probe"])
        experts = np.asarray(aux["moe_experts"])        # (L, B * S, k)
        want = {"ce": 0.0, "logits": []}
        want_grads = {k: 0.0 for k in names}
        differ, gaps = [], []
        k_top = config["num_experts_per_tok"]
        for i in range(n):
            one = {k: v[i:i + 1] for k, v in batch.items()}
            routing = [jnp.asarray(e[i * seq:(i + 1) * seq])
                       for e in experts]
            ref = reference.forward(config, params, one, routing, probe=pos)
            want["ce"] += float(ref["ce"]) / n
            want["logits"].append(np.asarray(ref["logits"])[0])
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
            {"ce": float(aux["ce"]),
             "logits": np.asarray(aux["probe_logits"])},
            {**want, "logits": np.stack(want["logits"])})
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


def build(config, traffic, chips, seed, spans) -> KimiLinearSystem:
    # before anything of this configuration touches the chip: a program
    # without the model fails here, at once
    from paddle_tpu.models import kimi_linear  # noqa: F401

    return KimiLinearSystem(config, traffic, chips, seed, spans)
