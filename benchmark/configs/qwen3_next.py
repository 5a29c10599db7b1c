"""Builder for Qwen3-Next autoregressive training (`"builder":
"qwen3_next"`).

Builds the system under test as a user of the functional path does —
`paddle_tpu.models.qwen3_next.build_train_step(model)`, one jitted step
a call — draws the cell's batches, and decides `correct` on the timed
step's OWN outputs, as benchmark/configs/laguna.py does: before the
warm-up the compiled step runs once on the first pool batch at learning
rate 0.  Its cross-entropy, its logits at the probed positions and the
experts its routers chose are compared with
`benchmark/reference/qwen3_next.py` — the Gated DeltaNet recurrence a
token at a time, attention in blocks, on the same weights and given the
same experts; the gradients are the step's too (Adam's first moment
after one step from zero moments is (1 - beta1) x the gradient) and are
compared leaf by leaf with the reference's `jax.grad`.  Then the moments
are zeros again, and the first warm-up step repeats that batch at the
real rate.

The batch recipe is the benchmark's own: one unpadded document a
sequence, token ids uniform over the vocabulary slice; the targets are
the same sequence shifted by one, made by the step.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs.joyai_flash import (_HELD_SHARE_BAND,
                                           _router_sort_keys, make_batch)
from benchmark.configs.kimi_linear import _Text
from benchmark.configs.sdar_moe import _memory_analysis, _mosaic_calls
from benchmark.lib import flops_qwen3_next as flops
from benchmark.reference import qwen3_next as reference

_BETA1 = 0.9
# leaves whose gradient is compared with the reference's: of the last
# Gated DeltaNet layer the decay's rate, the beta / decay projection, the
# convolution's taps and the large projection (every operand of the scan
# and its grouped q and k); the full layer's query-and-gate projection;
# the last layer's shared-expert gate and a held routed expert's down
# projection; the first layer's router
_GRAD_LEAVES = ("model.layers.{gdn}.linear_attn.A_log",
                "model.layers.{gdn}.linear_attn.in_proj_ba.weight",
                "model.layers.{gdn}.linear_attn.conv1d.weight",
                "model.layers.{gdn}.linear_attn.in_proj_qkvz.weight",
                "model.layers.{full}.self_attn.q_proj.weight",
                "model.layers.{last}.moe.shared_expert_gate.weight",
                "model.layers.{last}.moe.w_down",
                "model.layers.0.moe.gate_weight")


def model_config(config: dict):
    from paddle_tpu.models import qwen3_next

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "hidden_act",
            "max_position_embeddings", "rms_norm_eps", "rope_theta",
            "rope_scaling", "partial_rotary_factor",
            "full_attention_interval", "linear_conv_kernel_dim",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_num_key_heads", "linear_num_value_heads",
            "decoder_sparse_step", "mlp_only_layers",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts_per_tok", "norm_topk_prob", "tie_word_embeddings",
            "use_sliding_window", "model_type")
    return qwen3_next.Qwen3NextConfig(
        **{k: config[k] for k in keys},
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        initializer_range=config["assumed"]["initializer_range"],
        recompute="recompute" in config)


def condition_weights(model, config: dict) -> None:
    """Rescales the initializer's draws as `assumed.seeded_weights` of
    the configuration file says (absent: the draws stay as they are):
    benchmark/configs/kimi_linear.py's two conventions, with this
    model's two mixers' output projections."""
    spec = config["assumed"].get("seeded_weights")
    if not spec:
        return
    rows = model.model.embed_tokens.weight
    rows._value = rows._value * spec["embedding_multiplier"]
    for layer in model.model.layers:
        down = [layer.linear_attn.out_proj.weight
                if layer.kind == "linear_attention"
                else layer.self_attn.o_proj.weight]
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
    from paddle_tpu.models import qwen3_next

    paddle_tpu.seed(seed)
    model = qwen3_next.Qwen3NextForCausalLM(model_config(config))
    condition_weights(model, config)
    return model


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it (its own keys)."""
    return dict(config)


def grad_leaves(config: dict) -> list:
    kinds = flops.layer_kinds(config)
    last = lambda kind: len(kinds) - 1 - kinds[::-1].index(kind)
    return [n.format(gdn=last("gdn"), full=last("full"), last=len(kinds) - 1)
            for n in _GRAD_LEAVES]


def _kernel_calls(compiled) -> dict:
    """`_mosaic_calls` plus the scan's kernels of the Gated DeltaNet
    instances, by the jitted function in the call's `op_name`:
    "gdn_fwd" | "gdn_bwd"."""
    out = _mosaic_calls(compiled)
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if name and op:
            for fn, kind in (("_gdn_forward", "gdn_fwd"),
                             ("_gdn_backward", "gdn_bwd")):
                if fn in op.group(1):
                    out[name.group(1)] = kind
    return out


def _gate_dtypes(compiled) -> list:
    """The dtype of every forward instruction of the element-wise
    gate's sigmoid — differentiated, it lowers as 1 / (1 + exp(-x)):
    `…/self_attn/gate/logistic`, `…/gate/exp` or `…/gate/div` in the
    `op_name`; the backward pass's, under `transpose(`, left out."""
    return re.findall(
        r'= (\w+)\[[^\n]*op_name="(?![^"]*transpose\()[^"]*self_attn/'
        r'gate/(?:logistic|exp|div)"', compiled.as_text())


class Qwen3NextSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns (loss, count vectors) without waiting,
    `fetch` brings them to the host and feeds the program's `moe_*`
    counters, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        from paddle_tpu.models import qwen3_next

        if chips != 1:
            raise ValueError("the qwen3_next builder drives one chip")
        t = traffic
        self.spans = spans
        self._config, self._traffic, self._seed = config, traffic, seed
        self._qwen = qwen3_next
        self.items_per_step = t["batch"] * t["seq"]
        self.untrained_loss = math.log(config["vocab_size"])
        self.first_loss_band = config["first_loss_band"]
        self._held_visits, self._fetched = 0.0, 0

        def draw(i):
            return make_batch(config, t["batch"], t["seq"],
                              np.random.default_rng([seed, i]))

        with spans.span("setup.pool"):
            self.pool = [draw(i) for i in range(t["pool_batches"])]
        scale = lambda cost, n: {"flops": cost["flops"] * n,
                                 "bytes": cost["bytes"] * n}
        c = config
        self.kernels = {
            **{"gdn_core_" + k: scale(v, flops.layers_of(c, "gdn"))
               for k, v in flops.gdn_core_cost(
                   t["batch"], t["seq"], c["linear_num_key_heads"],
                   c["linear_num_value_heads"], c["linear_key_head_dim"],
                   c["linear_value_head_dim"]).items()},
            **{"flash_" + k: scale(v, flops.layers_of(c, "full"))
               for k, v in flops.full_flash_cost(
                   c, t["batch"], t["seq"]).items()}}
        with spans.span("setup.model"):
            self._model = build_model(config, seed)
            step, self._state = qwen3_next.build_train_step(
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
            text = _Text(self._compiled)
            self.kernel_ops = _kernel_calls(text)
            self.router_sort_keys = _router_sort_keys(text)
            self.gate_dtypes = _gate_dtypes(text)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference()

    # -- what the metric readers read ---------------------------------------
    @property
    def held_visits_per_layer_step(self) -> float:
        """Mean visits that landed on held experts, a step and expert
        layer, over the steps fetched so far; the share 32 / 512 expects
        before."""
        if self._fetched:
            return self._held_visits / self._fetched
        c = self._config
        return (self.items_per_step * c["num_experts_per_tok"]
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
        return loss, aux["moe_stats"]

    def fetch(self, handle) -> float:
        loss, stats = jax.device_get(handle)
        self._qwen.record_moe_stats(stats)
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
        count = lambda name: counters_now.get(name, 0)
        routed = count("moe_rows_routed_total")
        share = (count("moe_rows_held_total") / max(routed, 1)
                 * config["router_width"] / config["num_experts"])
        out = {"reference_matches": ref["ok"],
               "routing_differs_only_at_near_ties":
                   ref["routing"]["all_near_ties"],
               "gradients_match": ref["gradients"]["ok"],
               "first_loss_near_the_compared_one":
                   abs(first_loss - ref["loss"]) <= 1e-2 * abs(ref["loss"]),
               "moe_dropped_total_is_0":
                   count("moe_dropped_total") == 0
                   and count("moe_rows_held_total") > 0,
               "held_share_near_held_over_routed":
                   _HELD_SHARE_BAND[0] < share < _HELD_SHARE_BAND[1],
               "flash_fallback_total_is_0":
                   count("flash_fallback_total") == 0,
               "kda_fallback_total_is_0": count("kda_fallback_total") == 0,
               "kda_group_repeat_total_is_0":
                   count("kda_group_repeat_total") == 0}
        if jax.devices()[0].platform == "tpu":
            gdn, full = (flops.layers_of(config, k) for k in ("gdn", "full"))
            sparse = flops.sparse_layers(config)
            kinds = list(self.kernel_ops.values())
            passes = 2 if "recompute" in config else 1
            out["gdn_scan_kernels_in_executable"] = (
                kinds.count("gdn_fwd") == passes * gdn
                and kinds.count("gdn_bwd") == gdn
                and count("kda_head_decay_total") >= gdn
                and count("kda_grouped_heads_total") >= gdn)
            out["full_flash_kernels_in_executable"] = (
                kinds.count("flash_fwd") == passes * full
                and kinds.count("flash_bwd") == 2 * full)
            out["grouped_matmuls_in_executable"] = \
                kinds.count("grouped_matmul") >= 9 * sparse
            out["routers_choose_in_float32"] = (
                len(self.router_sort_keys) >= sparse
                and set(self.router_sort_keys) == {"f32"})
            out["gates_in_float32"] = (
                len(self.gate_dtypes) >= full
                and set(self.gate_dtypes) == {"f32"})
        return out

    def _compare_with_reference(self) -> dict:
        """The compiled step's own loss, probe logits, routing and
        gradients on the first pool batch (learning rate 0) against the
        reference, a sequence at a time."""
        t, config = self._traffic, reference_config(self._config)
        batch = self.pool[0]
        n = t["batch"]
        if t["reference_sample"] != n:
            raise ValueError("reference_sample must be the whole batch: "
                             "the step's loss is the batch's")
        state, loss, aux = self._compiled(
            self._state, jax.device_put(batch), jnp.float32(0.0))
        self._state = state
        names = grad_leaves(self._config) if t.get("grad_check") else []
        got_grads = {k: np.asarray(state["m"][k]) / (1 - _BETA1)
                     for k in names}
        for moments in (state["m"], state["v"]):
            for k in list(moments):     # a leaf at a time: no second copy
                moments[k] = jnp.zeros_like(moments[k])
        state["t"] = jnp.int32(0)
        params = state["params"]
        seq = t["seq"]
        pos = self._qwen.probe_positions(seq, t["probe"])
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
            for layer, scores in enumerate(ref["choose_by"]):
                scores = np.asarray(scores)
                own = np.argpartition(-scores, k_top - 1, axis=1)[:, :k_top]
                agree = reference.routing_agreement(
                    np.asarray(routing[layer]), own, scores,
                    reference.NEAR_TIE)
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


def build(config, traffic, chips, seed, spans) -> Qwen3NextSystem:
    # before anything of this configuration touches the chip: a program
    # without the model fails here, at once
    from paddle_tpu.models import qwen3_next  # noqa: F401

    return Qwen3NextSystem(config, traffic, chips, seed, spans)
