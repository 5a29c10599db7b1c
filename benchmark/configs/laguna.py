"""Builder for Laguna autoregressive training (`"builder": "laguna"`).

Builds the system under test as a user of the functional path does —
`paddle_tpu.models.laguna.build_train_step(model)`, one jitted step a
call — draws the cell's batches, and decides `correct` on the timed
step's OWN outputs, as benchmark/configs/kimi_linear.py does: before the
warm-up the compiled step runs once on the first pool batch at learning
rate 0.  Its cross-entropy, its logits at the probed positions and the
experts its routers chose are compared with
`benchmark/reference/laguna.py` — a dense band mask built from indices,
its own YaRN, rotation and router, on the same weights and given the
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
from benchmark.configs.kimi_linear import _Text, condition_weights
from benchmark.configs.sdar_moe import _memory_analysis, _mosaic_calls
from benchmark.lib import flops_laguna as flops
from benchmark.reference import laguna as reference

_BETA1 = 0.9
# leaves whose gradient is compared with the reference's: the gate's
# projection and W_q of the last window layer and of the last full one
# (both rotations, both masks, both head counts), W_k of the FIRST full
# layer (its gradient runs through every layer after it), a router, a
# held routed expert, a shared expert, the embedding
_GRAD_LEAVES = ("model.layers.{window}.self_attn.g_proj.weight",
                "model.layers.{full}.self_attn.g_proj.weight",
                "model.layers.{window}.self_attn.q_proj.weight",
                "model.layers.{full}.self_attn.q_proj.weight",
                "model.layers.{first_full}.self_attn.k_proj.weight",
                "model.layers.{sparse}.moe.gate_weight",
                "model.layers.{last}.moe.w_down",
                "model.layers.{window}.moe.shared_experts.down_proj.weight",
                "model.embed_tokens.weight")
# forward grid steps a live tile of a window instance: what the first q
# tiles' shorter bands leave of 1 (189 of 192 at (256, 256) tiles, 63 of
# 64 at (512, 512)); a rectangle's would be 189 of 4,096
_BAND_LIVE_SHARE = 0.95


def model_config(config: dict):
    from paddle_tpu.models import laguna

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "max_position_embeddings",
            "attention_bias", "rms_norm_eps", "num_experts_per_tok",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "tie_word_embeddings", "gating", "sliding_window",
            "rope_parameters", "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer",
            "moe_apply_router_weight_on_input", "partial_rotary_factor",
            "moe_routed_scaling_factor", "model_type")
    assumed = config["assumed"]
    return laguna.LagunaConfig(
        **{k: config[k] for k in keys},
        num_experts=config["router_width"],
        experts_held=tuple(config["experts_held"]),
        hidden_act=assumed["hidden_act"],
        router_scoring=assumed["router_scoring"],
        norm_topk_prob=assumed["norm_topk_prob"],
        initializer_range=assumed["initializer_range"],
        recompute="recompute" in config)


def build_model(config: dict, seed: int):
    """The model with the weights a run of `seed` starts from: the one
    path to them, for the system and for the scripts under
    benchmark/tests."""
    import paddle_tpu
    from paddle_tpu.models import laguna

    paddle_tpu.seed(seed)
    model = laguna.LagunaForCausalLM(model_config(config))
    condition_weights(model, config)
    return model


def reference_config(config: dict) -> dict:
    """The configuration as the reference reads it (its own keys)."""
    return dict(config)


def grad_leaves(config: dict) -> list:
    kinds = flops.layer_kinds(config)
    sparse = config["mlp_layer_types"][:len(kinds)]
    last = lambda kind: len(kinds) - 1 - kinds[::-1].index(kind)
    return [n.format(window=last("window"), full=last("full"),
                     first_full=kinds.index("full"),
                     sparse=sparse.index("sparse"), last=len(kinds) - 1)
            for n in _GRAD_LEAVES]


def _kernel_calls(config: dict, compiled) -> dict:
    """`_mosaic_calls`, the flash kernels told apart by the layer whose
    scope the call's `op_name` carries (`…/layers/<i>/self_attn/…`):
    "window_flash_fwd" | "window_flash_bwd" | "full_flash_fwd" |
    "full_flash_bwd" by the configuration's own list."""
    kinds = flops.layer_kinds(config)
    out = _mosaic_calls(compiled)
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not name or not op or not out.get(name.group(1), "").startswith(
                "flash_"):
            continue
        layer = re.search(r"layers/(\d+)/self_attn", op.group(1))
        if layer:
            out[name.group(1)] = (kinds[int(layer.group(1))] + "_"
                                  + out[name.group(1)])
    return out


def _gate_dtypes(compiled) -> list:
    """The dtype of every forward instruction of the per-head gate's
    logits and sigmoid (`…/self_attn/g_proj/dot_general`,
    `…/self_attn/gate/logistic` in the `op_name`; the backward pass's,
    under `transpose(`, left out): one or more a layer, fused or not."""
    return re.findall(
        r'= (\w+)\[[^\n]*op_name="(?![^"]*transpose\()[^"]*self_attn/'
        r'(?:g_proj/dot_general|gate/logistic)"', compiled.as_text())


class LagunaSystem:
    """The step runner the loop drives: `step(batch)` dispatches one
    train step and returns (loss, count vectors) without waiting,
    `fetch` brings them to the host and feeds the program's `moe_*`
    counters, `sync` waits until the device is idle."""

    def __init__(self, config, traffic, chips, seed, spans):
        from paddle_tpu.models import laguna

        if chips != 1:
            raise ValueError("the laguna builder drives one chip")
        t = traffic
        self.spans = spans
        self._config, self._traffic, self._seed = config, traffic, seed
        self._laguna = laguna
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
        self.kernels = {
            kind + "_flash_" + k: scale(c, flops.layers_of(config, kind))
            for kind, cost in (("window", flops.window_flash_cost),
                               ("full", flops.full_flash_cost))
            for k, c in cost(config, t["batch"], t["seq"]).items()}
        with spans.span("setup.model"):
            self._model = build_model(config, seed)
            step, self._state = laguna.build_train_step(
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
            self.kernel_ops = _kernel_calls(config, text)
            self.router_sort_keys = _router_sort_keys(text)
            self.gate_dtypes = _gate_dtypes(text)
        with spans.span("setup.reference"):
            self.reference = self._compare_with_reference()

    # -- what the metric readers read ---------------------------------------
    @property
    def held_visits_per_layer_step(self) -> float:
        """Mean visits that landed on held experts, a step and expert
        layer, over the steps fetched so far; the share 16 / 256 expects
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
        self._laguna.record_moe_stats(stats)
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
                   count("flash_fallback_total") == 0}
        if jax.devices()[0].platform == "tpu":
            window, full = (flops.layers_of(config, k)
                            for k in ("window", "full"))
            sparse = flops.sparse_layers(config)
            kinds = list(self.kernel_ops.values())
            passes = 2 if "recompute" in config else 1
            out["window_flash_kernels_in_executable"] = (
                kinds.count("window_flash_fwd") == passes * window
                and kinds.count("window_flash_bwd") == 2 * window
                and count("flash_window_total") >= window)
            out["full_flash_kernels_in_executable"] = (
                kinds.count("full_flash_fwd") == passes * full
                and kinds.count("full_flash_bwd") == 2 * full)
            steps = count("flash_window_grid_steps_total")
            out["window_grid_walks_the_band"] = (
                steps > 0 and count("flash_window_tiles_live_total")
                >= _BAND_LIVE_SHARE * steps)
            out["both_rotations_traced"] = (
                count("rope_yarn_total") >= full
                and count("rope_partial_total") >= full)
            out["grouped_matmuls_in_executable"] = \
                kinds.count("grouped_matmul") >= 9 * sparse
            # no comparison of results tells a bfloat16 router or a
            # bfloat16 gate from the noise of bfloat16 activations
            # (PERF.md §6, PR 38: the controls read under what the system
            # reads), so the executable is asked: every router's top-k
            # sorts float32 scores, every gate's logits and sigmoid are
            # float32
            out["routers_choose_in_float32"] = (
                len(self.router_sort_keys) >= sparse
                and set(self.router_sort_keys) == {"f32"})
            out["gates_in_float32"] = (
                len(self.gate_dtypes) >= window + full
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
        pos = self._laguna.probe_positions(seq, t["probe"])
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
        out["gradients"] = reference.compare_gradients(
            config, got_grads, want_grads)
        return out


def build(config, traffic, chips, seed, spans) -> LagunaSystem:
    # before anything of this configuration touches the chip: a program
    # without the model fails here, at once
    from paddle_tpu.models import laguna  # noqa: F401

    return LagunaSystem(config, traffic, chips, seed, spans)
