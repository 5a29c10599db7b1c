"""Common layers (reference: python/paddle/nn/layer/common.py)."""

from __future__ import annotations

import numpy as np

from ...fluid.dygraph.tracer import trace_op
from ...fluid.initializer import (ConstantInitializer, NormalInitializer,
                                  XavierInitializer)
from .. import functional as F
from .layers import Layer


class Linear(Layer):
    """y = xW + b with W (in_features, out_features)
    (reference: nn/layer/common.py Linear)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=XavierInitializer())
        self.bias = self.create_parameter(
            shape=[out_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.linear(input, self.weight, self.bias)

    def extra_repr(self):
        return f"in_features={self._in_features}, out_features={self._out_features}"


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.axis = axis
        self.mode = mode

    def forward(self, input):
        return F.dropout(input, p=self.p, axis=self.axis,
                         training=self.training, mode=self.mode)

    def extra_repr(self):
        return f"p={self.p}"


class Dropout2D(Layer):
    def __init__(self, p=0.5, data_format="NCHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, input):
        return F.dropout2d(input, p=self.p, training=self.training,
                           data_format=self.data_format)


class Embedding(Layer):
    """(reference: nn/layer/common.py Embedding; op lookup_table_v2,
    operators/lookup_table_v2_op.cc)."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self._padding_idx = padding_idx
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=NormalInitializer(0.0, 1.0))
        if padding_idx is not None:
            w = np.array(self.weight.numpy())
            w[padding_idx] = 0
            self.weight.set_value(w)

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx)

    def extra_repr(self):
        return f"{self._num_embeddings}, {self._embedding_dim}"


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, input):
        return trace_op("flatten_contiguous_range", {"X": input},
                        {"start_axis": self.start_axis,
                         "stop_axis": self.stop_axis})


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, align_mode=0, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.mode = mode
        self.align_corners = align_corners
        self.align_mode = align_mode
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, self.size, self.scale_factor, self.mode,
                             self.align_corners, self.align_mode,
                             self.data_format)


class UpsamplingNearest2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__(size, scale_factor, "nearest",
                         data_format=data_format)


class UpsamplingBilinear2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__(size, scale_factor, "bilinear", align_corners=True,
                         data_format=data_format)


class Pad1D(Layer):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCL", name=None):
        super().__init__()
        self.padding = padding
        self.mode = mode
        self.value = value

    def forward(self, x):
        return F.pad(x, self.padding, self.mode, self.value)


class Pad2D(Pad1D):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCHW", name=None):
        super().__init__(padding, mode, value, data_format)


class Pad3D(Pad1D):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCDHW", name=None):
        super().__init__(padding, mode, value, data_format)


class PixelShuffle(Layer):
    def __init__(self, upscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self.upscale_factor = upscale_factor

    def forward(self, x):
        return F.pixel_shuffle(x, self.upscale_factor)


class CosineSimilarity(Layer):
    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self.axis = axis
        self.eps = eps

    def forward(self, x1, x2):
        import jax.numpy as jnp

        from ...fluid.dygraph.tracer import trace_fn

        def f(a, b):
            dot = jnp.sum(a * b, axis=self.axis)
            na = jnp.linalg.norm(a, axis=self.axis)
            nb = jnp.linalg.norm(b, axis=self.axis)
            return dot / jnp.maximum(na * nb, self.eps)

        return trace_fn(f, {"a": x1, "b": x2})


class Bilinear(Layer):
    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            shape=[out_features, in1_features, in2_features],
            attr=weight_attr, default_initializer=XavierInitializer())
        self.bias = self.create_parameter(
            shape=[1, out_features], attr=bias_attr, is_bias=True)

    def forward(self, x1, x2):
        import jax.numpy as jnp

        from ...fluid.dygraph.tracer import trace_fn

        def f(x1, x2, w, b=None):
            out = jnp.einsum("bi,oij,bj->bo", x1, w, x2)
            return out + b if b is not None else out

        ins = {"x1": x1, "x2": x2, "w": self.weight}
        if self.bias is not None:
            ins["b"] = self.bias
        return trace_fn(f, ins)


import contextlib
import threading

_MOE_AUX = threading.local()


@contextlib.contextmanager
def moe_aux_scope():
    """Collect the DIFFERENTIABLE Switch aux losses of every SwitchMoE
    forward in the scope (works under jit tracing, where the layer
    attribute channel is deliberately detached): yields a list that
    fills with one aux Tensor per routed call — sum them into the
    training loss."""
    prev = getattr(_MOE_AUX, "items", None)
    _MOE_AUX.items = []
    try:
        yield _MOE_AUX.items
    finally:
        _MOE_AUX.items = prev


class SwitchMoE(Layer):
    """Switch-Transformer feed-forward: top-1 routed mixture of expert
    FFNs (Fedus et al. 2021).  The reference has no MoE (SURVEY.md §2.9
    "NOT present in the reference"); this layer is the eager/model-side
    face of the TPU-native expert-parallel design in
    paddle_tpu.parallel.moe — the SAME dispatch algebra runs here on
    local experts and there sharded over an `ep` mesh axis.

    forward(x (B, S, H)) -> (B, S, H).  The Switch load-balance aux
    loss: in eager, `.aux_loss` after the call is a tape-connected
    Tensor (add `aux_weight * layer.aux_loss` to the training loss);
    under jit/functional_call the attribute is NOT set (it would leak a
    tracer) — the value instead rides the `moe_aux_loss` buffer through
    functional_call's new_state, detached (jit callers that want the
    aux gradient should use parallel.moe.build_switch_moe, whose apply
    returns it).
    """

    def __init__(self, d_model, d_ff, num_experts, capacity_factor=1.25,
                 weight_attr=None, name=None):
        super().__init__()
        self._d_model, self._d_ff = d_model, d_ff
        self._num_experts = num_experts
        self._capacity_factor = capacity_factor
        self.gate_weight = self.create_parameter(
            shape=[d_model, num_experts], attr=weight_attr,
            default_initializer=XavierInitializer())
        # explicit fans: the generic _fan_in_out would read the 3D
        # stacked-expert shape as a conv kernel and under-scale by
        # ~sqrt(d_ff) (code-review r5; per-expert fans match
        # parallel.moe.init_moe_params)
        self.w1 = self.create_parameter(
            shape=[num_experts, d_model, d_ff], attr=weight_attr,
            default_initializer=XavierInitializer(fan_in=d_model,
                                                  fan_out=d_ff))
        self.b1 = self.create_parameter(shape=[num_experts, d_ff],
                                        is_bias=True)
        self.w2 = self.create_parameter(
            shape=[num_experts, d_ff, d_model], attr=weight_attr,
            default_initializer=XavierInitializer(fan_in=d_ff,
                                                  fan_out=d_model))
        self.b2 = self.create_parameter(shape=[num_experts, d_model],
                                        is_bias=True)
        self.moe_aux_loss = self.register_buffer(
            "moe_aux_loss", np.zeros([], np.float32), persistable=False)
        self.aux_loss = None

    def forward(self, x):
        from ...fluid.dygraph.tracer import trace_fn
        from ...parallel.moe import switch_moe_local

        d_model, n_experts = self._d_model, self._num_experts
        cf = self._capacity_factor

        def f(x, wg, w1, b1, w2, b2):
            lead = x.shape[:-1]
            out, aux = switch_moe_local(
                {"wg": wg, "w1": w1, "b1": b1, "w2": w2, "b2": b2},
                x.reshape(-1, d_model), n_experts, capacity_factor=cf)
            return out.reshape(lead + (d_model,)), aux

        out, aux = trace_fn(
            f, {"x": x, "wg": self.gate_weight, "w1": self.w1,
                "b1": self.b1, "w2": self.w2, "b2": self.b2},
            multi_out=True)
        import jax
        from jax import lax

        # buffer: pure-state channel under functional_call (detached)
        self.moe_aux_loss._value = lax.stop_gradient(aux._value)
        # attribute: eager tape recipe only — never stash a tracer
        self.aux_loss = (None if isinstance(aux._value, jax.core.Tracer)
                         else aux)
        # scope: the differentiable channel (eager AND traced)
        items = getattr(_MOE_AUX, "items", None)
        if items is not None:
            items.append(aux)
        return out


class RoutedMoE(Layer):
    """Dropless top-k routed experts (gated SiLU FFNs), told which
    experts it holds: the model-side face of
    `parallel.moe.routed_moe_local`.  It routes over all `num_experts`,
    computes the visits that land on the `held = (first, count)` experts
    whose weights it has (default: all) and leaves out what absent
    experts would have added; on one chip there is no exchange.

    `scoring` "softmax" (default) or "sigmoid" (DeepSeek-V3's router:
    sigmoid scores, the k weights renormalised and times
    `routed_scaling_factor`).  `selection_bias`: the buffer
    `e_score_correction_bias` (num_experts,) is added to the scores for
    the CHOICE alone — never to the weights, and outside the gradient;
    a train step moves it by `parallel.moe.update_selection_bias` from
    the `load` this layer then returns.  `n_shared_experts` > 0: one
    `GatedFFN` of width `n_shared_experts * d_ff` (sublayer
    `shared_experts`) that every row passes, added to the routed
    output.  `shared_gate`: that output times sigmoid(x w_sg) first,
    w_sg (d_model, 1) (sublayer `shared_expert_gate`, float32 logits;
    the Qwen3-Next family's).  `n_group`, `topk_group`: 1, no group
    limit on the choice (group-limited top-k is not built: anything
    else raises).

    forward(x (..., H)) -> (out (..., H), stats, experts[, load]):
    stats is the layer's (count + 2,) int32 count vector (rows per held
    expert, visits routed, held visits computed), experts (rows, k)
    what the router chose, load — with `selection_bias` — the
    (num_experts,) int32 rows of every router output."""

    def __init__(self, d_model, d_ff, num_experts, top_k, held=None,
                 norm_topk_prob=True, weight_attr=None, scoring="softmax",
                 routed_scaling_factor=1.0, selection_bias=False,
                 n_shared_experts=0, n_group=1, topk_group=1,
                 shared_gate=False):
        super().__init__()
        if n_group != 1 or topk_group != 1:
            raise NotImplementedError(
                f"group-limited routing (n_group {n_group}, topk_group "
                f"{topk_group}) is not built: 1 and 1, no limit, only")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
        self._top_k, self._renormalize = top_k, norm_topk_prob
        self._held = held
        self._scoring, self._scale = scoring, float(routed_scaling_factor)
        count = num_experts if held is None else held[1]
        self.gate_weight = self.create_parameter(
            shape=[d_model, num_experts], attr=weight_attr,
            default_initializer=XavierInitializer())
        fans = dict(attr=weight_attr, default_initializer=XavierInitializer(
            fan_in=d_model, fan_out=d_ff))
        self.w_gate = self.create_parameter(
            shape=[count, d_model, d_ff], **fans)
        self.w_up = self.create_parameter(
            shape=[count, d_model, d_ff], **fans)
        self.w_down = self.create_parameter(
            shape=[count, d_ff, d_model], **fans)
        self._biased, self._shared = selection_bias, bool(n_shared_experts)
        if selection_bias:
            import jax.numpy as jnp

            self.register_buffer("e_score_correction_bias",
                                 jnp.zeros((num_experts,), jnp.float32))
        if shared_gate and not n_shared_experts:
            raise ValueError("a shared-expert gate without a shared expert")
        self._shared_gate = shared_gate
        if n_shared_experts:
            from .transformer import GatedFFN, _Float32Linear

            self.shared_experts = GatedFFN(
                d_model, n_shared_experts * d_ff, "silu", weight_attr)
            if shared_gate:
                self.shared_expert_gate = _Float32Linear(
                    d_model, 1, weight_attr, False)

    def forward(self, x):
        from ...fluid.dygraph.tracer import trace_fn
        from ...parallel.moe import routed_moe_local, router_load

        biased = self._biased

        def f(x, wr, wg, wu, wd, br=None):
            params = {"wr": wr, "wg": wg, "wu": wu, "wd": wd}
            if br is not None:
                params["br"] = br
            out, stats, experts = routed_moe_local(
                params, x.reshape(-1, x.shape[-1]), self._top_k,
                held=self._held, renormalize=self._renormalize,
                scoring=self._scoring, scale=self._scale)
            out = out.reshape(x.shape), stats, experts
            if biased:
                out += (router_load(experts, wr.shape[1]),)
            return out

        ins = {"x": x, "wr": self.gate_weight, "wg": self.w_gate,
               "wu": self.w_up, "wd": self.w_down}
        if biased:
            ins["br"] = self.e_score_correction_bias
        out, *rest = trace_fn(f, ins, multi_out=True)
        if self._shared_gate:
            import jax
            import jax.numpy as jnp

            out = out + trace_fn(
                lambda y, g: (y.astype(jnp.float32) * jax.nn.sigmoid(g)
                              ).astype(y.dtype),
                {"y": self.shared_experts(x),
                 "g": self.shared_expert_gate(x)})
        elif self._shared:
            out = out + self.shared_experts(x)
        return (out, *rest)
