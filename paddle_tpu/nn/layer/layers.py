"""`paddle.nn.Layer` — the dygraph module base class.

Mirror of the reference's `python/paddle/fluid/dygraph/layers.py:64`
(`class Layer`) and its dygraph parameter type `ParamBase`
(`python/paddle/fluid/framework.py` dygraph branch): parameter/sublayer
auto-registration via `__setattr__`, state_dict save/load, train/eval
mode, forward pre/post hooks.

TPU-native re-design: parameters are eager Tensors wrapping immutable
`jax.Array`s (fluid/dygraph/varbase.py); initialization happens eagerly
through `Initializer.eager_value` instead of running startup-program init
ops; `paddle.jit.to_static`/`jax.jit` consumes `forward` directly since
the tape tracer records pure-functional jax calls.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import numpy as np

from ...fluid import core, unique_name
from ...fluid.dygraph.varbase import Tensor
from ...fluid.initializer import ConstantInitializer, XavierInitializer
from ...fluid.param_attr import ParamAttr
from ...profiler import stage, stat_add


class Parameter(Tensor):
    """A trainable parameter (the reference's dygraph `ParamBase`)."""

    def __init__(self, value, name=None, trainable=True, optimize_attr=None,
                 regularizer=None, need_clip=True):
        super().__init__(value, name=name or unique_name.generate("param"),
                         stop_gradient=not trainable, persistable=True)
        self.trainable = trainable
        self.optimize_attr = optimize_attr or {"learning_rate": 1.0}
        self.regularizer = regularizer
        self.need_clip = need_clip
        self.is_leaf_param = True

    @property
    def is_parameter(self):
        return True

    def __repr__(self):
        return (f"Parameter(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype}, trainable={self.trainable})\n"
                f"{self.numpy()}")


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base class for all neural network modules
    (reference: fluid/dygraph/layers.py:64)."""

    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        if name_scope is None:
            name_scope = self.__class__.__name__.lower()
        self._full_name = unique_name.generate(name_scope)
        self._scope_name = None      # set by the parent that registers it
        self._dtype = dtype
        self._parameters = OrderedDict()
        self._sub_layers = OrderedDict()
        self._buffers = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._hook_id = [0]

    # -- identity ----------------------------------------------------------
    def full_name(self):
        return self._full_name

    # -- parameter / buffer creation ---------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """Create an eagerly-initialized Parameter (the dygraph analogue of
        LayerHelper.create_parameter, which appends startup-program init
        ops in static mode)."""
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            init = ConstantInitializer(0.0) if is_bias else XavierInitializer()
        shape = [int(s) for s in shape]
        np_dt = core.np_dtype(dtype)
        name = attr.name or unique_name.generate(
            f"{self._full_name}.{'b' if is_bias else 'w'}")
        # draw, cast and the copy to the device (`Parameter` makes the
        # jax array): one start-up phase a leaf
        with stage("setup.param_init", "param_init_ms"):
            value = init.eager_value(shape, np.dtype(np_dt).name)
            param = Parameter(
                value, name=name, trainable=attr.trainable,
                optimize_attr={"learning_rate": attr.learning_rate},
                regularizer=attr.regularizer, need_clip=attr.need_clip)
        stat_add("param_init_total", value.size)
        stat_add("param_init_bytes_total", value.nbytes)
        return param

    def create_variable(self, name=None, persistable=False, dtype=None):
        value = np.zeros([1], dtype=core.np_dtype(dtype or self._dtype))
        return Tensor(value, name=name, persistable=persistable)

    def register_buffer(self, name, tensor, persistable=True):
        """Register a non-parameter state tensor (e.g. BN running mean)."""
        if not isinstance(tensor, Tensor) and tensor is not None:
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if persistable:
            self._non_persistable_buffer_names.discard(name)
        else:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            sublayer._set_scope_name(str(name))
        return sublayer

    def _set_scope_name(self, name):
        """The name `__call__` opens its `jax.named_scope` under: the one
        the parent registered this layer by."""
        self._scope_name = name

    # -- attribute magic ----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "call super().__init__() before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "call super().__init__() before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            value._set_scope_name(name)
        elif buffers is not None and name in buffers:
            buffers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return (list(super().__dir__()) + list(self._parameters)
                + list(self._sub_layers) + list(self._buffers))

    # -- traversal ----------------------------------------------------------
    def children(self):
        for _, layer in self.named_children():
            yield layer

    def named_children(self):
        seen = set()
        for name, layer in self._sub_layers.items():
            if layer is not None and id(layer) not in seen:
                seen.add(id(layer))
                yield name, layer

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self.named_children():
            if id(layer) in layers_set:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from layer.named_sublayers(
                prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def parameters(self, include_sublayers=True):
        return [p for _, p in
                self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        if include_sublayers:
            gen = self.named_sublayers(prefix=prefix, include_self=True)
        else:
            gen = [(prefix, self)]
        for layer_prefix, layer in gen:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield layer_prefix + ("." if layer_prefix else "") + name, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in
                self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        if include_sublayers:
            gen = self.named_sublayers(prefix=prefix, include_self=True)
        else:
            gen = [(prefix, self)]
        for layer_prefix, layer in gen:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield layer_prefix + ("." if layer_prefix else "") + name, b

    def apply(self, fn):
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    # -- train / eval -------------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id[0] += 1
        self._forward_pre_hooks[self._hook_id[0]] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id[0])

    def register_forward_post_hook(self, hook):
        self._hook_id[0] += 1
        self._forward_post_hooks[self._hook_id[0]] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id[0])

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        if destination is None:
            destination = OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            destination[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            # skip non-persistable buffers, mirroring the reference
            leaf = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = getattr(owner, part)
            if leaf in owner._non_persistable_buffer_names:
                continue
            destination[name] = b
        return destination

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            arr = value.numpy() if isinstance(value, Tensor) else np.asarray(value)
            target.set_value(arr.astype(target.numpy().dtype))
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device -----------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_params(dtype)
        return self

    def astype(self, dtype):
        self._cast_params(dtype)
        return self

    def _cast_params(self, dtype):
        np_dt = core.np_dtype(dtype)
        for p in self.parameters():
            p._value = p._value.astype(np_dt)
        for b in self.buffers():
            if b is not None and np.issubdtype(
                    np.asarray(b.numpy()).dtype, np.floating):
                b._value = b._value.astype(np_dt)
        self._dtype = core.convert_dtype(dtype)

    def float(self):
        return self.astype("float32")

    def bfloat16(self):
        return self.astype("bfloat16")

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        # trace-time only under jit: every HLO instruction of `forward`
        # carries the layer's path in its op_name (obs/opprof.scope_name)
        with jax.named_scope(self._scope_name
                             or self.__class__.__name__.lower()):
            outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # -- repr ---------------------------------------------------------------
    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self.named_children():
            sub = repr(layer).split("\n")
            sub = [sub[0]] + ["  " + l for l in sub[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub))
        main = f"{self.__class__.__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
