"""Normalization layers (reference: python/paddle/nn/layer/norm.py; ops
batch_norm/layer_norm/instance_norm/group_norm, operators/batch_norm_op.cc,
layer_norm_op.cc)."""

from __future__ import annotations

import numpy as np

from ...fluid.initializer import ConstantInitializer
from .. import functional as F
from .layers import Layer


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            shape=[num_features], attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter(
            shape=[num_features], attr=bias_attr, is_bias=True)
        self._mean = self.register_buffer(
            "_mean", np.zeros([num_features], np.float32))
        self._variance = self.register_buffer(
            "_variance", np.ones([num_features], np.float32))

    def forward(self, input):
        return F.batch_norm(
            input, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}"


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCHW", use_global_stats, name)


BatchNorm = _BatchNormBase


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm.  Under pjit/shard_map the batch axis is a
    mesh axis and the mean/var reduction rides a psum over it (the
    reference's sync_batch_norm_op.cu NCCL allreduce of statistics);
    single-device eager mode degenerates to plain BN."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        if isinstance(layer, _BatchNormBase) and not isinstance(
                layer, SyncBatchNorm):
            new = SyncBatchNorm(layer._num_features, layer._momentum,
                                layer._epsilon)
            new.weight = layer.weight
            new.bias = layer.bias
            new._mean = layer._mean
            new._variance = layer._variance
            return new
        for name, sub in list(layer._sub_layers.items()):
            layer.add_sublayer(name, cls.convert_sync_batchnorm(sub))
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=self._normalized_shape, attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter(
            shape=self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.layer_norm(input, self._normalized_shape, self.weight,
                            self.bias, self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}"


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned scale
    and no bias (F.rms_norm).  `zero_centred`: the scale is (1 +
    weight) and the weight starts at 0."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None,
                 name=None, zero_centred=False):
        super().__init__()
        self._hidden_size = hidden_size
        self._epsilon = epsilon
        self._zero_centred = zero_centred
        self.weight = self.create_parameter(
            shape=[hidden_size], attr=weight_attr,
            default_initializer=ConstantInitializer(
                0.0 if zero_centred else 1.0))

    def forward(self, input):
        if self._zero_centred:
            return F.rms_norm(input, self.weight, self._epsilon,
                              zero_centred=True)
        return F.rms_norm(input, self.weight, self._epsilon)

    def extra_repr(self):
        return f"hidden_size={self._hidden_size}"


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False or bias_attr is False:
            self.scale = None
            self.bias = None
        else:
            self.scale = self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=ConstantInitializer(1.0))
            self.bias = self.create_parameter(
                shape=[num_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.instance_norm(input, weight=self.scale, bias=self.bias,
                               eps=self._epsilon)


InstanceNorm1D = InstanceNorm2D
InstanceNorm3D = InstanceNorm2D


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[num_channels], attr=weight_attr,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter(
            shape=[num_channels], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.group_norm(input, self._num_groups, self._epsilon,
                            self.weight, self.bias)


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    def forward(self, input):
        return F.local_response_norm(input, self.size, self.alpha,
                                     self.beta, self.k)


class SpectralNorm(Layer):
    """Power-iteration spectral norm of a weight tensor
    (reference: nn/layer/norm.py SpectralNorm; op spectral_norm)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 name=None):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(
            shape=[h], attr=None,
            default_initializer=None)
        self.weight_v = self.create_parameter(
            shape=[w], attr=None,
            default_initializer=None)

    def forward(self, x):
        import jax
        import jax.numpy as jnp

        from ...fluid.dygraph.tracer import trace_fn

        dim, iters, eps = self._dim, self._power_iters, self._eps

        def f(w, u, v):
            perm = [dim] + [i for i in range(w.ndim) if i != dim]
            wm = jnp.transpose(w, perm).reshape(w.shape[dim], -1)
            for _ in range(iters):
                v = wm.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = wm @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ wm @ v
            return w / sigma, u, v

        out, u_new, v_new = trace_fn(
            f, {"w": x, "u": self.weight_u, "v": self.weight_v},
            multi_out=True)
        # reference SpectralNorm updates U/V in place with no grad each
        # forward so power iteration refines across steps
        self.weight_u._value = jax.lax.stop_gradient(
            u_new._value if hasattr(u_new, "_value") else u_new)
        self.weight_v._value = jax.lax.stop_gradient(
            v_new._value if hasattr(v_new, "_value") else v_new)
        return out
