"""Normalization layers (python/paddle/nn/layer/norm.py parity).

BatchNorm running stats live as non-trainable buffers updated eagerly in
training mode — inside a jitted train step, use the functional form with
explicit state threading (paddle_tpu.jit handles the capture).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import functional as F
from ...core.tensor import Tensor
from ..initializer import Constant
from .layers import Layer

__all__ = [
    "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D", "SyncBatchNorm",
    "LayerNorm", "GroupNorm", "InstanceNorm1D", "InstanceNorm2D",
    "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm", "RMSNorm",
    "ZeroCenteredGatedNorm", "zero_centered_scale",
]


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
            [num_features], attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = self.create_parameter(
            [num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros(num_features)))
        self.register_buffer("_variance", Tensor(jnp.ones(num_features)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}"


class BatchNorm(_BatchNormBase):
    """Legacy paddle.nn.BatchNorm (act fused)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 param_attr=None, bias_attr=None, dtype="float32",
                 data_layout="NCHW", in_place=False, moving_mean_name=None,
                 moving_variance_name=None, do_model_average_for_mean_and_var=True,
                 use_global_stats=False, trainable_statistics=False):
        super().__init__(num_channels, momentum, epsilon, param_attr,
                         bias_attr, data_layout, use_global_stats)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCHW" if data_format in ("NCL", "NC") else "NLC",
                         use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm. Inside pjit/shard_map the batch axis is
    sharded over 'dp'; stats sync is an axis-mean (lax.pmean) when tracing
    under a mesh context (reference: nn/layer/norm.py SyncBatchNorm over
    ProcessGroupNCCL)."""

    def forward(self, x):
        from ...distributed import env as dist_env

        axis = dist_env.current_sync_axis()
        if axis is None or not self.training:
            return super().forward(x)
        import jax

        def f(v, w, b):
            ch_ax = 1 if self._data_format.startswith("NC") else v.ndim - 1
            axes = tuple(i for i in range(v.ndim) if i != ch_ax)
            m = jax.lax.pmean(jnp.mean(v, axis=axes), axis)
            m2 = jax.lax.pmean(jnp.mean(v * v, axis=axes), axis)
            var = m2 - m * m
            shape = [1] * v.ndim
            shape[ch_ax] = v.shape[ch_ax]
            out = (v - m.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self._epsilon)
            return out * w.reshape(shape) + b.reshape(shape)

        from ...core.autograd import apply_op

        return apply_op(f, x, self.weight, self.bias, op_name="sync_batch_norm")

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum,
                                layer._epsilon, data_format=layer._data_format)
            out.weight.set_value(layer.weight)
            out.bias.set_value(layer.bias)
            out._mean.set_value(layer._mean)
            out._variance.set_value(layer._variance)
        for name, sub in list(layer._sub_layers.items()):
            out._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            self._normalized_shape, attr=weight_attr,
            default_initializer=Constant(1.0))
        self.bias = self.create_parameter(
            self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}"


class RMSNorm(Layer):
    """RMS norm (paddle.incubate.nn.FusedRMSNorm analog; Llama-family default)."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], attr=weight_attr, default_initializer=Constant(1.0))

    def forward(self, x):
        from ...core.autograd import apply_op
        from ...framework.flags import get_flags

        from ...ops._helpers import unwrap
        from ...ops.pallas import _kernel_routable

        # pallas only on a real TPU and off a GSPMD mesh (Mosaic kernels
        # cannot be partitioned automatically): elsewhere the model path
        # stays plain XLA. The kernel itself is still covered off-TPU
        # through the incubate functional surface (interpret mode).
        if (_kernel_routable(unwrap(x))
                and get_flags("FLAGS_use_pallas_kernels")["FLAGS_use_pallas_kernels"]):
            from ...ops import pallas_kernels as pk

            return apply_op(
                lambda v, w: pk.rms_norm(v, w, eps=self._epsilon),
                x, self.weight, op_name="rms_norm")
        import jax

        def f(v, w):
            var = jnp.mean((v.astype(jnp.float32)) ** 2, axis=-1, keepdims=True)
            out = v * jax.lax.rsqrt(var + self._epsilon).astype(v.dtype)
            return out * w

        return apply_op(f, x, self.weight, op_name="rms_norm")


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = self.create_parameter(
            [num_channels], attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = self.create_parameter(
            [num_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False or bias_attr is False:
            self.weight = None if weight_attr is False else self.create_parameter(
                [num_features], default_initializer=Constant(1.0))
            self.bias = None if bias_attr is False else self.create_parameter(
                [num_features], is_bias=True)
        else:
            self.weight = self.create_parameter(
                [num_features], attr=weight_attr, default_initializer=Constant(1.0))
            self.bias = self.create_parameter(
                [num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    """Power-iteration spectral normalization of a weight tensor."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        from ..initializer import Normal

        self.weight_u = self.create_parameter(
            [h], default_initializer=Normal(0, 1.0))
        self.weight_v = self.create_parameter(
            [w], default_initializer=Normal(0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ...core.autograd import apply_op
        import jax

        u0 = self.weight_u.value
        v0 = self.weight_v.value
        dim = self._dim
        iters = self._power_iters
        eps = self._eps

        def f(w):
            wm = jnp.moveaxis(w, dim, 0)
            mat = wm.reshape(wm.shape[0], -1)
            u, v = u0, v0
            for _ in range(iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + eps)
            u = jax.lax.stop_gradient(u)
            v = jax.lax.stop_gradient(v)
            sigma = u @ mat @ v
            return w / sigma

        out = apply_op(f, weight, op_name="spectral_norm")
        return out


def zero_centered_scale(w, gating: float = 2.0):
    """The multiplier [D] float32 of :class:`ZeroCenteredGatedNorm`'s
    weight ``w``: ``gating * sigmoid(w)``."""
    import jax

    return gating * jax.nn.sigmoid(w.astype(jnp.float32))


class ZeroCenteredGatedNorm(Layer):
    """RMS norm with a zero-centred, gated weight: ``x * rsqrt(mean(x^2) +
    eps) * gating * sigmoid(w)``, ``w`` zero at initialisation (at
    ``gating`` 2 a plain RMS norm then). float32 inside, the result in
    x's dtype."""

    def __init__(self, hidden_size, epsilon=1e-6, gating=2.0):
        super().__init__()
        self._epsilon, self._gating = epsilon, gating
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=Constant(0.0))

    def forward(self, x):
        import jax

        from ...core.autograd import apply_op

        def f(v, w):
            x32 = v.astype(jnp.float32)
            var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            return (x32 * jax.lax.rsqrt(var + self._epsilon)
                    * zero_centered_scale(w, self._gating)).astype(v.dtype)

        return apply_op(f, x, self.weight, op_name="zero_centered_gated_norm")
