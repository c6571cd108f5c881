"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas/pjit.

Top-level namespace mirrors ``paddle.*`` (reference: python/paddle/__init__.py)
so reference users find the same API shape; the execution model underneath is
traced XLA programs, not per-op kernel dispatch.
"""
from __future__ import annotations

import importlib

# dtypes
from .core.dtype import (
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.place import (
    CPUPlace,
    CUDAPlace,
    TPUPlace,
    device_count,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    set_device,
)
from .core.random import get_rng_state, seed, set_rng_state
from .core.tensor import Tensor, is_tensor, to_tensor
from .core.autograd import enable_grad, no_grad, set_grad_enabled, is_grad_enabled

# functional op surface
from .ops import *  # noqa: F401,F403

__version__ = "0.1.0"

# Subpackages load lazily (PEP 562): paddle_tpu.nn, .optimizer, .distributed...
_LAZY_SUBMODULES = {
    "inference",
    "signal",
    "geometric",
    "audio",
    "text",
    "hub",
    "onnx",
    "cost_model",
    "device",
    "reader",
    "dataset",
    "amp",
    "autograd",
    "distributed",
    "distribution",
    "fft",
    "quantization",
    "framework",
    "hapi",
    "incubate",
    "io",
    "jit",
    "metric",
    "models",
    "monitor",
    "nn",
    "optimizer",
    "profiler",
    "regularizer",
    "serving",
    "sparse",
    "static",
    "utils",
    "vision",
}

_LAZY_ATTRS = {
    "grad": ("paddle_tpu.autograd", "grad"),
    "save": ("paddle_tpu.framework.io", "save"),
    "load": ("paddle_tpu.framework.io", "load"),
    "to_static": ("paddle_tpu.jit", "to_static"),
    "DataParallel": ("paddle_tpu.distributed.parallel", "DataParallel"),
    "Model": ("paddle_tpu.hapi.model", "Model"),
    "summary": ("paddle_tpu.hapi.model_summary", "summary"),
    "flops": ("paddle_tpu.hapi.dynamic_flops", "flops"),
    "ParamAttr": ("paddle_tpu.nn.param_attr", "ParamAttr"),
    "get_flags": ("paddle_tpu.framework.flags", "get_flags"),
    "set_flags": ("paddle_tpu.framework.flags", "set_flags"),
    "finfo": ("paddle_tpu.core.dtype", "finfo"),
    "dtype": ("paddle_tpu.framework.compat", "dtype"),
    "iinfo": ("paddle_tpu.core.dtype", "iinfo"),
    "bool": ("paddle_tpu.core.dtype", "bool_"),
    "CUDAPinnedPlace": ("paddle_tpu.core.place", "CUDAPinnedPlace"),
    "batch": ("paddle_tpu.framework.compat", "batch"),
    "LazyGuard": ("paddle_tpu.framework.compat", "LazyGuard"),
    "check_shape": ("paddle_tpu.framework.compat", "check_shape"),
    "disable_signal_handler": ("paddle_tpu.framework.compat",
                               "disable_signal_handler"),
    "set_printoptions": ("paddle_tpu.framework.compat", "set_printoptions"),
    "tolist": ("paddle_tpu.framework.compat", "tolist"),
    "get_cuda_rng_state": ("paddle_tpu.core.random", "get_rng_state"),
    "set_cuda_rng_state": ("paddle_tpu.core.random", "set_rng_state"),
    "pow_": ("paddle_tpu.framework.compat", "pow_"),
    "index_add_": ("paddle_tpu.framework.compat", "index_add_"),
    "index_put_": ("paddle_tpu.framework.compat", "index_put_"),
    "scatter_": ("paddle_tpu.framework.compat", "scatter_"),
    "squeeze_": ("paddle_tpu.framework.compat", "squeeze_"),
    "tanh_": ("paddle_tpu.framework.compat", "tanh_"),
    "unsqueeze_": ("paddle_tpu.framework.compat", "unsqueeze_"),
    "callbacks": ("paddle_tpu.hapi", "callbacks"),
    "synchronize": ("paddle_tpu.device", "synchronize"),
}


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name in _LAZY_ATTRS:
        mod_name, attr = _LAZY_ATTRS[name]
        obj = getattr(importlib.import_module(mod_name), attr)
        globals()[name] = obj
        return obj
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def __dir__():
    # PEP 562 lazy names are invisible to dir() unless listed here —
    # discoverability matters for API-surface parity checks and tooling
    return sorted(set(globals()) | _LAZY_SUBMODULES | set(_LAZY_ATTRS))


def enable_static():
    """Enter static (record-then-jit) mode — see paddle_tpu.static."""
    from .static import enable_static as _e

    return _e()


def disable_static(place=None):
    from .static import disable_static as _d

    return _d()


def in_dynamic_mode() -> bool:
    from .static.program import in_static_mode

    return not in_static_mode()
