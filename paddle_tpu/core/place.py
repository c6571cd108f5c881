"""Device / place abstraction.

TPU-native analog of the reference's ``phi::Place`` (paddle/phi/common/place.h)
and device management (``phi/backends/device_manager.h:294``). On TPU the
"place" maps to a ``jax.Device``; there is no per-op stream management — XLA
owns scheduling.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """A logical device. Compares by (kind, index)."""

    kind = "unknown"

    def __init__(self, index: int = 0):
        self.index = int(index)

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (
            other.kind,
            other.index,
        )

    def __hash__(self):
        return hash((self.kind, self.index))

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def jax_device(self):
        devs = _devices_of_kind(self.kind)
        if not devs:
            raise RuntimeError(f"no {self.kind} devices visible to JAX")
        return devs[self.index % len(devs)]


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):
    # API-compat alias: reference code uses CUDAPlace; maps to accelerator 0..n.
    kind = "tpu"


@functools.lru_cache(maxsize=None)
def _devices_of_kind(kind: str):
    if kind == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple()
    # no accelerator -> empty, and jax_device() raises: a TPUPlace never
    # hands back CPU devices under another name
    return tuple(d for d in jax.devices() if d.platform != "cpu")


_current_device = [None]


def set_device(device: str):
    """paddle.set_device parity (python/paddle/device/__init__.py)."""
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(kind, kind)
    place = CPUPlace(idx) if kind == "cpu" else TPUPlace(idx)
    _current_device[0] = place
    return place


def get_device() -> str:
    p = _current_place()
    return f"{p.kind}:{p.index}"


def _current_place() -> Place:
    if _current_device[0] is None:
        default = jax.devices()[0]
        _current_device[0] = (
            CPUPlace(0) if default.platform == "cpu" else TPUPlace(0)
        )
    return _current_device[0]


def is_compiled_with_cuda() -> bool:  # API parity; always False on TPU build
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())


def device_count() -> int:
    return len(jax.devices())


class CUDAPinnedPlace(Place):
    """API-compat alias (reference pinned-host memory place); host memory is
    uniformly managed by JAX on TPU, so this is a tagged CPUPlace."""

    def __init__(self):
        super().__init__("cpu", 0)

    def __repr__(self):
        return "CUDAPinnedPlace"
