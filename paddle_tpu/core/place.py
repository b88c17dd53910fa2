"""Device placement.

TPU-native equivalent of ``phi::Place`` (reference: paddle/phi/common/place.h)
and ``paddle.set_device`` (reference: python/paddle/device/__init__.py).
A Place names a jax backend + device ordinal; the global current place decides
where new tensors are committed.
"""
from __future__ import annotations

import functools

import jax

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "set_device",
    "get_device",
    "current_place",
    "device_count",
    "is_compiled_with_tpu",
]


class Place:
    __slots__ = ("backend", "index")

    def __init__(self, backend: str, index: int = 0):
        self.backend = backend
        self.index = index

    def __repr__(self):
        return f"Place({self.backend}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.backend == other.backend
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.backend, self.index))

    @property
    def device(self):
        """The concrete jax.Device, or None if the backend is unavailable.
        An index past the backend's last device raises: ``TPUPlace(3)`` on
        one chip must not quietly mean chip 0."""
        devs = _backend_devices(self.backend)
        if not devs:
            return None
        if not 0 <= self.index < len(devs):
            raise ValueError(
                f"{self!r}: backend '{self.backend}' has {len(devs)} "
                f"device(s), valid indices are 0..{len(devs) - 1}")
        return devs[self.index]


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


@functools.lru_cache(maxsize=None)
def _backend_devices(backend: str):
    """``jax.devices(backend)`` or nothing: a backend JAX does not have
    (``"tpu"`` on a CPU-only host) is the empty tuple."""
    try:
        return tuple(jax.devices(backend))
    except RuntimeError:
        return ()


_current_place = None


def _default_place() -> Place:
    if _backend_devices("tpu"):
        return TPUPlace(0)
    return CPUPlace(0)


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.set_device analog. Accepts 'cpu', 'tpu', 'tpu:0', a Place."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    if ":" in device:
        backend, idx = device.split(":", 1)
        idx = int(idx)
    else:
        backend, idx = device, 0
    if backend in ("gpu", "xpu", "npu"):  # reference device strings map to the accelerator
        backend = "tpu"
    _current_place = Place(backend, idx)
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.backend}:{p.index}"


def device_count(backend: str = "tpu") -> int:
    return len(_backend_devices(backend))


def is_compiled_with_tpu() -> bool:
    return bool(_backend_devices("tpu"))
