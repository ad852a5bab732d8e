"""Device places.

Reference parity: `phi::Place` / `AllocationType` (`paddle/phi/common/place.h:28`) and the
Python ``paddle.CPUPlace()/CUDAPlace(i)`` objects.  Here a Place maps to a jax.Device;
``TPUPlace`` is the first-class accelerator (the reference's CUDAPlace analog).
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if _kind(d) == self.device_type]
        if not devs:
            # a place that was asked for by name never degrades to the host:
            # the caller would compute on the CPU believing it holds a chip
            raise RuntimeError(
                f"{self!r}: JAX reports no {self.device_type!r} device "
                f"(devices: {jax.devices()})")
        return devs[min(self.device_id, len(devs) - 1)]


class CPUPlace(Place):
    device_type = "cpu"

    def jax_device(self):
        return jax.devices("cpu")[0]


class TPUPlace(Place):
    device_type = "tpu"


# CUDA alias kept so reference-style code ports over; resolves to the accelerator.
class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


def _kind(dev) -> str:
    plat = dev.platform
    if plat == "tpu":
        return "tpu"
    if plat in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


@functools.lru_cache(None)
def _accelerator_available() -> bool:
    return any(_kind(d) == "tpu" for d in jax.devices())


_expected_place = None


def set_device(device) -> Place:
    """paddle.set_device("tpu"/"cpu"/"tpu:0").  Raises when JAX has no
    device of the kind asked for (an explicit "tpu" never lands on the CPU)."""
    global _expected_place
    if isinstance(device, Place):
        place = device
    else:
        name, _, idx = str(device).partition(":")
        idx = int(idx) if idx else 0
        name = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(name, name)
        if name == "tpu":
            place = TPUPlace(idx)
        elif name == "cpu":
            place = CPUPlace()
        else:
            place = CustomPlace(name, idx)
    place.jax_device()
    _expected_place = place
    return place


def get_device() -> str:
    p = _get_expected_place()
    return f"{p.device_type}:{p.device_id}" if p.device_type != "cpu" else "cpu"


def _get_expected_place() -> Place:
    global _expected_place
    if _expected_place is None:
        _expected_place = TPUPlace(0) if _accelerator_available() else CPUPlace()
    return _expected_place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def device_count() -> int:
    return len([d for d in jax.devices() if _kind(d) == "tpu"]) or 1
