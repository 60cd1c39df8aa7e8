"""Device/place abstraction over jax.devices().

Reference parity: paddle Places (phi/common/place.h) + DeviceManager
(paddle/phi/backends/device_manager.h:134). TPU-first: a "place" names a jax
device; default compute device is jax's default backend (TPU when present).
"""
from __future__ import annotations

import functools

import jax


class Place:
    """A device place. Wraps one jax.Device."""

    def __init__(self, device: "jax.Device | None" = None):
        self._device = device

    @property
    def jax_device(self):
        if self._device is None:
            self._device = jax.devices()[0]
        return self._device

    def is_cpu_place(self):
        return self.jax_device.platform == "cpu"

    def is_tpu_place(self):
        return self.jax_device.platform == "tpu"

    def is_gpu_place(self):  # parity shim; never true on this stack
        return self.jax_device.platform == "gpu"

    def __eq__(self, other):
        return isinstance(other, Place) and self.jax_device == other.jax_device

    def __hash__(self):
        return hash(self.jax_device)

    def __repr__(self):
        d = self.jax_device
        return f"Place({d.platform}:{d.id})"


class CPUPlace(Place):
    def __init__(self, idx: int = 0):
        devs = [d for d in jax.devices("cpu")] if _has_platform("cpu") else []
        super().__init__(devs[idx] if devs else None)


class TPUPlace(Place):
    def __init__(self, idx: int = 0):
        devs = _accelerators()
        super().__init__(devs[idx] if idx < len(devs) else None)


# Paddle calls its accelerator place CUDAPlace; alias for API parity.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CustomPlace = TPUPlace


@functools.lru_cache(maxsize=None)
def _has_platform(platform: str) -> bool:
    try:
        return len(jax.devices(platform)) > 0
    except RuntimeError:
        return False


def _accelerators():
    for p in ("tpu", "gpu"):
        if _has_platform(p):
            return jax.devices(p)
    return jax.devices()


_current_device: Place | None = None


def get_device() -> str:
    d = (_current_device or Place()).jax_device
    return f"{d.platform}:{d.id}"


def set_device(device: str) -> Place:
    global _current_device
    if isinstance(device, Place):
        _current_device = device
        return _current_device
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("cpu",):
        _current_device = CPUPlace(idx)
    else:
        _current_device = TPUPlace(idx)
    return _current_device


def current_place() -> Place:
    return _current_device or Place()


def _validate_place(device) -> None:
    """Accept a Place or a device string like 'cpu'/'gpu:0'/'tpu:0'; reject
    anything unparseable (used by Layer.to / Tensor.to device args)."""
    if isinstance(device, Place):
        return
    if not isinstance(device, str):
        raise ValueError(f"unsupported device spec {device!r}")
    name = device.split(":")[0]
    if name not in ("cpu", "gpu", "tpu", "xpu", "npu", "custom_device"):
        raise ValueError(f"unsupported device {device!r}")


def device_count() -> int:
    return len(_accelerators())


def is_compiled_with_cuda() -> bool:  # parity shim
    return False


def is_compiled_with_tpu() -> bool:
    return _has_platform("tpu")


class CUDAPinnedPlace(Place):
    """≙ paddle CUDAPinnedPlace (page-locked host staging memory). Host↔TPU
    transfers here always stage through pinned-equivalent buffers managed by
    the XLA runtime, so this place is informational (host-device backed)."""

    def __init__(self):
        super().__init__(None)

    @property
    def jax_device(self):
        import jax as _jax

        if self._device is None:
            try:
                self._device = _jax.devices("cpu")[0]
            except RuntimeError:
                self._device = _jax.devices()[0]
        return self._device

    def __repr__(self):
        return "Place(cuda_pinned)"
