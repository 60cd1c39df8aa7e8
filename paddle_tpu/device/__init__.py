"""paddle.device namespace (≙ python/paddle/device/__init__.py subset).

Device management rides jax.devices(); cuda/xpu sub-namespaces are honest
shims (is_available() -> False) so capability probes in ported code work.
"""
from __future__ import annotations

from ..core.device import get_device, set_device  # noqa: F401


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return get_available_device()


def device_count():
    import jax

    return jax.device_count()


class _UnavailableNamespace:
    def __init__(self, name):
        self._name = name

    def is_available(self) -> bool:
        return False

    def device_count(self) -> int:
        return 0

    def __getattr__(self, item):
        # AttributeError so hasattr/getattr capability probes return False
        # instead of crashing
        raise AttributeError(
            f"paddle.device.{self._name}.{item}: {self._name} is not part of "
            "the TPU backend (devices are TPU chips via jax.devices())")


cuda = _UnavailableNamespace("cuda")
xpu = _UnavailableNamespace("xpu")

__all__ = ["set_device", "get_device", "get_all_device_type",
           "get_available_device", "get_available_custom_device",
           "device_count", "cuda", "xpu"]


# ------------------------------------------------------- surface completion
# (≙ reference device/__init__.py __all__)
from ..core.device import (  # noqa: F401,E402
    XPUPlace,
    is_compiled_with_cuda,
)
from ..base.core import (  # noqa: F401,E402
    is_compiled_with_cinn,
    is_compiled_with_rocm,
    is_compiled_with_xpu,
    is_compiled_with_ipu,
)


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type=None):
    """The 'custom device' of this build IS the TPU plugin."""
    import jax

    platforms = {d.platform for d in jax.devices()}
    if device_type is None:
        return bool(platforms - {"cpu", "gpu"})
    return device_type in platforms


def get_all_custom_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()} - {"cpu", "gpu"})


def get_cudnn_version():
    return None  # no cuDNN in the TPU-native build


class IPUPlace:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU backends are not part of this build")


class Stream:
    """≙ device.Stream. XLA owns stream scheduling; the object records its
    device and supports the synchronize/wait API shape (each op-submission
    order is already program order under jit)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize(self.device)

    def wait_event(self, event):
        return None

    def wait_stream(self, stream):
        return None

    def record_event(self, event=None):
        return event or Event()


class Event:
    """≙ device.Event (CUDA events). XLA's dataflow ordering subsumes
    event dependencies; record/query/synchronize keep the API shape."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device

    def record(self, stream=None):
        return None

    def query(self):
        return True

    def synchronize(self):
        return None


_CURRENT_STREAM = Stream()


def current_stream(device=None):
    return _CURRENT_STREAM


def set_stream(stream):
    global _CURRENT_STREAM
    prev, _CURRENT_STREAM = _CURRENT_STREAM, stream
    return prev


class stream_guard:
    def __init__(self, stream):
        self._stream = stream

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def synchronize(device=None):
    """Block until all submitted device work completes (≙
    device.synchronize): XLA equivalent is waiting on the live arrays."""
    import jax

    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        except Exception:
            pass


class _PlatformNS:
    """cuda/xpu/npu/dcu/gpu capability namespaces — honest probes."""

    def __init__(self, platform, available=False):
        self._platform = platform
        self._available = available

    def is_available(self):
        return self._available

    def device_count(self):
        import jax

        return jax.device_count() if self._available else 0

    def synchronize(self, device=None):
        return synchronize(device)

    def current_stream(self, device=None):
        return current_stream(device)

    def stream_guard(self, stream):
        return stream_guard(stream)

    def get_device_properties(self, device=None):
        import jax

        d = jax.devices()[0]
        return type("DeviceProperties", (), {
            "name": getattr(d, "device_kind", d.platform),
            "major": 0, "minor": 0, "total_memory": 0,
            "multi_processor_count": jax.device_count()})()


gpu = _PlatformNS("gpu")
npu = _PlatformNS("npu")
dcu = _PlatformNS("dcu")


__all__ = [n for n in dir() if not n.startswith("_")]
