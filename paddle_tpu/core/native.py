"""Native (C++) runtime component loader.

The hot runtime pieces that are C++ in the reference stay C++ here
(SURVEY §2.1): csrc/*.cpp are compiled with g++ on first use into cached
shared objects and bound via ctypes (pybind11 isn't vendored in this
image). Every native component has a pure-Python fallback — load() returns
None when the toolchain is unavailable and callers degrade gracefully; the
reason is warned once and kept in `build_errors` (chip_smoke.py prints it),
so a machine without a compiler says so instead of just running slower.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

from . import lockdep

_CSRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc")
_BUILD = os.path.join(_CSRC, "_build")
# build-under-lock is deliberate (serializes concurrent g++ builds onto
# the atomic-rename cache), so this lock is NOT marked hot
_lock = lockdep.make_lock("core.native._lock")
_cache: dict[str, object] = {}    # guarded-by: _lock
#: component -> why its build or load failed here
build_errors: dict[str, str] = {}  # guarded-by: _lock


def _failed(name: str, e: Exception) -> None:  # requires-lock: _lock
    import warnings

    # a failed g++ run carries its last words in CalledProcessError.stderr
    tail = (getattr(e, "stderr", None) or b"").decode(
        errors="replace").strip().splitlines()[-1:]
    build_errors[name] = f"{type(e).__name__}: {e}" + \
        (f" ({tail[0]})" if tail else "")
    warnings.warn(f"native component '{name}' unavailable — "
                  f"{build_errors[name]}; using the pure-Python fallback")


def _compile(name: str) -> str | None:  # requires-lock: _lock
    src = os.path.join(_CSRC, f"{name}.cpp")
    if not os.path.exists(src):
        return None
    with open(src, "rb") as f:
        tag = hashlib.sha1(f.read()).hexdigest()[:12]
    so = os.path.join(_BUILD, f"{name}-{tag}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD, exist_ok=True)
    tmp = so + f".tmp{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)  # atomic: concurrent builders race safely
        return so
    except (subprocess.SubprocessError, OSError) as e:
        _failed(name, e)
        return None


def load(name: str):
    """ctypes.CDLL for csrc/<name>.cpp, or None (no toolchain / bad build)."""
    with _lock:
        if name in _cache:
            lib = _cache[name]
            return lib if lib is not None else None
        so = _compile(name)
        lib = None
        if so is not None:
            try:
                lib = ctypes.CDLL(so)
            except OSError as e:
                _failed(name, e)
        _cache[name] = lib
        return lib


def ring_lib():
    lib = load("ring_queue")
    if lib is not None and not getattr(lib, "_typed", False):
        u64, i64, i32 = ctypes.c_uint64, ctypes.c_longlong, ctypes.c_int
        p = ctypes.c_void_p
        lib.ring_header_bytes.restype = u64
        lib.ring_init.argtypes = [p, u64]
        lib.ring_push.argtypes = [p, ctypes.c_char_p, u64]
        lib.ring_push.restype = i32
        lib.ring_next_size.argtypes = [p]
        lib.ring_next_size.restype = i64
        lib.ring_pop.argtypes = [p, ctypes.c_char_p, u64]
        lib.ring_pop.restype = i64
        lib._typed = True
    return lib


def tracer_lib():
    lib = load("host_tracer")
    if lib is not None and not getattr(lib, "_typed", False):
        u64, u32 = ctypes.c_uint64, ctypes.c_uint32
        lib.tracer_intern.argtypes = [ctypes.c_char_p]
        lib.tracer_intern.restype = u32
        lib.tracer_name.argtypes = [u32]
        lib.tracer_name.restype = ctypes.c_char_p
        lib.tracer_record.argtypes = [u32, u64, u64, u32]
        lib.tracer_count.restype = u64
        lib.tracer_drain.argtypes = [ctypes.POINTER(u32), ctypes.POINTER(u32),
                                     ctypes.POINTER(u64), ctypes.POINTER(u64),
                                     u64]
        lib.tracer_drain.restype = u64
        lib._typed = True
    return lib
