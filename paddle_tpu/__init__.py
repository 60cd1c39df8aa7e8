"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built from scratch on JAX/XLA/Pallas/pjit.

Blueprint: SURVEY.md (structural analysis of the reference at
/root/reference). The engine is XLA: ops are jax compositions + Pallas
kernels, autograd is a define-by-run tape over jax.vjp closures, to_static
compiles whole train steps with jax.jit, and distributed training is
jax.sharding meshes + XLA collectives over ICI/DCN.
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# Full dtype surface (int64 labels, float64 CPU math — paddle defaults int64
# for integer tensors). Framework default float dtype stays float32; creation
# ops always pass explicit dtypes, so x64 never leaks into TPU programs.
_jax.config.update("jax_enable_x64", True)

from .core import dtype as _dtype_mod
from .core.dtype import (
    bool_ as bool,  # noqa: A001
    uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64,
    complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype,
)
from .core.device import (
    CPUPlace, CUDAPlace, TPUPlace, XPUPlace, CustomPlace, Place,
    get_device, set_device, device_count, is_compiled_with_cuda,
    is_compiled_with_tpu,
)
from .core.flags import set_flags, get_flags
from .core.tensor import Tensor, to_tensor
from .core.dispatch import no_grad, enable_grad, set_grad_enabled
from .core.rng import seed, get_rng_state, set_rng_state
from .core.engine import grad

from .ops import *  # noqa: F401,F403 — the ~300 tensor ops at top level
from .ops import _tensor_to  # noqa: F401

from . import autograd
from . import nn
from . import optimizer
from . import io
from . import amp
from . import jit
from . import metric
from . import vision
from . import distributed
# NOTE: `from .ops import *` above leaked the ops.linalg SUBMODULE as the
# `linalg` attribute, which makes `from . import linalg` short-circuit
# (the import system skips the submodule load when the attr exists) —
# force-load the real top-level namespace module instead.
import importlib as _importlib

linalg = _importlib.import_module(".linalg", __name__)
from . import incubate
from . import profiler
from . import hapi
from .hapi import Model
from . import distribution
from . import quantization
from . import sparse
from . import static
from . import device
from . import text
from . import inference
from . import serving
from . import ckpt
from . import audio
from . import onnx
from . import utils
from . import fft
from . import signal
from . import geometric
from . import obs
from . import version
from . import sysconfig
from . import hub
from . import regularizer
from . import callbacks
from . import reader
from . import framework
from . import base
from . import tensor
from . import dataset
from . import tensorrt
from . import cost_model
from . import decomposition
from .batch import batch
from .framework_io import save, load

# paddle.framework parity namespace bits
from .core.tensor import Parameter  # noqa

import numpy as _np


def disable_static(place=None):  # dygraph is the only mode; parity shim
    return None


def enable_static():
    raise NotImplementedError(
        "paddle_tpu is dygraph-first; use paddle_tpu.jit.to_static for compiled graphs"
    )


def in_dynamic_mode():
    return True


def is_grad_enabled():
    from .core.dispatch import grad_enabled

    return grad_enabled()


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _s

    return _s(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.summary import flops as _f

    return _f(net, input_size, custom_ops, print_detail)

# ---------------------------------------------------- top-level export closure
# (≙ reference python/paddle/__init__.py long tail)
import math as _math

e = _math.e
pi = _math.pi
inf = float("inf")
nan = float("nan")
newaxis = None  # paddle.newaxis ≙ np.newaxis

from .nn import ParamAttr  # noqa: E402
from .distributed.meta_parallel import DataParallel  # noqa: E402
from .core.device import CUDAPinnedPlace  # noqa: E402
dtype = _np.dtype  # paddle.dtype: dtype objects ARE numpy dtypes here
pstring = "pstring"  # string-tensor dtype tag (no string tensors yet)


class LazyGuard:
    """≙ paddle.LazyGuard (lazy parameter materialization). A parameter
    created inside the guard (`Layer.create_parameter`) has a shape, a
    dtype and its initializer, and no buffer: it is allocated when its
    data is first read, with the values an eager build of the same seed
    gives, or never, if weights are assigned to it first
    (`p._data = w`, `set_state_dict`). Guards nest."""

    def __enter__(self):
        from .core import lazy_init

        lazy_init.enter()
        return self

    def __exit__(self, *exc):
        from .core import lazy_init

        lazy_init.leave()
        return False


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """≙ paddle.set_printoptions → numpy print options (Tensor repr prints
    via numpy)."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def to_dlpack(x):
    """≙ paddle.utils.dlpack.to_dlpack: returns an object implementing the
    DLPack protocol (the jax.Array itself — zero copy; modern DLPack passes
    protocol objects, not raw capsules)."""
    return x._data


def from_dlpack(ext):
    """Accepts any object with __dlpack__ (torch/numpy/jax arrays, or the
    product of to_dlpack)."""
    import jax.numpy as _jnp

    arr = _jnp.from_dlpack(ext)
    return Tensor(arr, _internal=True, stop_gradient=True)


def get_cuda_rng_state():
    """CUDA alias of the device RNG state (the TPU key chain)."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


def disable_signal_handler():
    """≙ paddle.disable_signal_handler: the XLA runtime installs no python
    signal handlers — nothing to disable."""
    return None


def tolist(x):
    return x.tolist()  # Tensor.tolist is defined in core/tensor.py


def _cuda_lib_version_stub(_name):
    def version():
        return 0  # no CUDA libraries in the TPU-native build

    version.__name__ = _name
    version.__doc__ = f"{_name} version probe — CUDA-free build returns 0."
    return version


cublas = _cuda_lib_version_stub("cublas")
cudnn = _cuda_lib_version_stub("cudnn")
cufft = _cuda_lib_version_stub("cufft")
curand = _cuda_lib_version_stub("curand")
cusolver = _cuda_lib_version_stub("cusolver")
cusparse = _cuda_lib_version_stub("cusparse")
cuda_runtime = _cuda_lib_version_stub("cuda_runtime")
cuda_nvrtc = _cuda_lib_version_stub("cuda_nvrtc")
nvjitlink = _cuda_lib_version_stub("nvjitlink")

