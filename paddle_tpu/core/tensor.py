"""Eager Tensor: paddle.Tensor semantics over immutable jax.Array buffers.

Reference parity: the eager Tensor (paddle/phi/api/include/tensor.h:82 +
pybind eager_method.cc). Mutability (add_, set_value, optimizer updates) is
buffer-swap: ._data is replaced, never written through — old autograd
residuals keep referencing the old immutable buffers, so in-place updates
under no_grad are always safe. ``stop_gradient`` defaults True like paddle;
Parameters default False.

Most operator methods are attached by paddle_tpu.ops at import time (the
analog of generated pybind tensor methods).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .device import Place, current_place
from .dispatch import current_trace, no_grad
from .lazy_init import LazyInit


class Tensor:
    __slots__ = (
        "_data_buf",
        "stop_gradient",
        "_grad",
        "_node",
        "_out_idx",
        "name",
        "persistable",
        "_retain_grads",
        "_hooks",
        "_dist_attr",
        "_buf_version",
        "_seq",
        "__weakref__",
        "__dict__",
    )

    _iid = 0
    # globally-monotonic buffer-state counter: every construction AND every
    # buffer swap draws a fresh value, so no two buffer states ever share a
    # version — unlike id(), which CPython reuses after free (caches keying
    # on id() alone could silently serve stale weights). The bump lives in
    # the `_data` property setter so EVERY buffer swap in the codebase
    # (to_static _finish, checkpoint load, optimizer lr writes, ...) bumps
    # it — not just the _assign_raw funnel.
    _next_buf_version = 0

    @property
    def _data(self):
        return self._data_buf

    @_data.setter
    def _data(self, value):
        self._data_buf = value
        Tensor._next_buf_version += 1
        self._buf_version = Tensor._next_buf_version

    def __init__(self, data, dtype=None, place=None, stop_gradient=True, _internal=False):
        if _internal:
            self._data = data
        else:
            self._data = _to_jax(data, dtype, place)
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node = None
        self._out_idx = 0
        Tensor._iid += 1
        # creation-order stamp: dy2static uses it to tell tensors that
        # existed BEFORE a converted branch ran (external reads to thread
        # as op operands) from intermediates the branch itself created
        self._seq = Tensor._iid
        self.name = f"tensor_{Tensor._iid}"
        self.persistable = False
        self._retain_grads = False
        self._hooks = []
        self._dist_attr = None

    # ------------------------------------------------------------ properties
    # shape and dtype are read off what is held, not through `_data`: a
    # LazyGuard parameter holds a `LazyInit` (shape, dtype, no buffer)
    # and is not to make its buffer to answer them
    @property
    def shape(self):
        return list(self._data_buf.shape)

    @property
    def ndim(self):
        return len(self._data_buf.shape)

    dim = ndim

    @property
    def size(self):
        shape = self._data_buf.shape
        return int(np.prod(shape)) if shape else 1

    @property
    def dtype(self):
        return np.dtype(self._data_buf.dtype)

    @property
    def place(self):
        devs = getattr(self._data, "devices", None)
        if devs is not None and not isinstance(self._data, jax.core.Tracer):
            try:
                ds = self._data.devices()
            except RuntimeError:  # buffer donated/deleted by a jitted step
                ds = None
            if ds:
                return Place(next(iter(ds)))
        return current_place()

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        self._grad = value

    @property
    def is_leaf(self):
        return self._node is None

    # ------------------------------------------------- dist tensor surface
    @property
    def placements(self):
        return list(self._dist_attr.placements) if self._dist_attr is not None else None

    @property
    def process_mesh(self):
        return self._dist_attr.process_mesh if self._dist_attr is not None else None

    def is_dist(self):
        return self._dist_attr is not None

    def retain_grads(self):
        self._retain_grads = True

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    # ------------------------------------------------------------ conversion
    def numpy(self):
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def detach(self) -> "Tensor":
        t = Tensor(self._data, _internal=True, stop_gradient=True)
        return t

    def clone(self) -> "Tensor":
        from ..ops import assign

        return assign(self)

    def numel(self):
        return self.size

    def element_size(self):
        return self.dtype.itemsize

    # ------------------------------------------------------------ autograd
    def backward(self, grad_tensor=None, retain_graph=False):
        from .engine import run_backward

        run_backward(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            # IN-PLACE zero (buffer swap on the existing grad Tensor): under
            # to_static the write registers as a program output, so compiled
            # programs actually reset the accumulation buffer (gradient
            # merge's apply program depends on this; `= None` is a python-
            # level effect no compiled program can replay)
            self._grad._assign_raw(jnp.zeros_like(self._grad._data))
        else:
            self._grad = None

    # ------------------------------------------------------------ mutation
    def _assign_raw(self, value):
        """Swap the underlying buffer, notifying any active trace (mutation ⇒
        compiled-program output)."""
        tr = current_trace()
        if tr is not None:
            tr.on_read(self)
            tr.on_mutate(self)
        self._data = value

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        else:
            value = _to_jax(value, self.dtype, None)
        # shape and dtype of what is held (a LazyGuard parameter holds no
        # buffer yet, and is not to make one only to be overwritten)
        held = self._data_buf
        if tuple(value.shape) != tuple(held.shape):
            value = jnp.broadcast_to(value, held.shape)
        if value.dtype != held.dtype:
            value = value.astype(held.dtype)
        self._assign_raw(value)
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def _in_place(self, fn, *others):
        """Shared driver for add_/scale_/zero_ etc. (buffer swap)."""
        datas = [o._data if isinstance(o, Tensor) else o for o in others]
        self._assign_raw(fn(self._data, *datas))
        return self

    def zero_(self):
        return self._in_place(lambda x: jnp.zeros_like(x))

    def fill_(self, value):
        return self._in_place(lambda x: jnp.full_like(x, value))

    # ------------------------------------------------------------ misc parity
    def to(self, *args, **kwargs):
        from ..ops import _tensor_to

        return _tensor_to(self, *args, **kwargs)

    def cuda(self, *a, **k):  # parity shim: accelerator == TPU
        return self.to("tpu")

    def cpu(self):
        return self.to("cpu")

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    @property
    def T(self):
        from ..ops import transpose

        perm = list(range(self.ndim))[::-1]
        return transpose(self, perm)

    @property
    def mT(self):
        from ..ops import transpose

        perm = list(range(self.ndim))
        perm[-2], perm[-1] = perm[-1], perm[-2]
        return transpose(self, perm)

    def astype(self, dtype):
        from ..ops import cast

        return cast(self, dtype)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return bool(self._data)

    def __int__(self):
        return int(self._data)

    def __float__(self):
        return float(self._data)

    def __index__(self):
        return int(self._data)

    def __hash__(self):
        return id(self)

    def __repr__(self):
        prefix = "Parameter" if isinstance(self, Parameter) else "Tensor"
        if isinstance(self._data, jax.core.Tracer):
            return f"{prefix}(shape={self.shape}, dtype={self.dtype.name}, <traced>)"
        return (
            f"{prefix}(shape={self.shape}, dtype={self.dtype.name}, "
            f"stop_gradient={self.stop_gradient},\n{np.asarray(self._data)})"
        )

    # dict-style state for pickling via numpy
    def __getstate__(self):
        return {
            "data": self.numpy(),
            "stop_gradient": self.stop_gradient,
            "name": self.name,
        }

    def __setstate__(self, state):
        Tensor.__init__(self, state["data"], stop_gradient=state["stop_gradient"])
        self.name = state["name"]


class Parameter(Tensor):
    """Trainable tensor (≙ paddle EagerParamBase). stop_gradient=False."""

    def __init__(self, data, dtype=None, trainable=True, _internal=False):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, _internal=_internal)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v


class LazyParameter(Parameter):
    """A parameter made under `paddle.LazyGuard`: it holds a `LazyInit`
    where its buffer would be. Shape and dtype are answered from that;
    the first read of the data runs the initializer, an assignment
    replaces it; either way the object becomes a plain `Parameter` (same
    layout), so that only a parameter still without data pays for the
    check."""

    __slots__ = ()

    @property
    def _data(self):
        buf = self._data_buf
        if type(buf) is LazyInit:
            buf = buf.materialize()
            self._data = buf
        return buf

    @_data.setter
    def _data(self, value):
        Tensor._data.fset(self, value)
        if type(value) is not LazyInit:
            self.__class__ = Parameter


def _to_jax(data, dtype=None, place=None):
    dtype = dtypes.convert_dtype(dtype)
    if isinstance(data, Tensor):
        arr = data._data
        return arr.astype(dtype) if dtype is not None and arr.dtype != dtype else arr
    if isinstance(data, (jax.Array, jax.core.Tracer)):
        return data.astype(dtype) if dtype is not None and data.dtype != dtype else data
    arr = np.asarray(data)
    if dtype is None:
        # paddle default: python floats -> default dtype, ints -> int64
        if arr.dtype == np.float64:
            dtype = dtypes.get_default_dtype()
    dev = place.jax_device if isinstance(place, Place) else None
    out = jnp.asarray(arr, dtype=dtype)
    if dev is not None:
        out = jax.device_put(out, dev)
    return out


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
