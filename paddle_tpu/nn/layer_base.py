"""nn.Layer — module base class (≙ python/paddle/nn/layer/layers.py Layer).

Parameters/buffers/sublayers registries, state_dict with paddle-compatible
structure, forward hooks, train/eval mode, dtype/device movement.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterator

import numpy as np

from ..core import dtype as dtypes
from ..core import lazy_init
from ..core.dispatch import no_grad
from ..core.tensor import LazyParameter, Parameter, Tensor


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks: dict):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype)
        self._parameters: dict[str, Parameter] = collections.OrderedDict()
        self._buffers: dict[str, Tensor] = collections.OrderedDict()
        self._sub_layers: dict[str, Layer] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: dict[int, Callable] = collections.OrderedDict()
        self._casted_by_pure_fp16 = False
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------------------------------------------------------ registration
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                if value is None:
                    buffers.pop(name)
                else:
                    buffers[name] = value
            else:
                object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                params.pop(name)
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name, parameter):
        setattr(self, name, parameter)
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from .initializer import Constant, Initializer, XavierNormal

        dtype = dtypes.convert_dtype(dtype) if dtype else self._dtype
        init = default_initializer
        if attr is not None and getattr(attr, "initializer", None) is not None:
            init = attr.initializer
        if init is None:
            init = Constant(0.0) if is_bias else XavierNormal()
        if lazy_init.active() and isinstance(init, Initializer):
            # under paddle.LazyGuard: shape, dtype and initializer are
            # recorded and nothing is allocated until first use
            p = LazyParameter(lazy_init.defer(shape, dtype, init),
                              _internal=True)
        else:
            p = Parameter(np.zeros(shape, dtype), _internal=False)
            init(p)
        if attr is not None:
            if getattr(attr, "learning_rate", None) is not None:
                p.optimize_attr = {"learning_rate": attr.learning_rate}
            if getattr(attr, "trainable", True) is False:
                p.stop_gradient = True
            if getattr(attr, "regularizer", None) is not None:
                p.regularizer = attr.regularizer
        return p

    def shard_annotate(self, **param_axes):
        """Attach LOGICAL axis names to this layer's parameters for the
        declarative partitioner (distributed/partitioner): e.g.
        ``linear.shard_annotate(weight=("embed", "heads"))``. The rule
        table of a MeshConfig maps logical names to mesh axes at
        partition time — the model itself stays mesh-agnostic. Pass
        None to mark a parameter explicitly replicated."""
        for name, axes in param_axes.items():
            p = self._parameters.get(name)
            if p is None:
                raise KeyError(
                    f"shard_annotate: {type(self).__name__} has no "
                    f"parameter {name!r}")
            p.logical_axes = tuple(axes) if axes else None
        return self

    # ------------------------------------------------------------ iteration
    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator:
        memo = set()
        for name, layer_prefix, layer in self._walk(prefix):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in memo:
                    memo.add(id(p))
                    yield (layer_prefix + pname, p)
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        memo = set()
        for name, layer_prefix, layer in self._walk(prefix):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in memo:
                    memo.add(id(b))
                    yield (layer_prefix + bname, b)
            if not include_sublayers:
                break

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix.rstrip("."), self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}{name}"
            yield p, sub
            yield from sub.named_sublayers(prefix=p + ".")

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def _walk(self, prefix=""):
        yield ("", prefix, self)
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            yield from sub._walk(prefix=f"{prefix}{name}.")

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ------------------------------------------------------------ modes
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ------------------------------------------------------------ state dict
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        if include_sublayers:
            for name, p in self.named_parameters(prefix=structured_name_prefix):
                dest[name] = p
            for name, _, layer in self._walk(prefix=structured_name_prefix):
                for bname, b in layer._buffers.items():
                    if b is not None and bname not in layer._non_persistable_buffer_names:
                        dest[f"{_}{bname}" if _ else bname] = b
        else:
            for name, p in self._parameters.items():
                if p is not None:
                    dest[f"{structured_name_prefix}{name}"] = p
            for bname, b in self._buffers.items():
                if b is not None and bname not in self._non_persistable_buffer_names:
                    dest[f"{structured_name_prefix}{bname}"] = b
        if use_hook:
            for hook in getattr(self, "_state_dict_hooks", {}).values():
                out = hook(dest)
                if out is not None:
                    dest = out
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        # hooks (e.g. amp save_dtype's cast) return COPIES; loading must
        # target the live parameters
        own = self.state_dict(use_hook=False)
        if not use_structured_name:
            # keys are raw parameter .name attributes, not structured paths
            by_name = {getattr(p, "name", None): k for k, p in own.items()}
            state_dict = {by_name.get(k, k): v for k, v in state_dict.items()}
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                data = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                with no_grad():
                    own[k].set_value(data)
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ------------------------------------------------------------ hooks
    def register_forward_pre_hook(self, hook):
        h = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[h._id] = hook
        return h

    def register_forward_post_hook(self, hook):
        h = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[h._id] = hook
        return h

    # ------------------------------------------------------------ movement
    def to(self, device=None, dtype=None, blocking=None):
        # device is validated but placement is a no-op: this process owns one
        # logical XLA device and the runtime manages residency (`blocking`
        # likewise — transfers are async under XLA's dependency tracking)
        if device is not None:
            from ..core.device import _validate_place

            _validate_place(device)
        if dtype is not None:
            self._to_dtype(dtypes.convert_dtype(dtype))
        return self

    def _to_dtype(self, dt, only_float=True):
        with no_grad():
            for t in list(self.parameters()) + list(self.buffers()):
                if not only_float or dtypes.is_floating_point(t.dtype):
                    t._assign_raw(t._data.astype(dt))
        for l in self.sublayers(include_self=True):
            l._dtype = dt
        return self

    def astype(self, dtype):
        return self._to_dtype(dtypes.convert_dtype(dtype))

    def float(self):
        return self._to_dtype(dtypes.float32)

    def bfloat16(self):
        return self._to_dtype(dtypes.bfloat16)

    def half(self):
        return self._to_dtype(dtypes.float16)

    # ------------------------------------------------------------ call
    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            o = hook(self, inputs, outputs)
            if o is not None:
                outputs = o
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = [sub_repr[0]] + ["  " + l for l in sub_repr[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub_repr))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._name_scope

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
