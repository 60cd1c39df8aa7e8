"""partition() — any `to_static` train step shards from one MeshConfig.

The hand-wired path (distributed/meta_parallel) asks every model to
construct mp layers and scatter/gather helpers itself. Here the model
stays UNMODIFIED:

  1. `shard_model(model, config)` walks the parameters, maps each one's
     logical axes (annotation or heuristic, rules.py) to a NamedSharding
     and swaps the buffer onto the mesh — ZeRO-3 fsdp placement
     included (params live sharded along `fsdp`; GSPMD inserts the
     per-use all-gather and the grad reduce-scatter around the step).
     It also installs forward hooks on the norm layers so the residual
     stream carries explicit batch/sequence sharding constraints between
     blocks (what D9 audits, and what keeps GSPMD from replicating the
     stream).
  2. `partition(step_fn, config, model=...)` wraps the step: every
     tensor argument gets its batch (and sep-axis sequence) constraint,
     the partitioner context activates (attention routes through
     ring/ulysses when `sep > 1`), and the result compiles through the
     ordinary `to_static` machinery — donation, AOT cost capture, the
     compile watchdog and the D9-D11 auditors all see one normal
     compiled program. The mesh is recorded on the CompiledFunction
     (`_audit_mesh`) so `analysis.audit_compiled` judges D9 coverage
     without the caller re-declaring it.

A host that exposes fewer devices than the config needs is an error
(`MeshConfig.build_mesh` raises): numbers from an unsharded run say
nothing about the sharded config.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.dispatch import op_call
from ...core.flags import flag
from ...core.tensor import Tensor
from .mesh import MeshConfig
from .rules import (DEFAULT_RULES, PartitionPlan, ParamDecision,
                    infer_logical_axes, spec_for_param)

#: the active (config, mesh) while a partitioned step runs — consulted
#: by the sep-attention routing hook in nn/functional/attention.py and
#: the stream-constraint hooks shard_model installs. Set/cleared by the
#: partition() wrapper on the step-driving thread.
# thread-safe: rebound only by the single step-driving thread; readers
# on other threads only ever observe None or a complete tuple
_ACTIVE: list = []


class _activate:
    def __init__(self, config, mesh):
        self._entry = (config, mesh)

    def __enter__(self):
        _ACTIVE.append(self._entry)
        return self

    def __exit__(self, *exc):
        _ACTIVE.pop()
        return False


def active_config():
    """(MeshConfig, Mesh) of the innermost running partitioned step, or
    None — the hook surface for attention routing + stream hooks."""
    return _ACTIVE[-1] if _ACTIVE else None


# --------------------------------------------------------- constraints
def _constrain(t: Tensor, spec: P, mesh) -> Tensor:
    """Differentiable sharding annotation against an explicit mesh (the
    partitioner's analog of meta_parallel.mp_layers._constraint — that
    one resolves the fleet hcg mesh; this one is config-driven)."""
    sh = NamedSharding(mesh, spec)

    def fn(x):
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(x, sh)
        concrete = P(*(None if e is P.UNCONSTRAINED else e
                       for e in sh.spec))
        return jax.device_put(x, NamedSharding(sh.mesh, concrete))

    return op_call(fn, t, name="sharding_constraint")


def _stream_spec(config, mesh, shape) -> P | None:
    """Batch/sequence placement for one activation: dim 0 over
    batch_axes (product must divide), dim 1 over the stream sequence
    axis when it divides — every other dim UNCONSTRAINED so GSPMD
    propagation keeps filling in weights' tp placement."""
    import numpy as np

    sizes = config.axis_sizes
    entries = [P.UNCONSTRAINED] * len(shape)
    placed = False
    baxes = tuple(a for a in config.batch_axes
                  if sizes.get(a, 1) > 1)
    if baxes and shape[0] % int(np.prod([sizes[a] for a in baxes])) == 0:
        entries[0] = baxes if len(baxes) > 1 else baxes[0]
        placed = True
    seq_axis = config.seq_axis
    if len(shape) >= 2 and sizes.get(seq_axis, 1) > 1 \
            and shape[1] % sizes[seq_axis] == 0:
        entries[1] = seq_axis
        placed = True
    return P(*entries) if placed else None


def _constrain_stream(t: Tensor) -> Tensor:
    """Stream constraint under the ACTIVE partition context (the hook
    shard_model installs on norm layers); identity when inactive."""
    ctx = active_config()
    if ctx is None or not isinstance(t, Tensor) or t.ndim < 3:
        return t
    config, mesh = ctx
    spec = _stream_spec(config, mesh, tuple(t.shape))
    if spec is None:
        return t
    return _constrain(t, spec, mesh)


def _stream_hook(layer, inputs, outputs):
    """forward_post_hook placing the residual stream (norm outputs are
    the per-block stream waypoints in llama/gpt/bert)."""
    if active_config() is None:
        return None
    if isinstance(outputs, Tensor):
        return _constrain_stream(outputs)
    if isinstance(outputs, (tuple, list)):
        out = [_constrain_stream(o) if isinstance(o, Tensor) else o
               for o in outputs]
        return tuple(out) if isinstance(outputs, tuple) else out
    return None


#: layer classes whose outputs ARE the residual stream between blocks
_STREAM_LAYER_TYPES = ("RMSNorm", "LayerNorm")


# ---------------------------------------------------------- annotation
def annotate(param, axes) -> None:
    """Attach logical axis names to one parameter (the free-function
    form of nn.Layer.shard_annotate)."""
    param.logical_axes = tuple(axes) if axes else None


# --------------------------------------------------------- shard_model
def build_plan(model, config: MeshConfig, mesh=None) -> PartitionPlan:
    """Every placement decision the rule table makes for (model, config)
    WITHOUT touching a device buffer — the abstract half of
    `shard_model`. The autoplan scorer ranks candidate configs with it
    (mesh may be None: no devices are required to decide specs)."""
    network = getattr(model, "network", model)   # accept hapi Model
    plan = PartitionPlan(config, mesh)
    use_heuristics = bool(flag("FLAGS_partitioner_heuristics"))
    for name, p in network.named_parameters():
        axes = getattr(p, "logical_axes", None)
        heuristic = False
        if axes is None and use_heuristics:
            axes = infer_logical_axes(name, p.shape, config)
            heuristic = axes is not None
        d = ParamDecision(name=name, shape=tuple(p.shape),
                          logical_axes=axes, heuristic=heuristic)
        if axes is not None:
            d.spec, d.notes = spec_for_param(name, p.shape, axes, config)
        plan.add(d)
    return plan


def shard_model(model, config: MeshConfig, mesh=None) -> PartitionPlan:
    """Place every parameter of `model` per the config's rule table and
    install the stream-constraint hooks. Idempotent: re-running on a new
    config re-places (the resharding-on-restore path re-uses it)."""
    network = getattr(model, "network", model)   # accept hapi Model
    if mesh is None:
        mesh = config.build_mesh()
    plan = build_plan(model, config, mesh)
    by_name = {d.name: d for d in plan.decisions}
    for name, p in network.named_parameters():
        d = by_name[name]
        spec = P(*d.spec) if d.spec else P(*([None] * p.ndim))
        p._assign_raw(jax.device_put(p._data, NamedSharding(mesh, spec)))
    for _lname, layer in network.named_sublayers(include_self=True):
        if type(layer).__name__ in _STREAM_LAYER_TYPES \
                and not getattr(layer, "_partitioner_hooked", False):
            layer.register_forward_post_hook(_stream_hook)
            layer._partitioner_hooked = True
    return plan


def place_plan(plan: PartitionPlan, model) -> None:
    """Re-apply a plan's placements (after a checkpoint restore swapped
    host buffers into the params: set_value loses sharding)."""
    network = getattr(model, "network", model)
    by_name = {d.name: d for d in plan.decisions}
    for name, p in network.named_parameters():
        d = by_name.get(name)
        if d is None:
            continue
        spec = P(*d.spec) if d.spec else P(*([None] * p.ndim))
        p._assign_raw(jax.device_put(
            p._data, NamedSharding(plan.mesh, spec)))


# ------------------------------------------------------- sep attention
def maybe_sep_attention(query, key, value, is_causal, attn_mask=None,
                        dropout_p=0.0):
    """Context-parallel attention routing: when a partitioned step with
    `sep > 1` is active and the shapes cooperate, run the existing
    ring/ulysses kernels (meta_parallel/ring_attention.py) inside a
    shard_map over the sep axis. Returns None when the config/shape does
    not route — the caller falls through to its normal paths."""
    ctx = active_config()
    if ctx is None:
        return None
    config, mesh = ctx
    n = config.sep
    if n <= 1 or attn_mask is not None or dropout_p > 0.0:
        return None
    b, s, h, _d = query.shape
    if s % n or key.shape[1] != s:
        return None
    impl = str(flag("FLAGS_partitioner_sep_impl"))
    if impl == "ulysses" and h % n:
        impl = "ring"               # ulysses needs heads % sep == 0
    from ..meta_parallel.ring_attention import (ring_attention,
                                                ulysses_attention)
    import numpy as np
    from jax import shard_map

    sizes = config.axis_sizes
    baxes = tuple(a for a in config.batch_axes if sizes.get(a, 1) > 1)
    bentry = None
    if baxes and b % int(np.prod([sizes[a] for a in baxes])) == 0:
        bentry = baxes if len(baxes) > 1 else baxes[0]
    spec = P(bentry, "sep", None, None)
    kernel = ring_attention if impl != "ulysses" else ulysses_attention

    def f(q, k, v):
        fn = shard_map(
            lambda a, b_, c: kernel(a, b_, c, axis_name="sep",
                                    causal=is_causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)

    return op_call(f, query, key, value, name="sep_attention", n_diff=3)


# ------------------------------------------------------------ partition
def partition(fn, config: MeshConfig, *, model=None, static=True,
              donate_buffers=None, arg_specs=None, **to_static_kwargs):
    """Wrap `fn` (a train/eval step) so it runs sharded per `config`.

    model: when given, its parameters are placed first (`shard_model`)
    and the resulting plan rides the returned function as `.plan`.
    arg_specs: {tensor_leaf_position: PartitionSpec} overriding the
    default batch/sequence constraint — positions index the FLATTENED
    tensor leaves of (args, kwargs) in jit._flatten order (for plain
    positional-tensor steps that is just the arg position), identically
    on the static and eager paths.
    static: compile through to_static (default); False returns the bare
    wrapper (for eager debugging).

    Returns the compiled step with `.plan`, `.mesh`, `.config` and
    `_audit_mesh` attached (analysis.audit_compiled picks the mesh up
    automatically)."""
    mesh = config.build_mesh()
    plan = None if model is None else shard_model(model, config, mesh=mesh)

    def _arg_spec(i, shape, ndim):
        if arg_specs and i in arg_specs:
            return arg_specs[i]
        if ndim < 1:
            return None
        return _stream_spec(config, mesh, shape)

    def _leaf_shardings(leaves):
        # in-spec resolver for the to_static plumb-through: constraints
        # land on the traced arg inputs themselves (jit/api.py), so the
        # compiled program carries real in-specs without wrapper ops
        out = []
        for i, t in enumerate(leaves):
            spec = _arg_spec(i, tuple(t.shape), t.ndim)
            out.append(None if spec is None else NamedSharding(mesh, spec))
        return out

    def wrapped(*args, **kwargs):
        with _activate(config, mesh):
            return fn(*args, **kwargs)

    wrapped.__name__ = getattr(fn, "__name__", "partitioned_step")
    if static:
        from ...jit.api import to_static

        out = to_static(wrapped, donate_buffers=donate_buffers,
                        in_shardings=_leaf_shardings,
                        **to_static_kwargs)
    else:
        def eager(*args, **kwargs):
            # same leaf enumeration as the static path's in_shardings
            # resolver (jit._flatten order over (args, kwargs)), so
            # arg_specs indexes mean the same thing either way and
            # kwarg tensors are constrained too
            from ...jit.api import _flatten, _unflatten

            leaves: list = []
            struct = _flatten((args, kwargs), leaves)
            placed = []
            for i, t in enumerate(leaves):
                spec = _arg_spec(i, tuple(t.shape), t.ndim)
                placed.append(t if spec is None
                              else _constrain(t, spec, mesh))
            args, kwargs = _unflatten(struct, placed)
            with _activate(config, mesh):
                return fn(*args, **kwargs)

        eager.__name__ = wrapped.__name__
        out = eager
    out.plan = plan
    out.mesh = mesh
    out.config = config
    # analysis plumb-through: audit_compiled(cf) judges D9 against this
    # mesh without the caller re-declaring it
    out._audit_mesh = mesh
    return out
