"""Op dispatch: the single funnel every eager op goes through.

Reference parity: this is the TPU-native analog of the generated
`<op>_ad_func` + KernelFactory dispatch chain
(/root/reference/paddle/fluid/eager/auto_code_generator/generator/eager_gen.py,
/root/reference/paddle/phi/core/kernel_factory.h:326). Instead of a kernel
registry keyed by (name, backend, layout, dtype), every op is a pure jax
function; XLA is the kernel zoo. Autograd recording happens here: when any
floating input requires grad, the forward runs through jax.vjp and the
returned vjp closure (holding residuals on-device) becomes the GradNode —
the analog of TensorWrapper-saved inputs
(/root/reference/paddle/fluid/eager/tensor_wrapper.h:39).

The same funnel implements `to_static` capture: an active TraceContext is
notified of every concrete-valued Tensor read (a "capture", i.e. a free
variable of the traced program: parameters, optimizer state, RNG key) and
every in-place mutation (a program output to write back).
"""
from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.extend import core as jex_core

from . import dtype as dtypes
from . import flags as _flags_mod
from .flags import flag
from .lazy import LazyData as _LazyData
from .lazy import current_lazy as _current_lazy
from .lazy_init import LazyInit


class _HotFlags:
    """Per-generation snapshot of the flags the dispatch hot loop reads
    4-5 times per op; refreshed whenever set_flags bumps the generation."""

    __slots__ = ("gen", "use_cache", "defer_vjp", "benchmark",
                 "check_nan_inf", "double_grad")

    def __init__(self):
        # slots pre-populated so a concurrent reader that races a refresh
        # never sees unset attributes; gen starts stale so the first
        # _hot_flags() call refreshes
        self.gen = -1
        self.use_cache = self.defer_vjp = self.double_grad = True
        self.benchmark = self.check_nan_inf = False

    def refresh(self):
        # read the generation FIRST and publish it LAST: if set_flags runs
        # mid-refresh, gen stays stale and the next reader re-refreshes
        gen = _flags_mod.generation
        self.use_cache = flag("FLAGS_use_compiled_eager")
        self.defer_vjp = flag("FLAGS_eager_defer_vjp")
        self.benchmark = flag("FLAGS_benchmark")
        self.check_nan_inf = flag("FLAGS_check_nan_inf")
        self.double_grad = flag("FLAGS_enable_double_grad")
        self.gen = gen
        return self


_HOT_FLAGS = _HotFlags()


def _hot_flags():
    hf = _HOT_FLAGS
    if hf.gen != _flags_mod.generation:
        hf.refresh()
    return hf

_tls = threading.local()


# ---------------------------------------------------------------- grad mode
def grad_enabled() -> bool:
    return getattr(_tls, "grad_enabled", True)


@contextlib.contextmanager
def set_grad_enabled(mode: bool):
    old = grad_enabled()
    _tls.grad_enabled = mode
    try:
        yield
    finally:
        _tls.grad_enabled = old


class no_grad(contextlib.ContextDecorator):
    """paddle.no_grad: usable as context manager and decorator."""

    def __enter__(self):
        self._old = grad_enabled()
        _tls.grad_enabled = False
        return self

    def __exit__(self, *exc):
        _tls.grad_enabled = self._old
        return False


class enable_grad(contextlib.ContextDecorator):
    def __enter__(self):
        self._old = grad_enabled()
        _tls.grad_enabled = True
        return self

    def __exit__(self, *exc):
        _tls.grad_enabled = self._old
        return False


# ---------------------------------------------------------------- tracing
class TraceContext:
    """Active while paddle_tpu.jit.to_static discovers/retraces a program.

    phase == "discover": eager run; concrete Tensors read by ops are recorded
    as program inputs, in-place writes as program outputs.
    phase == "trace": running under jax.jit; captured Tensors carry tracers in
    ._data (bound by the jit wrapper), so ops Just Work.
    """

    def __init__(self, phase: str, borrowed: bool = False):
        self.phase = phase
        self.captures: dict[int, Any] = {}  # id(tensor) -> tensor (ordered)
        self.mutated: dict[int, Any] = {}
        # borrowed=True: this trace reuses a discovery from a DIFFERENT
        # input signature (to_static share_discovery); concrete tensor reads
        # here mean the borrowed capture set missed a tensor — it would be
        # silently baked in as a constant, so record for a warning
        self.borrowed = borrowed
        self.folded: dict[int, Any] = {}

    def on_read(self, tensor):
        if isinstance(tensor._data, jax.core.Tracer):
            return
        if self.phase == "discover":
            self.captures.setdefault(id(tensor), tensor)
        elif self.borrowed:
            self.folded.setdefault(id(tensor), tensor)

    def on_mutate(self, tensor):
        self.mutated.setdefault(id(tensor), tensor)


def current_trace() -> TraceContext | None:
    return getattr(_tls, "trace_ctx", None)


@contextlib.contextmanager
def trace_context(ctx: TraceContext):
    old = current_trace()
    _tls.trace_ctx = ctx
    try:
        yield ctx
    finally:
        _tls.trace_ctx = old


# ---------------------------------------------------------------- autograd tape
class GradNode:
    """One recorded op on the tape (≙ GradNodeBase, grad_node_info.h:197)."""

    __slots__ = ("vjp_fn", "inputs", "out_avals", "single_out", "name",
                 "diff_idx", "ctx", "__weakref__")

    def __init__(self, vjp_fn, inputs, out_avals, single_out, name,
                 diff_idx=None, ctx=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs  # list[Tensor] — differentiable inputs, positional
        self.out_avals = out_avals  # list[(shape, dtype)]
        self.single_out = single_out
        self.name = name
        # original arg positions of `inputs` (zero-bubble dW/dX split rules
        # need to know which operand is the activation vs the weight)
        self.diff_idx = diff_idx
        # (fn, datas): enough to RE-derive this op's vjp as a fresh traced
        # computation — how create_graph=True records backward ops onto the
        # tape (≙ the reference generating grad-of-grad GradNodes,
        # eager/backward.cc double-grad path)
        self.ctx = ctx


_amp_dtype_for = None


def _complexify_vjp(vjp_fn, single_out):
    """Convention bridge: JAX's complex cotangents/grads are the conjugate of
    Paddle's (reference AbsGradFunctor<complex>, funcs/complex_functors.h:158,
    computes dout·x/|x|, i.e. the non-holomorphic ∂L/∂conj(z) convention).
    The tape carries Paddle-convention grads, so conj on the way into
    jax.vjp and conj complex grads on the way out. Only installed when a
    complex dtype is involved — the real-dtype hot path is untouched."""
    import jax.numpy as jnp

    def wrapped(cot):
        if single_out:
            c = jnp.conj(cot) if jnp.iscomplexobj(cot) else cot
        else:
            c = tuple(jnp.conj(x) if jnp.iscomplexobj(x) else x for x in cot)
        grads = vjp_fn(c)
        return tuple(
            jnp.conj(g) if hasattr(g, "dtype") and jnp.iscomplexobj(g) else g
            for g in grads)

    return wrapped


_COMPLEX_DTYPE_MEMO: dict = {}


def _is_complex_dtype(dt) -> bool:
    r = _COMPLEX_DTYPE_MEMO.get(dt)
    if r is None:
        r = np.issubdtype(np.dtype(dt), np.complexfloating)
        _COMPLEX_DTYPE_MEMO[dt] = r
    return r


def _needs_complex_bridge(avals, datas, diff_idx):
    for _, dt in avals:
        if _is_complex_dtype(dt):
            return True
    for i in diff_idx:
        d = datas[i]
        dt = getattr(d, "dtype", None)
        if dt is not None and _is_complex_dtype(dt):
            return True
    return False


#: raw jax/numpy dtypes with meaningful VJPs (floats + complex — fft ops
#: have complex VJPs); frozen set of the dtype OBJECTS jax actually attaches
#: to arrays, so the hot diff-scan avoids np.dtype construction
_DIFF_DTYPES = frozenset(
    np.dtype(n) for n in ("float16", "bfloat16", "float32", "float64",
                          "float8_e4m3fn", "float8_e5m2",
                          "complex64", "complex128"))

_TENSOR_CLS = None


def _is_tensor(x) -> bool:
    # the Tensor class is bound lazily ONCE: an in-function import costs a
    # sys.modules lookup per call, and this predicate runs for every operand
    # of every eager op (the SURVEY §7-1 hot loop)
    global _TENSOR_CLS
    if _TENSOR_CLS is None:
        from .tensor import Tensor as _TENSOR_CLS  # noqa: F811
    return isinstance(x, _TENSOR_CLS)


# ------------------------------------------------- eager executable cache
# TPU-native analog of KernelFactory::SelectKernelOrThrowError
# (/root/reference/paddle/phi/core/kernel_factory.h:326) + the generated C++
# ad_funcs: instead of a registry of precompiled kernels, each (op, static
# operands, diff-mask, amp-target) gets a jitted executable pair — forward
# returns (out, vjp Partial), and vjp application itself runs through one
# shared jitted trampoline so backward is compiled too. jax.jit's internal
# C++ dispatch handles shape/dtype keying within an entry, so re-tracing
# happens only on genuinely new signatures.
_SIMPLE_TYPES = (int, float, bool, str, bytes, complex, type(None))
_UNCACHABLE = object()  # sentinel: this key can never be compiled

_eager_cache: dict = {}
_eager_hits = 0
_eager_misses = 0
_vjp_apply_jit = None

#: "fn inspects concrete values under tracing" — shared by the eager cache
#: (permanently uncachable key) and to_static (SOT-style graph break).
GRAPH_BREAK_ERRORS = (
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerBoolConversionError,
    jax.errors.TracerIntegerConversionError,
    jax.errors.ConcretizationTypeError,
)


def _freeze(v):
    """Hashable cache-key fragment for a static operand, or _UNCACHABLE."""
    if isinstance(v, _SIMPLE_TYPES):
        return (type(v).__name__, v)
    if isinstance(v, (tuple, list)):
        parts = tuple(_freeze(x) for x in v)
        if any(p is _UNCACHABLE for p in parts):
            return _UNCACHABLE
        return (type(v).__name__, parts)
    if isinstance(v, np.dtype) or (isinstance(v, type) and issubclass(v, np.generic)):
        return ("dtype", np.dtype(v).name)
    if callable(v):
        return _fn_key(v)
    return _UNCACHABLE


def _fn_key(fn):
    """Identity key for the op function. Keyed by code object (stable across
    per-call re-creation of nested defs — ops like rope build a fresh inner
    fn each call) plus frozen defaults/closure cells. Unhashable cells ⇒
    uncachable."""
    import functools

    if isinstance(fn, functools.partial):
        base = _fn_key(fn.func)
        args = tuple(_freeze(a) for a in fn.args)
        kws = tuple(sorted((k, _freeze(v)) for k, v in fn.keywords.items()))
        if base is _UNCACHABLE or any(
            p is _UNCACHABLE for p in args
        ) or any(v is _UNCACHABLE for _, v in kws):
            return _UNCACHABLE
        return ("partial", base, args, kws)

    code = getattr(fn, "__code__", None)
    if code is None:  # builtins / C-level callables: stable module objects
        try:
            hash(fn)
        except TypeError:
            return _UNCACHABLE
        return fn

    defaults = getattr(fn, "__defaults__", None) or ()
    frozen_defaults = tuple(_freeze(d) for d in defaults)
    if any(d is _UNCACHABLE for d in frozen_defaults):
        return _UNCACHABLE

    vals = []
    for c in fn.__closure__ or ():
        try:
            frozen = _freeze(c.cell_contents)
        except ValueError:  # empty cell
            return _UNCACHABLE
        if frozen is _UNCACHABLE:
            return _UNCACHABLE
        vals.append(frozen)
    return (code, frozen_defaults, tuple(vals))


def _is_dynamic(v) -> bool:
    return isinstance(v, (jax.Array, np.ndarray))


def _bwd_used_mask(bwd_raw, dyn, cot):
    """Which positions of `dyn` the deferred-vjp recompute actually reads.

    Reverse liveness over the (untraced) bwd jaxpr: start from the output
    vars, walk equations backwards, mark an equation's inputs live when any
    of its outputs are. Equations with sub-jaxprs are treated atomically
    (all inputs live) — conservative, never drops a needed operand. E.g.
    add: nothing read (mask all-False); mul: both read. Returns None when
    the jaxpr can't be built (unusual cotangents) — caller keeps all."""
    try:
        closed = jax.make_jaxpr(bwd_raw)(tuple(dyn), cot)
    except Exception:
        return None
    jaxpr = closed.jaxpr
    live = {v for v in jaxpr.outvars if isinstance(v, jex_core.Var)}
    for eqn in reversed(jaxpr.eqns):
        if any(ov in live for ov in eqn.outvars):
            for iv in eqn.invars:
                if isinstance(iv, jex_core.Var):
                    live.add(iv)
    return tuple(v in live for v in jaxpr.invars[:len(dyn)])


def _dyn_sig(dyn):
    return tuple((tuple(d.shape), str(d.dtype)) for d in dyn)


def _has_float0(cot) -> bool:
    leaves = cot if isinstance(cot, (tuple, list)) else (cot,)
    return any(getattr(c, "dtype", None) == jax.dtypes.float0 for c in leaves)


def _apply_vjp(vjp_fn, cot):
    global _vjp_apply_jit
    if _has_float0(cot):  # float0 cotangents can't cross a jit boundary
        return vjp_fn(cot)
    if _vjp_apply_jit is None:
        _vjp_apply_jit = jax.jit(lambda f, c: f(c))
    return _vjp_apply_jit(vjp_fn, cot)


def _build_entry(fn, datas, diff_idx, dyn_pos):
    """Compile-once closure over the static operands (they're in the key)."""
    raw = [None if i in dyn_pos else d for i, d in enumerate(datas)]

    def _vals(dyn):
        vals = list(raw)
        for p, v in zip(dyn_pos, dyn):
            vals[p] = v
        return vals

    if not diff_idx:
        def call(*dyn):
            return fn(*_vals(dyn))

        return ("nograd", jax.jit(call))

    def _primal_over(vals):
        def primal(*ds):
            vs = list(vals)
            for i, dv in zip(diff_idx, ds):
                vs[i] = dv
            return fn(*vs)

        return primal

    def fwd(*dyn):
        vals = _vals(dyn)
        return jax.vjp(_primal_over(vals), *[vals[i] for i in diff_idx])

    # deferred-vjp pair (FLAGS_eager_defer_vjp, default on): forward runs
    # the lean fwd-only executable — a jit call returning a vjp closure
    # costs ~2x a plain call in pytree packaging (measured on host CPU:
    # 103 vs 55 us) and eager dispatch overhead is the metric here.
    # Backward re-derives the vjp INSIDE one jitted call (fwd recompute +
    # cotangent application fused by XLA). Trade: ~1 extra forward of this
    # op's FLOPs in backward — negligible for the dispatch-bound regime
    # eager mode serves; compute-bound training runs under to_static where
    # none of this path exists.
    def fwd_only(*dyn):
        return fn(*_vals(dyn))

    def bwd(dyn, cot):
        vals = _vals(dyn)
        _, vjp = jax.vjp(_primal_over(vals), *[vals[i] for i in diff_idx])
        return vjp(cot)

    # trailing dict: per-shape-signature mask of which dyn operands the vjp
    # recompute actually reads (ADVICE r5: don't pin every forward operand
    # until backward); filled lazily by _bwd_used_mask on first backward
    return ("grad", jax.jit(fwd), jax.jit(fwd_only), jax.jit(bwd), bwd, {})


def _cached_dispatch(fn, fn_id, name, datas, diff_idx, target,
                     dyn_pos=None, has_tracer=None):
    """Returns (out, vjp_or_None) via the executable cache, or None to fall
    back to the uncached path (unhashable statics / trace failure).
    dyn_pos/has_tracer may be precomputed by the caller's operand scan
    (one pass instead of three over the hot loop's operands)."""
    global _eager_hits, _eager_misses
    if has_tracer is None:
        has_tracer = any(isinstance(d, jax.core.Tracer) for d in datas)
    if has_tracer:
        return None
    if dyn_pos is None:
        dyn_pos = tuple(i for i, d in enumerate(datas) if _is_dynamic(d))
    if len(dyn_pos) == len(datas):  # common case: every operand dynamic
        statics = ()
    else:
        dyn_set = set(dyn_pos)
        statics = tuple(
            _freeze(d) for i, d in enumerate(datas) if i not in dyn_set
        )
    if fn_id is _UNCACHABLE or any(s is _UNCACHABLE for s in statics):
        return None
    key = (fn_id, name, target, dyn_pos, tuple(diff_idx), statics)
    entry = _eager_cache.get(key)
    if entry is _UNCACHABLE:
        return None
    if entry is None:
        limit = flag("FLAGS_eager_cache_size")
        if limit <= 0:  # size 0 ⇒ cache disabled
            return None
        _eager_misses += 1
        while len(_eager_cache) >= limit and _eager_cache:
            _eager_cache.pop(next(iter(_eager_cache)))
        entry = _build_entry(fn, datas, diff_idx, dyn_pos)
        _eager_cache[key] = entry
        # compile watchdog: a miss means a new executable entry — record
        # it (obs/watchdog.py). Only this cold path pays the event; wall
        # time is ~0 here because jax.jit traces lazily on first call.
        # The key is digested: a re-BUILD of the same digest after
        # eviction is the cache-thrash signal audit_recompiles flags.
        digest = f"{name}#{hash(key) & 0xffffffff:08x}"
        _record_compile()("eager", name, digest)
        # cost ledger (obs/costs.py): count-only rows — per-op eager
        # executables lower lazily inside jax.jit, so no XLA analysis
        # is reachable without paying one extra compile per op; the
        # ledger still shows WHERE the eager program population lives
        _record_cost_program()("eager", name, digest)
    else:
        _eager_hits += 1
    kind, jitted, *defer = entry
    dyn = [datas[p] for p in dyn_pos]
    try:
        if kind == "nograd":
            return jitted(*dyn), None
        if defer and _hot_flags().defer_vjp:
            fwd_only, bwd, bwd_raw, masks = defer
            out = fwd_only(*dyn)
            # pin only the operands the vjp recompute reads (known after the
            # first backward of this signature); unused positions are
            # rebuilt as zeros at backward time — values can't matter, the
            # bwd program provably never reads them
            sig = _dyn_sig(dyn)
            mask = masks.get(sig)
            if mask is None:
                kept = tuple(dyn)
                avals = None
            else:
                kept = tuple(d if m else None for d, m in zip(dyn, mask))
                avals = tuple(None if m else (d.shape, d.dtype)
                              for d, m in zip(dyn, mask))

            def deferred(cot, _b=bwd, _k=kept, _a=avals, _raw=bwd_raw,
                         _ms=masks, _sig=sig):
                import jax.numpy as jnp

                if _a is None:
                    d = _k
                    if not _has_float0(cot) and _sig not in _ms:
                        m = _bwd_used_mask(_raw, d, cot)
                        if m is not None:
                            _ms[_sig] = m
                else:
                    d = tuple(k if k is not None else jnp.zeros(*a)
                              for k, a in zip(_k, _a))
                if _has_float0(cot):  # float0 can't cross a jit boundary
                    with jax.disable_jit():
                        return _b(d, cot)
                return _b(d, cot)

            return out, deferred
        out, vjp_fn = jitted(*dyn)
        return out, (lambda cot, _v=vjp_fn: _apply_vjp(_v, cot))
    except GRAPH_BREAK_ERRORS:
        # fn inspects concrete values — shape-independent, permanently
        # uncachable for this key
        _eager_cache[key] = _UNCACHABLE
        return None
    except TypeError:
        # usually a per-shape user error (e.g. mismatched contracting dims):
        # fall back for THIS call only — the uncached path raises the same
        # error to the user; valid calls keep using the cached entry
        return None


_RECORD_COMPILE = None
_RECORD_COST = None


def _record_compile():
    # bound lazily like _TENSOR_CLS: obs lives above core in the package
    # graph and this only runs on the rare miss path
    global _RECORD_COMPILE
    if _RECORD_COMPILE is None:
        from ..obs.watchdog import record_compile as _RECORD_COMPILE  # noqa: F811
    return _RECORD_COMPILE


def _record_cost_program():
    global _RECORD_COST
    if _RECORD_COST is None:
        from ..obs.costs import record_program as _RECORD_COST  # noqa: F811
    return _RECORD_COST


def eager_cache_info() -> dict:
    return {
        "entries": len(_eager_cache),
        "hits": _eager_hits,
        "misses": _eager_misses,
    }


def eager_cache_clear():
    global _eager_hits, _eager_misses
    _eager_cache.clear()
    _eager_hits = _eager_misses = 0


def _check_nan_inf(name, arrs):
    import jax.numpy as jnp

    def hit(msg):
        # FLAGS_check_nan_inf_level >= 1: report, don't abort (reference
        # nan_inf_utils level semantics)
        if flag("FLAGS_check_nan_inf_level") >= 1:
            import warnings

            warnings.warn(msg)
        else:
            raise FloatingPointError(msg)

    for a in arrs:
        if isinstance(a, jax.core.Tracer) or isinstance(a, _LazyData):
            continue
        if dtypes.is_floating_point(a.dtype):
            if bool(jnp.any(~jnp.isfinite(a))):
                hit(f"Operator '{name}' output contains NaN/Inf")
        elif dtypes.is_complex(a.dtype):
            if bool(jnp.any(~jnp.isfinite(a.real) | ~jnp.isfinite(a.imag))):
                hit(f"Operator '{name}' output contains NaN/Inf")


#: (pack, unpack) installed by autograd.saved_tensors_hooks; applied to the
#: ctx-pinned operand buffers (the framework-visible saved tensors — the
#: XLA-managed vjp residuals live in device memory outside hook scope)
saved_tensor_hooks = None


def _make_ctx(fn, datas, diff_idx):
    """Re-derivation ctx for create_graph. Differentiable operands are
    stored as None — _regrad rebuilds them from node.inputs, so the ctx
    pins only the non-diff operands (and most of those are already alive
    in the vjp residuals)."""
    if not _hot_flags().double_grad:
        return None
    diff = set(diff_idx)
    kept = [None if i in diff else d for i, d in enumerate(datas)]
    if saved_tensor_hooks is not None:
        pack, unpack = saved_tensor_hooks
        kept = [None if d is None else _PackedSaved(pack(d), unpack)
                for d in kept]
    return (fn, kept)


class _PackedSaved:
    """A ctx slot transformed by saved_tensors_hooks; unpacked lazily on
    first re-derivation use."""

    __slots__ = ("payload", "unpack")

    def __init__(self, payload, unpack):
        self.payload = payload
        self.unpack = unpack

    def get(self):
        return self.unpack(self.payload)


#: set by paddle_tpu.profiler while recording: callable(name) -> RecordEvent
_profiler_hook = None

#: set by amp.debugging while collecting op-dtype stats: fn(name, outputs)
_op_stat_fn = None


def op_call(fn: Callable, *args, name: str | None = None, n_diff: int | None = None):
    """Run pure jax function `fn` over mixed Tensor/raw args, recording autograd.

    Args after position `n_diff` (when given) are never differentiated —
    use for index/shape/flag operands. Returns Tensor or tuple[Tensor].
    """
    hook = _profiler_hook
    if hook is not None:
        ev = hook(name or getattr(fn, "__name__", "op"))
        ev.begin()
        try:
            return _op_call_impl(fn, *args, name=name, n_diff=n_diff)
        finally:
            ev.end()
    return _op_call_impl(fn, *args, name=name, n_diff=n_diff)


def _op_call_impl(fn: Callable, *args, name: str | None = None, n_diff: int | None = None):
    name = name or getattr(fn, "__name__", "op")
    trace = current_trace()

    # ONE pass over the operands collects buffers, dynamic positions and
    # tracer-ness (the eager hot loop previously re-scanned three times)
    datas = []
    dyn_pos_l = []
    has_tracer = False
    for i, a in enumerate(args):
        if _is_tensor(a):
            if trace is not None:
                trace.on_read(a)
            d = a._data_buf
            if type(d) is LazyInit:     # a LazyGuard parameter's first use
                d = a._data
        else:
            d = a
        datas.append(d)
        if isinstance(d, (jax.Array, np.ndarray)):
            dyn_pos_l.append(i)
            if isinstance(d, jax.core.Tracer):
                has_tracer = True
    dyn_pos = tuple(dyn_pos_l)

    # AMP O1/O2 input casting (paddle: amp_auto_cast.h logic inlined in ad_funcs)
    global _amp_dtype_for
    if _amp_dtype_for is None:
        from ..amp import amp_dtype_for as _adf

        _amp_dtype_for = _adf
    orig_fn = fn
    target = _amp_dtype_for(name)
    if target is not None:
        # cast inside the differentiated fn so vjp returns grads in the
        # original param dtype (cast is part of the recorded graph)
        inner_fn = fn

        def fn(*vals):  # noqa: F811
            vals = [
                v.astype(target)
                if hasattr(v, "dtype") and dtypes.is_floating_point(v.dtype)
                and v.dtype != target else v
                for v in vals
            ]
            return inner_fn(*vals)

    limit = len(args) if n_diff is None else n_diff
    diff_idx = []
    if grad_enabled():
        for i, a in enumerate(args[:limit]):
            # raw-dtype membership check: the Tensor.dtype property builds
            # a fresh np.dtype per access — measurable in this hot loop
            if _is_tensor(a) and not a.stop_gradient \
                    and getattr(a._data, "dtype", None) in _DIFF_DTYPES:
                diff_idx.append(i)

    # segmented lazy staging (to_static graph-break mode): record the op
    # into the open segment instead of executing; see core/lazy.py
    lazy = _current_lazy()
    if lazy is not None:
        staged = lazy.stage(fn, _fn_key(orig_fn), name, datas, diff_idx,
                            target)
        if staged is not None:
            out_lazy, vjp_box, avals, single = staged
            node = None
            if vjp_box is not None:
                node = GradNode(
                    vjp_box, [args[i] for i in diff_idx],
                    [(tuple(a.shape), a.dtype) for a in avals], single, name,
                    diff_idx=list(diff_idx),
                    ctx=_make_ctx(fn, datas, diff_idx))
            out = out_lazy[0] if single else tuple(out_lazy)
            wrapped = _wrap_outputs(out, node, name)
            for t in ([wrapped] if single else list(wrapped)):
                lazy.created.append(weakref.ref(t))
            return wrapped
        # un-stageable op: materialize lazy inputs, fall through to eager
        datas = [d.get() if isinstance(d, _LazyData) else d for d in datas]
        # materialization changes which operands are dynamic: recompute
        dyn_pos = has_tracer = None

    use_cache = _hot_flags().use_cache

    if not diff_idx:
        if use_cache:
            cached = _cached_dispatch(fn, _fn_key(orig_fn), name, datas, [],
                                      target, dyn_pos, has_tracer)
            if cached is not None:
                return _wrap_outputs(cached[0], None, name)
        out = fn(*datas)
        return _wrap_outputs(out, None, name)

    if use_cache:
        cached = _cached_dispatch(fn, _fn_key(orig_fn), name, datas,
                                  diff_idx, target, dyn_pos, has_tracer)
        if cached is not None:
            out, vjp_fn = cached
            single = not isinstance(out, (tuple, list))
            outs = [out] if single else list(out)
            avals = [(o.shape, o.dtype) for o in outs]
            if _needs_complex_bridge(avals, datas, diff_idx):
                vjp_fn = _complexify_vjp(vjp_fn, single)
            node = GradNode(vjp_fn, [args[i] for i in diff_idx], avals, single, name,
                            diff_idx=list(diff_idx), ctx=_make_ctx(fn, datas, diff_idx))
            return _wrap_outputs(out, node, name)

    if len(diff_idx) == len(datas):
        primal_fn = fn
        diff_vals = datas
    else:
        def primal_fn(*dvals):
            vals = list(datas)
            for i, v in zip(diff_idx, dvals):
                vals[i] = v
            return fn(*vals)

        diff_vals = [datas[i] for i in diff_idx]

    out, vjp_fn = jax.vjp(primal_fn, *diff_vals)

    single = not isinstance(out, (tuple, list))
    outs = [out] if single else list(out)
    avals = [(o.shape, o.dtype) for o in outs]
    if _needs_complex_bridge(avals, datas, diff_idx):
        vjp_fn = _complexify_vjp(vjp_fn, single)
    node = GradNode(vjp_fn, [args[i] for i in diff_idx], avals, single, name,
                    diff_idx=list(diff_idx), ctx=_make_ctx(fn, datas, diff_idx))
    return _wrap_outputs(out, node, name)


def _wrap_outputs(out, node, name):
    global _TENSOR_CLS
    if _TENSOR_CLS is None:
        from .tensor import Tensor as _TENSOR_CLS  # noqa: F811
    Tensor = _TENSOR_CLS

    hf = _hot_flags()
    if hf.benchmark:
        # benchmark mode: per-op completion barrier (≙ reference benchmark
        # flag forcing synchronous kernel launches); tracers pass through
        jax.block_until_ready(out)
    if hf.check_nan_inf:
        flat = [out] if not isinstance(out, (tuple, list)) else list(out)
        _check_nan_inf(name, [o for o in flat if hasattr(o, "dtype")])
    if _op_stat_fn is not None:
        flat = [out] if not isinstance(out, (tuple, list)) else list(out)
        _op_stat_fn(name, [o for o in flat if hasattr(o, "dtype")])

    if not isinstance(out, (tuple, list)):  # single output: the hot shape
        t = Tensor(out, stop_gradient=node is None, _internal=True)
        if node is not None:
            t._node = node
        return t

    def mk(o, idx):
        t = Tensor(o, stop_gradient=node is None, _internal=True)
        if node is not None:
            t._node = node
            t._out_idx = idx
        return t

    return tuple(mk(o, i) for i, o in enumerate(out))
