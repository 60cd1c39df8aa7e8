"""paddle_tpu.jit.to_static — whole-program capture and XLA compilation.

Reference parity: paddle.jit.to_static (python/paddle/jit/api.py:197) with the
SOT bytecode JIT (sot/translate.py:37) + PIR program + PirInterpreter replaced
by a TPU-native design:

  call 1: plain eager execution (warm-up; lazy state like optimizer moments
          gets created).
  call 2: eager "discovery" run under a TraceContext that records every
          pre-existing Tensor the program reads (captures: parameters,
          optimizer state, RNG key) and every in-place write (mutations).
  call 3+: the function is traced ONCE with jax.jit into a single XLA
          program whose inputs are (args, read-only captures, mutated
          captures) and whose outputs are (results, new values of mutated
          captures). Mutated buffers are donated — parameter updates reuse
          their input HBM, like paddle's in-place optimizer kernels.

Guards: cache keyed on args pytree structure + Tensor (shape, dtype,
stop_gradient) + values of non-tensor leaves — a new key compiles a new
specialization (the analog of SOT guards with graph-break fallback: we fall
back to eager while discovering).

XLA owns fusion/scheduling (the role of CINN + PirInterpreter).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

from ..core import lockdep

import jax
import numpy as np

from ..core.dispatch import TraceContext, trace_context
from ..core.flags import flag
from ..core.tensor import Tensor
from ..obs.trace import span as _span

_NOT_TO_STATIC: set = set()


def not_to_static(fn):
    _NOT_TO_STATIC.add(fn)
    return fn


def ignore_module(modules):
    return None


class _TensorLeaf:
    __slots__ = ("idx",)

    def __init__(self, idx):
        self.idx = idx


def _flatten(obj, leaves):
    if isinstance(obj, Tensor):
        leaves.append(obj)
        return _TensorLeaf(len(leaves) - 1)
    if isinstance(obj, (list, tuple)):
        t = [_flatten(v, leaves) for v in obj]
        return tuple(t) if isinstance(obj, tuple) else t
    if isinstance(obj, dict):
        return {k: _flatten(obj[k], leaves) for k in obj}
    return obj


def _unflatten(struct, leaf_vals):
    if isinstance(struct, _TensorLeaf):
        return leaf_vals[struct.idx]
    if isinstance(struct, list):
        return [_unflatten(v, leaf_vals) for v in struct]
    if isinstance(struct, tuple):
        return tuple(_unflatten(v, leaf_vals) for v in struct)
    if isinstance(struct, dict):
        return {k: _unflatten(v, leaf_vals) for k, v in struct.items()}
    return struct


def _struct_key(struct):
    if isinstance(struct, _TensorLeaf):
        return f"T{struct.idx}"
    if isinstance(struct, (list, tuple)):
        inner = ",".join(_struct_key(v) for v in struct)
        return f"[{inner}]" if isinstance(struct, list) else f"({inner})"
    if isinstance(struct, dict):
        return "{" + ",".join(f"{k}:{_struct_key(v)}" for k, v in struct.items()) + "}"
    return repr(struct)


class _Specialization:
    __slots__ = ("captures", "ro_caps", "mut_caps", "executable", "out_struct",
                 "n_out_leaves", "trace_muts", "debug", "debug_jaxpr",
                 "debug_index", "donated", "cost_entry")


#: exception types that mean "this program can't be captured as one graph"
#: (data-dependent Python control flow / concrete-value inspection under
#: tracing) — the analog of an SOT graph break
#: (/root/reference/python/paddle/jit/sot/translate.py:37 falls back to
#: eager frame execution on BreakGraphError). One shared definition with the
#: eager dispatch cache.
from ..core.dispatch import GRAPH_BREAK_ERRORS as _GRAPH_BREAK_ERRORS


def default_buckets(n: int) -> int:
    """Round a dynamic length up to its bucket: next power of two up to 512,
    then multiples of 512 (pad waste ≤ 2x small / ≤ 12% at 4k). The XLA
    answer to SURVEY §7 hard-part (3): recompilation count is O(log L), not
    O(#distinct lengths)."""
    if n <= 1:
        return 1
    if n <= 512:
        return 1 << (n - 1).bit_length()
    return ((n + 511) // 512) * 512


class BucketAxis:
    """Per-argument bucketing spec for to_static: pad tensor arg along
    `axis` up to the bucket boundary with `pad_value`. The wrapped function
    must be padding-neutral on that axis (e.g. pad labels with an
    ignore_index). ≙ the varlen/dynamic-shape policy the reference gets from
    flash_attn varlen + SOT dynamic dims
    (/root/reference/python/paddle/nn/functional/flash_attention.py:358)."""

    __slots__ = ("axis", "pad_value", "buckets")

    def __init__(self, axis: int, pad_value=0, buckets=None):
        self.axis = axis
        self.pad_value = pad_value
        self.buckets = sorted(buckets) if buckets else None

    def round_up(self, n: int) -> int:
        if self.buckets is not None:
            for b in self.buckets:
                if n <= b:
                    return b
            return n  # beyond the largest bucket: no padding
        return default_buckets(n)


class CompiledFunction:
    def __init__(self, fn: Callable, input_spec=None, build_strategy=None,
                 backend=None, full_graph=False, donate_buffers=None,
                 bucket_axes: dict | None = None, share_discovery=False,
                 in_shardings=None):
        functools.update_wrapper(self, fn)
        self._fn = fn
        #: the `fn` attr of this function's spans (obs/trace.py)
        self._span_fn = getattr(fn, "__name__", "to_static")
        # per-instance RLock serializing specialization bookkeeping:
        # phase counts, the compiled-spec cache and discovery contexts
        # (reads stay lock-free — a stale read only re-enters the
        # compile path, which re-checks under the lock)
        self._lock = lockdep.make_rlock("jit.CompiledFunction._lock")
        self._cache: dict[str, Any] = {}              # guarded-by: _lock
        # key -> call count (for warmup phases)
        self._state: dict[str, int] = {}              # guarded-by: _lock
        self._discovered: dict[str, TraceContext] = {}  # guarded-by: _lock
        self._donate = flag("FLAGS_to_static_donate") if donate_buffers is None \
            else donate_buffers
        self._full_graph = full_graph
        self._fallback_eager = False   # whole-function eager (segmented off)
        self._segmented = False        # graph-break → lazy segment mode
        self._last_segments = 0
        # arg position -> BucketAxis (or (axis[, pad]) shorthand)
        self._bucket_axes = {
            k: (v if isinstance(v, BucketAxis) else
                BucketAxis(*((v,) if isinstance(v, int) else tuple(v))))
            for k, v in (bucket_axes or {}).items()}
        # share_discovery: the capture set (params/opt-state/rng — free
        # variables) is shape-independent for shape-generic functions, so a
        # NEW input signature can skip the two eager phases and reuse the
        # last discovery — no eager pass at large shapes (an eager fp32
        # warm-up at full batch can exceed HBM long before the compiled,
        # donated program does). Prime with a tiny batch, then run big.
        self._share_discovery = share_discovery
        # in-spec plumb-through (the declarative partitioner rides this):
        # {arg_leaf_position: jax Sharding} or callable(leaves) -> list of
        # per-leaf Shardings/None, resolved once per specialization and
        # applied as with_sharding_constraint on the traced arg inputs —
        # the compiled program's in-specs without a wrapper function
        self._in_shardings = in_shardings
        # dy2static: the AST-rewritten capture function (lazily built) and
        # its transform report; _break_reason records why capture fell back
        self._cap_fn = None
        self._dy2st_report = None
        self._break_reason: str | None = None
        self._last_break_sites: list = []

    # -- paddle API parity
    @property
    def function(self):
        return self._fn

    def concrete_program(self):
        return None

    # -- dy2static capture function
    def _capture_fn(self):
        """The function all phases actually run: the dy2static AST rewrite
        of self._fn when it applies (tensor-predicate if/while/for become
        lax.cond/while_loop/scan at trace time, plain Python otherwise),
        else self._fn unchanged."""
        if self._cap_fn is None:
            if flag("FLAGS_dy2static"):
                from .dy2static import convert_to_static

                self._cap_fn, self._dy2st_report = convert_to_static(self._fn)
            else:
                self._cap_fn = self._fn
                from .dy2static.diagnostics import TransformReport

                self._dy2st_report = TransformReport(
                    getattr(self._fn, "__name__", "<callable>"))
                self._dy2st_report.skip_reason = "FLAGS_dy2static disabled"
        return self._cap_fn

    def graph_break_report(self) -> dict:
        """Capture-coverage introspection (tools/report_graph_breaks.py):
        transform report, capture outcome, fallback reason, and — in
        segmented mode — the concretization sites that split segments."""
        self._capture_fn()
        return {
            "function": getattr(self._fn, "__name__", str(self._fn)),
            "transform": self._dy2st_report,
            "compiled": bool(self._cache) and not self._segmented
            and not self._fallback_eager,
            "segmented": self._segmented,
            "eager": self._fallback_eager,
            "break_reason": self._break_reason,
            "break_sites": list(self._last_break_sites),
            "segments": self._last_segments,
        }

    def program_text(self, key: str | None = None) -> str:
        """Jaxpr of a compiled specialization (requires
        FLAGS_jit_debug_program=1 at compile time). For asserting capture
        properties — e.g. that a tensor `if` really lowered to `cond`."""
        return str(self.program_jaxpr(key))

    def program_jaxpr(self, key: str | None = None):
        """ClosedJaxpr of a compiled specialization (requires
        FLAGS_jit_debug_program=1 at compile time) — the object form of
        program_text(), consumed by paddle_tpu.analysis's jaxpr detectors.
        Cached per specialization (round 15): the compile path stores the
        jaxpr it already traced (jit .trace()), so repeated audits of the
        same program cost zero retraces.
        """
        if not self._cache:
            raise RuntimeError("program_text/jaxpr: nothing compiled yet")
        spec = self._cache[key] if key is not None \
            else next(iter(self._cache.values()))
        dbg = getattr(spec, "debug", None)
        if dbg is None:
            raise RuntimeError(
                "program_text/jaxpr needs FLAGS_jit_debug_program=1 before "
                "the compiling call (paddle.set_flags)")
        if getattr(spec, "debug_jaxpr", None) is None:
            pure, avals = dbg
            spec.debug_jaxpr = jax.make_jaxpr(pure)(*avals)
        return spec.debug_jaxpr

    def program_index(self, key: str | None = None):
        """analysis.ProgramIndex over a compiled specialization's jaxpr,
        built ONCE and cached on the specialization — the compile-site
        sizing, the collective-bytes ledger hook and every
        audit_compiled pass read the same walk (the round-15 single-walk
        property, held end to end)."""
        if not self._cache:
            raise RuntimeError("program_index: nothing compiled yet")
        spec = self._cache[key] if key is not None \
            else next(iter(self._cache.values()))
        if getattr(spec, "debug_index", None) is None:
            from ..analysis import build_index

            spec.debug_index = build_index(self.program_jaxpr(key))
        return spec.debug_index

    def __get__(self, instance, owner):
        if instance is None:
            return self
        return functools.partial(self.__call__, instance)

    def _leaf_shardings(self, leaves):
        """Per-arg-leaf Shardings from the `in_shardings` spec (None when
        unset or nothing resolves)."""
        if self._in_shardings is None:
            return None
        if callable(self._in_shardings):
            out = list(self._in_shardings(leaves) or ())
        else:
            out = [self._in_shardings.get(i)
                   for i in range(len(leaves))]
        out += [None] * (len(leaves) - len(out))
        return out if any(s is not None for s in out) else None

    def _key(self, struct, leaves):
        spec = ";".join(f"{tuple(t.shape)}|{t.dtype.name}|{t.stop_gradient}"
                        for t in leaves)
        return _struct_key(struct) + "##" + spec

    def _apply_buckets(self, args):
        import jax.numpy as jnp

        out = list(args)
        for idx, spec in self._bucket_axes.items():
            if idx >= len(out) or not isinstance(out[idx], Tensor):
                raise ValueError(
                    f"to_static(bucket_axes={{{idx}: ...}}): positional arg "
                    f"{idx} is "
                    + ("missing" if idx >= len(out)
                       else f"a {type(out[idx]).__name__}, not a Tensor")
                    + " — bucketed args must be passed positionally")
            t = out[idx]
            n = int(t.shape[spec.axis])
            m = spec.round_up(n)
            if m == n:
                continue
            pads = [(0, 0)] * t.ndim
            pads[spec.axis] = (0, m - n)
            out[idx] = Tensor(
                jnp.pad(t._data, pads, constant_values=spec.pad_value),
                _internal=True, stop_gradient=t.stop_gradient)
        return tuple(out)

    def __call__(self, *args, **kwargs):
        from ..core.flags import flag

        if self._fallback_eager or not flag("FLAGS_enable_to_static"):
            return self._fn(*args, **kwargs)
        if self._bucket_axes:
            args = self._apply_buckets(args)
        if self._segmented:
            return self._run_segmented(args, kwargs)
        # `jit.call` is the host's time in one call; the span inside it
        # says which phase the call was: `jit.warmup`, `jit.discover`,
        # `jit.compile`, or on the cached path `jit.dispatch` (the
        # executable up to the return of its async launch), so that the
        # call's self time is flatten, key, lock and `_finish`
        fn = self._span_fn
        with _span("jit.call", fn=fn):
            leaves: list[Tensor] = []
            struct = _flatten((args, kwargs), leaves)
            key = self._key(struct, leaves)
            with self._lock:
                n = self._state.get(key, 0)
                self._state[key] = n + 1
            shared = (self._share_discovery
                      and key not in self._discovered and self._discovered)
            if n == 0 and not shared:
                # warm-up: lazy state creation (already through the
                # dy2static rewrite so all phases share one code path)
                with _span("jit.warmup", fn=fn):
                    return self._capture_fn()(*args, **kwargs)
            if n == 1 and not shared:
                with _span("jit.discover", fn=fn):
                    return self._discover(key, args, kwargs)
            spec = self._cache.get(key)
            if spec is None:
                with _span("jit.compile", fn=fn):
                    return self._compile_and_run(key, struct, leaves, args,
                                                 kwargs)
            return self._run(spec, struct, leaves)

    # ------------------------------------------------------------ phases
    def _discover(self, key, args, kwargs):
        ctx = TraceContext("discover")
        cap = self._capture_fn()
        with trace_context(ctx):
            out = cap(*args, **kwargs)
        with self._lock:
            self._discovered[key] = ctx
        return out

    def _compile_and_run(self, key, struct, leaves, args, kwargs, _retry=0):
        ctx = self._discovered.get(key)
        borrowed = False
        if ctx is None and self._share_discovery and self._discovered:
            ctx = next(reversed(self._discovered.values()))
            borrowed = True
        if ctx is None:
            return self._discover(key, args, kwargs)
        captures = [t for t in ctx.captures.values()]
        cap_ids = {id(t) for t in captures}
        mut_caps = [t for t in ctx.mutated.values() if id(t) in cap_ids]
        mut_ids = {id(t) for t in mut_caps}
        ro_caps = [t for t in captures if id(t) not in mut_ids]

        spec = _Specialization()
        spec.captures = captures
        spec.ro_caps = ro_caps
        spec.mut_caps = mut_caps
        spec.cost_entry = None    # set below when the AOT path analyzed
        holder = {}
        cap_fn = self._capture_fn()
        arg_shards = self._leaf_shardings(leaves)

        def pure(arg_datas, ro_datas, mut_datas):
            if arg_shards:
                arg_datas = [
                    jax.lax.with_sharding_constraint(d, sh)
                    if sh is not None and isinstance(d, jax.core.Tracer)
                    else d
                    for d, sh in zip(arg_datas, arg_shards)]
            tctx = TraceContext("trace", borrowed=borrowed)
            holder["tctx"] = tctx
            saved = [(t, t._data) for t in ro_caps + mut_caps]
            for t, d in zip(ro_caps, ro_datas):
                t._data = d
            for t, d in zip(mut_caps, mut_datas):
                t._data = d
            try:
                arg_tensors = []
                for t, d in zip(leaves, arg_datas):
                    nt = Tensor(d, _internal=True, stop_gradient=t.stop_gradient)
                    arg_tensors.append(nt)
                a, k = _unflatten(struct, arg_tensors)
                with trace_context(tctx):
                    out = cap_fn(*a, **k)
                out_leaves: list = []
                out_struct = _flatten(out, out_leaves)
                # mutations observed at trace time (superset-safe)
                trace_muts = [t for t in tctx.mutated.values()
                              if isinstance(t._data, jax.core.Tracer)]
                holder["out_struct"] = out_struct
                holder["trace_muts"] = trace_muts
                return ([t._data for t in out_leaves], [t._data for t in trace_muts])
            finally:
                for t, d in saved:
                    t._data = d

        donate = (2,) if (self._donate and mut_caps) else ()
        spec.donated = bool(donate)   # analysis: donation audit (D2)
        jitted = jax.jit(pure, donate_argnums=donate)
        arg_datas = [t._data for t in leaves]
        ro_datas = [t._data for t in ro_caps]
        mut_datas = [t._data for t in mut_caps]
        from .dy2static.diagnostics import Dy2StFallback, classify_graph_break

        try:
            import time as _time

            _t0 = _time.perf_counter()
            # Under FLAGS_jit_debug_program + cost capture the program
            # compiles ONCE through the AOT path: jit(...).trace() gives
            # the jaxpr (cached for program_jaxpr/the lint auditors) and
            # the lowering in one trace, .compile() yields the executable
            # that both runs the step AND feeds XLA cost_analysis() into
            # the obs ledger. Pre-round-15 the debug path paid a second
            # full compile (jitted ran the step, lower().compile() redid
            # it for costs) — the lint smokes' dominant wall cost.
            _aot = _aot_jaxpr = None
            if flag("FLAGS_jit_debug_program") \
                    and flag("FLAGS_obs_cost_capture"):
                try:
                    _traced = jitted.trace(arg_datas, ro_datas, mut_datas)
                    _aot_jaxpr = _traced.jaxpr
                    _aot = _traced.lower().compile()
                except (Dy2StFallback,) + _GRAPH_BREAK_ERRORS:
                    raise
                except Exception:
                    _aot = _aot_jaxpr = None  # AOT unsupported: jit path
            if _aot is not None:
                out_datas, mut_out = _aot(arg_datas, ro_datas, mut_datas)
            else:
                out_datas, mut_out = jitted(arg_datas, ro_datas, mut_datas)
            _compile_wall = _time.perf_counter() - _t0
        except (Dy2StFallback,) + _GRAPH_BREAK_ERRORS as e:
            fn_name = getattr(self._fn, "__name__", str(self._fn))
            reason = classify_graph_break(e)
            loc = getattr(e, "loc", None)
            self._break_reason = (f"{loc}: {reason}" if loc else reason)
            if self._full_graph:
                raise RuntimeError(
                    f"to_static(full_graph=True): '{fn_name}' cannot be "
                    f"captured as one graph — {self._break_reason}. "
                    "Tensor-dependent if/while/for is captured "
                    "automatically (lax.cond/while_loop/scan); this "
                    "construct is one of the unsupported cases (run "
                    "tools/report_graph_breaks.py for every site), or pass "
                    "full_graph=False to fall back."
                ) from e
            # dy2static fallback messages route through the structured
            # logger (obs/logging.py: VLOG + rate limit + JSONL); the
            # Python warning stays emitted (also_warn) because the
            # graph-break contract is "warns once, then degrades" and
            # warnings.catch_warnings consumers (tests,
            # tools/report_graph_breaks.py) assert on it.
            from ..obs.logging import get_logger

            log = get_logger(__name__)
            if flag("FLAGS_to_static_segmented"):
                log.warning(
                    f"to_static: graph break in '{fn_name}' — "
                    f"{self._break_reason}; switching to segmented lazy "
                    "execution — ops run as compiled XLA segments bridged "
                    "eagerly at each concretization point. Python-level side "
                    "effects before the break ran once during capture and "
                    "run again this call.",
                    key=f"segmented:{fn_name}", also_warn=True,
                    stacklevel=3)
                self._segmented = True
                a, k = _unflatten(struct, leaves)
                return self._run_segmented(a, k)
            log.warning(
                f"to_static: graph break in '{fn_name}' — "
                f"{self._break_reason}; falling back to eager execution. "
                "Tensor state from the failed capture was rolled back, but "
                "Python-level side effects before the break ran once during "
                "capture and will run again eagerly this call.",
                key=f"eager:{fn_name}", also_warn=True, stacklevel=3)
            self._fallback_eager = True
            a, k = _unflatten(struct, leaves)
            return self._capture_fn()(*a, **k)

        folded = getattr(holder.get("tctx"), "folded", None)
        if folded:
            import warnings

            names = [t.name for t in list(folded.values())[:5]]
            warnings.warn(
                "to_static(share_discovery=True): the borrowed discovery "
                f"did not record tensor(s) {names} read by this trace — "
                "their CURRENT values were baked into the compiled program "
                "as constants; later updates to them will be ignored. "
                "Disable share_discovery for this function if these must "
                "stay live inputs.", stacklevel=3)
        # the AOT executable (when built) IS the execution path: same
        # donation, fixed avals per spec key, and it is the retained
        # object ROADMAP item-5 executable serialization needs. AOT is
        # stricter than jit about INPUT SHARDINGS: a GSPMD train step's
        # first execution returns optimizer state sharded by the
        # partitioner, so call 2 no longer matches the replicated
        # shardings call 1 compiled for — jit would transparently
        # recompile, the AOT executable raises. Demote to the jit path
        # on that mismatch only (ValueError "compiled for input shardings
        # that disagree" / TypeError "Argument types differ", both raised at
        # argument validation BEFORE execution or donation, so the
        # retry re-reads intact buffers); genuine runtime errors
        # propagate — retrying them would double host side effects and
        # mask the real failure behind donated-buffer errors.
        if _aot is not None:
            _MISMATCH_MARKS = (
                "that disagree with the",
                "for which this computation was compiled",
            )

            def _exec_aot(a, r, m, _aot=_aot, _jit=jitted, _spec=spec):
                try:
                    return _aot(a, r, m)
                except (ValueError, TypeError) as e:
                    msg = str(e)
                    if not any(mark in msg for mark in _MISMATCH_MARKS):
                        raise
                    _spec.executable = _jit
                    return _jit(a, r, m)

            spec.executable = _exec_aot
        else:
            spec.executable = jitted
        spec.out_struct = holder["out_struct"]
        spec.trace_muts = holder["trace_muts"]
        spec.debug = None
        spec.debug_jaxpr = _aot_jaxpr
        if flag("FLAGS_jit_debug_program"):
            def avals(ds):
                return [jax.ShapeDtypeStruct(d.shape, d.dtype) for d in ds]

            spec.debug = (pure, (avals(arg_datas), avals(ro_datas),
                                 avals(mut_datas)))
        with self._lock:
            self._cache[key] = spec
        # compile watchdog: one event per specialization (obs/watchdog).
        # Wall time includes the first execution (trace+compile+run, the
        # cold-start cost a caller actually feels). jaxpr size only under
        # FLAGS_jit_debug_program — sizing costs a retrace.
        from ..obs import watchdog as _watchdog

        fn_name = getattr(self._fn, "__name__", str(self._fn))
        eqns = None
        if spec.debug is not None:
            try:
                # ONE ProgramIndex walk per specialization: sizing here,
                # collective bytes below, and every audit_compiled pass
                # later all read the cached index
                eqns = len(self.program_index(key).eqns)
            except Exception:
                eqns = None
        # cost attribution (round 14, single-compile since round 15):
        # under FLAGS_jit_debug_program the step already compiled through
        # the AOT path above, so XLA cost_analysis()/memory_analysis()
        # ride the SAME executable that runs the program — no re-lower,
        # no second compile. The ledger row also carries the program's
        # jaxpr-level collective byte volume (analysis D10) next to
        # bytes-accessed.
        cost = None
        if _aot is not None and flag("FLAGS_obs_cost_capture"):
            try:
                import hashlib

                from ..obs import costs as _costs

                coll = 0
                try:
                    coll = self.program_index(key).collective_bytes()[
                        "total"]
                except Exception:
                    coll = 0
                digest = hashlib.sha1(key.encode()).hexdigest()[:8]
                entry = _costs.record_program(
                    "to_static", fn_name, f"{fn_name}/{digest}",
                    compiled=_aot, wall_s=_compile_wall,
                    collective_bytes=coll)
                # the train flight recorder joins this entry's flops
                # with measured step walls into train_mfu{program}
                spec.cost_entry = entry
                if entry.analyzed:
                    cost = {"flops": entry.flops,
                            "bytes_accessed": entry.bytes_accessed,
                            "peak_hbm_bytes": entry.peak_hbm_bytes}
            except Exception:
                cost = None
        # group per CompiledFunction INSTANCE: distinct wrapped functions
        # sharing a name (test suites are full of `train_step`s) must not
        # pool into one fake storm
        _watchdog.record_compile(
            "to_static", f"{fn_name}@{id(self) & 0xffff:04x}", key,
            wall_s=_compile_wall, jaxpr_eqns=eqns, donated=spec.donated,
            cost=cost)
        return self._finish(spec, out_datas, mut_out)

    def _run_segmented(self, args, kwargs):
        """Graph-break mode: re-run the Python with ops STAGED into lazy
        segments; each concretization point (float()/numpy()/bool/raw-jnp
        use) flushes one compiled XLA segment and Python continues — the
        traceable regions stay compiled, the break is bridged eagerly
        (core/lazy.py; ≙ SOT prefix-graph + resume,
        /root/reference/python/paddle/jit/sot/opcode_translator/executor/
        opcode_executor.py:320)."""
        from ..core.lazy import LazyContext, LazyData, lazy_context

        ctx = LazyContext()
        cap = self._capture_fn()
        with lazy_context(ctx):
            out = cap(*args, **kwargs)
            ctx.flush_all()
        self._last_segments = ctx.segments_flushed
        self._last_break_sites = list(ctx.break_sites)
        # swap concrete buffers into EVERY tensor staging created (params
        # mutated mid-call included) — a LazyData leaking into later eager
        # code would defeat the compiled-eager cache's dynamic-arg check
        for ref in ctx.created:
            t = ref()
            if t is not None and isinstance(t._data, LazyData):
                t._data = t._data.get()
        leaves: list = []
        _flatten(out, leaves)
        for t in leaves:
            if isinstance(t._data, LazyData):
                t._data = t._data.get()
        return out

    def _run(self, spec, struct, leaves):
        arg_datas = [t._data for t in leaves]
        ro_datas = [t._data for t in spec.ro_caps]
        mut_datas = [t._data for t in spec.mut_caps]
        with _span("jit.dispatch", fn=self._span_fn) as sp:
            out_datas, mut_out = spec.executable(arg_datas, ro_datas,
                                                 mut_datas)
        # training flight recorder (round 16): a compiled-step dispatch
        # during an instrumented fit becomes a span on the step timeline
        # (the `jit.dispatch` span's own clock reads) and its ledger flops
        # feed the MFU gauges. One module-attr read when no recorder is
        # active — per to_static CALL, not per op.
        from ..obs.train_flight import current as _tf_current

        rec = _tf_current()
        if rec is not None:
            rec.program_dispatch(self._span_fn, sp.start, sp.end,
                                 entry=getattr(spec, "cost_entry", None))
        return self._finish(spec, out_datas, mut_out)

    def _finish(self, spec, out_datas, mut_out):
        for t, v in zip(spec.trace_muts, mut_out):
            t._data = v
        out_tensors = [Tensor(d, _internal=True) for d in out_datas]
        return _unflatten(spec.out_struct, out_tensors)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=False, bucket_axes=None, share_discovery=False,
              in_shardings=None, **kwargs):
    """Decorator/wrapper compiling a dygraph callable into one XLA program.

    full_graph=False (default, ≙ SOT): a trace failure (data-dependent Python
    control flow) is a graph break — warns once and permanently falls back to
    eager for this function. full_graph=True (≙ AST mode): trace failure raises.

    bucket_axes: {arg_position: BucketAxis | axis | (axis, pad_value)} —
    varlen policy: the named tensor args are padded along `axis` up to bucket
    boundaries before cache lookup, so N distinct lengths compile O(log N)
    specializations instead of N (SURVEY §7 hard-part (3); the role of the
    reference's varlen flash-attention + SOT dynamic-shape guards).

    in_shardings: {tensor_leaf_position: jax Sharding} or
    callable(leaves) -> per-leaf Sharding list — applied as
    with_sharding_constraint on the traced arg inputs, so the compiled
    program carries real GSPMD in-specs (the declarative partitioner's
    plumb-through; distributed/partitioner).
    """

    def wrap(fn):
        if isinstance(fn, CompiledFunction):
            return fn
        from ..nn.layer_base import Layer

        donate = kwargs.get("donate_buffers")
        if isinstance(fn, Layer):
            layer = fn
            cf = CompiledFunction(layer.forward, input_spec, build_strategy, backend,
                                  full_graph, donate_buffers=donate,
                                  bucket_axes=bucket_axes,
                                  share_discovery=share_discovery,
                                  in_shardings=in_shardings)
            layer.forward = cf
            return layer
        return CompiledFunction(fn, input_spec, build_strategy, backend, full_graph,
                                donate_buffers=donate,
                                bucket_axes=bucket_axes,
                                share_discovery=share_discovery,
                                in_shardings=in_shardings)

    if function is not None:
        return wrap(function)
    return wrap
