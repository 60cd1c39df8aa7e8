"""Jaxpr-level program auditor (detectors D1-D4).

Walks the jaxpr of a compiled `CompiledFunction` specialization (via
``program_jaxpr()``, which needs FLAGS_jit_debug_program=1 at compile time)
and emits structured findings. Each detector generalizes a property an
earlier round proved with a one-off hand-written assertion:

  D1 dtype-stream  — under FLAGS_residual_dtype=bfloat16, no f32 tensor may
                     exist at residual-stream size, and no silent bf16->f32
                     promotion may re-widen the stream between kernels
                     (generalizes the round-8 test_pallas_norm jaxpr proof
                     from "the LLaMA block" to any captured program).
  D2 donation      — mutated captures (params/optimizer state in a train
                     step) that are NOT donated double their peak HBM; each
                     miss is reported with its byte cost.
  D3 host-sync     — device->host transfers inside a step: segmented-lazy
                     flush sites (graph breaks), eager fallbacks, and host
                     callback primitives left in the compiled program.
  D4 fusion-miss   — norm/rotary/SwiGLU/dropout-add compositions present in
                     the jaxpr that did not route to the Pallas fused
                     kernels of ops/pallas_norm.py, each annotated with the
                     gating reason (off-TPU, size threshold, dtype, GQA
                     mismatch) — legitimate gates are notes, a composition
                     that SHOULD have routed is a warning. Round-10 adds
                     the DECODE-ATTENTION anchor: a gather-over-cache
                     feeding rank-3 [S, H, T] attention scores that reach a
                     softmax (the seq-1-query paged decode composition of
                     ops/pallas_decode.py) — the gating reason is mirrored
                     from use_pallas_decode's real gates.

Sub-jaxpr recursion covers pjit/cond/while/scan/custom_vjp bodies but stops
at `pallas_call`: a kernel body is the fused implementation itself — its
internal f32 VMEM accumulation is exactly what the bf16-stream policy
permits, and its rsqrt IS the fused norm, not a missed one.
"""
from __future__ import annotations

import numpy as np

from .findings import Finding

#: primitives whose sub-jaxprs we do NOT descend into (see module doc)
_OPAQUE = {"pallas_call"}

#: primitives that force a device->host round trip inside a step (D3)
_HOST_SYNC_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                    "debug_print", "outfeed", "infeed")


def _closed(j):
    """Normalize Jaxpr/ClosedJaxpr to the raw Jaxpr."""
    return getattr(j, "jaxpr", j)


def _sub_jaxprs(params: dict):
    """Every jaxpr nested in an eqn's params (pjit jaxpr, cond branches,
    while cond/body, scan jaxpr, custom_vjp fun_jaxpr, ...)."""
    out = []
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "eqns") or hasattr(getattr(x, "jaxpr", None),
                                             "eqns"):
                out.append(x)
    return out


def iter_jaxprs(closed_jaxpr):
    """Yield every (sub-)jaxpr reachable from the root, skipping opaque
    (pallas kernel) bodies. Each yielded jaxpr is analyzed as one flat
    level — pattern matchers that chase producer/consumer edges work
    within a level."""
    stack = [_closed(closed_jaxpr)]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            if eqn.primitive.name in _OPAQUE:
                continue
            stack.extend(_closed(s) for s in _sub_jaxprs(eqn.params))


def iter_eqns(closed_jaxpr):
    for j in iter_jaxprs(closed_jaxpr):
        for eqn in j.eqns:
            yield eqn


def _aval(var):
    return getattr(var, "aval", None)


def _shape_dtype(var):
    av = _aval(var)
    if av is None or not hasattr(av, "shape"):
        return None, None
    return tuple(av.shape), str(getattr(av, "dtype", ""))


def _size(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def has_pallas_call(closed_jaxpr) -> bool:
    return any(e.primitive.name == "pallas_call"
               for e in iter_eqns(closed_jaxpr))


# --------------------------------------------------------------- D1 dtype

def infer_stream_shapes(closed_jaxpr, min_repeats: int = 3) -> list[tuple]:
    """Candidate residual-stream shapes: bf16 activation shapes (ndim >= 3)
    produced at least `min_repeats` times — the stream re-appears once or
    more per transformer layer, one-off tensors (logits, embeddings) don't.
    """
    counts: dict[tuple, int] = {}
    for eqn in iter_eqns(closed_jaxpr):
        for ov in eqn.outvars:
            shape, dt = _shape_dtype(ov)
            if shape is not None and dt == "bfloat16" and len(shape) >= 3:
                counts[shape] = counts.get(shape, 0) + 1
    return sorted(s for s, n in counts.items() if n >= min_repeats)


def audit_dtype_stream(closed_jaxpr, policy: str = "bfloat16",
                       stream_shapes=None, loc: str = "<program>"
                       ) -> list[Finding]:
    """D1. Under the bf16 residual-stream policy, every f32 value at stream
    shape is a policy violation crossing HBM in double width; a
    convert_element_type bf16->f32 at stream shape is additionally labeled
    a silent promotion (the usual culprit: an op outside the amp blacklist
    re-widening the stream between two fused kernels)."""
    if policy != "bfloat16":
        return []  # the f32-stream policy permits f32 everywhere
    if stream_shapes is None:
        stream_shapes = infer_stream_shapes(closed_jaxpr)
    targets = {tuple(s) for s in stream_shapes}
    if not targets:
        return []
    findings = []
    for eqn in iter_eqns(closed_jaxpr):
        for ov in eqn.outvars:
            shape, dt = _shape_dtype(ov)
            if shape not in targets or dt != "float32":
                continue
            prim = eqn.primitive.name
            if prim == "convert_element_type":
                in_dt = _shape_dtype(eqn.invars[0])[1]
                kind = (f"silent {in_dt}->f32 promotion"
                        if in_dt == "bfloat16" else f"{in_dt}->f32 cast")
            else:
                kind = f"f32 output of '{prim}'"
            findings.append(Finding(
                "dtype-stream", "warning", loc,
                f"{kind} at residual-stream shape {list(shape)} under the "
                "bfloat16 stream policy — this tensor crosses HBM at "
                "double width",
                {"shape": list(shape), "primitive": prim,
                 "bytes": _size(shape) * 4}))
    return findings


# ------------------------------------------------------------ D2 donation

def _tensor_bytes(t) -> int:
    data = getattr(t, "_data", None)
    nb = getattr(data, "nbytes", None)
    if nb is not None:
        return int(nb)
    shape = getattr(t, "shape", ())
    return _size(tuple(shape)) * 4


def audit_donation(cf, loc: str = "<function>") -> list[Finding]:
    """D2. A to_static train step whose mutated captures (params, optimizer
    moments) are not donated holds input AND output copies of every updated
    buffer live across the step — peak HBM cost = the full mutated set."""
    findings = []
    for key, spec in getattr(cf, "_cache", {}).items():
        muts = getattr(spec, "mut_caps", None) or []
        if not muts or getattr(spec, "donated", True):
            continue
        total = sum(_tensor_bytes(t) for t in muts)
        worst = sorted(muts, key=_tensor_bytes, reverse=True)[:5]
        findings.append(Finding(
            "donation", "warning", loc,
            f"{len(muts)} mutated capture(s) not donated — peak-HBM cost "
            f"{total / 2**20:.1f} MiB of duplicated buffers (donation "
            "would update them in place); largest: "
            + ", ".join(f"{getattr(t, 'name', '?')}"
                        f"{list(t.shape)}" for t in worst),
            {"buffers": len(muts), "bytes": total,
             "spec_key": key[:80]}))
    return findings


# ----------------------------------------------------------- D3 host sync

def audit_callbacks(closed_jaxpr, loc: str = "<program>") -> list[Finding]:
    """Host-callback primitives surviving in a compiled step: each is a
    device->host round trip per call."""
    findings = []
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name in _HOST_SYNC_PRIMS:
            findings.append(Finding(
                "host-sync", "warning", loc,
                f"host callback primitive '{eqn.primitive.name}' inside "
                "the compiled step — device->host sync every call",
                {"primitive": eqn.primitive.name}))
    return findings


def audit_host_sync(cf, loc: str = "<function>") -> list[Finding]:
    """D3. Per-finding view of the graph-break report (the per-report view
    is tools/report_graph_breaks.py): a segmented step pays one
    device->host sync per flush site per call; an eager fallback pays one
    per op."""
    rep = cf.graph_break_report()
    findings = []
    if rep["eager"]:
        findings.append(Finding(
            "host-sync", "warning", loc,
            "whole-function EAGER fallback — every op dispatches "
            f"individually (reason: {rep['break_reason']})",
            {"reason": rep["break_reason"]}))
    for s in rep["break_sites"]:
        findings.append(Finding(
            "host-sync", "warning", f"{s['loc']}",
            f"segment flush inside '{s['in']}' ({s['kind']}) — "
            f"device->host sync splitting the step into segments "
            f"({s['ops_in_segment']} staged op(s) before the flush)",
            dict(s)))
    if rep["segmented"] and not rep["break_sites"]:
        findings.append(Finding(
            "host-sync", "warning", loc,
            f"step runs SEGMENTED ({rep['segments']} segment(s)/call; "
            f"reason: {rep['break_reason']}) — enable "
            "FLAGS_lazy_break_sites for per-site locations",
            {"segments": rep["segments"], "reason": rep["break_reason"]}))
    return findings


# ---------------------------------------------------------- D4 fusion miss

#: primitives transparent to producer->consumer chasing (pure layout/dtype
#: plumbing between the pattern's anchor and its stream-size operand)
_TRANSPARENT = {"convert_element_type", "broadcast_in_dim", "reshape",
                "transpose", "copy"}


def _consumer_index(jaxpr):
    idx: dict = {}
    for eqn in jaxpr.eqns:
        for iv in eqn.invars:
            if _aval(iv) is not None and not isinstance(iv, (int, float)):
                idx.setdefault(id(iv), []).append(eqn)
    return idx


def _chase_to_mul(jaxpr, idx, var, depth=6):
    """Follow `var` through transparent ops to the first `mul` consumer;
    returns that mul eqn or None."""
    frontier = [var]
    for _ in range(depth):
        nxt = []
        for v in frontier:
            for eqn in idx.get(id(v), []):
                if eqn.primitive.name == "mul":
                    return eqn
                if eqn.primitive.name in _TRANSPARENT:
                    nxt.extend(eqn.outvars)
        frontier = nxt
        if not frontier:
            break
    return None


#: consumer plumbing between decode scores and their softmax (scale
#: divide, length-mask select/where — possibly wrapped in a jit — dtype
#: widening); producer plumbing between the cache gather and the score
#: matmul (layout + GQA head repeat)
_SOFTMAX_THROUGH = _TRANSPARENT | {"div", "mul", "sub", "max", "min",
                                   "select_n", "jit", "stop_gradient",
                                   "custom_jvp_call",
                                   "custom_jvp_call_jaxpr"}
_SOFTMAX_ANCHORS = {"reduce_max", "exp"}


def _chase_to_prims(idx, var, targets, through, depth=8):
    """Follow `var` through `through` ops to the first consumer in
    `targets`; returns that eqn or None."""
    frontier = [var]
    for _ in range(depth):
        nxt = []
        for v in frontier:
            for eqn in idx.get(id(v), []):
                if eqn.primitive.name in targets:
                    return eqn
                if eqn.primitive.name in through:
                    nxt.extend(eqn.outvars)
        frontier = nxt
        if not frontier:
            break
    return None


def _produced_by(producers, var, targets, through, depth=8):
    """Walk `var`'s producer chain through `through` ops; True when a
    producer in `targets` is reached."""
    frontier = [var]
    for _ in range(depth):
        nxt = []
        for v in frontier:
            eqn = producers.get(id(v))
            if eqn is None:
                continue
            if eqn.primitive.name in targets:
                return True
            if eqn.primitive.name in through:
                nxt.extend(eqn.invars)
        frontier = nxt
        if not frontier:
            break
    return False


def _gate_reason(n_elems: int, dtype: str, platform: str):
    """Why ops/pallas_norm.use_pallas would decline this tensor — mirrors
    its gate order so the reported reason is the real one."""
    from ..core.flags import flag
    from ..ops.pallas_norm import _MIN_ELEMS, _SUPPORTED_DTYPES

    if not flag("FLAGS_pallas_fused_ops"):
        return "FLAGS_pallas_fused_ops=0 (fused kernels disabled)", "note"
    if platform != "tpu":
        return ("not on TPU — the XLA composition is the intended "
                "fallback path here"), "note"
    if n_elems < _MIN_ELEMS:
        return (f"below the fused-kernel size threshold "
                f"({n_elems} < {_MIN_ELEMS} elements: launch overhead "
                "beats the bandwidth saving)"), "note"
    if dtype not in _SUPPORTED_DTYPES:
        return f"dtype {dtype} unsupported by the fused kernels", "note"
    return ("no gating reason — this composition should have routed to "
            "the Pallas fused kernel"), "warning"


def audit_fusion_misses(closed_jaxpr, platform: str | None = None,
                        min_elems: int | None = None,
                        loc: str = "<program>") -> list[Finding]:
    """D4. Pattern-match the XLA compositions the Pallas fused kernels
    replace; every match that is NOT a pallas_call is a fusion miss with
    its gating reason. Anchors (cheap and low-false-positive):

      norm       — `rsqrt` whose output reaches a `mul` on a stream-size
                   tensor (rms/layer norm both normalize via rsqrt)
      swiglu     — `logistic` (sigmoid) whose output reaches a `mul`
                   (silu(gate)*up keeps two stream-size HBM round trips)
      rotary     — `concatenate` with a `neg`-produced operand (the
                   rotate-half) feeding `mul`s against cos/sin tables
      dropout-add— RNG bits compared (`lt/gt/ge/le`) then scaled into a
                   stream-size `mul` (mask materialized + separate add)
      decode-attn— a `dot_general` emitting rank-3 [S, H, T] scores whose
                   CACHE side comes from a `gather` (the block-table page
                   gather) and whose output reaches a softmax — the seq-1
                   paged decode composition that should ride
                   ops/pallas_decode.py's kernel on TPU; gating reason
                   mirrored from use_pallas_decode (off-TPU/size/dtype/
                   head-dim alignment are notes, should-have-routed is a
                   warning)
    """
    import jax

    from ..core.flags import flag

    if platform is None:
        platform = jax.default_backend()
    if min_elems is None:
        min_elems = int(flag("FLAGS_analysis_fusion_min_elems"))
    findings = []
    rope_head_counts: list[int] = []
    rope_findings: list[Finding] = []

    def emit(kind, shape, dtype, extra=None):
        n = _size(shape)
        if n < min_elems:
            return None
        reason, sev = _gate_reason(n, dtype, platform)
        if extra:
            reason = f"{extra}; {reason}"
        f = Finding(
            "fusion-miss", sev, loc,
            f"{kind} composition at {dtype}{list(shape)} did not route to "
            f"the Pallas fused kernel: {reason}",
            {"kind": kind, "shape": list(shape), "dtype": dtype,
             "elements": n, "gate": reason})
        findings.append(f)
        return f

    has_rng = any(e.primitive.name in ("random_bits", "threefry2x32")
                  for e in iter_eqns(closed_jaxpr))

    def emit_decode(eqn):
        """The decode-attention anchor's finding: severity from the REAL
        routing gates of ops/pallas_decode (ONE definition, so the
        reported reason can never drift from what the router would do)."""
        from ..ops.pallas_decode import decode_gate_reason

        shape, dtype = _shape_dtype(eqn.outvars[0])
        if shape is None:
            return
        n = _size(shape)
        if n < min_elems:
            return
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = _shape_dtype(eqn.invars[0])[0] or ()
        head_dim = lhs_shape[lhs_c[0]] if lhs_c else None
        in_dtype = _shape_dtype(eqn.invars[0])[1]
        reason, sev = decode_gate_reason(n, in_dtype, platform,
                                         head_dim=head_dim)
        findings.append(Finding(
            "fusion-miss", sev, loc,
            f"decode-attention composition (gather-over-cache + softmax "
            f"at seq-1 query scores {in_dtype}{list(shape)}) did not "
            f"route to the Pallas decode kernel: {reason}",
            {"kind": "decode-attn", "shape": list(shape),
             "dtype": in_dtype, "elements": n, "gate": reason}))

    for j in iter_jaxprs(closed_jaxpr):
        idx = _consumer_index(j)
        producers = {id(ov): e for e in j.eqns for ov in e.outvars}
        for eqn in j.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                shape = _shape_dtype(eqn.outvars[0])[0]
                if (shape is not None and len(shape) == 3
                        and _produced_by(producers, eqn.invars[1],
                                         {"gather"},
                                         _TRANSPARENT | {"mul"})
                        and _chase_to_prims(idx, eqn.outvars[0],
                                            _SOFTMAX_ANCHORS,
                                            _SOFTMAX_THROUGH) is not None):
                    emit_decode(eqn)
                continue
            if prim in ("rsqrt", "logistic"):
                mul = _chase_to_mul(j, idx, eqn.outvars[0])
                if mul is None:
                    continue
                shape, dtype = _shape_dtype(mul.outvars[0])
                if shape is None:
                    continue
                emit("norm" if prim == "rsqrt" else "swiglu/silu",
                     shape, dtype)
            elif prim == "concatenate":
                if not any(producers.get(id(iv)) is not None
                           and producers[id(iv)].primitive.name == "neg"
                           for iv in eqn.invars):
                    continue
                mul = _chase_to_mul(j, idx, eqn.outvars[0])
                if mul is None:
                    continue
                shape, dtype = _shape_dtype(eqn.outvars[0])
                if shape is None or len(shape) != 4:
                    continue
                f = emit("rotary", shape, dtype)
                if f is not None:
                    rope_head_counts.append(int(shape[2]))
                    rope_findings.append(f)
            elif prim in ("lt", "gt", "ge", "le") and has_rng:
                mul = _chase_to_mul(j, idx, eqn.outvars[0])
                if mul is None:
                    continue
                shape, dtype = _shape_dtype(mul.outvars[0])
                if shape is None:
                    continue
                emit("dropout-add", shape, dtype)

    # fused rope shares one block shape between Q and K: two rotary sites
    # with different head counts is the GQA gate from round 8
    if len(set(rope_head_counts)) > 1:
        for f in rope_findings:
            f.data["gate"] = (
                "GQA head-count mismatch (fused rope kernel shares Q/K "
                "block shapes); " + f.data["gate"])
            f.message += " [GQA head-count mismatch across rotary sites]"
    return findings


# --------------------------------------------------------------- umbrella

def audit_compiled(cf, policy: str | None = None,
                   platform: str | None = None,
                   loc: str = "<function>") -> list[Finding]:
    """Run every jaxpr/function-level detector over a CompiledFunction:
    D3 on the capture outcome, D2 on the donation state, and (for each
    compiled specialization whose program was retained) D1/D4 plus the
    callback scan on the jaxpr."""
    from ..core.flags import flag

    findings = list(audit_host_sync(cf, loc))
    findings += audit_donation(cf, loc)
    if policy is None:
        policy = str(flag("FLAGS_residual_dtype"))
    for key, spec in getattr(cf, "_cache", {}).items():
        if getattr(spec, "debug", None) is None:
            findings.append(Finding(
                "auditor", "note", loc,
                "specialization compiled without FLAGS_jit_debug_program=1 "
                "— jaxpr detectors (dtype-stream, fusion-miss, callbacks) "
                "skipped for it", {"spec_key": str(key)[:80]}))
            continue
        jx = cf.program_jaxpr(key)
        findings += audit_dtype_stream(jx, policy=policy, loc=loc)
        findings += audit_fusion_misses(jx, platform=platform, loc=loc)
        findings += audit_callbacks(jx, loc=loc)
    return findings
