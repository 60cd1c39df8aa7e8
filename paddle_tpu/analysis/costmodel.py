"""Static cost model — predict step time + peak HBM from the jaxpr.

ROADMAP item 4 ("close the loop the ledgers enable"): the repo already
MEASURES flops / bytes-accessed per compiled program (obs/costs.py, D8)
and per-axis collective byte volume (D10) — this pass PREDICTS them for
a candidate plan before anything runs, over the same ProgramIndex walk
every other detector reads:

  compute_ms     per-eqn flop estimate (dot_general = 2·B·M·K·N from its
                 dimension numbers, transcendentals weighted, reductions
                 by input size; `scan` bodies multiplied by trip count)
                 at FLAGS_obs_peak_tflops, divided by the plan's compute
                 parallelism.
  hbm_ms         per-eqn bytes-accessed at FLAGS_obs_peak_gbps. Only
                 MATERIALIZING primitives (matmuls, reductions, gathers,
                 reshapes-through-memory) are charged — elementwise ops
                 are assumed fused into their consumers, matching how
                 XLA's own bytes-accessed counts after fusion.
  collective_ms  alpha-beta interconnect model with DISTINCT fabrics:
                 mesh axes a MeshConfig maps to `dcn_axes` are charged
                 at FLAGS_analysis_dcn_gbps / _dcn_alpha_us, everything
                 else at the ICI rates (the hybrid-mesh split ROADMAP
                 item 1 anticipates). Jaxpr-level collective sites (D10)
                 are charged directly; GSPMD collectives live in HLO
                 below the jaxpr, so plan-derived volumes arrive as
                 `extra_collectives` (autoplan computes them from the
                 rule-table plan).
  peak_hbm       a LIVENESS pass over the jaxpr: per-buffer lifetime
                 intervals in eqn order. Non-donated inputs are live for
                 the whole program (the caller keeps them); donated
                 inputs (D2's records) die at last use — exactly why
                 donation halves a train step's param footprint. Remat
                 falls out structurally: a checkpoint body's residuals
                 are not its outputs, so they die inside it. Per-device
                 shard bytes come from a `live_bytes` callback (autoplan
                 divides by the plan's shard factors).

The roofline composition is `max(compute, hbm) + collective` — compute
overlaps HBM traffic (that is what a roofline says), collectives are
charged exposed (the pessimistic, schedule-free bound).

Two gated detectors ride the Finding/baseline machinery:

  D18 `audit_plan`  — the chosen MeshConfig predicted at least
      FLAGS_analysis_plan_regress_pct slower than the best valid
      candidate in its PlanReport is a warning; predicted peak HBM over
      FLAGS_analysis_hbm_limit_mb is an error (an OOM caught at lint
      time, not at runtime).
  D19 `audit_cost_model_calibration` — the predicted ordering of the
      top candidates must match the MEASURED tok/s ordering (the
      partitioner_scaling harness). A model that mispredicts ordering
      is a silently-dead analysis and fails the gate. Virtual-mesh
      walls are noisy, so a pair only counts as a misprediction when
      the measured winner beats the predicted winner by more than
      FLAGS_analysis_calibration_tol_pct.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..core.flags import flag
from .dataflow import (COLLECTIVE_PRIMS, STOP_PRIMS, ProgramIndex, _closed,
                       _nbytes, _shape_dtype, _size, _sub_jaxprs)
from .findings import Finding

# --------------------------------------------------------------- flops
#: primitives whose per-element cost is far above one flop (polynomial
#: approximations on the VPU) — weighted so a softmax-heavy program is
#: not scored like an add
TRANSCENDENTAL_PRIMS = frozenset({
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "erf", "erfc",
    "erf_inv", "rsqrt", "sqrt", "sin", "cos", "tan", "pow",
    "integer_pow", "cbrt", "lgamma", "digamma"})
TRANSCENDENTAL_FLOPS = 8.0

#: reduction-shaped primitives: flops ~ input size (one combine per
#: input element)
REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax",
    "cumprod", "reduce_precision"})


def eqn_flops(eqn) -> float:
    """Estimated flops of ONE eqn (its body NOT multiplied by any
    enclosing scan trip count — `estimate_flops` owns multipliers)."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        dn = eqn.params.get("dimension_numbers")
        if dn is None:
            return 0.0
        (lc, rc), (lb, _rb) = dn
        lshape, _ = _shape_dtype(eqn.invars[0])
        rshape, _ = _shape_dtype(eqn.invars[1])
        if lshape is None or rshape is None:
            return 0.0
        batch = _size(tuple(lshape[i] for i in lb))
        k = _size(tuple(lshape[i] for i in lc))
        m = _size(lshape) // max(batch * k, 1)
        n = _size(rshape) // max(batch * k, 1)
        return 2.0 * batch * m * k * n
    if prim == "conv_general_dilated":
        # 2 * out_elems * (receptive field): rhs holds in_ch x kernel
        oshape, _ = _shape_dtype(eqn.outvars[0])
        rshape, _ = _shape_dtype(eqn.invars[1])
        if oshape is None or rshape is None:
            return 0.0
        rfield = _size(rshape) // max(rshape[0] if rshape else 1, 1)
        return 2.0 * _size(oshape) * max(rfield, 1)
    out_elems = sum(_size(_shape_dtype(ov)[0] or ()) for ov in eqn.outvars)
    if prim in TRANSCENDENTAL_PRIMS:
        return TRANSCENDENTAL_FLOPS * out_elems
    if prim in REDUCE_PRIMS:
        return float(sum(_size(_shape_dtype(iv)[0] or ())
                         for iv in eqn.invars
                         if _shape_dtype(iv)[0] is not None))
    return float(out_elems)


#: primitives that MATERIALIZE their operands/results through HBM even
#: after XLA fusion — everything else is assumed fused into a consumer
#: (elementwise chains cost zero extra traffic, which is how the real
#: bytes-accessed analysis counts them too)
MATERIALIZE_PRIMS = frozenset(
    {"dot_general", "conv_general_dilated", "gather", "scatter",
     "scatter_add", "dynamic_slice", "dynamic_update_slice", "sort",
     "top_k", "cumsum", "while", "scan", "pallas_call", "rng_bit_generator",
     "custom_jvp_call", "custom_vjp_call"}
    | REDUCE_PRIMS | COLLECTIVE_PRIMS)


def eqn_bytes(eqn) -> float:
    """HBM bytes ONE eqn moves: operand + result bytes for materializing
    primitives, zero for fusable elementwise ops."""
    if eqn.primitive.name not in MATERIALIZE_PRIMS:
        return 0.0
    ins = sum(_nbytes(iv) for iv in eqn.invars
              if _shape_dtype(iv)[0] is not None)
    outs = sum(_nbytes(ov) for ov in eqn.outvars)
    return float(ins + outs)


def _walk_eqns(jaxpr, mult=1.0):
    """(eqn, multiplier) over every eqn, descending into sub-jaxprs with
    `scan` bodies multiplied by their trip count. STOP_PRIMS bodies
    (pallas kernels) are charged at the call eqn, not walked."""
    for eqn in _closed(jaxpr).eqns:
        prim = eqn.primitive.name
        yield eqn, mult
        if prim in STOP_PRIMS:
            continue
        sub_mult = mult
        if prim == "scan":
            sub_mult = mult * max(int(eqn.params.get("length", 1) or 1), 1)
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub, sub_mult)


#: higher-order prims whose own eqn must not ALSO be charged when the
#: walk descends into the body (the body already carries the cost)
_HOP_TRANSPARENT = frozenset({
    "jit", "closed_call", "core_call", "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr", "scan",
    "while", "cond", "shard_map", "named_call"})


def estimate_flops(jaxpr_or_index) -> float:
    """Whole-program flop estimate (global shapes — divide by the plan's
    compute parallelism for per-device time)."""
    root = _root_jaxpr(jaxpr_or_index)
    total = 0.0
    for eqn, mult in _walk_eqns(root):
        if eqn.primitive.name in _HOP_TRANSPARENT \
                and _sub_jaxprs(eqn.params):
            continue
        total += mult * eqn_flops(eqn)
    return total


def estimate_bytes(jaxpr_or_index) -> float:
    """Whole-program HBM bytes-accessed estimate (fusion-aware: only
    MATERIALIZE_PRIMS are charged), plus program argument/result I/O."""
    root = _root_jaxpr(jaxpr_or_index)
    jx = _closed(root)
    total = 0.0
    for eqn, mult in _walk_eqns(root):
        if eqn.primitive.name in _HOP_TRANSPARENT \
                and _sub_jaxprs(eqn.params):
            continue
        total += mult * eqn_bytes(eqn)
    total += sum(_nbytes(v) for v in list(jx.constvars) + list(jx.invars))
    total += sum(_nbytes(v) for v in jx.outvars
                 if _shape_dtype(v)[0] is not None)
    return total


def _root_jaxpr(jaxpr_or_index):
    if isinstance(jaxpr_or_index, ProgramIndex):
        return jaxpr_or_index.root
    return jaxpr_or_index


# --------------------------------------------------- alpha-beta fabric
def fabric_rates(fabric: str) -> tuple:
    """(gbps, alpha_us) for one interconnect: "ici" (intra-slice) or
    "dcn" (cross-host) — the FLAGS_analysis_* knobs."""
    if fabric == "dcn":
        return (float(flag("FLAGS_analysis_dcn_gbps")),
                float(flag("FLAGS_analysis_dcn_alpha_us")))
    return (float(flag("FLAGS_analysis_ici_gbps")),
            float(flag("FLAGS_analysis_ici_alpha_us")))


def collective_time_us(prim: str, nbytes: float, axis_size: int, *,
                       gbps: float | None = None,
                       alpha_us: float | None = None,
                       fabric: str = "ici") -> float:
    """Alpha-beta time of one collective over one mesh axis.

    `nbytes` is the PER-DEVICE payload the op materializes (what
    CollectiveSite.out_bytes records): the gathered array for
    all_gather, the reduced array for psum. Ring algorithms:

      all_gather / reduce_scatter / all_to_all:
          (n-1) * (alpha + (nbytes/n) / bw)
      psum (all-reduce = reduce_scatter + all_gather):
          2 * (n-1) * (alpha + (nbytes/n) / bw)
      ppermute (one neighbor hop, full payload):
          alpha + nbytes / bw

    Hand check (tests/test_costmodel.py): a 1 MB (1e6 B) all_gather over
    a 2-device axis at 1 GB/s with 1 us alpha is exactly
    (2-1) * (1 + (1e6/2)/1e3) = 501 us.
    """
    n = max(int(axis_size), 1)
    if n <= 1 or nbytes <= 0:
        return 0.0
    if gbps is None or alpha_us is None:
        fg, fa = fabric_rates(fabric)
        gbps = fg if gbps is None else gbps
        alpha_us = fa if alpha_us is None else alpha_us
    bytes_per_us = max(float(gbps), 1e-12) * 1e3   # 1 GB/s = 1e3 B/us
    chunk_us = (float(nbytes) / n) / bytes_per_us
    if prim in ("psum", "pmax", "pmin", "reduce_precision_psum"):
        return 2.0 * (n - 1) * (alpha_us + chunk_us)
    if prim in ("all_gather", "reduce_scatter", "all_to_all", "pgather"):
        return (n - 1) * (alpha_us + chunk_us)
    # ppermute and anything unrecognized: one hop, full payload
    return alpha_us + float(nbytes) / bytes_per_us


def collective_time(index: ProgramIndex | None, config=None,
                    extra_collectives=()) -> tuple:
    """(total_ms, per_axis_us) over every jaxpr-level collective site in
    `index` (D10's walk) plus analytic `extra_collectives` entries of
    (prim, axis, nbytes, count) — GSPMD's HLO-level collectives that a
    plan implies but the jaxpr cannot show (the D10 boundary).

    Axis sizes resolve from the MeshConfig when given (abstract
    candidates), else from the index's recorded meshes; the fabric per
    axis is `config.fabric(axis)` (ICI without a config)."""
    sizes = dict(getattr(index, "mesh_axes", {}) or {}) if index else {}
    if config is not None:
        sizes.update(config.axis_sizes)
    per_axis: dict = {}
    total_us = 0.0
    sites = list(getattr(index, "collectives", ()) or ()) if index else []
    entries = [(c.prim, c.axes or ("<unnamed>",), c.out_bytes, 1)
               for c in sites]
    entries += [(prim, (axis,), nbytes, count)
                for prim, axis, nbytes, count in extra_collectives]
    for prim, axes, nbytes, count in entries:
        for ax in axes:
            n = int(sizes.get(ax, 0) or 0)
            fabric = config.fabric(ax) if config is not None \
                and hasattr(config, "fabric") else "ici"
            us = collective_time_us(prim, nbytes, n, fabric=fabric) \
                * max(int(count), 0)
            per_axis[ax] = per_axis.get(ax, 0.0) + us
            total_us += us
    return total_us / 1e3, per_axis


# ------------------------------------------------------------ liveness
def liveness_peak_bytes(jaxpr_or_index, donated=(), live_bytes=None) -> int:
    """Peak resident bytes of one program by per-buffer lifetimes.

    Walks eqns in order; a var is born at its producer and dies after
    its last consumer. Program inputs/consts are live from the start;
    NON-donated inputs stay live for the whole program (the caller owns
    those buffers), donated inputs (`donated`: invar positions or var
    objects — D2's mut_caps records) die at their last use, which is
    exactly the in-place-update footprint saving. Outputs stay live to
    the end. Sub-jaxpr bodies (pjit/scan/remat) contribute their own
    internal peak minus the operands already counted outside — so a
    remat body's residuals never escape it.

    `live_bytes(var) -> bytes` overrides the per-var byte count (the
    autoplan path divides by each buffer's per-device shard factor);
    default is the global (unsharded) size."""
    root = _closed(_root_jaxpr(jaxpr_or_index))
    nbytes = live_bytes or _nbytes
    donated = set(donated or ())
    donated_ids = set()
    for d in donated:
        if isinstance(d, int):
            if 0 <= d < len(root.invars):
                donated_ids.add(id(root.invars[d]))
        else:
            donated_ids.add(id(d))
    return _jaxpr_peak(root, donated_ids, nbytes)


def _jaxpr_peak(jaxpr, donated_ids, nbytes) -> int:
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for iv in eqn.invars:
            if _shape_dtype(iv)[0] is not None:
                last_use[id(iv)] = i
    out_ids = {id(ov) for ov in jaxpr.outvars
               if _shape_dtype(ov)[0] is not None}
    persistent = set(out_ids)
    sizes: dict = {}
    live = 0
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        if _shape_dtype(v)[0] is None:
            continue
        b = int(nbytes(v))
        sizes[id(v)] = b
        live += b
        if id(v) not in donated_ids:
            persistent.add(id(v))
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        born = 0
        for ov in eqn.outvars:
            if _shape_dtype(ov)[0] is None:
                continue
            b = int(nbytes(ov))
            sizes[id(ov)] = b
            born += b
        inner = 0
        if eqn.primitive.name not in STOP_PRIMS:
            subs = _sub_jaxprs(eqn.params)
            if subs:
                operand = sum(sizes.get(id(iv), 0) for iv in eqn.invars)
                inner = max(_jaxpr_peak(_closed(s), set(), nbytes)
                            for s in subs)
                inner = max(inner - operand, 0)
        peak = max(peak, live + born + inner)
        live += born
        for ov in eqn.outvars:          # dead code: never consumed
            if id(ov) in sizes and id(ov) not in last_use \
                    and id(ov) not in persistent:
                live -= sizes[id(ov)]
        for vid, j in list(last_use.items()):
            if j == i and vid not in persistent and vid in sizes:
                live -= sizes.pop(vid)
                del last_use[vid]
    return int(peak)


# ---------------------------------------------------------- prediction
@dataclass
class CostPrediction:
    """One candidate plan's predicted step profile (all per-device)."""

    flops: float = 0.0              # whole-program (global shapes)
    bytes_accessed: float = 0.0     # whole-program (global shapes)
    compute_ms: float = 0.0
    hbm_ms: float = 0.0
    collective_ms: float = 0.0
    step_ms: float = 0.0            # max(compute, hbm) + collective
    peak_hbm_bytes: int = 0
    num_devices: int = 1
    per_axis_collective_us: dict = field(default_factory=dict)
    notes: tuple = ()

    @property
    def peak_hbm_mb(self) -> float:
        return self.peak_hbm_bytes / 2 ** 20

    def to_dict(self) -> dict:
        return {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "compute_ms": round(self.compute_ms, 4),
                "hbm_ms": round(self.hbm_ms, 4),
                "collective_ms": round(self.collective_ms, 4),
                "predicted_step_ms": round(self.step_ms, 4),
                "peak_hbm_mb": round(self.peak_hbm_mb, 3),
                "num_devices": self.num_devices,
                "per_axis_collective_us": {
                    k: round(v, 2)
                    for k, v in self.per_axis_collective_us.items()},
                "notes": list(self.notes)}


def predict_step(jaxpr_or_index, config=None, *, compute_divisor=None,
                 hbm_divisor=None, donated=(), live_bytes=None,
                 extra_collectives=(), extra_hbm_bytes=0,
                 extra_serial_bytes=0, notes=()) -> CostPrediction:
    """Predict one partitioned train/serving step from its (abstract or
    compiled) jaxpr. See the module doc for the model; `autoplan` feeds
    the plan-derived divisors, donation records, shard-aware
    `live_bytes` and analytic GSPMD `extra_collectives`.
    `extra_serial_bytes` is HBM traffic moved in DEPENDENT stages that
    cannot overlap compute (ring-attention hop rescales) — charged at
    peak bandwidth on top of the roofline max, like collectives."""
    from ..obs.costs import peak_gbps
    from ..obs.goodput import peak_tflops

    index = ProgramIndex.ensure(jaxpr_or_index) \
        if not isinstance(jaxpr_or_index, ProgramIndex) else jaxpr_or_index
    ndev = int(getattr(config, "num_devices", 1) or 1) if config else 1
    flops = estimate_flops(index)
    nbytes = estimate_bytes(index)
    cdiv = float(compute_divisor if compute_divisor else ndev) or 1.0
    hdiv = float(hbm_divisor if hbm_divisor else ndev) or 1.0
    compute_ms = flops / cdiv / (peak_tflops() * 1e12) * 1e3
    hbm_ms = nbytes / hdiv / (peak_gbps() * 1e9) * 1e3
    coll_ms, per_axis = collective_time(index, config, extra_collectives)
    serial_ms = float(extra_serial_bytes) / (peak_gbps() * 1e9) * 1e3
    peak = liveness_peak_bytes(index, donated=donated,
                               live_bytes=live_bytes) + int(extra_hbm_bytes)
    return CostPrediction(
        flops=flops, bytes_accessed=nbytes, compute_ms=compute_ms,
        hbm_ms=hbm_ms, collective_ms=coll_ms + serial_ms,
        step_ms=max(compute_ms, hbm_ms) + coll_ms + serial_ms,
        peak_hbm_bytes=peak,
        num_devices=ndev, per_axis_collective_us=per_axis,
        notes=tuple(notes))


# ------------------------------------------------------- D18 audit_plan
def _describe(config_or_str) -> str:
    if config_or_str is None:
        return ""
    if isinstance(config_or_str, str):
        return config_or_str
    return config_or_str.describe()


def audit_plan(report, chosen=None, *, regress_pct=None,
               hbm_limit_mb=None, loc="autoplan") -> list:
    """D18 — is the plan you picked defensible against the search?

    `report` is an `autoplan.PlanReport` (ranked valid candidates with
    predictions + named rejections); `chosen` is the MeshConfig (or its
    describe() string) actually deployed, defaulting to the report's
    top-1. Warnings/errors:

      * chosen predicted >= `regress_pct` (FLAGS_analysis_plan_regress_pct)
        slower than the best valid candidate -> warning;
      * chosen predicted peak HBM over `hbm_limit_mb`
        (FLAGS_analysis_hbm_limit_mb; 0 = off) -> error;
      * chosen was REJECTED by the search (divisibility, dead axis, or
        over-budget HBM) -> error.
    """
    if regress_pct is None:
        regress_pct = float(flag("FLAGS_analysis_plan_regress_pct"))
    if hbm_limit_mb is None:
        hbm_limit_mb = float(flag("FLAGS_analysis_hbm_limit_mb"))
    findings: list = []
    cands = list(getattr(report, "candidates", ()) or ())
    if not cands:
        findings.append(Finding(
            "plan", "warning", loc,
            "PlanReport has no valid candidates — every enumerated "
            "MeshConfig was rejected; nothing to rank the chosen plan "
            "against",
            data={"rejected": len(getattr(report, "rejected", ()) or ())}))
        return findings
    best = cands[0]
    want = _describe(chosen) or best.describe
    match = next((c for c in cands if c.describe == want), None)
    if match is None:
        rej = next((r for r in getattr(report, "rejected", ()) or ()
                    if r.get("config") == want), None)
        findings.append(Finding(
            "plan", "error", f"{loc}:{want}",
            f"chosen config {want} is not a valid candidate"
            + (f" — the search rejected it: {'; '.join(rej['reasons'])}"
               if rej else " — the search never enumerated it "
               "(wrong device count for this pod?)"),
            data={"chosen": want,
                  "reasons": (rej or {}).get("reasons", [])}))
        return findings
    slow = match.prediction.step_ms
    fast = best.prediction.step_ms
    if fast > 0 and (slow - fast) / fast * 100.0 >= regress_pct \
            and match.describe != best.describe:
        findings.append(Finding(
            "plan", "warning", f"{loc}:{want}",
            f"chosen config {want} is predicted "
            f"{(slow - fast) / fast:+.0%} slower than the best valid "
            f"candidate {best.describe} ({slow:.3f} ms vs {fast:.3f} ms "
            f"predicted step; threshold {regress_pct:g}%) — the plan "
            "search found a better mesh for this model",
            data={"chosen": want, "best": best.describe,
                  "chosen_ms": round(slow, 4), "best_ms": round(fast, 4),
                  "regress_pct": regress_pct}))
    peak_mb = match.prediction.peak_hbm_mb
    if hbm_limit_mb > 0 and peak_mb > hbm_limit_mb:
        findings.append(Finding(
            "plan", "error", f"{loc}:{want}",
            f"chosen config {want} predicted peak HBM {peak_mb:.1f} MiB "
            f"exceeds the {hbm_limit_mb:g} MiB budget "
            "(FLAGS_analysis_hbm_limit_mb) — this plan OOMs; rejected "
            "statically instead of at runtime",
            data={"chosen": want, "peak_hbm_mb": round(peak_mb, 2),
                  "hbm_limit_mb": hbm_limit_mb}))
    if not findings:
        findings.append(Finding(
            "plan", "note", loc,
            f"plan ok: chosen {want} within {regress_pct:g}% of the best "
            f"valid candidate ({len(cands)} ranked, "
            f"{len(getattr(report, 'rejected', ()) or ())} rejected)",
            data={"chosen": want, "candidates": len(cands)}))
    return findings


# ------------------------------------- D19 cost-model calibration gate
def audit_cost_model_calibration(report, measured, *, top=3,
                                 tol_pct=None,
                                 loc="autoplan") -> list:
    """D19 — does the static model predict the MEASURED ordering?

    `measured` maps config describe() strings to measured tok/s (the
    partitioner_scaling harness). The predicted ranking restricted to
    the measured configs (first `top`) must match the measured tok/s
    ordering: any pair where the predicted-slower config measures more
    than `tol_pct` faster than the predicted-faster one is an ERROR —
    a cost model that misorders real configs is a silently-dead
    analysis, and the gate exists to catch exactly that."""
    if tol_pct is None:
        tol_pct = float(flag("FLAGS_analysis_calibration_tol_pct"))
    findings: list = []
    cands = [c for c in (getattr(report, "candidates", ()) or ())
             if c.describe in measured][:max(int(top), 2)]
    if len(cands) < 2:
        findings.append(Finding(
            "cost-model-calibration", "note", loc,
            f"calibration skipped: {len(cands)} predicted candidate(s) "
            f"overlap the {len(measured)} measured config(s) — need 2 "
            "(run the autoplan bench rung to produce measured rows)",
            data={"measured": sorted(measured)}))
        return findings
    mis = []
    for i in range(len(cands)):
        for j in range(i + 1, len(cands)):
            fast, slow = cands[i], cands[j]      # predicted order
            m_fast = float(measured[fast.describe])
            m_slow = float(measured[slow.describe])
            if m_fast <= 0:
                continue
            if m_slow > m_fast * (1.0 + tol_pct / 100.0):
                mis.append((fast, slow, m_fast, m_slow))
    for fast, slow, m_fast, m_slow in mis:
        findings.append(Finding(
            "cost-model-calibration", "error",
            f"{loc}:{fast.describe}",
            f"cost model misprediction: {fast.describe} ranked above "
            f"{slow.describe} ({fast.prediction.step_ms:.3f} vs "
            f"{slow.prediction.step_ms:.3f} ms predicted) but measured "
            f"tok/s says otherwise ({m_fast:.0f} vs {m_slow:.0f}, "
            f"{(m_slow - m_fast) / m_fast:+.0%} past the {tol_pct:g}% "
            "tolerance) — the static model misorders real configs and "
            "its rankings cannot be trusted",
            data={"predicted_faster": fast.describe,
                  "predicted_slower": slow.describe,
                  "measured_fast": round(m_fast, 1),
                  "measured_slow": round(m_slow, 1),
                  "tol_pct": tol_pct}))
    if not mis:
        order = [c.describe for c in cands]
        findings.append(Finding(
            "cost-model-calibration", "note", loc,
            f"calibration ok: predicted top-{len(cands)} ordering "
            f"matches measured tok/s (within {tol_pct:g}% ties): "
            f"{' > '.join(order)}",
            data={"order": order, "tol_pct": tol_pct,
                  "measured": {k: round(float(v), 1)
                               for k, v in measured.items()
                               if k in order}}))
    return findings
