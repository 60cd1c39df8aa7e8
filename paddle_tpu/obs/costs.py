"""Compiled-program cost attribution — the ledger that turns the roofline
from a hand-computed PERF.md footnote into continuously measured data.

Every AOT compile site (the serving engine's bucket programs, the static
generation engine, `to_static` under FLAGS_jit_debug_program) hands its
compiled executable here; XLA's own `cost_analysis()` /
`memory_analysis()` give flops, bytes accessed and the HBM footprint
(argument/output/temp bytes) **for free** — the analysis rides the
executable object, no extra trace or compile is paid. The eager dispatch
cache registers its entries too (count + key only: per-op executables
lower lazily inside jax.jit, forcing an analysis there would cost one
extra compile per op — by design the ledger's cost rows are
program-scale, not op-scale).

Combining the static bytes with measured wall time per execution yields
the roofline story per program:

    achieved GB/s = bytes_accessed * executions / exec_wall
    roofline_utilization{program} = achieved / peak     (obs gauge)

`tools/roofline_report.py` prints the table; bench serving/decode rungs
attach the same numbers to their rows; and **analysis D8**
(`audit_cost_regressions`) compares each program's bytes-accessed
against a committed baseline (`tools/cost_baseline.json`) — a program
whose memory traffic quietly grew past FLAGS_obs_cost_regress_pct fails
`tools/graft_lint.py` exactly like a dtype regression, which is how a
"minor refactor" that un-fuses a decode step gets caught before a
capture run does.

Thread-safety follows obs/watchdog.py: appends and counter bumps rely on
the GIL; compile sites are cold paths, `observe_wall` is a dict lookup
plus a few float ops per program invocation (ticks, not tokens).
"""
from __future__ import annotations

import json
import time

from ..core.flags import flag
from .peaks import peak_gbps, peak_tflops

#: roofline gauges get a wider label cap than the default 64: a serving
#: ladder (prefill x chunk x decode buckets) legitimately exceeds it
_GAUGE_LABEL_CAP = 256


def extract_cost(compiled) -> dict | None:
    """flops / bytes-accessed / HBM-footprint dict from a jax AOT
    ``Compiled`` object, or None when the backend exposes neither
    analysis. cost_analysis() returns a list of per-partition dicts on
    this jax; single-device programs have exactly one."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        outb = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        ali = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        out["arg_bytes"] = arg
        out["out_bytes"] = outb
        out["temp_bytes"] = tmp
        # donated (aliased) outputs reuse argument HBM — don't count twice
        out["peak_hbm_bytes"] = arg + max(outb - ali, 0) + tmp
    return out or None


class ProgramCost:
    """One compiled program's ledger row: static XLA costs + measured
    execution walls."""

    __slots__ = ("program", "site", "group", "key", "bucket", "flops",
                 "bytes_accessed", "arg_bytes", "out_bytes", "temp_bytes",
                 "peak_hbm_bytes", "collective_bytes", "compile_wall_s",
                 "analyzed", "exec_count", "exec_wall_s", "last_util", "t",
                 "_gauge")

    def __init__(self, program, site, group, key, bucket=None,
                 compile_wall_s=0.0, cost=None, collective_bytes=0):
        self.program = program      # stable id: "site|key"
        self.site = site
        self.group = group
        self.key = key
        self.bucket = bucket
        self.compile_wall_s = float(compile_wall_s)
        cost = cost or {}
        self.analyzed = bool(cost)
        self.flops = float(cost.get("flops", 0.0))
        self.bytes_accessed = float(cost.get("bytes_accessed", 0.0))
        self.arg_bytes = int(cost.get("arg_bytes", 0))
        self.out_bytes = int(cost.get("out_bytes", 0))
        self.temp_bytes = int(cost.get("temp_bytes", 0))
        self.peak_hbm_bytes = int(cost.get("peak_hbm_bytes", 0))
        # per-device collective byte volume of the program's jaxpr-level
        # collectives (analysis D10, jaxpr_collective_bytes) — the SPMD
        # twin of bytes_accessed: HBM traffic vs fabric traffic
        self.collective_bytes = int(collective_bytes or 0)
        self.exec_count = 0
        self.exec_wall_s = 0.0
        self.last_util = None
        self.t = time.time()
        self._gauge = None          # resolved roofline gauge handle

    # ------------------------------------------------------ measurement
    def observe(self, wall_s: float):
        """One measured execution of this program. Updates the rolling
        achieved-bandwidth numbers and the roofline_utilization{program}
        gauge in the default registry."""
        self.exec_count += 1
        self.exec_wall_s += float(wall_s)
        if not self.analyzed or wall_s <= 0.0:
            return None
        util = self.bytes_accessed / (wall_s * peak_gbps() * 1e9)
        self.last_util = util
        if self._gauge is None:
            from . import default_registry

            self._gauge = default_registry().gauge(
                "roofline_utilization",
                "achieved HBM bandwidth of one compiled program over the "
                "device roofline (bytes_accessed from XLA cost_analysis / "
                "measured wall / FLAGS_obs_peak_gbps)",
                ("program",), label_cap=_GAUGE_LABEL_CAP).labels(
                    self.program)
        self._gauge.set(util)
        return util

    def achieved_gbps(self) -> float | None:
        """Mean achieved bandwidth over every measured execution."""
        if not (self.analyzed and self.exec_count and self.exec_wall_s > 0):
            return None
        return self.bytes_accessed * self.exec_count / self.exec_wall_s / 1e9

    def utilization(self) -> float | None:
        g = self.achieved_gbps()
        return None if g is None else g / peak_gbps()

    def predicted(self) -> tuple[float | None, float | None]:
        """(predicted_step_ms, collective_time_ms) for this program from
        the static cost model (analysis/costmodel.py): roofline
        max(compute, HBM) at the obs peaks plus the program's D10
        collective volume billed at the ICI line rate. None when XLA
        never analyzed the executable."""
        if not self.analyzed:
            return None, None
        coll_ms = 0.0
        if self.collective_bytes:
            coll_ms = self.collective_bytes \
                / (float(flag("FLAGS_analysis_ici_gbps")) * 1e9) * 1e3
        compute_ms = self.flops / (peak_tflops() * 1e12) * 1e3
        hbm_ms = self.bytes_accessed / (peak_gbps() * 1e9) * 1e3
        return max(compute_ms, hbm_ms) + coll_ms, coll_ms

    def to_dict(self) -> dict:
        g = self.achieved_gbps()
        pred_ms, coll_ms = self.predicted()
        return {"program": self.program, "site": self.site,
                "group": self.group, "key": self.key, "bucket": self.bucket,
                "analyzed": self.analyzed, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "arg_bytes": self.arg_bytes, "out_bytes": self.out_bytes,
                "temp_bytes": self.temp_bytes,
                "peak_hbm_bytes": self.peak_hbm_bytes,
                "collective_bytes": self.collective_bytes,
                "predicted_step_ms": (None if pred_ms is None
                                      else round(pred_ms, 4)),
                "collective_time_ms": (None if coll_ms is None
                                       else round(coll_ms, 4)),
                "compile_wall_s": round(self.compile_wall_s, 4),
                "exec_count": self.exec_count,
                "exec_wall_s": round(self.exec_wall_s, 6),
                "achieved_gbps": None if g is None else round(g, 3),
                "roofline_utilization": (None if g is None
                                         else round(g / peak_gbps(), 4))}


#: program id -> ProgramCost; process-global like the compile-event
#: window (executables themselves are shared across engine instances)
_ledger: dict[str, ProgramCost] = {}

#: the eager dispatch cache registers count-only rows (its per-op
#: executables lower lazily; forcing an analysis would cost one compile
#: per op) — cap them so a shape-churning eager workload can't grow the
#: ledger without bound. Dropped registrations are counted.
_EAGER_LEDGER_CAP = 2048
eager_rows_dropped = 0
_site_counts: dict[str, int] = {}


def record_program(site: str, group: str, key: str, compiled=None,
                   wall_s: float = 0.0, bucket=None,
                   collective_bytes=0) -> ProgramCost:
    """Register one compiled program in the ledger (idempotent per
    program id — a cleared event mirror re-recording an already-compiled
    executable keeps the original analysis). Returns the entry; the
    caller attaches ``entry.observe(wall)`` per execution.
    `collective_bytes` carries the program's jaxpr-level collective
    volume (analysis.jaxpr_collective_bytes) next to bytes-accessed."""
    pid = f"{site}|{key}"
    entry = _ledger.get(pid)
    if entry is not None:
        if collective_bytes and not entry.collective_bytes:
            entry.collective_bytes = int(collective_bytes)
        return entry
    if site == "eager" and compiled is None \
            and _site_counts.get("eager", 0) >= _EAGER_LEDGER_CAP:
        global eager_rows_dropped

        eager_rows_dropped += 1
        return ProgramCost(pid, site, group, key, bucket=bucket,
                           compile_wall_s=wall_s, cost=None)
    cost = None
    if compiled is not None and flag("FLAGS_obs_cost_capture"):
        cost = extract_cost(compiled)
    entry = ProgramCost(pid, site, group, key, bucket=bucket,
                        compile_wall_s=wall_s, cost=cost,
                        collective_bytes=collective_bytes)
    _ledger[pid] = entry
    _site_counts[site] = _site_counts.get(site, 0) + 1
    from . import metrics

    metrics.log_event("program_cost", **entry.to_dict())
    return entry


def reregister(entry: "ProgramCost") -> "ProgramCost":
    """Re-insert a live ProgramCost whose row was dropped by
    ``clear_ledger()``. Compiled executables outlive the ledger (the
    serving engine's module-level AOT cache), so a cache-HIT program
    must surface its original analysis in the fresh ledger instead of
    silently vanishing from roofline/bench/D8 views."""
    if entry.program not in _ledger:
        _ledger[entry.program] = entry
        _site_counts[entry.site] = _site_counts.get(entry.site, 0) + 1
    return entry


def get_program(site: str, key: str) -> ProgramCost | None:
    return _ledger.get(f"{site}|{key}")


def ledger(site: str | None = None) -> list[ProgramCost]:
    """Ledger rows, optionally filtered by site prefix (``"serving"``
    matches serving.prefill / serving.decode / serving.chunk_prefill)."""
    rows = list(_ledger.values())
    if site is not None:
        rows = [e for e in rows if e.site == site
                or e.site.startswith(site + ".")]
    return sorted(rows, key=lambda e: e.program)


def clear_ledger():
    global eager_rows_dropped

    _ledger.clear()
    _site_counts.clear()
    _baselined_this_run.clear()
    eager_rows_dropped = 0


def reset_exec_stats():
    """Zero the measured-execution accumulators (bench rungs call this
    next to obs.clear_events() so each row's utilization is its own);
    the static analyses stay — they belong to the executable."""
    for e in _ledger.values():
        e.exec_count = 0
        e.exec_wall_s = 0.0
        e.last_util = None


def roofline_rows(site: str | None = None, measured_only: bool = False
                  ) -> list[dict]:
    rows = [e.to_dict() for e in ledger(site)]
    if measured_only:
        rows = [r for r in rows if r["roofline_utilization"] is not None]
    return rows


# -------------------------------------------------------------- baseline
#: programs committed by write_baseline() IN THIS PROCESS — D8 skips its
#: "new unbaselined program" note for them, so `roofline_report
#: --write-baseline` followed by an audit in the same run doesn't nag
#: about rows it just wrote to disk itself
_baselined_this_run: set = set()


def write_baseline(path: str, site: str = "serving",
                   threshold_pct: float | None = None) -> dict:
    """Commit the current ledger's analyzed programs as the D8 baseline.
    Only static quantities are recorded (bytes accessed, flops, HBM
    footprint) — walls are machine-dependent and have no business in a
    committed gate."""
    if threshold_pct is None:
        threshold_pct = float(flag("FLAGS_obs_cost_regress_pct"))
    progs = {e.program: {"bytes_accessed": e.bytes_accessed,
                         "flops": e.flops,
                         "peak_hbm_bytes": e.peak_hbm_bytes}
             for e in ledger(site) if e.analyzed}
    _baselined_this_run.update(progs)
    base = {"_comment": "analysis D8 baseline: per-program XLA "
                        "bytes-accessed/flops from the graft_lint obs "
                        "smoke (tiny-LLaMA serving engine). Regenerate "
                        "with tools/roofline_report.py --write-baseline "
                        "after an INTENTIONAL cost change.",
            "threshold_pct": float(threshold_pct), "programs": progs}
    with open(path, "w") as fh:
        json.dump(base, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return base


def load_baseline(path_or_dict) -> dict:
    if isinstance(path_or_dict, dict):
        return path_or_dict
    with open(path_or_dict) as fh:
        return json.load(fh)


def audit_cost_regressions(baseline, entries=None,
                           threshold_pct: float | None = None,
                           loc: str = "obs/costs") -> list:
    """D8 — compiled-program cost regressions vs a committed baseline.

    A program present in the baseline whose CURRENT bytes-accessed grew
    more than ``threshold_pct`` (baseline's own value, else
    FLAGS_obs_cost_regress_pct) is a **warning** — the memory-traffic
    budget regressed, which on a bandwidth-bound device is the perf
    budget. Programs the baseline knows but this run never compiled are
    notes (partial runs are normal); new unbaselined programs are one
    note (additions are fine until someone commits them). Shrunk
    programs are explicitly called out as notes too — an improvement
    worth re-baselining."""
    from ..analysis import Finding

    base = load_baseline(baseline)
    if threshold_pct is None:
        threshold_pct = float(base.get("threshold_pct",
                                       flag("FLAGS_obs_cost_regress_pct")))
    if entries is None:
        entries = ledger()
    cur = {e.program: e for e in entries}
    findings: list = []
    grown, shrunk, missing, checked = [], [], [], 0
    for pid, b in sorted(base.get("programs", {}).items()):
        e = cur.get(pid)
        if e is None or not e.analyzed:
            missing.append(pid)
            continue
        checked += 1
        b_bytes = float(b.get("bytes_accessed", 0.0))
        if b_bytes <= 0:
            continue
        growth = (e.bytes_accessed - b_bytes) / b_bytes
        if growth * 100.0 > threshold_pct:
            grown.append((pid, b_bytes, e.bytes_accessed, growth))
        elif growth < -0.05:
            shrunk.append((pid, b_bytes, e.bytes_accessed, growth))
    for pid, b_bytes, now, growth in grown:
        findings.append(Finding(
            "cost-regression", "warning", f"{loc}:{pid}",
            f"bytes accessed grew {growth:+.0%} over the committed "
            f"baseline ({b_bytes:.0f} -> {now:.0f} B, threshold "
            f"{threshold_pct:g}%) — this program's HBM traffic budget "
            "regressed; if intentional, regenerate "
            "tools/cost_baseline.json (tools/roofline_report.py "
            "--write-baseline)",
            data={"program": pid, "baseline_bytes": b_bytes,
                  "bytes": now, "growth_pct": round(growth * 100, 1),
                  "threshold_pct": threshold_pct}))
    for pid, b_bytes, now, growth in shrunk:
        findings.append(Finding(
            "cost-regression", "note", f"{loc}:{pid}",
            f"bytes accessed SHRANK {growth:+.0%} vs baseline "
            f"({b_bytes:.0f} -> {now:.0f} B) — re-baseline to lock the "
            "improvement in",
            data={"program": pid, "baseline_bytes": b_bytes,
                  "bytes": now}))
    if missing:
        findings.append(Finding(
            "cost-regression", "note", loc,
            f"{len(missing)} baselined program(s) not compiled this run "
            f"(partial smoke): {missing[:4]}"
            f"{'...' if len(missing) > 4 else ''}",
            data={"missing": missing}))
    new = sorted(pid for pid, e in cur.items()
                 if e.analyzed and pid not in base.get("programs", {})
                 and pid not in _baselined_this_run)
    if new:
        findings.append(Finding(
            "cost-regression", "note", loc,
            f"{len(new)} analyzed program(s) not in the baseline "
            f"(unbaselined additions): {new[:4]}"
            f"{'...' if len(new) > 4 else ''}",
            data={"new": new}))
    if not grown:
        findings.append(Finding(
            "cost-regression", "note", loc,
            f"{checked} baselined program(s) within the "
            f"{threshold_pct:g}% bytes-accessed budget",
            data={"checked": checked,
                  "threshold_pct": threshold_pct}))
    return findings
