#!/usr/bin/env python
"""roofline_report — the measured-vs-roofline table for compiled programs.

Reads the obs cost ledger (paddle_tpu.obs.costs): per program, XLA
`cost_analysis()` flops / bytes accessed, the HBM footprint, the measured
compile wall, and — for programs that executed — mean execution wall,
achieved GB/s and roofline utilization (achieved / FLAGS_obs_peak_gbps).
This is the "~103 GB/s roofline" story from PERF.md as continuously
measured data instead of a per-round hand computation.  Rows also carry
`predicted_step_ms` / `collective_time_ms` — the static cost model's
estimate (analysis/costmodel.py: roofline max of compute at
FLAGS_obs_peak_tflops and HBM at FLAGS_obs_peak_gbps, plus the D10
collective volume billed at FLAGS_analysis_ici_gbps) — so predicted vs
measured sits in one table.

The ledger is per-process, so by default this tool drives the same tiny
serving smokes `tools/graft_lint.py` gates on (`--smoke`; implied by
`--write-baseline`) and reports on them.  Inside a live process, call
`paddle_tpu.obs.roofline_rows()` directly — bench rungs attach the same
rows to their BENCH_DETAILS entries.

`--write-baseline` regenerates `tools/cost_baseline.json`, the committed
analysis-D8 gate (`audit_cost_regressions`): a program whose
bytes-accessed grows more than FLAGS_obs_cost_regress_pct over the
baseline fails lint. Regenerate ONLY after an intentional cost change,
and commit the diff with the change that caused it.

Usage:
    python tools/roofline_report.py --smoke            # drive + table
    python tools/roofline_report.py --smoke --site serving.decode
    python tools/roofline_report.py --write-baseline   # regenerate D8 gate
    python tools/roofline_report.py --smoke --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

DEFAULT_BASELINE = os.path.join(REPO, "tools", "cost_baseline.json")


def _fmt_bytes(b):
    if b is None or b <= 0:
        return "-"
    for unit in ("B", "KB", "MB", "GB"):
        if b < 1024 or unit == "GB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{int(b)}B"
        b /= 1024.0
    return f"{b:.1f}GB"


def render_table(rows) -> str:
    head = (f"{'program':<52} {'flops':>12} {'bytes':>10} {'hbm':>10} "
            f"{'pred_ms':>8} {'coll_ms':>8} "
            f"{'compile_s':>9} {'execs':>6} {'wall_ms':>8} {'GB/s':>8} "
            f"{'util':>6}")
    lines = [head, "-" * len(head)]
    for r in rows:
        if not r["analyzed"]:
            note = "(count-only: no XLA analysis at this site)"
            lines.append(f"{r['program']:<52} {note}")
            continue
        wall = (r["exec_wall_s"] / r["exec_count"] * 1e3
                if r["exec_count"] else None)
        gbps = r["achieved_gbps"]
        util = r["roofline_utilization"]
        pred = r.get("predicted_step_ms")
        coll = r.get("collective_time_ms")
        wall_s = f"{wall:.2f}" if wall is not None else "-"
        gbps_s = f"{gbps:.2f}" if gbps is not None else "-"
        util_s = f"{util:.1%}" if util is not None else "-"
        pred_s = f"{pred:.3f}" if pred is not None else "-"
        coll_s = f"{coll:.3f}" if coll is not None else "-"
        lines.append(
            f"{r['program']:<52} {r['flops']:>12.3g} "
            f"{_fmt_bytes(r['bytes_accessed']):>10} "
            f"{_fmt_bytes(r['peak_hbm_bytes']):>10} "
            f"{pred_s:>8} {coll_s:>8} "
            f"{r['compile_wall_s']:>9.3f} {r['exec_count']:>6} "
            f"{wall_s:>8} {gbps_s:>8} {util_s:>6}")
    return "\n".join(lines)


def run_smoke():
    """Drive the graft_lint serving smokes so the ledger holds the same
    deterministic tiny-engine programs the CI gate audits."""
    import graft_lint

    graft_lint.audit_serving()
    graft_lint.audit_obs()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="drive the tiny lint serving smokes first (the "
                         "ledger is per-process and starts empty)")
    ap.add_argument("--site", default=None,
                    help="filter by site (serving / serving.decode / "
                         "generate / to_static / eager)")
    ap.add_argument("--json", dest="as_json", action="store_true")
    ap.add_argument("--write-baseline", nargs="?", const=DEFAULT_BASELINE,
                    default=None, metavar="PATH",
                    help=f"regenerate the D8 baseline (default "
                         f"{DEFAULT_BASELINE}) from the smoke's serving "
                         "programs; implies --smoke")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import obs

    if os.environ["JAX_PLATFORMS"] == "cpu":
        obs.peaks.set_off_chip_peaks()  # the CPU is in no peaks table
    if args.smoke or args.write_baseline:
        run_smoke()

    rows = obs.roofline_rows(args.site)
    if args.write_baseline:
        base = obs.write_baseline(args.write_baseline, site="serving")
        print(f"wrote {len(base['programs'])} program baseline(s) to "
              f"{args.write_baseline} (threshold "
              f"{base['threshold_pct']:g}%)", file=sys.stderr)
    if args.as_json:
        print(json.dumps({"peak_gbps": obs.peak_gbps(), "programs": rows},
                         indent=2))
    else:
        print(f"peak bandwidth: {obs.peak_gbps():g} GB/s "
              "(FLAGS_obs_peak_gbps; 0 = this device_kind's published "
              "peak, obs/peaks.py)")
        print(render_table(rows) if rows else
              "cost ledger is empty — run with --smoke, or call from a "
              "process that compiled programs")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
