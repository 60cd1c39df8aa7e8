"""What every cell's run shares: resolving `--workload` through
`BENCHMARK.json` to data files, the device check, the peaks table, set-up
and window bookkeeping, the trace, the readers, and the result line.

Everything that belongs to ONE configuration, mix, metric or kernel lives
in a file of its own, found here by the name in `BENCHMARK.json`:

    configs/<config>.json     sizes, with `family` -> families/<family>.py
    traffic/<mix>.json        parameters, with `role` -> drivers/<role>.py,
                              `dist` -> dists/, `process` -> arrivals/,
                              `prompts` -> prompts/
    metrics/<metric>.json     `reader` -> readers/<reader>.py, + its params
    kernels/<name>.py         operations and bytes of a kernel's work
"""
from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def progress(msg: str) -> None:
    """Where a run got to, on stderr: stdout's last line is the result."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def annotate(name: str):
    """A host span in the profiler's own trace (no-op when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(man: dict, workload: str) -> dict:
    """The cell's entry, configuration, mix, and the metrics it reports."""
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    mix = load_json("traffic", cell["traffic"] + ".json")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "cfg": cfg, "mix": mix,
            "end_to_end": mine(man["end_to_end"]),
            "per_layer": mine(man["per_layer"])}


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise LookupError(f"no published peaks for device_kind "
                          f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def kernels_in(text: str) -> dict:
    """{kernel name: count} of the Mosaic custom calls in a program's text
    (copied from chip_smoke.py, PR 24): lowered programs carry
    `kernel_name = "<name>"`, compiled ones an op_name ending in
    `<name>/pallas_call`."""
    out: dict = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'kernel_name = "(\w+)"', line) \
            or re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        name = m.group(1) if m else "<unnamed>"
        out[name] = out.get(name, 0) + 1
    return out


class Context:
    """One run's arguments and what it resolved to."""

    def __init__(self, workload, seed, seconds, trace, resolved,
                 process_start, require_tpu=True):
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.cell, self.cfg, self.mix = (resolved["cell"], resolved["cfg"],
                                         resolved["mix"])
        self.end_to_end = resolved["end_to_end"]
        self.per_layer = resolved["per_layer"]
        self.family = importlib.import_module(
            f"benchmark.families.{self.cfg['family']}")
        self.driver = importlib.import_module(
            f"benchmark.drivers.{self.mix['role']}")
        self.process_start = process_start
        self.require_tpu = require_tpu
        self.setup_s = None
        self.trace_dir = os.path.join(ROOT, ".bench_trace",
                                      workload.replace("/", "_"))
        self.device = None
        self.peaks = None
        #: where the trace holds device operations (the CPU tests differ)
        self.trace_planes = {}

    def check_device(self) -> None:
        import jax

        devs = jax.devices()
        d0 = devs[0]
        if self.require_tpu:
            if d0.platform != "tpu":
                raise SystemExit(
                    f"the benchmark needs a TPU; jax found {d0.platform!r} "
                    f"({d0.device_kind} x {len(devs)})")
            if len(devs) < int(self.cell["chips"]):
                raise SystemExit(
                    f"{self.workload} needs {self.cell['chips']} chip(s); "
                    f"jax found {len(devs)}")
            self.peaks = peaks(d0.device_kind)
        self.device = {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devs)}

    def hbm(self, where: str) -> None:
        import jax

        st = jax.devices()[0].memory_stats() or {}
        progress(f"hbm at {where}: in use "
                 f"{st.get('bytes_in_use', 0) / 1e9:.3f} GB, peak "
                 f"{st.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
                 f"{st.get('bytes_limit', 0) / 1e9:.3f}")

    def memory_peak_bytes(self) -> int:
        import jax

        n = int(self.cell["chips"]) if self.require_tpu else 1
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:n])

    def mark_window_start(self) -> None:
        self.setup_s = time.time() - self.process_start


def start_trace(ctx) -> None:
    """Profiler on, Python's own tracer off (a million events a second
    of no use here): device operations and the benchmark's host spans."""
    import jax

    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)


def open_slice(ctx):
    """Start the trace and the host span that bounds the traced slice."""
    start_trace(ctx)
    span = annotate("bench.slice")
    span.__enter__()
    return span


def close_slice(span) -> None:
    import jax

    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def read_metrics(ctx, rec: dict, metrics: list) -> dict:
    """{name: {"value", "unit"}} of the metrics whose reader finds
    something to read; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in metrics:
        spec = load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        v = reader.read(spec.get("params", {}), rec, ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def context(workload, seed, seconds, trace, process_start, overrides=()):
    """The `Context` of one run of a cell of `BENCHMARK.json`. `overrides`
    ("mix.<dotted key>=<json>" or "cfg...."; calibrate.py's sweeps and
    probes alone) set a value otherwise than the cell's files do."""
    resolved = resolve(manifest(), workload)
    for item in overrides:
        path, value = item.split("=", 1)
        *keys, last = path.split(".")
        at = resolved
        for k in keys:
            at = at[k]
        at[last] = json.loads(value)
    return Context(workload, seed, seconds, trace, resolved, process_start)


def judge(comparisons: list) -> bool:
    """Every number compared within its limit. A number tagged `control`
    (a control's, a planted fault's, one printed and not compared) is not
    the program's and is not judged here."""
    ok = True
    for c in comparisons:
        if c.get("control"):
            continue
        if c["limit"] is None:
            ok = False
        elif c.get("at_least"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def control_verdicts(comparisons: list) -> dict:
    """{tag: verdict} for each control or planted fault among the
    comparisons: its numbers (`<tag>.<number>`) put in the program's place
    and judged against the same limits. Each has to come out False."""
    tags = {}
    for c in comparisons:
        if c.get("control") and c["limit"] is not None:
            tags.setdefault(c["control"], []).append(dict(c, control=None))
    return {tag: judge(mine) for tag, mine in tags.items()}


def run_cell(ctx, precisions=("f32",)) -> dict:
    """Set-up, window, the trace's reduction, release, the check. Returns
    the result object; `main` prints it."""
    from . import trace_reduce

    ctx.check_device()
    progress(f"{ctx.workload}: seed {ctx.seed}, {ctx.seconds:g}s, trace "
             f"{int(ctx.trace)}, device {ctx.device}")
    state = ctx.driver.setup(ctx)
    rec = ctx.driver.window(ctx, state)
    rec["setup_s"] = ctx.setup_s
    peak = ctx.memory_peak_bytes()
    device = dict(ctx.device, memory_peak_bytes=peak)
    progress(f"{ctx.workload}: kernels "
             f"{ctx.driver.kernels_present(state)}")
    comparisons = [{"name": "compiles_in_window",
                    "value": ctx.driver.compiles_in_window(state),
                    "limit": 0}]
    result = {}
    if ctx.trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir),
                               **ctx.trace_planes)
        rec["trace"] = trace_reduce.reduce(tr)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    metrics = read_metrics(ctx, rec,
                           ctx.per_layer if ctx.trace else ctx.end_to_end)
    ctx.driver.release(state)
    del state
    ctx.hbm("released")
    comparisons += ctx.driver.check(ctx, rec, precisions)
    ctx.hbm("checked")
    comparisons.append({"name": "failed", "value": rec["failed"],
                        "limit": 0})
    out = {"correct": judge(comparisons), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    out.update(result)
    verdicts = control_verdicts(comparisons)
    if verdicts:
        out["controls_correct"] = verdicts
    out["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in comparisons}
    return out


def print_compared(out: dict) -> None:
    for tag, verdict in out.get("controls_correct", {}).items():
        print(f"{tag} in the program's place: correct={verdict}",
              file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
