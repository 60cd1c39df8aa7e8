"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, a kernel's device time, the operations that took most time, and the
idle gaps named by what the host was doing in them.

Two steps, so that the arithmetic is testable without a chip: `load` reads
the file with `jax.profiler.ProfileData` into plain tuples, and everything
after it works on those tuples. Times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

#: host spans the benchmark's own files put around calls into each layer
SPAN_PREFIX = "bench."
#: the device line that holds one event per executed operation
OPS_LINES = ("XLA Ops",)
DEVICE_PLANE_PREFIX = "/device:TPU:"


@dataclass
class Trace:
    #: {device plane name: [(name, start_ns, dur_ns)]}, operations only
    device_ops: dict = field(default_factory=dict)
    #: [(name, start_ns, dur_ns)] of the benchmark's host spans
    host_spans: list = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, device_prefix: str = DEVICE_PLANE_PREFIX,
         ops_lines=OPS_LINES) -> Trace:
    """Device operations from the planes called `device_prefix`..., lines
    whose name starts with one of `ops_lines`; the benchmark's host spans
    from every line of every plane. (The CPU tests read XLA's CPU worker
    threads as the device: `/host:CPU`, `tf_XLA`.)"""
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith(device_prefix)
        ops = []
        for line in plane.lines:
            if is_dev and line.name.startswith(tuple(ops_lines)):
                ops += [(short(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
            else:
                tr.host_spans += [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
        if is_dev:
            tr.device_ops[plane.name] = ops
    tr.host_spans.sort(key=lambda s: s[1])
    return tr


def short(name: str) -> str:
    """An operation's event name is its whole HLO line
    (`%paged_decode.11 = bf16[...] custom-call(...)`): keep the
    instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


#: operations that only contain others (a scan over layers is one `while`)
CONTAINERS = ("while", "conditional", "call")


def clip(events, t0: int, t1: int) -> list:
    """Events cut to [t0, t1); those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events) -> list:
    """Merged [start, end) intervals of possibly overlapping events."""
    iv = sorted((s, s + d) for _, s, d in events)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events) -> int:
    return sum(b - a for a, b in union(events))


def kernel_ns(events, match: str) -> tuple:
    """(total device ns, count) of the events whose name contains `match`."""
    hit = [d for name, _, d in events if match in name]
    return sum(hit), len(hit)


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time,
    summed by name."""
    agg: dict = {}
    for name, _, d in events:
        if name.split(".")[0] in CONTAINERS:
            continue
        agg[name] = agg.get(name, 0) + d
    return [[k, v / 1e9] for k, v in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def gaps(events, t0: int, t1: int) -> list:
    """Idle [start, end) intervals of [t0, t1) between the busy ones."""
    out, at = [], t0
    for a, b in union(clip(events, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def gaps_by_span(events, spans, t0: int, t1: int, n: int = 10) -> list:
    """[[span name, idle seconds]]: every idle nanosecond of the window
    goes to the innermost (latest-started) benchmark span that covers it,
    or to "(no span)"; the largest `n` totals."""
    agg: dict = {}
    spans = clip(spans, t0, t1)
    for ga, gb in gaps(events, t0, t1):
        # cut the gap at every span boundary inside it
        cuts = sorted({ga, gb} | {x for _, s, d in spans
                                  for x in (s, s + d) if ga < x < gb})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2.0
            cover = [(s, name) for name, s, d in spans if s <= mid < s + d]
            name = max(cover)[1] if cover else "(no span)"
            agg[name] = agg.get(name, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def window_of(spans, name: str) -> tuple:
    """[t0, t1) of the first host span called `name`."""
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise LookupError(f"no host span {name!r} in the trace")


def reduce(tr: Trace, slice_span: str = "bench.slice") -> dict:
    """The reduction every reader shares: the slice's bounds, per-device
    clipped operations, busy seconds averaged over the devices used."""
    try:
        t0, t1 = window_of(tr.host_spans, slice_span)
    except LookupError:
        # no host span reached the trace: the window is what the device
        # events span, and no gap can be named
        every = [e for ev in tr.device_ops.values() for e in ev]
        if not every:
            raise LookupError("the trace holds no device operation")
        t0 = min(s for _, s, _ in every)
        t1 = max(s + d for _, s, d in every)
    per_dev = {p: clip(ev, t0, t1) for p, ev in tr.device_ops.items()}
    used = {p: ev for p, ev in per_dev.items() if ev}
    if not used:
        raise LookupError("no operation ran on a device inside the slice")
    busy = sum(busy_ns(ev) for ev in used.values()) / len(used)
    first = used[sorted(used)[0]]
    spans = [s for s in tr.host_spans if s[0] != slice_span]
    return {"t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9,
            "busy_s": busy / 1e9, "devices": len(used), "ops": used,
            "device_ops": top_ops(first),
            "idle_gaps": gaps_by_span(first, spans, t0, t1)}
