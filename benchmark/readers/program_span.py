"""A number from the PROGRAM's own span log (`paddle_tpu.obs.span_events()`:
records `(name, start, end, parent, attrs)` on `time.perf_counter()`), over
one phase of the run. Readers run in the program's process before
`release`, so the log is read where it lies; nothing is exported.

params:
  "phase"  "setup":  [process start, process start + `setup_s`)
           "window": the `rec["window_s"]` seconds after that
           A span belongs to the phase its START lies in. The harness keeps
           `time.time()`; one offset taken at read time maps it to the
           log's clock.
  "spans"  the names read
  "stat"   "sum":             scale x sum of durations
           "percentile":      scale x the `q`th percentile of durations
           "per_attr":        scale x sum of durations / sum of attrs[`attr`]
           "self_share":      100 x (sum of `spans` - sum of the `minus` spans
                              that lie inside one of them) / sum of `spans`
           "self_percentile": scale x the `q`th percentile, over `spans`, of
                              (its duration - the `minus` spans inside it)
  "scale"  default 1.0 (seconds)

`None`, never a partial number and never 0: where the program has no such
log (a parent commit from before it), where the log was cut after the
phase started (`obs.span_log_start()`), and where the phase holds no span
of these names.

The spans, and the metric each is for (PERF.md section 3 has the table):
`serving.step` around `serving.{prefill,chunk,decode,verify}.run` (host
share of a tick), `serving.chunk.run` + `serving.prefill.run` with `tokens`
(prefill time per token: the program alone, not the ticks between a
request's chunks), `serving.decode.run`, `jit.call`, and in set-up
`jit.warmup` + `jit.discover`, `jit.compile` + `serving.compile`.
"""
import bisect
import time

import numpy as np


def program_log():
    """(records, the perf_counter time from which the log is whole), or
    None where the program has no span log with starts and ends."""
    try:
        from paddle_tpu import obs

        return obs.span_events(), obs.span_log_start()
    except (ImportError, AttributeError):
        return None


def self_times(parents, inner) -> list:
    """[(parent's duration, its duration less the `inner` spans that lie
    inside it)], by containment in time (one thread drives an engine)."""
    inner = sorted(inner, key=lambda r: r[1])
    starts = [r[1] for r in inner]
    out = []
    for _, a, b, *_ in parents:
        lo = bisect.bisect_left(starts, a)
        hi = bisect.bisect_right(starts, b)
        took = sum(r[2] - r[1] for r in inner[lo:hi] if r[2] <= b)
        out.append((b - a, (b - a) - took))
    return out


def compute(params, records, whole_since, a, b):
    """The statistic over the records that start in [a, b), all times on
    one clock. Split from `read` so the arithmetic is testable on a
    hand-written log."""
    if whole_since > a:
        return None                         # a cut log
    inside = [r for r in records if a <= r[1] < b]
    mine = [r for r in inside if r[0] in params["spans"]]
    if not mine:
        return None                         # an empty phase
    stat, scale = params["stat"], params.get("scale", 1.0)
    durs = [r[2] - r[1] for r in mine]
    if stat == "sum":
        return scale * sum(durs)
    if stat == "percentile":
        return scale * float(np.percentile(durs, params["q"]))
    if stat == "per_attr":
        n = sum(r[4].get(params["attr"], 0) for r in mine)
        return scale * sum(durs) / n if n else None
    own = self_times(mine, [r for r in inside if r[0] in params["minus"]])
    if stat == "self_share":
        return 100.0 * sum(s for _, s in own) / sum(d for d, _ in own)
    if stat == "self_percentile":
        return scale * float(np.percentile([s for _, s in own],
                                           params["q"]))
    raise ValueError(f"unknown stat {stat!r}")


def read(params, rec, ctx):
    log = program_log()
    if log is None or ctx.setup_s is None:
        return None
    records, whole_since = log
    to_log_clock = time.perf_counter() - time.time()
    a = ctx.process_start + to_log_clock
    b = a + ctx.setup_s
    if params["phase"] == "window":
        a, b = b, b + rec["window_s"]
    return compute(params, records, whole_since, a, b)
