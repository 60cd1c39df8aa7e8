"""A ratio of two attributes summed over spans of the PROGRAM's span log
(`readers/program_span.py` says what the log is and how a phase is cut):
scale x sum of attrs[`num`] / sum of attrs[`den`] over the `spans` that
start in the phase.

params {"phase": "setup" | "window", "spans": [names], "num", "den",
"scale" (default 1.0)}.

`None`, never a partial number and never 0: where the program has no span
log, where the log was cut after the phase started, where the phase holds
no span of these names, and where no such span carries `den` (a program
from before the attributes: the metric is left out of the line).
"""
import time

from .program_span import program_log


def compute(params, records, whole_since, a, b):
    """The ratio over the records that start in [a, b), all times on one
    clock. Split from `read` so the arithmetic is testable on a
    hand-written log."""
    if whole_since > a:
        return None                         # a cut log
    mine = [r for r in records
            if a <= r[1] < b and r[0] in params["spans"]]
    den = sum(r[4].get(params["den"], 0) for r in mine)
    if not den:
        return None                         # an empty phase, or no attrs
    num = sum(r[4].get(params["num"], 0) for r in mine)
    return params.get("scale", 1.0) * num / den


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
