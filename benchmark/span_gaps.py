#!/usr/bin/env python3
"""python3 benchmark/span_gaps.py --workload <name> --seed <n> --seconds <s> [--log <file>]

A builder's tool, never what the driver runs: one traced run of a cell
through `harness.run_cell` with the reducer's span prefix widened from the
benchmark's own (`bench.`) to the program's as well (`serving.`, `jit.`).
The program's spans are `TraceAnnotation`s too, so they lie in the same
capture on the device trace's timeline, nested inside `bench.engine_step`
/ `bench.train_step`, and `gaps_by_span` gives each idle nanosecond to the
innermost span that covers it: the idle the driver's `breakdown.idle_gaps`
puts on one benchmark span comes out by the program's phase. One JSON line
on standard output: the run's result object plus `idle_gaps_by_span` (every
name, not the largest ten) and `span_seconds` (the summed length of each
span name inside the traced slice). `--log` writes the program's whole span
log (`obs.span_events()`, one JSON array a record) beside it, for what no
metric reads yet.
"""
import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PREFIXES = ("bench.", "serving.", "jit.")


def run(ctx) -> dict:
    """`harness.run_cell(ctx)` under the widened prefix; the result object
    with the two tables added."""
    from benchmark import harness, trace_reduce

    found = {}
    narrow, reduce = trace_reduce.SPAN_PREFIX, trace_reduce.reduce

    def reduce_and_keep(tr, slice_span="bench.slice"):
        red = reduce(tr, slice_span)
        t0, t1 = red["t0"], red["t1"]
        spans = trace_reduce.clip(
            [s for s in tr.host_spans if s[0] != slice_span], t0, t1)
        first = red["ops"][sorted(red["ops"])[0]]
        found["idle_gaps_by_span"] = trace_reduce.gaps_by_span(
            first, spans, t0, t1, n=len(spans))
        total: dict = {}
        for name, _, dur in spans:
            total[name] = total.get(name, 0.0) + dur / 1e9
        found["span_seconds"] = total
        return red

    trace_reduce.SPAN_PREFIX = PREFIXES     # str.startswith takes a tuple
    trace_reduce.reduce = reduce_and_keep
    try:
        out = harness.run_cell(ctx)
    finally:
        trace_reduce.SPAN_PREFIX, trace_reduce.reduce = narrow, reduce
    out.update(found)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--log", help="write the program's span log here")
    args = ap.parse_args(argv)

    from benchmark import harness
    from paddle_tpu.core.compile_cache import enable_compile_cache

    harness.progress(f"compile cache: {enable_compile_cache()}")
    out = run(harness.context(args.workload, args.seed, args.seconds, 1,
                              PROCESS_START))
    harness.print_compared(out)
    if args.log:
        from paddle_tpu import obs

        with open(args.log, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in obs.span_events())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
