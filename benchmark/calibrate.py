#!/usr/bin/env python3
"""python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 --seconds <s>
       [--set mix.arrivals.rate_per_s=1.2 ...] [--control 1] [--trace 0|1] [--first-steps 1]

What a benchmark PR runs on the chip to SET a cell's numbers, never what
the driver runs: several seeds of one cell in one process (set-up is most
of a run), optionally with a value of the mix or the configuration set
otherwise (`--set mix.<dotted key>=<json>` or `cfg....`: the sweep of
offered rates that finds the knee, the probe of depths), optionally with
the lower-precision control and the planted fault read beside the program
(`--control 1`): each sets an upper reading of the cell's limits and gets
a verdict of its own (`controls_correct`: its numbers in the program's
place, judged against the same limits; each has to read false). One JSON
line per seed on standard output.
"""
import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="mix|cfg.<dotted key>=<json>")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--first-steps", type=int, choices=(0, 1), default=0,
                    help="train cells: no window, one set-up for all seeds")
    args = ap.parse_args(argv)

    from benchmark import harness
    from paddle_tpu.core.compile_cache import enable_compile_cache

    harness.progress(f"compile cache: {enable_compile_cache()}")
    precisions = ("f32", "fp8") if args.control else ("f32",)

    def context(seed, start):
        return harness.context(args.workload, seed, args.seconds, args.trace,
                               start, args.set)

    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = context(seeds[0], PROCESS_START)
    if args.first_steps:
        # a train cell's readings need no window: one set-up, every seed's
        # first steps through the same compiled step, then (the program's
        # state freed) the reference, the control and the fault per seed
        from benchmark import traffic_gen

        ctx.check_device()
        state = ctx.driver.setup(ctx)
        firsts = {}
        for seed in seeds:
            c = context(seed, PROCESS_START)
            firsts[seed] = ctx.driver.first_steps(
                c, state, traffic_gen.train_batches(
                    c.mix, seed, c.cfg["vocab_size"]))
        ctx.driver.release(state)
        del state
        gc.collect()
        for seed in seeds:
            comps = ctx.driver.check(context(seed, PROCESS_START),
                                     {"first": firsts[seed]}, precisions)
            print(json.dumps({
                "seed": seed, "set": args.set,
                "correct": harness.judge(comps),
                "controls_correct": harness.control_verdicts(comps),
                "compared": {c["name"]: {"value": c["value"],
                                         "limit": c["limit"]}
                             for c in comps}}), flush=True)
        return 0
    start = PROCESS_START
    for seed in seeds:
        ctx = context(seed, start)
        out = harness.run_cell(ctx, precisions)
        harness.print_compared(out)
        print(json.dumps({"seed": seed, "set": args.set, **out}),
              flush=True)
        del ctx, out
        gc.collect()
        start = time.time()
    return 0


if __name__ == "__main__":
    sys.exit(main())
