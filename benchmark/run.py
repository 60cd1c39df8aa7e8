#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One cell once, in one process that holds the cell's chip(s). Fails where
jax finds no TPU: there is no fallback. The last line of standard output
is the result object; everything else goes to standard error.
"""
import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    ctx = harness.context(args.workload, args.seed, args.seconds,
                          args.trace, PROCESS_START)
    # the program is imported only now: in a directory that holds the
    # benchmark alone this raises, and no result is printed
    from paddle_tpu.core.compile_cache import enable_compile_cache

    harness.progress(f"compile cache: {enable_compile_cache()}")
    out = harness.run_cell(ctx)
    harness.print_compared(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
