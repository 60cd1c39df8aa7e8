"""A kernel's share of its roofline in the traced slice: the least time
the chip could take for the WORK the kernel did (operations over peak
FLOP/s, or bytes over peak bytes/s, whichever is larger) over the device
time of its events. params {"events": [substrings of the event names],
"work": module under benchmark/kernels}. The work is counted from the
shapes and live lengths the benchmark knows, whatever implements it.
No event in the slice: None, never 0."""
import importlib

from .. import trace_reduce


def read(params, rec, ctx):
    tr, sl = rec.get("trace"), rec.get("slice")
    if not tr or not sl or ctx.peaks is None:
        return None
    ns = calls = 0
    for ev in tr["ops"].values():
        for match in params["events"]:
            t, n = trace_reduce.kernel_ns(ev, match)
            ns, calls = ns + t, calls + n
    if not ns:
        return None
    work = importlib.import_module(f"benchmark.kernels.{params['work']}")
    flops, nbytes = work.work(ctx.cfg, sl, calls / tr["devices"])
    least_s = max(flops / ctx.peaks["bf16_flops_per_s"],
                  nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9 / tr["devices"])
