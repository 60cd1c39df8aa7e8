"""The one general traffic generator: a mix is a data file of parameters
(`benchmark/traffic/<mix>.json`), and this module turns it and `--seed`
into the inputs of a run. A new mix is a new file, never new code; a new
length distribution or arrival process is a new module under `dists/` or
`arrivals/`, found by the name the mix gives.

The sizes and the inter-arrival gaps are the stratified quantiles of the
mix's distributions, shuffled by the MIX's `schedule_seed`: every run of a
cell offers the same requests at the same times, and `--seed` draws the
token ids (and the weights). With some tens of requests in a window, which
long prompt meets which burst moves a tail by tens of percent; the schedule
is part of the cell, as its rate is.
"""
from __future__ import annotations

import importlib
import math

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def sizes(spec: dict, n: int) -> np.ndarray:
    """n whole numbers: the (i + 0.5)/n quantiles of the distribution that
    `spec["dist"]` names (a module under `benchmark/dists/`), clipped to
    [min, max]. Ascending; the caller shuffles."""
    dist = importlib.import_module(f"benchmark.dists.{spec['dist']}")
    x = dist.quantiles(spec, _quantiles(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps in seconds whose multiset is fixed: the
    stratified quantiles of the gap distribution of the process that
    `arrivals["process"]` names (a module under `benchmark/arrivals/`)."""
    process = importlib.import_module(
        f"benchmark.arrivals.{arrivals['process']}")
    return process.gap_quantiles(arrivals, _quantiles(n))


def _arrivals(mix: dict, seconds: float) -> tuple:
    """(how many requests, the seconds within which all are due): arrivals
    stop at `due_within` of the window, so that the last request has time
    for its first token and only a stalled engine leaves one without."""
    arr = mix["arrivals"]
    within = float(arr.get("due_within", 1.0)) * seconds
    return max(1, int(round(float(arr["rate_per_s"]) * within))), within


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The open loop's schedule for a window of `seconds`: a list of
    {"due_s", "prompt" (int64 ids), "max_new_tokens"}, ordered by due time.
    round(rate x due_within x seconds) requests."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(mix["schedule_seed"]))
    n, within = _arrivals(mix, seconds)
    prompts = order.permutation(sizes(mix["prompt_tokens"], n))
    outs = order.permutation(sizes(mix["output_tokens"], n))
    g = order.permutation(gaps(mix["arrivals"], n))
    # the gaps' sum is n/rate less a little (the last quantile is finite):
    # the first request is due after its own gap, the last within `within`
    due = np.cumsum(g)
    due = due * min(1.0, 0.999 * within / due[-1])
    cap = int(mix["max_total_tokens"])
    source = importlib.import_module(f"benchmark.prompts.{mix['prompts']}")
    ids = source.token_ids(mix, rng, prompts, vocab)
    return [{"due_s": float(d), "prompt": i,
             "max_new_tokens": int(min(o, cap - len(i)))}
            for d, i, o in zip(due, ids, outs)]


def warmup_lengths(mix: dict, seconds: float) -> list:
    """Every distinct prompt length the window will send, ascending."""
    n, _ = _arrivals(mix, seconds)
    return sorted({int(x) for x in sizes(mix["prompt_tokens"], n)})


def train_batches(mix: dict, seed: int, vocab: int):
    """An endless host-side generator of [batch, seq] int64 token batches,
    every row different, from the seed."""
    rng = np.random.default_rng(int(seed))
    b = int(mix["tokens_per_step"]) // int(mix["seq"])
    while True:
        yield rng.integers(0, vocab, size=(b, int(mix["seq"])),
                           dtype=np.int64)


def histogram(values, edges=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> dict:
    """{"<=edge": count} for the progress lines."""
    v = np.asarray(list(values))
    out, lo = {}, -math.inf
    for e in edges:
        out[f"<={e}"] = int(((v > lo) & (v <= e)).sum())
        lo = e
    return out
