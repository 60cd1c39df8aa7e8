"""The work of decode attention over the SLIDING-WINDOW layers
(`paged_window_decode`): each decode token reads the last `sliding_window`
keys and values of its sequence once per window layer. That is exact only
where every context is at least the window (below it the work depends on
each request's length, which the slice's sums do not carry: PERF.md Open
questions), so a cell may list `paged_decode_window_roofline` only if its
mix sends no prompt shorter than the window:
`tests/benchmark/test_bench_cohere2_moe.py` holds every listed cell to
that."""
from .paged_decode_full import layers_work

SLIDING = "sliding_attention"


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_tokens"]` tokens, each over a whole
    window, in each window layer of `sl["layers"]`."""
    return layers_work(cfg, sl, SLIDING,
                       sl["decode_tokens"] * cfg["sliding_window"])
