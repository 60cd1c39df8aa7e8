"""The work of one causal attention backward over [batch, seq]: five
matmuls over the lower triangle (S again, dP, dV, dK, dQ) against the
forward's two, whatever the implementation recomputes beyond them; q, k,
v, o, dO read and dQ, dK, dV written. The repo runs it as two kernels
(`flash_bwd_dq`, `flash_bwd_dkv`); their times are added and one
backward's work is counted for each PAIR of calls."""
from . import flash_fwd


def work(cfg: dict, sl: dict, calls: int) -> tuple:
    f_flops, f_bytes = flash_fwd.per_call(cfg, sl)
    backwards = calls / 2.0
    return backwards * 2.5 * f_flops, backwards * 2.5 * f_bytes
