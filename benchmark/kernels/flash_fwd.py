"""The work of one causal attention forward over [batch, seq]: QK^T and PV
over the lower triangle (a query attends to itself and what precedes it),
q and o moved once, k and v once. Per call x the calls the trace shows:
under full recompute the forward runs twice a layer a step, and each run
is work the kernel did."""
import importlib


def per_call(cfg: dict, sl: dict) -> tuple:
    sh = importlib.import_module(
        f"benchmark.families.{cfg['family']}").attention_shape(cfg)
    b, s = sl["batch"], sl["seq"]
    pairs = b * s * (s + 1) / 2.0
    flops = 4.0 * sh["heads"] * sh["head_dim"] * pairs
    nbytes = 2.0 * b * s * sh["head_dim"] * (2 * sh["heads"]
                                             + 2 * sh["kv_heads"])
    return flops, nbytes


def work(cfg: dict, sl: dict, calls: int) -> tuple:
    flops, nbytes = per_call(cfg, sl)
    return calls * flops, calls * nbytes
