"""The work of one decode token of the gated delta rule in each linear
layer, in one traced slice: the token's layer state (a float32 [dk, dv]
matrix a value head) read once and written once, its q, k, v, beta and g
in and its output out, and the rule's arithmetic on every element of the
state (the decay, S^T k, k u^T added, S^T q: 7 FLOPs). The least any
decode step of this recurrence does, whatever implements it; the bucket's
rows that hold no request are not work. From the lengths the benchmark
knows and the family's `layer_kinds` and `state_shape`."""
import importlib


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_tokens"]` tokens in each linear
    layer of `sl["layers"]`."""
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    linear = sum(a == fam.LINEAR for a, _ in fam.layer_kinds(cfg, sl["layers"]))
    nv, dk, dv = fam.state_shape(cfg)
    nk = cfg["linear_num_key_heads"]
    steps = sl["decode_tokens"] * linear
    flops = steps * fam.state_flops_per_token_layer(cfg)
    # the state in and out; q, k (a key head each), v, beta, g in, o out
    nbytes = steps * 4 * (2 * nv * dk * dv + 2 * nk * dk + 2 * nv * dv
                          + 2 * nv)
    return flops, nbytes
