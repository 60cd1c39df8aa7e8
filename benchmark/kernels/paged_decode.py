"""The work of decode attention over a paged cache, in one traced slice:
each decode token reads the keys and values LIVE for its sequence (not the
pool, not the pages past its end) once per layer, and does QK^T and PV
over them. From the lengths the benchmark knows."""
import importlib


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_ctx_tokens"]` attended positions in
    each of `sl["layers"]` layers."""
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    ctx_tokens, layers = sl["decode_ctx_tokens"], sl["layers"]
    flops = layers * fam.attn_flops_per_layer(cfg, sl["decode_tokens"],
                                              ctx_tokens)
    # K and V of every live position, plus each token's q in and o out
    sh = fam.attention_shape(cfg)
    qo = 2 * sh["heads"] * sh["head_dim"] * 2
    nbytes = layers * (ctx_tokens * fam.kv_bytes_per_token_layer(cfg)
                       + sl["decode_tokens"] * qo)
    return flops, nbytes
