"""The work of decode attention over the full layers of a hybrid model
whose layer kinds are (attention, FFN) pairs (the family's `layer_kinds`:
a linear layer beside a full one), in one traced slice: each decode token
reads the keys and values live for its sequence once per full layer and
does QK^T and PV over them; its q in and its output out. The least any
decode over K/V pages does, whatever implements it. From the lengths the
benchmark knows and the family's `attention_shape`,
`attn_flops_per_layer` and `kv_bytes_per_token_layer`."""
import importlib


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_ctx_tokens"]` attended positions in
    each full layer of `sl["layers"]`."""
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    n = sum(a == fam.FULL for a, _ in fam.layer_kinds(cfg, sl["layers"]))
    ctx, tokens = sl["decode_ctx_tokens"], sl["decode_tokens"]
    sh = fam.attention_shape(cfg)
    qo = 2 * sh["heads"] * sh["head_dim"] * 2
    return (n * fam.attn_flops_per_layer(cfg, tokens, ctx),
            n * (ctx * fam.kv_bytes_per_token_layer(cfg) + tokens * qo))
