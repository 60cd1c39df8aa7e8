"""The work of decode attention over the FULL-attention layers of a model
whose layers are of two kinds (the `paged_decode` calls of such a model:
its window layers' calls carry another name): each decode token reads the
keys and values live for its sequence once per full layer, and does QK^T
and PV over them. From the lengths the benchmark knows; the layer kinds
from the configuration's family (`layer_kinds`)."""
import importlib

FULL = "full_attention"


def layers_work(cfg: dict, sl: dict, kind: str, ctx_tokens: float) -> tuple:
    """(FLOPs, bytes) of `sl["decode_tokens"]` decode tokens attending
    `ctx_tokens` positions in all, in each layer of `kind`: QK^T and PV,
    K and V of every attended position, each token's q in and o out."""
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    n = sum(k == kind for k in fam.layer_kinds(cfg, sl["layers"]))
    tokens = sl["decode_tokens"]
    sh = fam.attention_shape(cfg)
    qo = 2 * sh["heads"] * sh["head_dim"] * 2
    return (n * fam.attn_flops_per_layer(cfg, tokens, ctx_tokens),
            n * (ctx_tokens * fam.kv_bytes_per_token_layer(cfg)
                 + tokens * qo))


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_ctx_tokens"]` attended positions in
    each full layer of `sl["layers"]`."""
    return layers_work(cfg, sl, FULL, sl["decode_ctx_tokens"])
