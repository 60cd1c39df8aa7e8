"""The work of decode attention over a paged LATENT cache (MLA), in one
traced slice: each decode token reads the cached row of every position
live for its sequence ONCE per layer — the latent and the rotary key, no
heads, no separate V — and every query head takes a score over the row's
`latent_dim + rope_dim` values and a weighted sum over its `latent_dim`.
The least any decode over a latent cache does, whatever implements it
(the absorbed form; expanding the rows again would do more). From the
lengths the benchmark knows and the family's `attention_shape`."""
import importlib


def work(cfg: dict, sl: dict, calls: int = 0) -> tuple:
    """(FLOPs, bytes) for `sl["decode_ctx_tokens"]` attended positions in
    each of `sl["layers"]` layers: 2 x heads x (row + latent) FLOPs and
    the row's published bytes a position; each token's absorbed query
    (latent and rotary parts) in and its latent-space output out."""
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    sh = fam.attention_shape(cfg)
    ctx_tokens, layers = sl["decode_ctx_tokens"], sl["layers"]
    row = sh["latent_dim"] + sh["rope_dim"]
    flops = layers * 2.0 * sh["heads"] * (row + sh["latent_dim"]) * ctx_tokens
    qo = sh["heads"] * (row + sh["latent_dim"]) * 2
    nbytes = layers * (ctx_tokens * fam.kv_bytes_per_token_layer(cfg)
                       + sl["decode_tokens"] * qo)
    return flops, nbytes
