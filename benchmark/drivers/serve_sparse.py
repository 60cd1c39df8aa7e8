"""Role `serve_sparse`: the open loop of `drivers/serve.py` as it stands
(`setup`, `window`, `release` and the rest ARE that module's), with a
check of the served tokens that can judge a model whose router is
discrete.

`serve.check` compares the WIDEST gap by which a served token's logit
lies below the float32 reference's best. Behind a top-k router one pick
that flips on a rounding swaps a whole expert for that token: the widest
gap over some 550 tokens then reads the one rarest flip, which is as wide
in the stated precision as in the one below it (PERF.md section 6, PR 31:
0.38 against 0.38-0.78). What the precisions differ in is HOW MANY tokens
are off, by orders of magnitude. So this check compares

    served_tokens_off_share   the share of the sampled served tokens whose
                              reference logit lies more than
                              `check.token_gap_sigma` standard deviations
                              below the reference's best at its position
                              (limit `check.off_share_limit`), and
    served_logit_gap_sigma    the widest gap, as `serve.check` has it,
                              held to `check.gap_sigma_limit`: no single
                              token further off than one flipped pick
                              puts it (a token from stale or foreign
                              state reads several sigma),

and with "fp8" in `precisions` the same two numbers of the token the
lower precision puts first. The sample is `serve.check`'s: the longest
finished request, then others drawn from the seed.
"""
from __future__ import annotations

import numpy as np

from .. import weights
from ..harness import progress
from .serve import (compiles_in_window, kernels_present,  # noqa: F401
                    release, setup, window)

#: the gaps counted on stderr beside the one compared (sigma)
STEPS = (0.0, 0.01, 0.02, 0.05, 0.1, 0.2)


def token_gaps(ctx, done: list, control: bool) -> dict:
    """{"served": gaps, "control": gaps} over the sampled requests'
    tokens, each gap in units of its position's logit standard deviation
    under the float32 reference."""
    fam, cfg, mix = ctx.family, ctx.cfg, ctx.mix
    layers = fam.depth(cfg, "serve")
    out = {"served": [], "control": []}
    if not done:
        return out
    rng = np.random.default_rng(ctx.seed + 104729)
    order = sorted(range(len(done)), key=lambda i: -(
        len(done[i].prompt) + len(done[i].tokens)))
    pick = [order[0]] + [int(i) for i in rng.permutation(order[1:])]
    pick = pick[:int(mix["check"]["requests"])]
    w = weights.make(fam.weight_spec(cfg, layers), ctx.seed)
    pad = int(mix["max_total_tokens"])
    for i in pick:
        b = done[i]
        toks = np.asarray(b.tokens, np.int64)
        ids = np.zeros(pad, np.int64)
        seq = np.concatenate([b.prompt, toks[:-1]])
        ids[:len(seq)] = seq
        rows = len(b.prompt) - 1 + np.arange(len(toks))
        ref = fam.reference_rows(cfg, layers, w, ids, rows, "f32")
        sd = ref.std(axis=-1)
        best = ref.max(axis=-1)
        at = np.arange(len(toks))
        out["served"].append((best - ref[at, toks]) / sd)
        if control:
            low = fam.reference_rows(cfg, layers, w, ids, rows, "fp8")
            out["control"].append((best - ref[at, low.argmax(axis=-1)]) / sd)
    return out


def check(ctx, rec, precisions=("f32",)) -> list:
    chk = ctx.mix["check"]
    t = chk["token_gap_sigma"]
    done = rec["done"]
    control = "fp8" in precisions
    gaps = token_gaps(ctx, done, control)
    out = []
    for who in ("served", "control") if control else ("served",):
        g = np.concatenate(gaps[who]) if gaps[who] else np.zeros(1)
        by_request = [[int((r > 0).sum()), int((r > t).sum()), len(r)]
                      for r in gaps[who]]
        progress(f"serve: {who} tokens past {list(STEPS)} sigma "
                 f"{[int((g > s).sum()) for s in STEPS]} of {g.size}, mean "
                 f"gap {float(g.mean()):.3g}; a request [past 0, past "
                 f"{t:g}, tokens] {by_request}")
        pre, tag = (("control_fp8.", {"control": "control_fp8"})
                    if who == "control" else ("", {}))
        out.append({"name": pre + "served_tokens_off_share",
                    "value": float((g > t).mean()),
                    "limit": chk["off_share_limit"],
                    "tokens": sum(len(r) for r in gaps[who]),
                    "requests": len(done), **tag})
        out.append({"name": pre + "served_logit_gap_sigma",
                    "value": float(g.max()),
                    "limit": chk["gap_sigma_limit"], **tag})
    out.append({"name": "requests_finished", "value": len(done),
                "limit": 1, "at_least": True})
    return out
