"""Chunk-size sweep for the fused linear+cross-entropy tail (round 6).

Measures, ON THE CHIP, the flagship lm_head+CE configuration ([tokens, H] @
[H, 32000] + CE, fwd+bwd) across:

  - the unfused full-logits baseline,
  - the vocab-chunked path at several chunk sizes,
  - the token(sequence)-chunked path at several chunk sizes,

each as ONE jitted program chained over `reps` iterations so the per-call
dispatch cost amortizes.
Prints a JSON table for PERF.md; pick the winner via FLAGS_flce_chunk_axis
/ FLAGS_flce_token_chunk.

Usage: python tools/sweep_ce_chunk.py [tokens] [hidden] [vocab]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.functional.fused_loss import (  # noqa: E402
    _best_chunk, _flce, _flce_tok)


def _time(fn, *args, iters=6, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.device_get(jnp.ravel(out[0] if isinstance(out, tuple) else out)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.device_get(jnp.ravel(out[0] if isinstance(out, tuple) else out)[0])
    return (time.perf_counter() - t0) / iters


def main(n=4096, hid=2048, v=32000, dtype="bfloat16"):
    rs = np.random.RandomState(0)
    dt = jnp.dtype(dtype)
    h = jnp.asarray(rs.randn(n, hid).astype("float32") * 0.1, dt)
    w = jnp.asarray(rs.randn(hid, v).astype("float32") * 0.02, dt)
    lab = jnp.asarray(rs.randint(0, v, (n,)).astype("int32"))

    rows = []

    def grad_of(loss_fn):
        return jax.jit(jax.grad(loss_fn, argnums=(0, 1)))

    def plain(hh, ww):
        logits = (hh.astype(jnp.float32) @ ww.astype(jnp.float32))
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]
        return jnp.mean(lse - picked)

    dt_s = _time(grad_of(plain), h, w)
    rows.append({"path": "unfused_full_logits", "ms": dt_s * 1e3})

    for chunk in (1600, 3200, 6400, 8000, 16000):
        c = _best_chunk(v, chunk)
        if not c or any(r.get("chunk") == c and r["path"] == "vocab"
                        for r in rows):
            continue
        fn = grad_of(lambda hh, ww, c=c: _flce(hh, ww, lab, c, -100))
        rows.append({"path": "vocab", "chunk": c, "ms": _time(fn, h, w) * 1e3})

    for cn in (256, 512, 1024, 2048, 4096):
        if cn > n:
            continue
        # ragged n: pad with ignored labels (like the public wrapper) so
        # every row processes ALL n tokens and timings stay comparable
        pad = (-n) % cn

        def loss_fn(hh, ww, cn=cn, pad=pad):
            if pad:
                hh = jnp.pad(hh, ((0, pad), (0, 0)))
            lp = jnp.pad(lab, (0, pad), constant_values=-1)
            return _flce_tok(hh, ww, lp, cn, -100)

        rows.append({"path": "tokens", "chunk": cn,
                     "ms": _time(grad_of(loss_fn), h, w) * 1e3})

    base = rows[0]["ms"]
    for r in rows:
        r["vs_unfused"] = round(base / r["ms"], 2)
        r["ms"] = round(r["ms"], 2)
    out = {"shape": [n, hid, v], "dtype": dtype,
           "platform": jax.devices()[0].platform, "rows": rows}
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:4]]
    main(*args)
