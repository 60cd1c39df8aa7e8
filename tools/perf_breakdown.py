"""Decompose the LLaMA train step cost on the real chip (VERDICT r2 item 2:
'commit a per-step breakdown showing where time goes').

Times jitted sub-programs: matmul peak, fwd-only, fwd+bwd, lm_head/CE cost.
Prints one JSON line per probe.
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


def _sync(t):
    jax.device_get(jnp.ravel(t._data if hasattr(t, "_data") else t)[0])


def timeit(f, iters=8, warmup=3):
    for _ in range(warmup):
        _sync(f())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def probe_matmul_peak():
    """bf16 MXU peak a plain jitted matmul achieves."""
    for n in (4096, 8192):
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16)
        f = jax.jit(lambda x, y: x @ y)
        dt = timeit(lambda: f(a, b))
        print(json.dumps({"probe": f"matmul_bf16_{n}",
                          "ms": round(dt * 1e3, 2),
                          "tflops": round(2 * n**3 / dt / 1e12, 1)}),
              flush=True)

    a = jnp.ones((8192, 8192), jnp.bfloat16)
    w = jnp.ones((8192, 8192), jnp.bfloat16)

    def chain(x, w):
        for _ in range(8):
            x = x @ w
        return x

    f = jax.jit(chain)
    dt = timeit(lambda: f(a, w))
    print(json.dumps({"probe": "matmul_chain8_bf16_8192",
                      "ms": round(dt * 1e3, 2),
                      "tflops": round(8 * 2 * 8192**3 / dt / 1e12, 1)}),
          flush=True)


def probe_llama_parts(batch=8, seq=1024):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16, max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000, (batch, seq)).astype("int64"))
    small = paddle.to_tensor(rs.randint(0, 32000, (1, 128)).astype("int64"))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    toks = batch * seq
    fwd_flops = 2 * n_params * toks
    head_frac = (32000 * 1024) / n_params  # lm_head share of param matmuls

    def mk(fn):
        c = paddle.jit.to_static(fn, share_discovery=True)
        c(small)
        c(small)
        return c

    def fwd_ce(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            from paddle_tpu.core.dispatch import no_grad

            with no_grad():
                return model(x, x)

    def fwd_no_head(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            from paddle_tpu.core.dispatch import no_grad

            with no_grad():
                h = model.model(x)
                return (h.astype("float32") ** 2).mean()

    def fwd_bwd(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            loss = model(x, x)
        loss.backward()
        for p in model.parameters():
            p.clear_gradient()
        return loss

    for name, fn, flops in (
            ("fwd_with_ce", fwd_ce, fwd_flops),
            ("fwd_no_head", fwd_no_head, fwd_flops * (1 - head_frac)),
            ("fwd_bwd_with_ce", fwd_bwd, 3 * fwd_flops)):
        c = mk(fn)
        dt = timeit(lambda: c(ids), iters=6, warmup=3)
        print(json.dumps({"probe": name, "ms": round(dt * 1e3, 1),
                          "tflops": round(flops / dt / 1e12, 1)}),
              flush=True)


def probe_residual_policy(batch=8, seq=1024):
    """Round-8 A/B: the full fwd+bwd step with the f32 vs bf16 residual
    stream (FLAGS_residual_dtype) — the non-attention bandwidth lever. The
    fused Pallas norm/rope/swiglu kernels engage on TPU in both rows; only
    the inter-kernel stream dtype changes."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    for policy in ("float32", "bfloat16"):
        paddle.set_flags({"FLAGS_residual_dtype": policy})
        try:
            paddle.seed(0)
            cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                              intermediate_size=2816, num_hidden_layers=8,
                              num_attention_heads=16,
                              max_position_embeddings=seq)
            model = LlamaForCausalLM(cfg)
            model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                        master_weight=False)
            rs = np.random.RandomState(0)
            ids = paddle.to_tensor(
                rs.randint(0, 32000, (batch, seq)).astype("int64"))
            small = paddle.to_tensor(
                rs.randint(0, 32000, (1, 128)).astype("int64"))

            @paddle.jit.to_static(share_discovery=True)
            def fwd_bwd(x):
                with paddle.amp.auto_cast(enable=True, dtype="bfloat16",
                                          level="O2"):
                    loss = model(x, x)
                loss.backward()
                for p in model.parameters():
                    p.clear_gradient()
                return loss

            fwd_bwd(small)
            fwd_bwd(small)
            dt = timeit(lambda: fwd_bwd(ids), iters=6, warmup=3)
            n_params = sum(int(np.prod(p.shape))
                           for p in model.parameters())
            flops = 3 * 2 * n_params * batch * seq
            print(json.dumps({"probe": f"fwd_bwd_resid_{policy}",
                              "ms": round(dt * 1e3, 1),
                              "tokens_per_sec": round(batch * seq / dt, 1),
                              "tflops": round(flops / dt / 1e12, 1)}),
                  flush=True)
        finally:
            paddle.set_flags({"FLAGS_residual_dtype": "float32"})


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "matmul"):
        probe_matmul_peak()
    if which in ("all", "llama"):
        probe_llama_parts()
    if which in ("all", "resid"):
        probe_residual_policy()
