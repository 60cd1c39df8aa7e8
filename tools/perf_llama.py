"""Single-chip LLaMA perf experiments (VERDICT r2 item 2: find the missing
MFU). Runs one variant per invocation on the real TPU and prints one JSON
line. Variants sweep batch/seq/amp-mode/remat so the winning recipe can be
promoted into bench.py.

Usage: python tools/perf_llama.py <variant>
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    import jax
    import jax.numpy as jnp

    arr = x._data if hasattr(x, "_data") else x
    jax.device_get(jnp.ravel(arr)[0])


def run(batch, seq, mode, layers=8, hidden=1024, inter=2816, heads=16,
        iters=6, warmup=4, recompute=False):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=inter, num_hidden_layers=layers,
                      num_attention_heads=heads,
                      max_position_embeddings=seq, use_recompute=recompute)
    model = LlamaForCausalLM(cfg)
    if mode == "o2":
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True)
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    else:
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000, (batch, seq)).astype("int64"))

    amp_on = mode in ("o1", "o2")
    level = "O2" if mode == "o2" else "O1"

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x):
        with paddle.amp.auto_cast(enable=amp_on, dtype="bfloat16",
                                  level=level):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # prime eager warmup/discovery at TINY shapes (eager fp32 residuals at
    # full batch would exceed HBM); big shapes go straight to compile
    small = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 32000, (1, 128)).astype("int64"))
    _sync(train_step(small))
    _sync(train_step(small))
    for _ in range(warmup):
        out = train_step(ids)
        _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = train_step(ids)
    _sync(out)
    dt = (time.perf_counter() - t0) / iters
    toks = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # 6ND decoder flops + attention term 12*L*H*S^2... report plain 6ND for
    # comparability with the round-2 row plus the attention-inclusive number
    flops6nd = 6 * n_params * toks
    attn = 12 * layers * hidden * seq * (batch * seq / dt)
    return {"batch": batch, "seq": seq, "mode": mode, "recompute": recompute,
            "step_ms": round(dt * 1e3, 1), "tokens_per_sec": round(toks),
            "tflops_6nd": round(flops6nd / 1e12, 1),
            "tflops_with_attn": round((flops6nd + attn) / 1e12, 1),
            "n_params": n_params, "loss": float(out)}


VARIANTS = {
    "base": lambda: run(4, 512, "o1"),            # the round-2 shape
    "b8s1024": lambda: run(8, 1024, "o1"),
    "b16s1024": lambda: run(16, 1024, "o1"),
    "b8s1024_o2": lambda: run(8, 1024, "o2"),
    "b16s1024_o2": lambda: run(16, 1024, "o2"),
    "b32s1024_o2": lambda: run(32, 1024, "o2"),
    "b8s2048_o2": lambda: run(8, 2048, "o2"),
    "b16s1024_o2_rc": lambda: run(16, 1024, "o2", recompute=True),
    "fp32": lambda: run(8, 1024, "fp32"),
}

if __name__ == "__main__":
    name = sys.argv[1]
    t0 = time.time()
    res = VARIANTS[name]()
    res["name"] = name
    res["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(res))
