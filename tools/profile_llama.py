"""Capture an xplane trace of the LLaMA train step on the real chip and
print the device op-time breakdown (VERDICT r2 item 2 'committed breakdown').

Uses paddle_tpu.profiler's jax.profiler bridge + tensorboard_plugin_profile
to parse the xplane into per-op totals.
"""
from __future__ import annotations

import glob
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


def main(batch=8, seq=1024, logdir="/tmp/llama_trace", config="168m",
         remat="mlp"):
    """config="168m" (default) profiles the proxy; config="1b" profiles the
    REAL 1.14B flagship step (pass batch/remat to match the bench row, e.g.
    `python tools/profile_llama.py 4 1024 /tmp/t 1b flash_resident`) — the
    round-6 xplane capture that drives the PERF.md breakdown."""
    import paddle_tpu as paddle
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    enable_compile_cache()
    paddle.seed(0)
    if config == "1b":
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=20,
                          num_attention_heads=16,
                          max_position_embeddings=seq,
                          use_recompute=True, recompute_granularity=remat)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=16,
                          max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    if config == "1b":
        # match the bench_llama_1b row: bf16 params + bf16 AdamW moments
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16",
                                         master_weight=False)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000, (batch, seq)).astype("int64"))
    small = paddle.to_tensor(rs.randint(0, 32000, (1, 128)).astype("int64"))

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    _sync(train_step(small))
    _sync(train_step(small))
    for _ in range(3):
        _sync(train_step(ids))

    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        for _ in range(4):
            out = train_step(ids)
        _sync(out)

    xs = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True), key=os.path.getmtime)
    if not xs:
        print("no xplane captured", file=sys.stderr)
        return
    from tensorboard_plugin_profile.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xs[-1]], "framework_op_stats", params={})
    rows = json.loads(data) if isinstance(data, (str, bytes)) else data
    print(json.dumps(rows)[:200], file=sys.stderr)
    # framework_op_stats returns a list-of-dicts table; fall back to raw dump
    with open("/tmp/op_stats.json", "w") as f:
        json.dump(rows, f, indent=1)
    print("wrote /tmp/op_stats.json")


if __name__ == "__main__":
    a = sys.argv[1:]
    main(batch=int(a[0]) if len(a) > 0 else 8,
         seq=int(a[1]) if len(a) > 1 else 1024,
         logdir=a[2] if len(a) > 2 else "/tmp/llama_trace",
         config=a[3] if len(a) > 3 else "168m",
         remat=a[4] if len(a) > 4 else "mlp")
