"""ResNet-50 conv-perf decomposition on the real chip (VERDICT r3 Weak #2:
'ResNet-50 MFU ~8%; do for config 2 what round 3 did for LLaMA').

Probes, each as an isolated jitted program (one JSON line each):
  conv_peak   — one big NHWC conv (the chip's conv roofline)
  fwd         — resnet50 forward only
  fwd_bwd     — forward + gradients
  train       — full train step (grads + momentum update + BN stats)
  train_nhwc  — same but with images fed NHWC (conversion cost probe)
  pieces      — stem / stages / head timed separately
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


def timeit(f, iters=6, warmup=3):
    for _ in range(warmup):
        _sync(f())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def emit(name, ms, extra=None):
    rec = {"probe": name, "ms": round(ms * 1e3, 3)}
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)


def main(batch=256):
    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    # --- conv roofline: 3x3 conv on a mid-stage shape, bf16
    rs = np.random.RandomState(0)
    for (n, h, c_in, c_out, k) in [(batch, 28, 128, 128, 3),
                                   (batch, 14, 256, 256, 3),
                                   (batch, 56, 64, 64, 3)]:
        x = jnp.asarray(rs.randn(n, h, h, c_in), jnp.bfloat16)
        w = jnp.asarray(rs.randn(k, k, c_in, c_out), jnp.bfloat16)

        @jax.jit
        def conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        dt = timeit(lambda: conv(x, w))
        flops = 2 * n * h * h * c_in * c_out * k * k
        emit(f"conv_peak_{h}x{h}x{c_in}", dt,
             {"tflops": round(flops / dt / 1e12, 1)})

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    X = paddle.to_tensor(rs.randn(batch, 3, 224, 224).astype("float32"))
    Y = paddle.to_tensor(rs.randint(0, 1000, (batch,)).astype("int64"))

    @paddle.jit.to_static(share_discovery=True)
    def fwd(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O1"):
            return model(x)

    Xs = paddle.to_tensor(rs.randn(4, 3, 224, 224).astype("float32"))
    _sync(fwd(Xs)); _sync(fwd(Xs))
    dt = timeit(lambda: fwd(X))
    fwd_flops = 4.1e9 * batch
    emit("fwd", dt, {"imgs_per_sec": round(batch / dt, 1),
                     "tflops": round(fwd_flops / dt / 1e12, 1)})

    @paddle.jit.to_static(share_discovery=True)
    def fwd_bwd(x, y):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O1"):
            logits = model(x)
        loss = F.cross_entropy(logits.astype("float32"), y)
        loss.backward()
        opt.clear_grad()
        return loss

    Ys = paddle.to_tensor(rs.randint(0, 1000, (4,)).astype("int64"))
    _sync(fwd_bwd(Xs, Ys)); _sync(fwd_bwd(Xs, Ys))
    dt = timeit(lambda: fwd_bwd(X, Y))
    emit("fwd_bwd", dt, {"imgs_per_sec": round(batch / dt, 1),
                         "tflops": round(3 * fwd_flops / dt / 1e12, 1)})

    @paddle.jit.to_static(share_discovery=True)
    def train(x, y):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O1"):
            logits = model(x)
        loss = F.cross_entropy(logits.astype("float32"), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    _sync(train(Xs, Ys)); _sync(train(Xs, Ys))
    dt = timeit(lambda: train(X, Y))
    emit("train", dt, {"imgs_per_sec": round(batch / dt, 1),
                       "tflops": round(3 * fwd_flops / dt / 1e12, 1)})


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 256)
