"""Benchmark harness — BASELINE.md config ladder on the real chip.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
Headline = config-5-proxy (LLaMA 168M bf16 train, tokens/sec). Per-config
details go to stderr and BENCH_DETAILS.json.

Ladder (BASELINE.json configs, honestly named):
  1 lenet_mnist_dygraph        — pure eager dispatch path
  2 resnet50_to_static[,_bf16] — vision train step, one XLA program
  3 bert_base_finetune         — encoder fine-tune + achieved_tflops
  4 gpt_medium_dp_sharding2    — ZeRO-2 machinery engaged (1-chip degenerate)
  5 llama_168m_train[,_bf16]   — decoder pretrain proxy (Pallas flash path)
  5b llama_1b_train_bf16       — REAL ~1.1B-param config (bf16 params +
                                 bf16 moments + recompute fit one v5e)
  5b' llama_1b_resid_bf16      — same config, bf16 residual-stream policy
                                 ON (FLAGS_residual_dtype, round 8 A/B)
  5c llama_1b_bf16_s4096/s8192 — long-context rungs (full remat)
  5d flashmask_s8192/s16384    — block-sparse fwd+bwd vs causal flash
  5e llama_1b_bf16_decode      — flagship-scale KV-cached generation
  + fused_micro (round 8): norm/rotary/SwiGLU/dropout-add Pallas kernels
    vs the XLA compositions at the 1B geometry (ops/pallas_norm.py),
    eager dispatch micro-bench, chained + single-op int8 vs bf16,
    fused multi-tensor adam vs per-param
  + decode_micro / llama_serving (round 10): paged flash-decode kernel
    A/B (bf16 + int8-KV) and the continuous-batching serving engine on a
    mixed-length request stream (tok/s, TTFT, slot utilization vs the
    static-wave baseline)

The ladder is TIME-BOXED (BENCH_BUDGET_S, default 1500 s): flagship rows
run first, configs that no longer fit the remaining budget are skipped and
listed under "skipped" in BENCH_DETAILS.json, and the run exits rc 0 —
unless a rung that ran failed: then its error row is kept and the exit code
is 1. Every rung except the three in _CPU_RUNGS needs a TPU and fails
without one; nothing here falls back to the CPU.

History (round 16): every completed rung ALSO appends one platform-tagged
JSONL record to BENCH_HISTORY.jsonl ({run, t, rung, platform, record}),
so the perf trajectory persists across runs instead of each capture
overwriting the last — `tools/bench_trend.py` diffs the latest two
comparable (same rung, same platform) records and flags >10% regressions.

Reference parity: the role of tools/ci_op_benchmark.sh +
python/paddle/cost_model/static_op_benchmark.json — self-measured A/B
numbers, since the reference publishes no end-to-end figures (BASELINE.md).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _sync(x):
    """Completion barrier: dispatch is asynchronous, so every timed region
    ends here. (chip_smoke.py's device phase times one long matmul chain
    against this barrier and against a scalar fetch on every run.)"""
    import jax

    jax.block_until_ready(x._data if hasattr(x, "_data") else x)


def _require_tpu():
    """A rung that writes a speed runs on the chip or not at all: a number
    from the CPU backend or the Pallas interpreter is a count, never a
    speed, and is not written under a speed's name."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"this rung measures the TPU; jax found {d.platform!r} "
            f"({d.device_kind}). Tiny CPU sizes live in tests/.")


def _is_oom(e) -> bool:
    """The one failure the remat/batch fallbacks are for: the device ran
    out of memory compiling or running the attempt."""
    import jax

    return isinstance(e, jax.errors.JaxRuntimeError) \
        and "RESOURCE_EXHAUSTED" in str(e)


def _timeit(step, iters=10, warmup=3):
    for _ in range(warmup):
        out = step()
        _sync(out)  # bound in-flight buffers during eager warmup/discovery
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _timeit_median(step, iters=5, groups=5, warmup=3):
    """Steadied protocol for host-jitter-sensitive (eager) configs: time
    `groups` independent groups of `iters` steps, drop the min/max group,
    return (median_dt, spread) where spread = (max-min)/median over the
    kept groups. Eager throughput on a shared host swings run-to-run
    (round 3 saw 7x: 314 vs 2244 img/s); median-of-groups makes the
    reported number reproducible."""
    for _ in range(warmup):
        out = step()
        _sync(out)
    times = []
    for _ in range(groups):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step()
        _sync(out)
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    kept = times[1:-1] if len(times) > 2 else times
    med = kept[len(kept) // 2]
    spread = (kept[-1] - kept[0]) / med if med else 0.0
    return med, round(spread, 3)


def bench_lenet(iters=20):
    """Config-1: LeNet on synthetic MNIST, pure dygraph (per-op dispatch)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    batch = 128
    model = LeNet()
    # fused multi-tensor momentum (≙ merged_momentum_): one jitted donated
    # update instead of ~10 per-param invocations per step
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=model.parameters(),
                                    use_multi_tensor=True)
    rs = np.random.RandomState(0)
    X = paddle.to_tensor(rs.randn(batch, 1, 28, 28).astype("float32"))
    Y = paddle.to_tensor(rs.randint(0, 10, (batch,)).astype("int64"))

    def step():
        loss = F.cross_entropy(model(X), Y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    dt, spread = _timeit_median(step, iters=max(4, iters // 4), groups=5,
                                warmup=4)
    return {"name": "lenet_mnist_dygraph", "images_per_sec": batch / dt,
            "step_ms": dt * 1e3, "batch": batch, "spread": spread}


def bench_resnet50(iters=8, batch=128, image=224, amp=False):
    """Config-2: ResNet-50 train step under to_static (one XLA program);
    amp=True wraps the forward in bf16 autocast. Eager warm-up/discovery
    runs at batch 4 via share_discovery (a full-batch eager fp32 pass would
    blow HBM on residuals)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    rs = np.random.RandomState(0)
    X = paddle.to_tensor(rs.randn(batch, 3, image, image).astype("float32"))
    Y = paddle.to_tensor(rs.randint(0, 1000, (batch,)).astype("int64"))

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x, y):
        with paddle.amp.auto_cast(enable=amp, dtype="bfloat16", level="O1"):
            logits = model(x)
        loss = F.cross_entropy(logits.astype("float32"), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    Xs = paddle.to_tensor(rs.randn(4, 3, image, image).astype("float32"))
    Ys = paddle.to_tensor(rs.randint(0, 1000, (4,)).astype("int64"))
    _sync(train_step(Xs, Ys))
    _sync(train_step(Xs, Ys))
    dt = _timeit(lambda: train_step(X, Y), iters=iters, warmup=3)
    # ResNet-50 fwd ≈ 4.1 GFLOP/image @224; train ≈ 3x fwd
    flops = 3 * 4.1e9 * batch / dt
    name = "resnet50_to_static_bf16" if amp else "resnet50_to_static"
    return {"name": name, "images_per_sec": batch / dt,
            "step_ms": dt * 1e3, "batch": batch, "achieved_tflops": flops / 1e12}


def bench_bert(iters=8, batch=32, seq=128, amp=False):
    """Config-3: BERT-base fine-tune step, to_static, single device;
    amp=True fine-tunes under bf16 autocast (O2) with bf16 master state
    and batch 64 — s128 sequences underfill the MXU at b32 (25% MFU in
    rounds 3-4); doubling the token count per step was the missing lever
    (PERF.md round 5)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import BertConfig, BertForSequenceClassification

    if amp:
        batch = max(batch, 64)
    paddle.seed(0)
    model = BertForSequenceClassification(BertConfig())
    opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                 parameters=model.parameters())
    if amp:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16",
                                         master_weight=False)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 30000, (batch, seq)).astype("int64"))
    lab = paddle.to_tensor(rs.randint(0, 2, (batch,)).astype("int64"))

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x, y):
        with paddle.amp.auto_cast(enable=amp, dtype="bfloat16", level="O2"):
            loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids_s = paddle.to_tensor(rs.randint(0, 30000, (2, seq)).astype("int64"))
    lab_s = paddle.to_tensor(rs.randint(0, 2, (2,)).astype("int64"))
    _sync(train_step(ids_s, lab_s))
    _sync(train_step(ids_s, lab_s))
    dt = _timeit(lambda: train_step(ids, lab), iters=iters, warmup=3)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops = 6 * n_params * batch * seq / dt
    name = "bert_base_finetune_bf16" if amp else "bert_base_finetune"
    return {"name": name, "sequences_per_sec": batch / dt,
            "step_ms": dt * 1e3, "batch": batch, "seq": seq,
            "achieved_tflops": flops / 1e12, "n_params": n_params}


def bench_gpt_medium_sharding(iters=6, batch=4, seq=1024):
    """Config-4: GPT-3-medium (~350M) with the ZeRO-2 (os_g) group-sharded
    machinery engaged — single-chip degenerate run: the sharding optimizer,
    reduce-scatter paths, and param-group plumbing all execute over a
    1-device mesh (≙ collective DP + sharding stage-2 of BASELINE.json;
    multi-chip scaling is validated by dryrun_multichip on the CPU mesh)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(max_position_embeddings=seq))
    # round-5 recovery (VERDICT r4 Weak #2): bf16 params + bf16 moments
    # (decorate O2) with the FUSED multi-tensor update — the per-param
    # update path under os_g+bf16 regresses 73 -> 30 TFLOP/s (PERF.md
    # round 5), the fused pytree update does not
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 use_multi_tensor=True)
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16", master_weight=False)
    model, opt, _ = group_sharded_parallel(model, opt, level="os_g")
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 50304, (batch, seq)).astype("int64"))

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    small = paddle.to_tensor(rs.randint(0, 50304, (1, 128)).astype("int64"))
    _sync(train_step(small))
    _sync(train_step(small))
    dt = _timeit(lambda: train_step(ids), iters=iters, warmup=3)
    toks = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return {"name": "gpt_medium_dp_sharding2", "tokens_per_sec": toks,
            "step_ms": dt * 1e3, "batch": batch, "seq": seq,
            "achieved_tflops": 6 * n_params * toks / 1e12,
            "n_params": n_params}


def _llama_step(model, opt, level):
    import paddle_tpu as paddle

    @paddle.jit.to_static(share_discovery=True)
    def train_step(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level=level):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return train_step


def bench_llama_train(iters=6, batch=24, seq=1024, amp=True):
    """Config-5 single-chip proxy: 168M-param LLaMA-architecture causal LM
    (honestly named — round 2's 'llama_1b_proxy' row was this exact model).
    bf16 O2 + Pallas flash attention."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                      num_hidden_layers=8, num_attention_heads=16,
                      max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000, (batch, seq)).astype("int64"))
    level = "O2" if amp else "O1"
    train_step = _llama_step(model, opt, level)
    small = paddle.to_tensor(rs.randint(0, 32000, (1, 128)).astype("int64"))
    _sync(train_step(small))
    _sync(train_step(small))
    dt = _timeit(lambda: train_step(ids), iters=iters, warmup=3)
    toks = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops = 6 * n_params * toks
    attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq * toks
    name = "llama_168m_train_bf16" if amp else "llama_168m_train"
    return {"name": name, "tokens_per_sec": toks,
            "step_ms": dt * 1e3, "batch": batch, "seq": seq,
            "achieved_tflops": flops / 1e12,
            "achieved_tflops_with_attn": (flops + attn) / 1e12,
            "n_params": n_params}


def bench_llama_1b(iters=4, batch=4, seq=1024):
    """Config-5 at REAL scale: ~1.14B params on one v5e chip — bf16 params
    (amp.decorate O2), bf16 AdamW moments. Round-6 primary config: batch 4
    with the flash_resident remat policy (full-block remat that keeps ONLY
    the flash-attention outputs + softmax stats resident, ~16 MB/layer at
    b4 — the activation-memory work that unlocks b4) + the chunked fused
    CE. Falls back to the round-4/5 config (batch 3, MLP-granularity remat,
    89.9 -> 136.6 TFLOP/s then) if the chip can't hold batch 4. Measured
    under the committed median-of-5-groups protocol with spread reported."""
    import gc

    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    rs = np.random.RandomState(0)
    last_err = fell_back = None
    for b, gran in ((batch, "flash_resident"), (3, "mlp")):
        model = opt = train_step = ids = small = None
        try:
            paddle.seed(0)
            cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                              intermediate_size=5504, num_hidden_layers=20,
                              num_attention_heads=16,
                              max_position_embeddings=seq,
                              use_recompute=True,
                              recompute_granularity=gran)
            model = LlamaForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())
            model, opt = paddle.amp.decorate(model, opt, level="O2",
                                             dtype="bfloat16",
                                             master_weight=False)
            ids = paddle.to_tensor(
                rs.randint(0, 32000, (b, seq)).astype("int64"))
            train_step = _llama_step(model, opt, "O2")
            small = paddle.to_tensor(
                rs.randint(0, 32000, (1, 128)).astype("int64"))
            _sync(train_step(small))
            _sync(train_step(small))
            dt, spread = _timeit_median(lambda: train_step(ids), iters=iters,
                                        groups=5, warmup=2)
        except Exception as e:
            if not _is_oom(e):    # only out-of-memory drops to b3/mlp
                raise
            last_err = e
            fell_back = f"b{b}/{gran}: RESOURCE_EXHAUSTED"
            print(f"[bench] llama_1b {fell_back} ({str(e)[:120]}); "
                  "falling back", file=sys.stderr)
            # free EVERYTHING from the failed attempt before the retry
            # allocates a second full model — train_step's to_static capture
            # set pins all params/moments, ids pins the batch
            del model, opt, train_step, ids, small
            gc.collect()
            continue
        toks = b * seq / dt
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        return {"name": "llama_1b_train_bf16", "tokens_per_sec": toks,
                "step_ms": dt * 1e3, "batch": b, "seq": seq,
                "remat": gran, "spread": spread,
                "fell_back_from": fell_back,
                "achieved_tflops": 6 * n_params * toks / 1e12,
                "n_params": n_params}
    raise last_err


def bench_llama_longctx(iters=3, batch=4, seq=4096):
    """Long-context rung (VERDICT r4 Missing #2): the 168M decoder trained
    at s4096/s8192 with full-block recompute — the regime SURVEY §5.7
    names the north star. 168M rather than the 1.14B flagship because the
    chip's usable HBM could not hold the 1B's ~9.2 GB bf16 AdamW state
    PLUS 4k-token activations (measured: ResourceExhausted at b1 s4096;
    the r3 ladder already established 4k tokens/step as the 1B activation
    ceiling at s1024). Token budget per step is held at 16k across rungs
    so MXU utilization is comparable; reports TFLOP/s retention vs the
    same model's s1024 capture. Attention FLOPs are no longer negligible
    at these lengths, so both 6ND and with-attn numbers are recorded.
    Round 6: primary remat is flash_resident — at s8192 full-block remat
    re-runs the (dominant) flash forward once per layer in the backward;
    keeping its outputs resident costs ~32 MB/layer and removes that —
    falling back to the round-5 full-remat config if it doesn't fit.
    Long-seq flash blocks autotune on first sighting (seq-keyed
    candidates, fwd/dq/dkv tuned separately)."""
    import gc

    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    rs = np.random.RandomState(0)
    last_err = fell_back = None
    for gran in ("flash_resident", "full"):
        model = opt = train_step = small = None
        try:
            paddle.seed(0)
            cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                              intermediate_size=2816, num_hidden_layers=8,
                              num_attention_heads=16,
                              max_position_embeddings=seq,
                              use_recompute=True,
                              recompute_granularity=gran)
            model = LlamaForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())
            model, opt = paddle.amp.decorate(model, opt, level="O2",
                                             dtype="bfloat16",
                                             master_weight=False)
            ids = paddle.to_tensor(
                rs.randint(0, 32000, (batch, seq)).astype("int64"))
            train_step = _llama_step(model, opt, "O2")
            small = paddle.to_tensor(
                rs.randint(0, 32000, (1, 128)).astype("int64"))
            _sync(train_step(small))
            _sync(train_step(small))
            dt = _timeit(lambda: train_step(ids), iters=iters, warmup=2)
            break
        except Exception as e:
            if not _is_oom(e):    # only out-of-memory drops to full remat
                raise
            last_err = e
            fell_back = f"{gran}: RESOURCE_EXHAUSTED"
            print(f"[bench] longctx s{seq} {fell_back} "
                  f"({str(e)[:120]}); falling back", file=sys.stderr)
            # free the to_static closure too — it pins params/moments
            del model, opt, train_step, small
            gc.collect()
    else:
        raise last_err
    toks = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops = 6 * n_params * toks
    attn = 12 * cfg.num_hidden_layers * cfg.hidden_size * seq * toks
    # denominator: the committed s1024 capture of the SAME model, so the
    # ratio tracks the current ladder rather than a hard-coded number
    base = 84.9
    try:
        with open("BENCH_DETAILS.json") as f:
            base = json.load(f)["results"]["llama_bf16"]["achieved_tflops"]
    except (OSError, KeyError, ValueError):
        pass
    return {"name": f"llama_168m_bf16_s{seq}", "tokens_per_sec": toks,
            "step_ms": dt * 1e3, "batch": batch, "seq": seq, "remat": gran,
            "fell_back_from": fell_back,
            "achieved_tflops": flops / 1e12,
            "achieved_tflops_with_attn": (flops + attn) / 1e12,
            "retention_vs_s1024": round(flops / 1e12 / base, 3),
            "s1024_baseline_tflops": round(base, 1),
            "n_params": n_params}


def bench_flashmask_longctx(iters=5, s=8192, window=1024, b=1, h=16, d=128):
    """FlashMask block-sparse kernel at long context (VERDICT r4 Missing
    #1): fwd+bwd of a sliding-window pattern vs dense-causal flash fwd+bwd
    at the 1B head geometry. Also records the compiled backward's temp
    memory (memory_analysis) as evidence that the bwd kernels never
    materialize an [Sq,Sk] buffer (a dense f32 8192x8192 score matrix per
    head would be 256 MB x B x H)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_attention import (flash_attention_raw,
                                                 flashmask_attention_raw)

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, h, s, d).astype("float32") * 0.2,
                    jnp.bfloat16)
    k = jnp.asarray(rs.randn(b, h, s, d).astype("float32") * 0.2,
                    jnp.bfloat16)
    v = jnp.asarray(rs.randn(b, h, s, d).astype("float32"), jnp.bfloat16)
    start = jnp.broadcast_to(
        jnp.asarray((np.arange(s) + window).clip(0, s).astype("int32")),
        (b, h, s))

    def fm_loss(q, k, v):
        return jnp.sum(flashmask_attention_raw(q, k, v, start, causal=True)
                       .astype(jnp.float32) ** 2)

    def causal_loss(q, k, v):
        return jnp.sum(flash_attention_raw(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    fm = jax.jit(jax.grad(fm_loss, argnums=(0, 1, 2)))
    ca = jax.jit(jax.grad(causal_loss, argnums=(0, 1, 2)))
    out = {"name": f"flashmask_s{s}_w{window}_fwdbwd",
           "shape": [b, h, s, d], "window": window}
    try:  # temp bytes of the compiled sparse fwd+bwd program
        mem = fm.lower(q, k, v).compile().memory_analysis()
        out["fm_temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", -1))
        out["dense_scores_would_be_bytes"] = 4 * b * h * s * s
    except Exception as e:  # memory_analysis not available on this backend
        out["fm_temp_bytes_error"] = str(e)[:120]

    dt_fm = _timeit(lambda: fm(q, k, v)[0], iters=iters, warmup=2)
    dt_ca = _timeit(lambda: ca(q, k, v)[0], iters=iters, warmup=2)
    out.update({"flashmask_ms": dt_fm * 1e3, "causal_flash_ms": dt_ca * 1e3,
                "speedup_vs_causal_flash": round(dt_ca / dt_fm, 2)})
    return out


def bench_decode_1b(batch=4, prompt=128, new_tokens=128):
    """Flagship-scale decode (VERDICT r4 Missing #3 + Weak #3): KV-cached
    generation at the REAL 1.14B config — tokens/sec, ms/token-step,
    prefill split via a 2-token calibration run — in bf16 AND with
    weight-only int8 (decode GEMVs are weight-bandwidth-bound; int8
    weights halve the bytes/step)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5504, num_hidden_layers=20,
                      num_attention_heads=16,
                      max_position_embeddings=prompt + new_tokens + 8)
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000,
                                      (batch, prompt)).astype("int64"))

    def measure(wq):
        kw = {"weight_quant": wq}
        _sync(model.generate(ids, max_new_tokens=2, **kw))
        _sync(model.generate(ids, max_new_tokens=new_tokens, **kw))
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new_tokens, **kw)
        _sync(out)
        t_long = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(model.generate(ids, max_new_tokens=2, **kw))
        t_prefill = time.perf_counter() - t0
        dt = max(t_long - t_prefill, 1e-6)
        toks = batch * (new_tokens - 2)
        return toks / dt, dt / (new_tokens - 2) * 1e3, t_prefill, t_long

    tps, ms_step, t_prefill, t_long = measure("none")
    tps_i8, ms_step_i8, _, _ = measure("int8")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return {"name": "llama_1b_bf16_decode",
            "decode_tokens_per_sec": tps,
            "ms_per_token_step": ms_step,
            "int8_decode_tokens_per_sec": tps_i8,
            "int8_ms_per_token_step": ms_step_i8,
            "int8_speedup": round(tps_i8 / tps, 2),
            "prefill_plus_invoke_ms": t_prefill * 1e3,
            "batch": batch, "prompt": prompt, "new_tokens": new_tokens,
            "n_params": n_params, "wall_total_s": round(t_long, 2)}


def bench_fused_elementwise(iters=20, rows=4096, h=2048, inter=5504,
                            heads=16, dh=128, seq=1024):
    """Round-8 micro-rung: the bandwidth-bound elementwise chains at the 1B
    flagship geometry (rows = b4 x s1024, h 2048) — Pallas fused kernel vs
    the unfused XLA composition, fwd+bwd, bf16 operands. On this device
    every one of these chains is HBM-bound (PERF.md round 4: ~103 GB/s
    effective), so ms here IS bytes moved."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_norm as pn

    rs = np.random.RandomState(0)
    bf = jnp.bfloat16
    x = jnp.asarray(rs.randn(rows, h).astype("float32"), bf)
    r = jnp.asarray(rs.randn(rows, h).astype("float32"), bf)
    w = jnp.asarray(rs.randn(h).astype("float32"), bf)
    g1 = jnp.asarray(rs.randn(rows, inter).astype("float32"), bf)
    u1 = jnp.asarray(rs.randn(rows, inter).astype("float32"), bf)
    b4 = rows // seq
    q = jnp.asarray(rs.randn(b4, seq, heads, dh).astype("float32"), bf)
    k = jnp.asarray(rs.randn(b4, seq, heads, dh).astype("float32"), bf)
    emb = np.concatenate([np.outer(np.arange(seq),
                                   1.0 / 10000.0 ** (np.arange(0, dh, 2) / dh))] * 2,
                         -1)
    cos = jnp.asarray(np.cos(emb)[None, :, None, :].astype("float32"), bf)
    sin = jnp.asarray(np.sin(emb)[None, :, None, :].astype("float32"), bf)
    mask = jnp.asarray((rs.rand(rows, h) > 0.1).astype("float32"), bf)

    def fwdbwd(loss_fn, *args):
        f = jax.jit(jax.grad(loss_fn, argnums=tuple(range(len(args)))))
        return _timeit(lambda: f(*args)[0], iters=iters, warmup=3)

    def l_sum(y):
        return jnp.sum(y.astype(jnp.float32) ** 2)

    pairs = {
        "add_rms_norm": (
            lambda a, b, ww: (lambda yz: l_sum(yz[0]) + l_sum(yz[1]))(
                pn.add_rms_norm_raw(a, b, ww)),
            lambda a, b, ww: (lambda s: l_sum(
                (s.astype(jnp.float32)
                 * jax.lax.rsqrt(jnp.mean(jnp.square(s.astype(jnp.float32)),
                                          -1, keepdims=True) + 1e-6)
                 ).astype(a.dtype) * ww) + l_sum(s))(a + b),
            (x, r, w)),
        "swiglu": (
            lambda a, b: l_sum(pn.swiglu_fused(a, b)),
            lambda a, b: l_sum(jax.nn.silu(a) * b),
            (g1, u1)),
        "rope_qk": (
            lambda a, b: (lambda qk: l_sum(qk[0]) + l_sum(qk[1]))(
                pn.rope_qk_fused(a, b, cos, sin)),
            lambda a, b: (lambda rot: l_sum(rot(a)) + l_sum(rot(b)))(
                lambda t: t * cos + jnp.concatenate(
                    [-t[..., dh // 2:], t[..., :dh // 2]], -1) * sin),
            (q, k)),
        "dropout_add": (
            lambda a, b: l_sum(pn.dropout_add_fused(a, b, mask,
                                                    1.0 / 0.9)),
            lambda a, b: l_sum(jnp.where(mask != 0,
                                         a * jnp.asarray(1.0 / 0.9, bf),
                                         jnp.zeros((), bf)) + b),
            (x, r)),
    }
    out = {"name": "fused_elementwise_micro", "rows": rows, "h": h,
           "inter": inter, "dtype": "bfloat16"}
    for nm, (fused, unfused, args) in pairs.items():
        dt_f = fwdbwd(fused, *args)
        dt_u = fwdbwd(unfused, *args)
        out[f"{nm}_fused_ms"] = round(dt_f * 1e3, 3)
        out[f"{nm}_xla_ms"] = round(dt_u * 1e3, 3)
        out[f"{nm}_speedup"] = round(dt_u / dt_f, 2)
    return out


def bench_llama_1b_resid_bf16(iters=4, batch=4, seq=1024):
    """The 1B flagship row with the bf16 residual-stream policy ON
    (FLAGS_residual_dtype=bfloat16): A/B against the plain llama_1b row —
    the round-8 bandwidth lever (fused norm kernels keep f32 inside VMEM,
    the stream crosses HBM in bf16)."""
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_residual_dtype": "bfloat16"})
    try:
        res = bench_llama_1b(iters=iters, batch=batch, seq=seq)
    finally:
        paddle.set_flags({"FLAGS_residual_dtype": "float32"})
    res["name"] = "llama_1b_train_bf16_resid_bf16"
    res["residual_dtype"] = "bfloat16"
    return res


def bench_int8_chain(iters=8, m=2048, k=4096, n=4096, depth=12):
    """Honest int8-vs-bf16 measurement (VERDICT r4 Weak #3): `depth` GEMMs
    chained under lax.scan inside ONE compiled program, so the per-call
    dispatch cost is amortized over the chain instead of dominating a
    single-op probe. Paths:
      full int8  — quantize act, int8xint8 MXU GEMM (int32 acc), dequant
      weight-only — int8 weights dequantized in-program, bf16 GEMM
      bf16       — plain bf16 GEMM chain (the denominator)."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    w = rs.randn(depth, k, n).astype("float32") * 0.02
    ws = np.abs(w).max(axis=(1, 2), keepdims=False) / 127.0  # [depth]
    w8 = jnp.asarray(np.clip(np.round(w / ws[:, None, None]), -128, 127),
                     jnp.int8)
    wbf = jnp.asarray(w, jnp.bfloat16)
    wsj = jnp.asarray(ws, jnp.float32)
    x0 = jnp.asarray(rs.randn(m, k).astype("float32") * 0.5, jnp.bfloat16)
    a_s = np.float32(3.0 / 127.0)

    # weights ride as ARGUMENTS, not closure constants: closed-over arrays
    # become literal constants in the program: ~600 MB baked into the
    # executable and into every compile-cache entry
    @jax.jit
    def chain_int8(x, w8a, wsa):
        def step(xc, wl):
            w8l, wsl = wl
            x8 = jnp.clip(jnp.round(xc.astype(jnp.float32) / a_s),
                          -128, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(
                x8, w8l, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = (acc.astype(jnp.float32) * (a_s * wsl)).astype(jnp.bfloat16)
            return jnp.tanh(out), None  # bound activations between GEMMs

        y, _ = jax.lax.scan(step, x, (w8a, wsa))
        return y

    @jax.jit
    def chain_wo(x, w8a, wsa):
        def step(xc, wl):
            w8l, wsl = wl
            out = xc @ (w8l.astype(jnp.bfloat16) * wsl.astype(jnp.bfloat16))
            return jnp.tanh(out), None

        y, _ = jax.lax.scan(step, x, (w8a, wsa))
        return y

    @jax.jit
    def chain_bf16(x, wa):
        def step(xc, wl):
            return jnp.tanh(xc @ wl), None

        y, _ = jax.lax.scan(step, x, wa)
        return y

    dts = {}
    for nm, fn, args in (("int8", chain_int8, (w8, wsj)),
                         ("weight_only", chain_wo, (w8, wsj)),
                         ("bf16", chain_bf16, (wbf,))):
        dts[nm] = _timeit(lambda f=fn, a=args: f(x0, *a), iters=iters,
                          warmup=3)
    flops = 2 * m * k * n * depth
    return {"name": "int8_chained_gemms", "m_k_n_depth": [m, k, n, depth],
            "int8_ms": dts["int8"] * 1e3,
            "weight_only_ms": dts["weight_only"] * 1e3,
            "bf16_ms": dts["bf16"] * 1e3,
            "int8_tops": flops / dts["int8"] / 1e12,
            "bf16_tflops": flops / dts["bf16"] / 1e12,
            "speedup_vs_bf16": round(dts["bf16"] / dts["int8"], 2),
            "weight_only_speedup_vs_bf16":
                round(dts["bf16"] / dts["weight_only"], 2)}


def bench_decode(batch=8, prompt=128, new_tokens=256):
    """Autoregressive decode throughput: KV-cached generation as ONE
    compiled XLA program (text/generation.py ≙ masked_multihead_attention's
    role). Reports decode tokens/sec (excludes prefill via a 2-token
    calibration run)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                      num_hidden_layers=8, num_attention_heads=16,
                      max_position_embeddings=prompt + new_tokens + 8)
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 32000, (batch, prompt)).astype("int64"))

    _sync(model.generate(ids, max_new_tokens=2))        # compile short
    _sync(model.generate(ids, max_new_tokens=new_tokens))  # compile long
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new_tokens)
    _sync(out)
    t_long = time.perf_counter() - t0
    t0 = time.perf_counter()
    _sync(model.generate(ids, max_new_tokens=2))
    t_short = time.perf_counter() - t0
    dt = max(t_long - t_short, 1e-6)
    toks = batch * (new_tokens - 2)
    out = {"name": "llama_168m_bf16_decode",
           "decode_tokens_per_sec": toks / dt,
           "ms_per_token_step": dt / (new_tokens - 2) * 1e3,
           "batch": batch, "prompt": prompt, "new_tokens": new_tokens,
           "wall_total_s": round(t_long, 2)}
    # round 14: whole-generation-program roofline (prefill+decode fused
    # in one program here, so utilization is the blended number; the
    # paged serving rungs report the pure-decode one)
    from paddle_tpu import obs

    rows = obs.roofline_rows("generate", measured_only=True)
    if rows:
        best = max(rows, key=lambda r: r["roofline_utilization"])
        out["peak_gbps"] = obs.peak_gbps()
        out["roofline_utilization"] = best["roofline_utilization"]
        out["roofline_achieved_gbps"] = best["achieved_gbps"]
    return out


def bench_decode_micro(iters=8):
    """Round-10 kernel rung: paged flash-decode (ops/pallas_decode.py)
    vs the XLA gather+softmax composition at the 1B decode geometry
    (16 heads x d128, 1k context, block 16), bf16 AND int8-KV — the
    decode-side analog of fused_micro."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_decode import (paged_decode_attention_raw,
                                              paged_decode_attention_xla)

    _require_tpu()
    s, hq, hkv, d, bs, ctx = 8, 16, 16, 128, 16, 1024
    pages = ctx // bs
    n_blocks = 1 + s * pages
    rs = np.random.RandomState(0)
    bf = jnp.bfloat16
    q = jnp.asarray(rs.randn(s, hq, d).astype("float32") * 0.3, bf)
    kc = jnp.asarray(rs.randn(n_blocks, hkv, bs, d).astype("float32") * 0.3,
                     bf)
    vc = jnp.asarray(rs.randn(n_blocks, hkv, bs, d).astype("float32"), bf)
    tables = jnp.asarray(
        np.arange(1, 1 + s * pages, dtype="int32").reshape(s, pages))
    lens = jnp.full((s,), ctx, jnp.int32)       # worst-case cache sweep

    kern = jax.jit(paged_decode_attention_raw)
    comp = jax.jit(paged_decode_attention_xla)
    dt_k = _timeit(lambda: kern(q, kc, vc, tables, lens), iters=iters,
                   warmup=2)
    dt_x = _timeit(lambda: comp(q, kc, vc, tables, lens), iters=iters,
                   warmup=2)

    # int8 KV: per-block scales, the paged_cache storage convention
    ks_np = np.maximum(np.abs(np.asarray(kc, "float32")).max(axis=(1, 2, 3))
                       / 127.0, 1e-8)
    vs_np = np.maximum(np.abs(np.asarray(vc, "float32")).max(axis=(1, 2, 3))
                       / 127.0, 1e-8)
    k8 = jnp.asarray(np.clip(np.round(
        np.asarray(kc, "float32") / ks_np[:, None, None, None]),
        -127, 127).astype("int8"))
    v8 = jnp.asarray(np.clip(np.round(
        np.asarray(vc, "float32") / vs_np[:, None, None, None]),
        -127, 127).astype("int8"))
    ksj = jnp.asarray(ks_np.astype("float32"))
    vsj = jnp.asarray(vs_np.astype("float32"))
    dt_i8 = _timeit(lambda: kern(q, k8, v8, tables, lens, ksj, vsj),
                    iters=iters, warmup=2)
    out = {"name": "decode_micro_paged_attention",
           "geometry": {"slots": s, "hq": hq, "hkv": hkv, "d": d,
                        "block_size": bs, "context": ctx},
           "pallas_ms": round(dt_k * 1e3, 3),
           "xla_gather_ms": round(dt_x * 1e3, 3),
           "speedup_vs_xla": round(dt_x / dt_k, 2),
           "int8_kv_pallas_ms": round(dt_i8 * 1e3, 3),
           "int8_kv_speedup_vs_bf16": round(dt_k / dt_i8, 2),
           "cache_read_bytes_per_step": 2 * s * hkv * ctx * d * 2}
    return out


def bench_llama_serving(n_requests=None):
    """Round-10 serving rung: a mixed-length request stream through the
    continuous-batching paged engine (inference/engine.py) — decode
    tok/s, TTFT, slot utilization — A/B'd against the admission="static"
    whole-batch-wave baseline ON THE SAME STREAM. Continuous batching's
    win IS the utilization gap: freed slots refill mid-flight."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=1024)
    slots, n_req = 8, int(n_requests or 24)
    p_lo, p_hi, g_lo, g_hi = 16, 192, 16, 96
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()
    rs = np.random.RandomState(0)
    stream = [(int(rs.randint(p_lo, p_hi)), int(rs.randint(g_lo, g_hi)))
              for _ in range(n_req)]
    prompts = [rs.randint(0, cfg.vocab_size, (ln,)).astype("int64")
               for ln, _ in stream]

    def drive(mode, warmed=False):
        eng = ServingEngine(model, max_slots=slots, admission=mode)
        if warmed:
            # the warmup drive compiled every bucket this stream needs:
            # a compile during the measured drive is a watchdog finding
            eng.finish_warmup()
        for p, (_, nt) in zip(prompts, stream):
            eng.add_request(p, max_new_tokens=nt)
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        return wall, eng.stats()

    drive("continuous")                    # warm the per-bucket programs
    wall_c, st_c = drive("continuous", warmed=True)
    wall_s, st_s = drive("static", warmed=True)
    ttfts = sorted(st_c["ttft_s"])
    qwaits = sorted(st_c["queue_wait_s"])
    prefills = sorted(x - q for x, q in zip(st_c["ttft_s"],
                                            st_c["queue_wait_s"]))
    util_c = st_c["slot_utilization"]
    util_s = st_s["slot_utilization"]
    out = {"name": "llama_serving_continuous_batching",
           "slots": slots, "requests": n_req,
           "prompt_range": [p_lo, p_hi], "gen_range": [g_lo, g_hi],
           "decode_tokens": st_c["decode_tokens"],
           # decode throughput divides by the DECODE clock (the engine
           # splits decode vs prefill wall time); the whole-stream rate
           # incl. prefill + scheduling is reported separately
           "decode_tokens_per_sec": round(
               st_c["decode_tokens"] / max(st_c["decode_time_s"], 1e-9),
               1),
           "stream_tokens_per_sec": round(
               (st_c["decode_tokens"] + n_req) / wall_c, 1),
           "prefill_time_s": round(st_c["prefill_time_s"], 3),
           "wall_s_continuous": round(wall_c, 2),
           "wall_s_static": round(wall_s, 2),
           "ttft_ms_mean": round(1e3 * sum(ttfts) / len(ttfts), 1),
           "ttft_ms_p95": round(1e3 * ttfts[int(0.95 * (len(ttfts) - 1))],
                                1),
           # TTFT decomposition (round 11, satellite 6): p95 TTFT =
           # queue wait (admission blocked on slots/blocks) + prefill
           # (the program span) — quoting one number hid which side a
           # regression lived on
           "queue_wait_ms_p95": round(
               1e3 * qwaits[int(0.95 * (len(qwaits) - 1))], 1),
           "prefill_ms_p95": round(
               1e3 * prefills[int(0.95 * (len(prefills) - 1))], 1),
           "slot_utilization": util_c,
           "static_slot_utilization": util_s,
           "utilization_gain": round(util_c / max(util_s, 1e-9), 2),
           "continuous_beats_static": bool(util_c > util_s),
           "kv_pool_hbm_bytes": st_c["kv_hbm_bytes"]}
    out.update(_serving_roofline())
    return out


def _serving_roofline():
    """Measured-vs-roofline utilization of the serving DECODE programs
    (round 14): XLA cost_analysis bytes over measured per-tick wall over
    the device's peak (obs/peaks.py). Decode is the bandwidth-bound phase
    — its utilization IS the fraction-of-roofline number PERF.md used to
    hand-compute per round."""
    from paddle_tpu import obs

    rows = obs.roofline_rows("serving.decode", measured_only=True)
    if not rows:
        return {}
    best = max(rows, key=lambda r: r["roofline_utilization"])
    return {"peak_gbps": obs.peak_gbps(),
            "roofline_utilization": best["roofline_utilization"],
            "roofline_achieved_gbps": best["achieved_gbps"],
            "roofline_program": best["program"],
            "roofline_per_program": {
                r["program"]: r["roofline_utilization"] for r in rows}}


def bench_llama_serving_slo(n_requests=None, rate=None, ttft_slo_ms=None):
    """Round-13 SLO rung: a POISSON-ARRIVAL request stream through the
    continuous-batching engine, swept over shared-system-prompt fractions
    (0% / 50% / 95% of prompt tokens shared across the stream), plus a
    no-prefix-cache A/B at the 95% point. Reported per sweep point:
    p95 TTFT, GOODPUT (requests whose TTFT met the SLO, per second —
    the number a traffic-serving claim needs, not batch tok/s) and the
    prefix-cache hit rate. The acceptance headline is
    `ttft_p95_reduction_95shared`: cache-off p95 / cache-on p95 on the
    SAME 95%-shared arrival schedule."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=1024)
    slots, n_req = 8, int(n_requests or 32)
    prompt_len, g_lo, g_hi = 512, 16, 48
    rate = float(rate or 16.0)
    slo_ms = float(ttft_slo_ms or 250.0)
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()

    def make_stream(shared_frac, seed):
        rs = np.random.RandomState(seed)
        shared = rs.randint(0, cfg.vocab_size,
                            (int(prompt_len * shared_frac),))
        prompts, gens = [], []
        for _ in range(n_req):
            uniq = rs.randint(0, cfg.vocab_size,
                              (prompt_len - shared.size,))
            prompts.append(np.concatenate([shared, uniq]).astype("int64"))
            gens.append(int(rs.randint(g_lo, g_hi)))
        gaps = rs.exponential(1.0 / rate, size=n_req)
        arrivals = np.cumsum(gaps)
        return prompts, gens, arrivals

    def drive(stream, cache_on, warmed):
        prompts, gens, arrivals = stream
        eng = ServingEngine(model, max_slots=slots, prefix_cache=cache_on)
        if warmed:
            eng.finish_warmup()
        t0 = time.perf_counter()
        i = 0
        while i < len(prompts) or eng.has_work():
            now = time.perf_counter() - t0
            while i < len(prompts) and arrivals[i] <= now:
                eng.add_request(prompts[i], max_new_tokens=gens[i])
                i += 1
            if eng.has_work():
                eng.step()
            elif i < len(prompts):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.002))
        wall = time.perf_counter() - t0
        st = eng.stats()
        ttfts = sorted(st["ttft_s"])
        p95 = ttfts[int(0.95 * (len(ttfts) - 1))]
        met = sum(1 for t in st["ttft_s"] if t * 1e3 <= slo_ms)
        hit, miss = st["prefix_blocks_hit"], st["prefix_blocks_missed"]
        return {
            "offered_rps": round(rate, 1),
            "goodput_rps": round(met / wall, 1),
            "slo_met_frac": round(met / len(ttfts), 3),
            "ttft_ms_p50": round(1e3 * ttfts[len(ttfts) // 2], 1),
            "ttft_ms_p95": round(1e3 * p95, 1),
            "prefix_hit_rate": round(hit / max(hit + miss, 1), 3),
            "prefill_chunks": st["prefill_chunks"],
            "wall_s": round(wall, 2)}

    def warm(stream, cache_on):
        """Deterministic program warm-up: admit EXACTLY k requests at a
        time for every decode bucket k (1, 2, 4, ..., slots) so each
        slot-count program compiles, plus the prefill and (via the
        shared-prefix hits within this warm engine) the cache-hit chunk
        programs — a Poisson warm drive can skip a bucket the measured
        drive then compiles mid-flight."""
        prompts, gens, _ = stream
        eng = ServingEngine(model, max_slots=slots, prefix_cache=cache_on)
        k = 1
        while True:
            for j in range(k):
                eng.add_request(prompts[j % len(prompts)],
                                max_new_tokens=4)
            eng.run()
            if k >= slots:
                break
            k = min(2 * k, slots)

    sweep = {}
    for tag, frac, cache_on in (("shared0", 0.0, True),
                                ("shared50", 0.5, True),
                                ("shared95", 0.95, True),
                                ("shared95_nocache", 0.95, False)):
        stream = make_stream(frac, seed=17)
        warm(stream, cache_on)
        sweep[tag] = drive(stream, cache_on, warmed=True)
    red = sweep["shared95_nocache"]["ttft_ms_p95"] \
        / max(sweep["shared95"]["ttft_ms_p95"], 1e-9)
    out = {"name": "llama_serving_slo_goodput",
           "slots": slots, "requests": n_req, "prompt_len": prompt_len,
           "gen_range": [g_lo, g_hi], "ttft_slo_ms": slo_ms,
           "sweep": sweep,
           "goodput_rps": sweep["shared95"]["goodput_rps"],
           "ttft_p95_reduction_95shared": round(red, 2),
           "goodput_gain_95shared": round(
               sweep["shared95"]["goodput_rps"]
               / max(sweep["shared95_nocache"]["goodput_rps"], 1e-9), 2),
           "prefix_cache_beats_nocache": bool(red > 1.0)}
    out.update(_serving_roofline())
    return out


def bench_llama_fleet_slo(n_requests=None, rate=None, ttft_slo_ms=None):
    """Round-20 FLEET rung: the same Poisson-arrival MULTI-TENANT
    stream (4 prefix families, 95% shared within a family — distinct
    system prompts) offered to multi-replica fleets behind the serving
    Router, swept over replica count 1 / 2 / 4 at a FIXED TTFT budget,
    with a prefix_affine vs round_robin placement A/B at each
    multi-replica point. Goodput (requests whose engine-side TTFT met
    the SLO, per second of drive wall) is the headline — the number a
    fleet-sizing claim needs: `goodput_scaling_2rep` (2-replica affine
    over 1-replica) and `affinity_goodput_gain_2rep` (affine over
    round_robin on the SAME arrival schedule — round_robin scatters
    every family across every replica's cache, paying each family's
    cold prefill N times, where affinity gives each family a home
    replica). All replicas share this process's first device
    (serving/replica.py: ServingEngine takes no device — ROADMAP)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.serving import Router
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=1024)
    slots, n_req = 4, int(n_requests or 24)
    prompt_len, g_lo, g_hi = 512, 16, 48
    rate = float(rate or 24.0)
    slo_ms = float(ttft_slo_ms or 250.0)
    pool_blocks = None  # default slots*pages+1 = 257 already fits
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()

    def make_stream(n_families, shared_frac, seed):
        rs = np.random.RandomState(seed)
        fams = [rs.randint(0, cfg.vocab_size,
                           (int(prompt_len * shared_frac),))
                for _ in range(n_families)]
        # balanced but SHUFFLED family order — a strided i%n_families
        # sequence resonates with round_robin's stride and hands it
        # perfect affinity by accident
        order = rs.permutation(np.arange(n_req) % n_families)
        prompts, gens = [], []
        for i in range(n_req):
            shared = fams[order[i]]
            uniq = rs.randint(0, cfg.vocab_size,
                              (prompt_len - shared.size,))
            prompts.append(np.concatenate([shared, uniq]).astype("int64"))
            gens.append(int(rs.randint(g_lo, g_hi)))
        gaps = rs.exponential(1.0 / rate, size=n_req)
        return prompts, gens, np.cumsum(gaps)

    # warm prompts share their own prefix (NOT the measured stream's, so
    # the drive starts cache-cold) at the stream's shapes; the ladder
    # admits exactly k requests per decode bucket k like the SLO rung
    wrs = np.random.RandomState(5)
    warm_shared = wrs.randint(0, cfg.vocab_size,
                              (int(prompt_len * 0.95),))
    warm_prompts = [np.concatenate(
        [warm_shared,
         wrs.randint(0, cfg.vocab_size, (prompt_len - warm_shared.size,))
         ]).astype("int64") for _ in range(max(slots, 2) + 1)]

    def _warm(eng):
        k = 1
        while True:
            for j in range(k):
                eng.add_request(warm_prompts[(k + j) % len(warm_prompts)],
                                max_new_tokens=4)
            eng.run()
            if k >= slots:
                break
            k = min(2 * k, slots)

    def drive_fleet(n_rep, policy, stream):
        prompts, gens, arrivals = stream
        engines = [ServingEngine(model, max_slots=slots,
                                 num_kv_blocks=pool_blocks)
                   for _ in range(n_rep)]
        router = Router(engines, policy=policy, warmup=_warm)
        try:
            if not router.wait_ready(900):
                raise RuntimeError("fleet warmup timed out")
            t0 = time.perf_counter()
            futs, i = [], 0
            while i < len(prompts):
                now = time.perf_counter() - t0
                if arrivals[i] <= now:
                    futs.append(router.submit(prompts[i],
                                              max_new_tokens=gens[i]))
                    i += 1
                else:
                    time.sleep(min(arrivals[i] - now, 0.002))
            for f in futs:
                f.result(900)
            wall = time.perf_counter() - t0
            assert all(f.completions == 1 for f in futs), \
                "fleet drive duplicated a completion"
            ttfts, hit, miss = [], 0, 0
            for eng in engines:
                st = eng.stats()
                ttfts += list(st["ttft_s"])
                hit += st["prefix_blocks_hit"]
                miss += st["prefix_blocks_missed"]
            fstats = router.fleet_stats()
            ttfts.sort()
            met = sum(1 for t in ttfts if t * 1e3 <= slo_ms)
            return {
                "replicas": n_rep, "policy": policy,
                "offered_rps": round(rate, 1),
                "goodput_rps": round(met / wall, 1),
                "slo_met_frac": round(met / len(ttfts), 3),
                "ttft_ms_p50": round(1e3 * ttfts[len(ttfts) // 2], 1),
                "ttft_ms_p95": round(
                    1e3 * ttfts[int(0.95 * (len(ttfts) - 1))], 1),
                "fleet_prefix_hit_rate": round(
                    hit / max(hit + miss, 1), 3),
                "affinity_hits": fstats["affinity_hits"],
                "wall_s": round(wall, 2)}
        finally:
            router.close()

    stream = make_stream(4, 0.95, seed=23)
    sweep = {"rep1": drive_fleet(1, "prefix_affine", stream)}
    for n in (2, 4):
        sweep[f"rep{n}_affine"] = drive_fleet(n, "prefix_affine", stream)
        sweep[f"rep{n}_rr"] = drive_fleet(n, "round_robin", stream)
    out = {"name": "llama_fleet_slo_goodput",
           "slots": slots, "requests": n_req, "prompt_len": prompt_len,
           "gen_range": [g_lo, g_hi], "ttft_slo_ms": slo_ms,
           "sweep": sweep,
           "goodput_rps_1rep": sweep["rep1"]["goodput_rps"],
           "goodput_rps_2rep": sweep["rep2_affine"]["goodput_rps"],
           "goodput_rps_4rep": sweep["rep4_affine"]["goodput_rps"],
           "goodput_scaling_2rep": round(
               sweep["rep2_affine"]["goodput_rps"]
               / max(sweep["rep1"]["goodput_rps"], 1e-9), 2),
           "affinity_goodput_gain_2rep": round(
               sweep["rep2_affine"]["goodput_rps"]
               / max(sweep["rep2_rr"]["goodput_rps"], 1e-9), 2),
           "affinity_hit_rate_gain_2rep": round(
               sweep["rep2_affine"]["fleet_prefix_hit_rate"]
               / max(sweep["rep2_rr"]["fleet_prefix_hit_rate"], 1e-9),
               2),
           # affinity's edge widens with fleet size — round_robin pays
           # each family's cold prefill on every replica it touches
           "affinity_goodput_gain_4rep": round(
               sweep["rep4_affine"]["goodput_rps"]
               / max(sweep["rep4_rr"]["goodput_rps"], 1e-9), 2),
           "affinity_hit_rate_gain_4rep": round(
               sweep["rep4_affine"]["fleet_prefix_hit_rate"]
               / max(sweep["rep4_rr"]["fleet_prefix_hit_rate"], 1e-9),
               2)}
    return out


def bench_llama_spec_decode(n_requests=None):
    """Round-16 speculative-decoding rung: greedy decode tok/s and
    acceptance rate for the n-gram and draft-model proposers at
    K ∈ {2, 4, 8}, on a REPETITIVE stream (prompt-lookup's best case —
    the prompt is a short motif tiled many times, so proposals come from
    history) AND an ADVERSARIAL uniform-random-token stream (acceptance
    collapses; records how much a degenerate proposer costs), each A/B'd
    against the non-speculative engine ON THE SAME STREAM. The headline
    is `speedup_repetitive_best`: best spec tok/s over the baseline's."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.inference.speculative import SpecConfig
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=1024)
    dcfg = LlamaConfig(vocab_size=32000, hidden_size=256,
                       intermediate_size=704, num_hidden_layers=2,
                       num_attention_heads=4,
                       max_position_embeddings=1024)
    slots, n_req, motif, tiles, gen = 4, int(n_requests or 8), 16, 8, 96
    model = LlamaForCausalLM(cfg)
    draft = LlamaForCausalLM(dcfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    draft = paddle.amp.decorate(draft, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()
    draft.eval()
    rs = np.random.RandomState(0)
    streams = {
        "repetitive": [np.tile(rs.randint(0, cfg.vocab_size, (motif,)),
                               tiles).astype("int64")
                       for _ in range(n_req)],
        "adversarial": [rs.randint(0, cfg.vocab_size,
                                   (motif * tiles,)).astype("int64")
                        for _ in range(n_req)],
    }

    def drive(prompts, spec):
        eng = ServingEngine(model, max_slots=slots, spec_decode=spec)
        for p in prompts:
            eng.add_request(p, max_new_tokens=gen)
        eng.run()          # warm every program this stream rides
        eng = ServingEngine(model, max_slots=slots, spec_decode=spec)
        eng.finish_warmup()
        for p in prompts:
            eng.add_request(p, max_new_tokens=gen)
        eng.run()
        st = eng.stats()
        return (round(st["decode_tokens"]
                      / max(st["decode_time_s"], 1e-9), 1),
                round(eng.spec_stats()["accept_rate"], 3))

    out = {"name": "llama_spec_decode", "slots": slots,
           "requests": n_req, "prompt_len": motif * tiles, "gen": gen,
           "draft_layers": dcfg.num_hidden_layers,
           "draft_hidden": dcfg.hidden_size}
    best_rep = 0.0
    for sname, prompts in streams.items():
        tok_s, _ = drive(prompts, None)
        out[f"baseline_{sname}_tok_s"] = tok_s
        for method in ("ngram", "draft"):
            for k in (2, 4, 8):
                spec = SpecConfig(method=method, k=k,
                                  draft_model=draft
                                  if method == "draft" else None)
                tok_s, acc = drive(prompts, spec)
                out[f"{method}_k{k}_{sname}_tok_s"] = tok_s
                out[f"{method}_k{k}_{sname}_accept"] = acc
                if sname == "repetitive":
                    best_rep = max(best_rep, tok_s)
    out["speedup_repetitive_best"] = round(
        best_rep / max(out["baseline_repetitive_tok_s"], 1e-9), 2)
    out["spec_beats_baseline"] = bool(
        best_rep > out["baseline_repetitive_tok_s"])
    return out


def bench_quant_decode(n_requests=None, new_tokens=None):
    """Round-20 quantization rung: the bandwidth-bound decode matrix —
    weight storage {bf16, int8, int4} × KV cache {model, int8, int4} on
    the paged ServingEngine, each cell a warmed greedy-decode drive on
    the SAME request stream. Alongside tok/s every cell reports the
    RATIOS the quantization claims: engine.param_bytes vs the bf16 twin
    (storage actually packed, scales included) and the decode program's
    D8-ledger bytes-accessed vs the (bf16, model-KV) twin (traffic
    actually saved — the number D20 audit_quantized_bytes budgets).
    Key naming rides tools/bench_trend.py's direction rules:
    *_tokens_per_sec higher-better, *bytes* lower-better."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.obs import costs as _costs
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    # the 1B decode geometry (bench_decode_1b) — big enough that the
    # weight stream dominates decode HBM traffic, i.e. the regime
    # where weight-only quantization is supposed to pay
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5504, num_hidden_layers=20,
                      num_attention_heads=16,
                      max_position_embeddings=512)
    slots, n_req = 4, int(n_requests or 4)
    prompt_len, gen = 128, int(new_tokens or 48)
    model = LlamaForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                master_weight=False)
    model.eval()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (prompt_len,)).astype("int64")
               for _ in range(n_req)]

    def drive(wq, kv):
        def build():
            return ServingEngine(model, max_slots=slots, weight_quant=wq,
                                 kv_cache_dtype=kv)

        eng = build()
        for p in prompts:
            eng.add_request(p, max_new_tokens=gen)
        eng.run()                       # warm every program this cell rides
        eng = build()
        eng.finish_warmup()
        for p in prompts:
            eng.add_request(p, max_new_tokens=gen)
        eng.run()
        st = eng.stats()
        # the decode program's ledger rows are keyed by the engine's
        # kv{mode}/w{quant} program keystr — the same rows D20 audits
        rows = [e for e in _costs.ledger("serving.decode")
                if f"/kv{kv}/w{wq}" in e.program and e.analyzed]
        dec_bytes = max((e.bytes_accessed for e in rows), default=0)
        return (round(st["decode_tokens"]
                      / max(st["decode_time_s"], 1e-9), 1),
                int(eng.param_bytes), int(st["kv_hbm_bytes"]),
                int(dec_bytes))

    out = {"name": "quant_decode", "slots": slots, "requests": n_req,
           "prompt_len": prompt_len, "gen": gen,
           "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers}
    base = {}
    for wq in ("none", "int8", "int4"):
        for kv in ("model", "int8", "int4"):
            tok_s, pbytes, kv_bytes, dec_bytes = drive(wq, kv)
            out[f"w{wq}_kv{kv}_tokens_per_sec"] = tok_s
            if wq == "none" and kv == "model":
                base = {"p": pbytes, "kv": kv_bytes, "dec": dec_bytes}
                out["bf16_param_bytes"] = pbytes
                out["model_kv_hbm_bytes"] = kv_bytes
            if kv == "model":
                # storage side of the claim: packed weights + scales
                # over the bf16 stack (int8 ≈ 0.5, int4 ≈ 0.25)
                out[f"w{wq}_weight_bytes_ratio"] = round(
                    pbytes / max(base["p"], 1), 3)
            if wq == "none":
                out[f"kv{kv}_kv_hbm_bytes_ratio"] = round(
                    kv_bytes / max(base["kv"], 1), 3)
            if dec_bytes and base.get("dec"):
                # traffic side: XLA bytes-accessed of the decode program
                # vs the full-precision twin — what D20 budgets
                out[f"w{wq}_kv{kv}_decode_bytes_ratio"] = round(
                    dec_bytes / base["dec"], 3)
    return out


def bench_int8(iters=30, m=2048, k=4096, n=4096):
    """Int8 quantized execution ON THE CHIP (VERDICT r3 Weak #6): the PTQ
    QuantizedLinear full int8×int8→int32 MXU path vs the same GEMM in bf16.
    Verifies the quantized path is actually faster/at-parity on real
    hardware rather than silently dequantizing to float."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.quantization.ptq import QuantizedLinear

    paddle.seed(0)
    lin = paddle.nn.Linear(k, n)
    w = np.asarray(lin.weight._data)
    wscale = float(np.abs(w).max() / 127.0)
    rs = np.random.RandomState(0)
    x = rs.randn(m, k).astype("float32")
    ascale = float(np.abs(x).max() / 127.0)
    q = QuantizedLinear(lin, wscale, ascale)
    xt = paddle.to_tensor(x)

    # like-for-like: BOTH paths run through the same eager dispatch funnel
    # (same per-invocation overhead), differing only in GEMM dtype
    lin_bf16 = paddle.nn.Linear(k, n)
    lin_bf16.set_state_dict(lin.state_dict())
    lin_bf16.bfloat16()
    xb_t = paddle.to_tensor(x).astype("bfloat16")

    q_wo = QuantizedLinear(lin, wscale)          # weight-only int8

    # host contention makes single-group eager timings swing run to run:
    # median-of-5-groups with outlier discard, spreads reported
    dt_int8, sp_i = _timeit_median(lambda: q(xt), iters=max(4, iters // 6),
                                   groups=5, warmup=5)
    dt_wo, sp_w = _timeit_median(lambda: q_wo(xt), iters=max(4, iters // 6),
                                 groups=5, warmup=5)
    dt_bf16, sp_b = _timeit_median(lambda: lin_bf16(xb_t),
                                   iters=max(4, iters // 6), groups=5,
                                   warmup=5)

    tops = 2 * m * k * n
    return {"name": "int8_quantized_linear", "m_k_n": [m, k, n],
            "int8_ms": dt_int8 * 1e3, "weight_only_ms": dt_wo * 1e3,
            "bf16_ms": dt_bf16 * 1e3,
            "int8_tops": tops / dt_int8 / 1e12,
            "bf16_tflops": tops / dt_bf16 / 1e12,
            "speedup_vs_bf16": round(dt_bf16 / dt_int8, 2),
            "weight_only_speedup_vs_bf16": round(dt_bf16 / dt_wo, 2),
            "spreads": [sp_i, sp_w, sp_b]}


def bench_eager_dispatch(iters=50, size=256):
    """Micro-bench: per-op eager dispatch overhead (matmul chain), the
    SURVEY §7-1 hot loop — measured with the per-op executable cache off
    (uncached jax.vjp re-trace) and on (jitted fwd/vjp pairs, the analog of
    KernelFactory's precompiled kernels).

    `size` matters for honesty: on the HOST CPU backend a 256-square matmul
    costs ~340 us of actual compute inside the timed region, swamping
    dispatch (round 3 reported that as '502 us dispatch overhead'). The
    eager_host row therefore runs size=16 so the number isolates the
    FRAMEWORK's per-op cost."""
    import paddle_tpu as paddle
    from paddle_tpu.core import dispatch

    paddle.seed(0)
    x = paddle.rand([size, size])
    w = paddle.rand([size, size])
    w.stop_gradient = False
    n_ops = 20

    def step():
        y = x
        for _ in range(n_ops):
            y = paddle.matmul(y, w)
        return y

    paddle.set_flags({"FLAGS_use_compiled_eager": False})
    dt_uncached = _timeit(step, iters=iters, warmup=5)
    paddle.set_flags({"FLAGS_use_compiled_eager": True})
    dt = _timeit(step, iters=iters, warmup=5)
    return {"name": "eager_dispatch_matmul_chain",
            "ops_per_sec": n_ops / dt, "us_per_op": dt / n_ops * 1e6,
            "us_per_op_uncached": dt_uncached / n_ops * 1e6,
            "dispatch_cache_speedup": round(dt_uncached / dt, 2),
            "cache": dispatch.eager_cache_info()}


def bench_fused_adam(iters=15):
    """Eager-mode fused multi-tensor AdamW (ONE jitted donated update over
    the param pytree, ≙ phi fused_adam_kernel.h) vs the per-param loop."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    def build(use_mt):
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=8192, hidden_size=512,
                          intermediate_size=1408, num_hidden_layers=8,
                          num_attention_heads=8, max_position_embeddings=128)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     use_multi_tensor=use_mt)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 8192, (2, 128)).astype("int64"))
        loss = model(ids, ids)
        loss.backward()  # grads once; we time only opt.step()
        return opt

    def run(opt):
        opt.step()
        return opt._parameters[-1]  # sync target: an actually-updated buffer

    opt_pp = build(False)
    dt_pp = _timeit(lambda: run(opt_pp), iters=iters, warmup=3)
    opt_mt = build(True)
    dt_mt = _timeit(lambda: run(opt_mt), iters=iters, warmup=3)
    return {"name": "fused_multi_tensor_adamw",
            "per_param_step_ms": dt_pp * 1e3, "fused_step_ms": dt_mt * 1e3,
            "fused_speedup": round(dt_pp / dt_mt, 2),
            "n_tensors": len(opt_mt._parameters)}


def bench_ckpt(iters=3):
    """Round-12 robustness rung: checkpoint save/restore wall + bytes for
    the 1B-config train state (bf16 params + AdamW moments + RNG).  Two
    numbers matter for a training run: `save_blocking_ms` — how long the
    train loop actually stalls per async save (the synchronous
    device→host snapshot) — and `save_total_ms` — commit wall including
    serialize + fsync + atomic rename, which bounds the save interval."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import ckpt
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    _require_tpu()
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5504, num_hidden_layers=20,
                      num_attention_heads=16,
                      max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16",
                                     master_weight=False)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size,
                                      (1, 128)).astype("int64"))
    loss = model(ids, ids)
    loss.backward()
    opt.step()            # materialize the moment buffers
    opt.clear_grad()

    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        saver = ckpt.AsyncCheckpointer(root, keep_last_n=2)
        blocking_ms, total_ms, nbytes = [], [], 0
        for i in range(iters):
            tree = ckpt.capture_train_state(model, opt, step=i + 1)
            t0 = time.perf_counter()
            saver.save(i + 1, tree)          # returns after the host copy
            blocking_ms.append((time.perf_counter() - t0) * 1e3)
            saver.wait()                     # commit barrier for timing
            total_ms.append((time.perf_counter() - t0) * 1e3)
        nbytes = saver.results[-1]["bytes"]
        saver.close()
        t0 = time.perf_counter()
        res = ckpt.restore_checkpoint(root)
        restore_ms = (time.perf_counter() - t0) * 1e3
        assert res.step == iters
    finally:
        shutil.rmtree(root, ignore_errors=True)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    med = sorted(total_ms)[len(total_ms) // 2]
    out = {"name": "ckpt_train_state",
           "save_blocking_ms": round(sorted(blocking_ms)
                                     [len(blocking_ms) // 2], 2),
           "save_total_ms": round(med, 2),
           "restore_ms": round(restore_ms, 2),
           "bytes": int(nbytes), "n_params": n_params,
           "write_gb_per_s": round(nbytes / max(med / 1e3, 1e-9) / 1e9, 3)}
    return out


def bench_partitioner_scaling(iters=4, batch=8, seq=128):
    """Round-18 declarative-partitioner rung: the SAME unmodified
    tiny-LLaMA train step compiled from three MeshConfigs on the
    8-device virtual mesh — pure data parallel, data×tp, and a sep
    (ring-attention context-parallel) config — reporting tok/s per
    config next to the D10 per-axis jaxpr-level collective-byte ledger
    (ppermute bytes for the sep config; GSPMD's own collectives live in
    HLO below the jaxpr and are noted as such). Off-chip this is a
    placement/compile-health probe on virtual CPU devices
    (platform:"cpu", excluded from README claims by check_scoreboard);
    the relative tok/s ordering is NOT an ICI scaling claim."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed.partitioner import MeshConfig, partition
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    paddle.set_flags({"FLAGS_jit_debug_program": True})
    configs = [MeshConfig(data=8), MeshConfig(data=4, tp=2),
               MeshConfig(data=2, sep=4)]
    rows = {}
    for mc in configs:
        paddle.seed(0)
        cfg = llama_tiny_config(hidden_size=128, intermediate_size=256,
                                num_hidden_layers=4,
                                max_position_embeddings=seq)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def step(ids, labels, model=model, opt=opt):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        pstep = partition(step, mc, model=model)
        rs = np.random.RandomState(0)

        def batch_pair():
            return (paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size, (batch, seq)).astype("int64")),
                    paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size, (batch, seq)).astype("int64")))

        for _ in range(3):                     # eager/discovery/compile
            float(pstep(*batch_pair()))
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = float(pstep(*batch_pair()))  # host sync per step
        wall = time.perf_counter() - t0
        vol = analysis.jaxpr_collective_bytes(pstep.program_jaxpr())
        rows[mc.describe()] = {
            "tokens_per_sec": round(iters * batch * seq / wall, 1),
            "step_ms": round(wall / iters * 1e3, 2),
            "loss": round(loss, 4),
            "sharded_params": pstep.plan.summary()["sharded"],
            "collective_bytes_total": vol["total"],
            "collective_bytes_per_axis": vol["per_axis"],
            "collective_sites": vol["sites"],
        }
    return {"name": "partitioner_scaling", "configs": rows,
            "note": ("virtual-mesh placement probe (one host, 8 XLA CPU "
                     "devices) — config-relative tok/s is not an ICI "
                     "scaling claim; GSPMD collectives live below the "
                     "jaxpr, only shard_map-level (sep/ring) bytes are "
                     "in the ledger")}


def bench_autoplan(iters=4, batch=8, seq=128):
    """Round-21 auto-plan rung: `autoplan.search` ranks every valid
    MeshConfig for the partitioner_scaling tiny-LLaMA statically (one
    abstract lowering, nothing executes), then the predicted top-3 are
    ACTUALLY compiled and measured on the 8-device virtual mesh — the
    row is the cost model's report card: predicted step_ms next to
    measured step_ms per config, plus D19 calibration over the measured
    set. Flat numeric keys on purpose: bench_trend flattens one dict
    level, and predicted/measured walls must trend (lower-better via
    the ms/mb components)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed.partitioner import autoplan, partition
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny_config

    cfg = llama_tiny_config(hidden_size=128, intermediate_size=256,
                            num_hidden_layers=4,
                            max_position_embeddings=seq)
    paddle.seed(0)
    t0 = time.perf_counter()
    report = autoplan.search(LlamaForCausalLM(cfg), 8, batch=batch,
                             seq=seq)
    search_wall = time.perf_counter() - t0

    measured = {}
    rows = {}
    for cand in report.top(3):
        mc = cand.config
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def step(ids, labels, model=model, opt=opt):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        pstep = partition(step, mc, model=model)
        rs = np.random.RandomState(0)

        def batch_pair():
            return (paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size, (batch, seq)).astype("int64")),
                    paddle.to_tensor(rs.randint(
                        0, cfg.vocab_size, (batch, seq)).astype("int64")))

        for _ in range(3):                     # eager/discovery/compile
            float(pstep(*batch_pair()))
        t0 = time.perf_counter()
        for _ in range(iters):
            float(pstep(*batch_pair()))
        wall = time.perf_counter() - t0
        measured[mc.describe()] = iters * batch * seq / wall
        rows[mc.describe()] = {
            "predicted_step_ms": round(cand.prediction.step_ms, 3),
            "measured_step_ms": round(wall / iters * 1e3, 2),
            "peak_hbm_mb": round(cand.prediction.peak_hbm_mb, 1),
            "tokens_per_sec": round(measured[mc.describe()], 1),
        }
    cal = analysis.audit_cost_model_calibration(report, measured,
                                                loc="bench/autoplan")
    top1 = report.candidates[0]
    top1_row = rows[top1.describe]
    return {"name": "autoplan",
            "valid_candidates": len(report.candidates),
            "rejected_candidates": len(report.rejected),
            "search_wall_s": round(search_wall, 2),
            "top1_config": top1.describe,
            "top1_predicted_step_ms": top1_row["predicted_step_ms"],
            "top1_measured_step_ms": top1_row["measured_step_ms"],
            "top1_tokens_per_sec": top1_row["tokens_per_sec"],
            "peak_hbm_mb": top1_row["peak_hbm_mb"],
            "predicted_measured_ratio": round(
                top1_row["predicted_step_ms"]
                / top1_row["measured_step_ms"], 4),
            "calibration_errors": sum(1 for f in cal
                                      if f.severity == "error"),
            "configs": rows,
            "note": ("virtual-mesh report card (one host, 8 XLA CPU "
                     "devices): predicted/measured RATIO is meaningless "
                     "off-chip (CPU peaks), only the predicted ORDERING "
                     "vs measured tok/s is gated — D19")}


def bench_eager_host(iters=50):
    """bench_eager_dispatch on the host CPU backend, with tiny operands
    so compute is negligible: the framework's own per-op
    dispatch overhead (VERDICT r3 Weak #4 target: <=150 us/op cached)."""
    res = bench_eager_dispatch(iters=iters, size=16)
    res["name"] = "eager_dispatch_on_host_cpu"
    return res


ALL = {
    "lenet": bench_lenet,
    "resnet50": bench_resnet50,
    "resnet50_bf16": lambda: bench_resnet50(batch=256, amp=True),
    "bert": bench_bert,
    "bert_bf16": lambda: bench_bert(amp=True),
    "gpt_sharding": bench_gpt_medium_sharding,
    "llama": lambda: bench_llama_train(batch=8, amp=False),
    "llama_bf16": bench_llama_train,
    "llama_1b": bench_llama_1b,
    "llama_1b_resid_bf16": bench_llama_1b_resid_bf16,
    "fused_micro": bench_fused_elementwise,
    "longctx_4k": bench_llama_longctx,
    "longctx_8k": lambda: bench_llama_longctx(batch=2, seq=8192),
    "flashmask_8k": bench_flashmask_longctx,
    "flashmask_16k": lambda: bench_flashmask_longctx(iters=3, s=16384,
                                                     window=1024),
    "decode": bench_decode,
    "decode_1b": bench_decode_1b,
    "decode_micro": bench_decode_micro,
    "llama_serving": bench_llama_serving,
    "llama_serving_slo": bench_llama_serving_slo,
    "llama_fleet_slo": bench_llama_fleet_slo,
    "llama_spec_decode": bench_llama_spec_decode,
    "quant_decode": bench_quant_decode,
    "ckpt": bench_ckpt,
    "partitioner_scaling": bench_partitioner_scaling,
    "autoplan": bench_autoplan,
    "int8": bench_int8,
    "int8_chain": bench_int8_chain,
    "eager": bench_eager_dispatch,
    "eager_host": bench_eager_host,
    "fused_adam": bench_fused_adam,
}


#: the three rungs that force the CPU backend by design; their rows say
#: platform:"cpu" (the benchmark PR of ROADMAP A.1 decides on them)
_CPU_RUNGS = ("eager_host", "partitioner_scaling", "autoplan")


def run_one(name):
    """Entry for the per-config subprocess (prints one JSON line)."""
    import os

    if name == "eager_host":
        # on-host dispatch measurement: with tiny operands on the host CPU
        # backend nothing but the FRAMEWORK's own per-op overhead is left
        # (SURVEY §7 hard-part (1) quantified)
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif name in _CPU_RUNGS:
        # the partitioner/auto-plan rungs need the 8-device virtual mesh
        # (same platform tests/conftest.py and the spmd lint smoke
        # force); rows land platform:"cpu" = excluded from README claims
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if name in _CPU_RUNGS:
        jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.obs.peaks import set_off_chip_peaks

        set_off_chip_peaks()    # the CPU is in no peaks table

    # persistent compile cache: subprocess isolation must not mean
    # recompiling the ladder every round (core/compile_cache.py: where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache)
    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    res = ALL[name]()
    res["wall_s"] = round(time.perf_counter() - t0, 1)
    dev = jax.devices()[0]
    res["platform"] = dev.platform
    res["device_kind"] = dev.device_kind
    res["device_count"] = len(jax.devices())
    # round 11: every rung's row carries its compile counts + cache hit
    # rates (obs watchdog + the executable caches) — the scoreboard can
    # see a retrace regression (e.g. a bucketing change recompiling per
    # length) right in BENCH_DETAILS.json, next to the tok/s it cost
    from paddle_tpu import obs
    from paddle_tpu.core.dispatch import eager_cache_info
    from paddle_tpu.core.lazy import seg_cache_info

    res["obs"] = {"compiles": obs.compile_counts(),
                  "post_warmup_compiles": obs.post_warmup_compiles(),
                  "eager_cache": eager_cache_info(),
                  "seg_cache": seg_cache_info()}
    # round 14: measured-vs-roofline utilization per compiled program
    # (obs cost ledger — XLA bytes accessed over measured wall over the
    # device's peak). Only programs this rung actually executed carry a
    # utilization; the serving/decode rungs are the ones with hot
    # per-program walls.
    roof = [r for r in obs.roofline_rows(measured_only=True)
            if r["site"] != "eager"]
    if roof:
        res["obs"]["peak_gbps"] = obs.peak_gbps()
        res["obs"]["roofline"] = {
            r["program"]: {"utilization": r["roofline_utilization"],
                           "achieved_gbps": r["achieved_gbps"],
                           "bytes_accessed": r["bytes_accessed"],
                           "execs": r["exec_count"]}
            for r in roof}
    print("BENCH_RESULT " + json.dumps(res))


def _headline(results):
    """Best-available headline, preferring the flagship. vs_baseline
    denominators are the LATEST captured round's numbers (flagship:
    round-4's 19,925 tok/s) — the reference publishes no absolute figures,
    so the baseline is our own prior round (same role as
    tools/ci_op_benchmark.sh's develop-branch-relative gate). No silent
    metric substitution: if no llama row has landed yet the metric name
    says exactly what it is."""
    ll1b = results.get("llama_1b", {})
    if "tokens_per_sec" in ll1b:
        return {"metric": "llama_1b_bf16_tokens_per_sec",
                "value": round(ll1b["tokens_per_sec"], 0),
                "unit": "tokens/sec/chip",
                # vs the ROUND-4 driver capture: 19925 tok/s = 136.6 TFLOP/s
                # (BENCH_r04.json). Re-based from round-3's 13078 per
                # VERDICT r5 Weak #3 — the headline must compare against
                # the latest captured round, not a two-round-stale floor
                "vs_baseline": round(ll1b["tokens_per_sec"] / 19925.0, 2)}
    ll = results.get("llama_bf16", {})
    if "tokens_per_sec" in ll:
        return {"metric": "llama_168m_bf16_tokens_per_sec",
                "value": round(ll["tokens_per_sec"], 0),
                "unit": "tokens/sec/chip",
                # vs round-3 self-run 83.0k tok/s (BASELINE.md)
                "vs_baseline": round(ll["tokens_per_sec"] / 83006.0, 2)}
    for name, baseline in [("gpt_sharding", 26890.0)]:
        r = results.get(name, {})
        if "tokens_per_sec" in r:
            return {"metric": f"{name}_tokens_per_sec_PARTIAL_LADDER",
                    "value": round(r["tokens_per_sec"], 0),
                    "unit": "tokens/sec/chip",
                    "vs_baseline": round(r["tokens_per_sec"] / baseline, 2)}
    return {"metric": "ladder_incomplete_no_flagship_row", "value": 0.0,
            "unit": "none", "vs_baseline": 0.0}


#: rough per-config wall-clock estimates (s), calibrated from the round-5
#: committed wall_s records (+margin for the first-run autotune probes at
#: long sequence); only used to decide whether a config still fits the
#: remaining budget — the subprocess timeout enforces the hard cap
_COST_EST = {
    "llama_1b": 300, "llama_1b_resid_bf16": 300, "fused_micro": 90,
    "longctx_4k": 350, "longctx_8k": 400,
    "flashmask_8k": 120, "flashmask_16k": 200, "llama_bf16": 130,
    "llama": 120, "gpt_sharding": 220, "bert_bf16": 200, "bert": 200,
    "resnet50_bf16": 250, "resnet50": 340, "lenet": 50, "decode": 70,
    "decode_1b": 190, "decode_micro": 90, "llama_serving": 180,
    "llama_serving_slo": 200, "llama_spec_decode": 220,
    "llama_fleet_slo": 240, "quant_decode": 260,
    "ckpt": 150, "partitioner_scaling": 150, "autoplan": 150,
    "int8_chain": 70, "int8": 60, "eager": 25,
    "eager_host": 15, "fused_adam": 170,
}


#: per-run rung history (round 16): BENCH_DETAILS.json is a merge-on-store
#: snapshot (a rerun REPLACES a rung's row), so the perf trajectory was
#: empty — nothing persisted across runs. Each completed rung now also
#: appends one platform-tagged record here; tools/bench_trend.py diffs
#: the latest two comparable records per rung.
HISTORY_PATH = "BENCH_HISTORY.jsonl"


def _append_history(run_id, name, res, path=HISTORY_PATH):
    """One JSONL history line per completed rung. Best-effort: a broken
    history file must never fail the bench run. Error rows are skipped —
    a failed rung has no numbers to trend."""
    if not isinstance(res, dict) or "error" in res:
        return False
    try:
        with open(path, "a") as fh:
            fh.write(json.dumps(
                {"run": run_id, "t": time.time(), "rung": name,
                 "platform": res.get("platform"), "record": res}) + "\n")
        return True
    except OSError:
        return False


def main(argv):
    import os
    import subprocess

    # NOTE: the parent must NOT import/initialize jax — a chip belongs to
    # one process at a time, and a parent that touched jax would hold it
    # while every per-config child fails or hangs waiting for it

    # default run = the BASELINE.md ladder, FLAGSHIP FIRST: round 3 lost its
    # headline numbers to a driver timeout because the ladder ran
    # smallest-first and the llama rows never executed. The flagship rows run
    # first and the headline JSON is re-printed after EVERY config, so a
    # timeout's captured tail still carries the best-so-far headline.
    default = ["llama_1b", "llama_1b_resid_bf16", "decode_micro",
               "llama_serving", "llama_serving_slo", "llama_spec_decode",
               "llama_fleet_slo", "quant_decode",
               "ckpt",
               "partitioner_scaling", "autoplan", "fused_micro",
               "longctx_8k", "flashmask_16k", "longctx_4k",
               "flashmask_8k", "llama_bf16", "gpt_sharding", "bert_bf16",
               "llama", "lenet", "decode_1b", "resnet50_bf16", "bert",
               "decode", "int8_chain", "resnet50", "int8", "eager",
               "eager_host", "fused_adam"]
    which = [a.lstrip("-") for a in argv if a.lstrip("-") in ALL] or default
    details = {"platform": "per-config subprocess", "results": {},
               "skipped": []}
    if os.path.exists("BENCH_DETAILS.json"):
        try:  # partial reruns MERGE into the existing ladder results
            with open("BENCH_DETAILS.json") as f:
                details["results"] = json.load(f).get("results", {})
        except (OSError, ValueError, AttributeError):
            pass  # unreadable or not a details file: start a fresh one
    here = os.path.dirname(os.path.abspath(__file__))
    which = [n for n in which if n in ALL]
    # TIME-BOX (VERDICT r5 Weak #2): the full 20-config ladder (~2500 s of
    # committed wall_s) no longer fits the driver budget, which produced an
    # rc-124 capture with missing rows. The ladder now spends at most
    # BENCH_BUDGET_S (default 1500 s): configs that don't fit the remaining
    # budget are SKIPPED — recorded in details["skipped"] so the capture
    # says exactly what didn't run — and the whole run exits rc 0 with the
    # flagship rows always first in line.
    budget = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    t_start = time.perf_counter()
    # one id per ladder invocation: bench_trend groups history lines by
    # run so a partial rerun's rows don't pair with themselves
    run_id = f"{time.strftime('%Y%m%dT%H%M%S')}-{os.getpid()}"
    failed = []
    for name in which:
        remaining = budget - (time.perf_counter() - t_start)
        est = _COST_EST.get(name, 180)
        if remaining < max(30.0, 0.5 * est):
            details["skipped"].append(name)
            print(f"[bench] {name} SKIPPED (remaining budget "
                  f"{remaining:.0f}s < est {est}s)", file=sys.stderr)
            continue
        # one SUBPROCESS per config: each starts with an empty chip (the
        # reference op-benchmark harness isolates runs the same way; a prior
        # config's pinned buffers or a previous OOM can't poison the next).
        # ONE PROCESS PER CHIP: this parent never touches jax (see the note
        # at the top of main) and waits for each child before the next.
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 f"import sys; sys.path.insert(0, {here!r}); "
                 f"import bench; bench.run_one({name!r})"],
                capture_output=True, text=True, cwd=here,
                timeout=min(remaining + 30.0, 1800.0))
            rc, out, err = r.returncode, r.stdout, r.stderr
        except subprocess.TimeoutExpired as e:
            rc = 124
            out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
                else (e.stdout or "")
            err = (e.stderr or b"").decode() if isinstance(e.stderr, bytes) \
                else (e.stderr or "")
        res = None
        for ln in out.splitlines():
            if ln.startswith("BENCH_RESULT "):
                res = json.loads(ln[len("BENCH_RESULT "):])
        if res is not None:
            details["results"][name] = res
            _append_history(run_id, name, res)
            print(f"[bench] {name}: {res}", file=sys.stderr)
        else:
            tail = ((err or out).strip().splitlines() or ["<no output>"])[-3:]
            details["results"][name] = {"error": " | ".join(tail), "rc": rc}
            failed.append(name)
            print(f"[bench] {name} FAILED rc={rc}: {tail}", file=sys.stderr)

        # INCREMENTAL contract: rewrite details + re-print the headline after
        # every config — a driver timeout mid-ladder still captures both
        with open("BENCH_DETAILS.json", "w") as f:
            json.dump(details, f, indent=2)
        print(json.dumps(_headline(details["results"])), flush=True)
    if details["skipped"]:
        with open("BENCH_DETAILS.json", "w") as f:
            json.dump(details, f, indent=2)
        print(json.dumps(_headline(details["results"])), flush=True)
    # a rung the time box skipped is not a failure (rc 0); a rung that ran
    # and failed is — its error row must not pass for a completed ladder
    if failed:
        print(f"[bench] FAILED rungs: {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
