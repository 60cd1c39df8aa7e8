#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, train, serve
    python chip_smoke.py --chips 4   # four chips: the sharded step only

Drives the two normal entry points once, in ONE process (a chip belongs to
one process at a time; nothing here starts a child), at the full widths of
LLaMA-2-7B (`llama_7b_config`: hidden 4096, FFN 11008, 32 heads x 128,
vocab 32000) with the depth cut to what 16 GB holds and random weights from
`--seed`:

  device  jax.devices() must be a TPU; versions, compile-cache directory,
          native components; one long matmul chain timed against
          block_until_ready and against a scalar fetch.
  train   amp O2 bf16 + AdamW + one `@paddle.jit.to_static` step with
          recompute at batch x seq = 2 x 2048: loss finite and decreasing,
          every Pallas kernel the model routes to present in the compiled
          program, logits against a plain float32 jax.numpy forward.
  serve   `ServingEngine(model, max_slots=8)`: mixed-length requests all
          complete, greedy tokens against the static engine, the paged
          flash-decode kernel present in the decode programs, zero compiles
          after `finish_warmup()`.

`--chips 4` runs only the same train step under `partition(...)` with
`MeshConfig(fsdp=2, tp=2)` and the one-device step it is compared with.

Each phase prints one JSON line. No phase is wrapped in `except` and none
retries at another size: whatever raises ends the process non-zero with no
`ok` line. The LAST line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Every time and rate printed on the earlier lines is a SMOKE reading — one
cold run, compilation included where it says so — never a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Sizes:
    """What the phases run at. The defaults are the real thing; the CPU
    rehearsal in tests/test_chip_smoke.py passes tiny ones."""

    model: dict                      # LlamaConfig overrides (widths: none)
    train_layers: int = 3            # 0.87 B params: 8.9 GB resident after
    #                                  the steps. 4 layers (1.07 B, 10.9 GB)
    #                                  ran too; 3 leaves a wider margin of
    #                                  the chip's 16.91 GB for the same proof
    serve_layers: int = 6            # the engine holds the weights twice
    #                                  (model + stacked copy) and its step
    #                                  programs hold temporaries of weights
    #                                  + KV pool again: ~12 GB (PERF.md)
    batch: int = 2
    seq: int = 2048                  # batch x seq = 4096 tokens a step
    warm_shape: tuple = (1, 128)     # the two eager warm-up calls
    steps: int = 4
    ref_tokens: int = 128            # logits-vs-reference input length
    chain_n: int = 4096              # device phase: matmul chain size ...
    chain_len: int = 200             # ... and length (27 TFLOP)
    #: (prompt length, new tokens): 64..1024 mixed, two alike for the
    #: static-engine comparison (one B=2 program)
    requests: tuple = ((64, 32), (64, 32), (96, 48), (200, 64), (256, 32),
                       (512, 48), (640, 64), (768, 32), (1024, 48))
    max_model_len: int = 2048


REAL = Sizes(model={})
OF_LAYERS = 32                      # LLaMA-2-7B's published depth

#: the kernels `LlamaForCausalLM` routes a recomputed bf16 train step to
#: (ops/pallas_attention.py, ops/pallas_norm.py: the pallas_call `name=`s)
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "rms_norm_fwd", "add_rms_norm_fwd", "rms_norm_bwd",
                 "rope_qk_fwd", "rope_qk_bwd", "swiglu_fwd", "swiglu_bwd")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def progress(msg: str) -> None:
    """Where a run got to, on stderr: stdout carries the JSON lines only."""
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


class CacheCounter:
    """Hits/misses of jax's persistent compilation cache, from jax's own
    monitoring events: a second run in the same call must show hits."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def hbm(devices=None) -> list:
    """bytes_in_use / peak_bytes_in_use per device, in GB."""
    import jax

    out = []
    for d in devices or jax.devices():
        st = d.memory_stats() or {}
        out.append({k: round(st.get(v, 0) / 1e9, 3) for k, v in (
            ("in_use_gb", "bytes_in_use"), ("peak_gb", "peak_bytes_in_use"),
            ("limit_gb", "bytes_limit"))})
    return out


def kernels_in(text: str) -> dict:
    """{kernel name: count} of the Mosaic custom calls in a program's text;
    the name is the pallas_call's `name=`. A Pallas kernel is, in a LOWERED
    program, `stablehlo.custom_call @tpu_custom_call(...) {kernel_name =
    "<name>"}` and, in a COMPILED one, an instruction with
    `custom_call_target="tpu_custom_call"` whose op_name metadata ends in
    `<name>/pallas_call` (possibly wrapped as `transpose(jvp(<name>))`).
    Its XLA composition leaves no such line in either."""
    out: dict = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'kernel_name = "(\w+)"', line) \
            or re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        name = m.group(1) if m else "<unnamed>"
        out[name] = out.get(name, 0) + 1
    return out


def require_kernels(text: str, names) -> dict:
    found = kernels_in(text)
    missing = [n for n in names if n not in found]
    if missing:
        raise AssertionError(
            f"kernels {missing} are not in the compiled program (found "
            f"{sorted(found)}): their XLA compositions ran instead")
    return found


def lower_step(train_step, batch):
    """The compiled `to_static` step lowered once more from its cached
    specialization. Its `.as_text()` is the program as handed to the
    chip's compiler, kernels included, and costs no device memory.
    `.compile()` (a persistent-cache hit) adds what the compiler put in —
    the collectives the four-chip phase looks for — but loads the program a
    second time, and loading reserves a program's temporaries in HBM."""
    (spec,) = train_step._cache.values()
    return spec.executable.lower(
        [batch._data], [t._data for t in spec.ro_caps],
        [t._data for t in spec.mut_caps])


# ---------------------------------------------------------------- device

def phase_device(sizes: Sizes, cache_dir: str) -> dict:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import native

    t_phase = time.perf_counter()
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax found {d0.platform!r} "
            f"({d0.device_kind} x {len(devs)})")
    from paddle_tpu import obs

    peaks = obs.device_peaks()          # raises for a chip not in the table
    libs = {n: native.load(n) is not None
            for n in ("ring_queue", "host_tracer")}

    # Does block_until_ready wait? One long dependent matmul chain, timed
    # to (a) the dispatch's return, (b) block_until_ready, (c) a scalar
    # fetch of the result — on every run, so the question stays answered.
    n, length = sizes.chain_n, sizes.chain_len

    @jax.jit
    def chain(x, w):
        return jax.lax.fori_loop(
            0, length, lambda _, a: (a @ w).astype(jnp.bfloat16), x)

    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    w = jnp.eye(n, dtype=jnp.bfloat16)
    def fetch(o):
        return jax.device_get(jnp.ravel(o)[0])

    t0 = time.perf_counter()
    fetch(chain(x, w))                  # compiles the chain AND the fetch
    compile_s = time.perf_counter() - t0

    def timed(wait):
        t0 = time.perf_counter()
        out = chain(x, w)
        t_disp = time.perf_counter() - t0
        wait(out)
        return t_disp, time.perf_counter() - t0

    disp_s, bur_s = timed(jax.block_until_ready)
    _, fetch_s = timed(fetch)
    floor_s = 2.0 * n ** 3 * length / (peaks["bf16_tflops"] * 1e12)
    if bur_s < floor_s:
        raise AssertionError(
            f"block_until_ready returned after {bur_s:.4f}s; the chain "
            f"cannot finish under {floor_s:.4f}s at this chip's peak — it "
            "did not wait")

    # the fixed cost of one small call, dispatch to completion
    tiny = jax.jit(lambda a: a + 1)
    a = jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(tiny(a))
    calls = []
    for _ in range(50):
        t0 = time.perf_counter()
        jax.block_until_ready(tiny(a))
        calls.append(time.perf_counter() - t0)

    out = {
        "platform": d0.platform, "device_kind": d0.device_kind,
        "count": len(devs),
        "versions": {"jax": jax.__version__,
                     "jaxlib": md.version("jaxlib"),
                     "libtpu": md.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "native": libs, "native_errors": dict(native.build_errors),
        "peaks": peaks,
        "barrier": {"chain_tflop": round(2e-12 * n ** 3 * length, 2),
                    "dispatch_s": round(disp_s, 5),
                    "block_until_ready_s": round(bur_s, 5),
                    "scalar_fetch_s": round(fetch_s, 5),
                    "peak_floor_s": round(floor_s, 5),
                    "chain_tflops_smoke": round(
                        2e-12 * n ** 3 * length / bur_s, 1)},
        "small_call_us_median": round(1e6 * float(np.median(calls)), 1),
        "compile_seconds": round(compile_s, 2),
        "seconds": round(time.perf_counter() - t_phase, 2),
        "checked": ["platform is tpu", "device_kind in the peaks table",
                    "block_until_ready waited at least the peak floor"],
    }
    return out


# ----------------------------------------------------------------- train

def build_trainer(sizes: Sizes, layers: int, seed: int):
    """The normal trainer path: amp O2 bf16 params + bf16 AdamW moments and
    one to_static step over forward, backward, update."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM, llama_7b_config

    paddle.seed(seed)
    cfg = llama_7b_config(num_hidden_layers=layers,
                          max_position_embeddings=sizes.max_model_len,
                          use_recompute=True, **sizes.model)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16", master_weight=False)

    def train_step(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return cfg, model, opt, train_step


def batches(sizes: Sizes, vocab: int, seed: int):
    import paddle_tpu as paddle

    rs = np.random.RandomState(seed)
    small = paddle.to_tensor(
        rs.randint(0, vocab, sizes.warm_shape).astype("int64"))
    big = paddle.to_tensor(
        rs.randint(0, vocab, (sizes.batch, sizes.seq)).astype("int64"))
    return small, big


def run_steps(step, small, big, n_steps):
    """Two eager warm-up calls at the small shape (lazy state creation,
    then capture discovery), the compiling call, then timed steps on the
    SAME batch. Returns (losses, first-call seconds, step seconds)."""
    import jax

    for _ in range(2):
        jax.block_until_ready(step(small)._data)
    losses, walls = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        loss = step(big)
        jax.block_until_ready(loss._data)
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, walls[0], walls[1:]


def check_losses(losses):
    if not all(np.isfinite(losses)):
        raise AssertionError(f"loss not finite: {losses}")
    if any(b >= a for a, b in zip(losses, losses[1:])):
        raise AssertionError(
            f"loss not decreasing on a repeated batch: {losses}")


def reference_logits(model, ids: np.ndarray):
    """The plain reference: the same decoder written out in float32
    jax.numpy — no framework op, no kernel — over the same (bf16) weights,
    matmuls at precision "highest"."""
    import jax
    import jax.numpy as jnp

    cfg = model.config
    f32 = jnp.float32
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads

    def w(layer):
        return layer.weight._data

    weights = {
        "embed": w(model.model.embed_tokens), "norm": w(model.model.norm),
        "lm_head": w(model.lm_head),
        "layers": [{
            "ln1": w(l.input_layernorm), "ln2": w(l.post_attention_layernorm),
            "q": w(l.self_attn.q_proj), "k": w(l.self_attn.k_proj),
            "v": w(l.self_attn.v_proj), "o": w(l.self_attn.o_proj),
            "gate": w(l.mlp.gate_proj), "up": w(l.mlp.up_proj),
            "down": w(l.mlp.down_proj)} for l in model.model.layers]}
    s = ids.shape[1]
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float64)
                                    / hd))
    emb = np.outer(np.arange(s, dtype=np.float64), inv)
    emb = np.concatenate([emb, emb], axis=-1)
    cos = jnp.asarray(np.cos(emb), f32)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), f32)[None, :, None, :]

    def rms(x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + cfg.rms_norm_eps) * g.astype(f32)

    def rope(a):
        a1, a2 = jnp.split(a, 2, axis=-1)
        return a * cos + jnp.concatenate([-a2, a1], axis=-1) * sin

    @jax.jit
    def block(x, lw):
        b = x.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
        h = rms(x, lw["ln1"])
        q = rope((h @ lw["q"].astype(f32)).reshape(b, s, nh, hd))
        k = rope((h @ lw["k"].astype(f32)).reshape(b, s, nkv, hd))
        v = (h @ lw["v"].astype(f32)).reshape(b, s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
        x = x + a.reshape(b, s, nh * hd) @ lw["o"].astype(f32)
        h = rms(x, lw["ln2"])
        return x + (jax.nn.silu(h @ lw["gate"].astype(f32))
                    * (h @ lw["up"].astype(f32))) @ lw["down"].astype(f32)

    # one program per block, not one for the model: the float32 copies of
    # the weights then exist one layer at a time (0.8 GB, not 4.3 GB)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e.astype(f32)[t])(weights["embed"],
                                                   jnp.asarray(ids))
        for lw in weights["layers"]:
            x = block(x, lw)
        return np.asarray(jax.jit(
            lambda x, g, w: rms(x, g) @ w.astype(f32))(
                x, weights["norm"], weights["lm_head"]))


#: the system computes in bf16 (8 mantissa bits, eps 2^-8 = 3.9e-3 per
#: rounding) and the reference in float32. Every matmul output, norm,
#: rope and residual add rounds once; an error that relative size,
#: accumulated in quadrature over the ~10 roundings of each of up to 4
#: layers, is ~3.9e-3 * sqrt(40) = 2.5e-2 of the logits' scale. The bound
#: is twice that (6.9e-3 was read on the chip at 4 layers); a wrong kernel
#: (a swapped rope half, a missed mask) is off by O(1) of the scale.
LOGITS_REL_L2 = 5e-2


def check_logits(model, sizes: Sizes, seed: int) -> dict:
    import paddle_tpu as paddle

    rs = np.random.RandomState(seed + 1)
    ids = rs.randint(0, model.config.vocab_size,
                     (1, sizes.ref_tokens)).astype("int64")
    model.eval()
    with paddle.no_grad(), paddle.amp.auto_cast(
            enable=True, dtype="bfloat16", level="O2"):
        got = np.asarray(model(paddle.to_tensor(ids))._data, np.float32)
    model.train()
    want = reference_logits(model, ids)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"logits {got.shape} vs reference "
                             f"{want.shape}, or not finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if rel > LOGITS_REL_L2:
        raise AssertionError(
            f"logits differ from the float32 reference: relative L2 "
            f"{rel:.4f} > {LOGITS_REL_L2}")
    return {"positions": int(got.shape[1]), "vocab": int(got.shape[2]),
            "rel_l2": round(rel, 5), "bound": LOGITS_REL_L2,
            "max_abs": round(float(np.abs(got - want).max()), 5),
            "ref_rms": round(float(np.sqrt(np.mean(want ** 2))), 5)}


def phase_train(sizes: Sizes, seed: int, cache: CacheCounter) -> dict:
    import paddle_tpu as paddle

    t_phase = time.perf_counter()
    cfg, model, opt, fn = build_trainer(sizes, sizes.train_layers, seed)
    step = paddle.jit.to_static(fn, share_discovery=True)
    small, big = batches(sizes, cfg.vocab_size, seed)
    losses, first_s, walls = run_steps(step, small, big, sizes.steps)
    progress(f"train: losses {losses}, first call {first_s:.1f}s, "
             f"steps {[round(w, 4) for w in walls]}")
    check_losses(losses)
    if len(step._cache) != 1:
        raise AssertionError(
            f"expected one compiled specialization, found "
            f"{len(step._cache)} (fallback: eager={step._fallback_eager}, "
            f"segmented={step._segmented})")
    found = require_kernels(lower_step(step, big).as_text(), TRAIN_KERNELS)
    progress(f"train: kernels {found}")
    mem = hbm()[:1]
    del step, opt, fn                   # the moments go; the model stays
    gc.collect()
    logits = check_logits(model, sizes, seed)
    progress(f"train: logits {logits}")
    step_s = float(np.median(walls))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    out = {
        "model": {"hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
                  "heads": cfg.num_attention_heads, "head_dim": cfg.head_dim,
                  "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
                  "of": OF_LAYERS, "params": n_params},
        "batch": sizes.batch, "seq": sizes.seq, "losses": losses,
        "kernels": found, "logits_vs_f32_reference": logits,
        "step_seconds_smoke": round(step_s, 4),
        "tokens_per_sec_smoke": round(sizes.batch * sizes.seq / step_s, 1),
        "compile_seconds": round(first_s - step_s, 2),
        "compile_cache": cache.take(), "hbm": mem,
        "seconds": round(time.perf_counter() - t_phase, 2),
        "checked": ["loss finite and decreasing on a repeated batch",
                    "one compiled specialization in fn._cache",
                    "every routed Pallas kernel is a tpu_custom_call in "
                    "the step's program",
                    "logits within bound of the float32 jnp reference"],
    }
    return out


# ----------------------------------------------------------------- serve

#: where a greedy token of the paged engine differs from the static
#: engine's, both must be near-ties of the model's own bf16 logits at that
#: step: the loser's logit within this many bf16 roundings (eps 2^-8) of
#: the winner's, relative to the largest |logit|. Random weights give
#: near-uniform logits, so such ties are expected; a wrong cache read
#: yields a token whose logit is not near the top at all.
TIE_ROUNDINGS = 4


def check_against_static(model, prompts, paged, n_new) -> dict:
    """Greedy tokens of the paged engine vs `model.generate` (the static
    one-program engine) on the same prompts."""
    import paddle_tpu as paddle

    ids = np.stack(prompts).astype("int64")
    out = np.asarray(model.generate(paddle.to_tensor(ids),
                                    max_new_tokens=n_new)._data)
    static = out[:, ids.shape[1]:]
    equal, ties = 0, []
    for i, (st, pg) in enumerate(zip(static, paged)):
        if np.array_equal(st, pg):
            equal += 1
            continue
        t = int(np.argmax(st != pg))            # first differing step
        ctx = np.concatenate([ids[i], st[:t]])[None]
        with paddle.no_grad(), paddle.amp.auto_cast(
                enable=True, dtype="bfloat16", level="O2"):
            lg = np.asarray(model(paddle.to_tensor(ctx))._data,
                            np.float32)[0, -1]
        gap = float(lg.max() - min(lg[st[t]], lg[pg[t]]))
        tol = TIE_ROUNDINGS * 2.0 ** -8 * float(np.abs(lg).max())
        if gap > tol:
            raise AssertionError(
                f"request {i}: paged token {pg[t]} vs static {st[t]} at "
                f"step {t} is no bf16 tie (logit gap {gap:.5f} > "
                f"{tol:.5f})")
        ties.append({"request": i, "step": t, "gap": round(gap, 6),
                     "tol": round(tol, 6)})
    return {"compared": len(paged), "token_identical": equal,
            "bf16_ties": ties}


def phase_serve(sizes: Sizes, seed: int, cache: CacheCounter) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.inference import engine as engine_mod
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.text.models import LlamaForCausalLM, llama_7b_config

    t_phase = time.perf_counter()
    hbm_start = hbm()[:1]               # what the train phase left behind
    paddle.seed(seed)
    cfg = llama_7b_config(num_hidden_layers=sizes.serve_layers,
                          max_position_embeddings=sizes.max_model_len,
                          **sizes.model)
    model = paddle.amp.decorate(LlamaForCausalLM(cfg), level="O2",
                                dtype="bfloat16", master_weight=False)
    model.eval()
    obs.clear_events()
    eng = ServingEngine(model, max_slots=8)

    def drive(stream_seed):
        rs = np.random.RandomState(stream_seed)
        prompts = [rs.randint(0, cfg.vocab_size, (ln,)).astype("int64")
                   for ln, _ in sizes.requests]
        rids = [eng.add_request(p, max_new_tokens=nt)
                for p, (_, nt) in zip(prompts, sizes.requests)]
        t0 = time.perf_counter()
        done = eng.run()
        return prompts, [done[r] for r in rids], time.perf_counter() - t0

    # warm every program this stream needs on OTHER prompts of the same
    # lengths (the same ones would ride the prefix cache instead)
    _, _, warm_s = drive(seed + 100)
    compiled = len(obs.compile_events())
    progress(f"serve: warm drive {warm_s:.1f}s, {compiled} programs")
    eng.finish_warmup()
    before = eng.stats()
    prompts, tokens, wall_s = drive(seed + 200)
    after = eng.stats()
    ticks, decode_s, prefill_s = (
        after[k] - before[k]
        for k in ("steps", "decode_time_s", "prefill_time_s"))
    for (ln, nt), tk in zip(sizes.requests, tokens):
        if len(tk) != nt:
            raise AssertionError(f"request (prompt {ln}) produced "
                                 f"{len(tk)} of {nt} tokens")
    if obs.post_warmup_compiles():
        raise AssertionError(
            f"{obs.post_warmup_compiles()} compile(s) after "
            "finish_warmup(): steady-state ticks must not compile")

    decode_kernels: dict = {}
    for key, (exe, _) in engine_mod._SERVING_EXECUTABLES.items():
        if key[0] == "serving.decode":
            decode_kernels[f"bucket{key[3]}"] = require_kernels(
                exe.as_text(), ("paged_decode",))
    if not decode_kernels:
        raise AssertionError("no serving.decode program was compiled")

    (ln0, nt0), (ln1, nt1) = sizes.requests[:2]
    assert (ln0, nt0) == (ln1, nt1), "first two requests must be alike"
    progress(f"serve: stream {wall_s:.2f}s, decode kernels "
             f"{decode_kernels}")
    parity = check_against_static(model, prompts[:2], tokens[:2], nt0)

    new_tokens = sum(nt for _, nt in sizes.requests)
    out = {
        "model": {"hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
                  "heads": cfg.num_attention_heads, "head_dim": cfg.head_dim,
                  "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
                  "of": OF_LAYERS},
        "slots": eng.max_slots, "requests": len(sizes.requests),
        "prompt_lengths": [ln for ln, _ in sizes.requests],
        "new_tokens": new_tokens, "programs_compiled": compiled,
        "post_warmup_compiles": obs.post_warmup_compiles(),
        "decode_kernels": decode_kernels, "vs_static_engine": parity,
        "stream_seconds_smoke": round(wall_s, 3),
        "stream_tokens_per_sec_smoke": round(new_tokens / wall_s, 1),
        "scheduler_ticks": ticks,
        "decode_seconds_smoke": round(decode_s, 3),
        "decode_ms_per_tick_smoke": round(1e3 * decode_s / ticks, 2),
        "prefill_seconds_smoke": round(prefill_s, 3),
        "kv_pool_gb": round(after["kv_hbm_bytes"] / 1e9, 3),
        "compile_seconds": round(sum(e.wall_s for e in
                                     obs.compile_events()), 2),
        "warm_drive_seconds": round(warm_s, 2),
        "compile_cache": cache.take(), "hbm_at_start": hbm_start,
        "hbm": hbm()[:1],
        "seconds": round(time.perf_counter() - t_phase, 2),
        "checked": ["every request completed with its token budget",
                    "zero compiles after finish_warmup()",
                    "paged_decode is a tpu_custom_call in every decode "
                    "program",
                    "greedy tokens equal the static engine's, or differ "
                    "at a bf16 tie of the model's own logits"],
    }
    return out


# ------------------------------------------------------------ four chips

#: sharded vs one-device loss, step for step. Both run bf16 matmuls with
#: float32 accumulation; tp=2 splits the contraction of o_proj/down_proj
#: into two partial sums that are added after rounding, and fsdp=2 halves
#: the batch per device, so the mean loss regroups. Each is a few bf16
#: roundings (2^-8) on O(1) activations, averaged over 4096 tokens: 4e-6
#: was read on four chips; a wrong shard or a missed reduction is O(1).
SHARDED_LOSS_REL = 1e-3

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter")


def phase_four_chips(sizes: Sizes, seed: int, cache: CacheCounter) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.partitioner import MeshConfig, partition

    t_phase = time.perf_counter()
    devs = jax.devices()[:4]
    mc = MeshConfig(fsdp=2, tp=2)

    # -- sharded: partition() raises when four devices cannot be had
    cfg, model, opt, fn = build_trainer(sizes, sizes.train_layers, seed)
    step = partition(fn, mc, model=model, share_discovery=True)
    small, big = batches(sizes, cfg.vocab_size, seed)
    sh_losses, sh_first, sh_walls = run_steps(step, small, big, sizes.steps)
    progress(f"four_chips: sharded losses {sh_losses}, first call "
             f"{sh_first:.1f}s, steps {[round(w, 4) for w in sh_walls]}")
    check_losses(sh_losses)
    plan = step.plan
    sharded = [d for d in plan.decisions if d.spec and any(d.spec)]
    by_name = dict(model.named_parameters())
    for d in sharded:
        on = {s.device for s in by_name[d.name]._data.addressable_shards}
        if len(on) != 4:
            raise AssertionError(
                f"parameter {d.name} ({d.spec}) lives on {len(on)} "
                "device(s), not 4")
    mem = hbm(devs)
    used = [m["in_use_gb"] for m in mem]
    if min(used) <= 0 or max(used) > 2 * min(used):
        raise AssertionError(f"HBM in use is uneven across devices: {used}")
    text = lower_step(step, big).compile().as_text()
    kernels = kernels_in(text)
    colls = {c: len(re.findall(rf"\b{c}(?:-start)?\(", text))
             for c in COLLECTIVES}
    if not colls["all-gather"] or not (colls["all-reduce"]
                                       + colls["reduce-scatter"]):
        raise AssertionError(
            f"expected fsdp/tp collectives in the sharded program, found "
            f"{colls}")
    sh_compiles = cache.take()
    del step, model, opt, fn, by_name, plan
    gc.collect()

    # -- the one-device step it is compared with: same seed, same batch
    cfg, model, opt, fn = build_trainer(sizes, sizes.train_layers, seed)
    one = paddle.jit.to_static(fn, share_discovery=True)
    small, big = batches(sizes, cfg.vocab_size, seed)
    one_losses, one_first, one_walls = run_steps(one, small, big,
                                                 sizes.steps)
    progress(f"four_chips: one-device losses {one_losses}, first call "
             f"{one_first:.1f}s, steps {[round(w, 4) for w in one_walls]}")
    check_losses(one_losses)
    rel = [abs(a - b) / abs(b) for a, b in zip(sh_losses, one_losses)]
    if max(rel) > SHARDED_LOSS_REL:
        raise AssertionError(
            f"sharded losses {sh_losses} vs one-device {one_losses}: "
            f"relative difference {max(rel):.5f} > {SHARDED_LOSS_REL}")

    sh_s, one_s = float(np.median(sh_walls)), float(np.median(one_walls))
    toks = sizes.batch * sizes.seq
    return {
        "mesh": mc.describe(), "layers": cfg.num_hidden_layers,
        "of": OF_LAYERS, "batch": sizes.batch, "seq": sizes.seq,
        "sharded_losses": sh_losses, "one_device_losses": one_losses,
        "loss_rel_diff": [round(r, 6) for r in rel],
        "bound": SHARDED_LOSS_REL,
        "sharded_params": len(sharded), "hbm_sharded": mem,
        "collectives": colls, "kernels": kernels,
        # the step after the compiling call compiles AGAIN: the optimizer
        # state comes back sharded and jit re-specializes (PERF.md)
        "sharded_step_seconds_all": [round(w, 4) for w in sh_walls],
        "sharded_step_seconds_smoke": round(sh_s, 4),
        "sharded_tokens_per_sec_smoke": round(toks / sh_s, 1),
        "one_device_step_seconds_smoke": round(one_s, 4),
        "one_device_tokens_per_sec_smoke": round(toks / one_s, 1),
        "compile_seconds": round((sh_first - sh_s) + (one_first - one_s),
                                 2),
        "compile_cache": {"sharded": sh_compiles, "one": cache.take()},
        "seconds": round(time.perf_counter() - t_phase, 2),
        "checked": ["losses finite and decreasing on both",
                    "sharded vs one-device loss within bound, step for "
                    "step",
                    "every sharded parameter has shards on 4 devices",
                    "HBM in use within 2x across the 4 devices",
                    "all-gather and all-reduce/reduce-scatter in the "
                    "sharded program"],
    }


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step and its "
                         "one-device comparison, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and batches are made from it")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    dev = phase_device(REAL, cache_dir)
    emit("device", **dev)
    if args.chips == 4:
        if len(jax.devices()) < 4:
            raise RuntimeError(
                f"--chips 4 needs four devices, jax found "
                f"{len(jax.devices())}")
        emit("four_chips", device_kind=dev["device_kind"],
             note="smoke run, not a benchmark",
             **phase_four_chips(REAL, args.seed, cache))
    else:
        emit("train", device_kind=dev["device_kind"],
             note="smoke run, not a benchmark",
             **phase_train(REAL, args.seed, cache))
        gc.collect()
        emit("serve", device_kind=dev["device_kind"],
             note="smoke run, not a benchmark",
             **phase_serve(REAL, args.seed, cache))
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
