"""Role `train`: the normal trainer path — `paddle.amp.decorate` O2 bf16,
AdamW, one `paddle.jit.to_static` step of forward, loss, backward and
update — fed a fresh batch each step by a host-side generator.

Set-up builds ONE object, the compiled step with its state; puts the
state back to the seed's after the warm-ups; drives it through its first
steps (what `check` compares with the reference) and hands that same
object to the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import reference_train, traffic_gen, weights
from ..harness import (annotate, close_slice, kernels_in, open_slice,
                       progress)

CHECKED_STEPS = 3


def _named_params(model) -> dict:
    return dict(model.named_parameters())


def _reset_optimizer(model, opt) -> None:
    """Moments and step count back to nought and the float32 master
    copies back to the (just re-made) parameters: the eager warm-ups and
    the compiling call were optimizer steps too."""
    import jax.numpy as jnp

    for p in model.parameters():
        master = opt._master_weights.get(id(p))
        if master is not None:
            master._assign_raw(p._data.astype(jnp.float32))
    for store in opt._accumulators.values():
        for t in store.values():
            t._assign_raw(jnp.zeros(t._data.shape, t._data.dtype))
    opt._step_count = 0
    opt._step_t._assign_raw(jnp.zeros((), jnp.float32))


def _leaf_norms(arrays: dict, minus: dict | None = None, scale=1.0) -> dict:
    """{leaf: scale * |a - minus|} in float32, one program for all."""
    import jax
    import jax.numpy as jnp

    names = sorted(arrays)

    @jax.jit
    def f(xs, ys):
        return [scale * jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - (0.0 if y is None
                                     else y.astype(jnp.float32)))))
                for x, y in zip(xs, ys)]

    out = f([arrays[n] for n in names],
            [None if minus is None else minus[n] for n in names])
    return {n: float(v) for n, v in zip(names, out)}


def setup(ctx) -> dict:
    import jax

    import paddle_tpu as paddle

    fam, cfg, mix, hp = ctx.family, ctx.cfg, ctx.mix, ctx.mix["optimizer"]
    layers = fam.depth(cfg, "train")
    progress(f"train: {cfg['name']} \"layers\": {layers}, \"of\": "
             f"{cfg['published'][fam.DEPTH_KEY]}")
    model = fam.build_model(cfg, layers, "train")
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"], parameters=model.parameters())
    model, opt = paddle.amp.decorate(
        model, opt, level="O2", dtype="bfloat16",
        master_weight=bool(mix["master_weight"]))
    spec = fam.weight_spec(cfg, layers)
    n = weights.assign(model, weights.make(spec, ctx.seed))
    progress(f"train: {n:,} parameters made on the device from the seed")
    ctx.hbm("model built")

    def train_step(x):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level="O2"):
            loss = model(x, x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, share_discovery=True)
    vocab = cfg["vocab_size"]
    feed = traffic_gen.train_batches(mix, ctx.seed, vocab)
    warm = np.random.default_rng(ctx.seed + 7919)
    small = paddle.to_tensor(warm.integers(0, vocab, tuple(mix["warm_shape"]),
                                           dtype=np.int64))
    b, s = int(mix["tokens_per_step"]) // int(mix["seq"]), int(mix["seq"])
    big = paddle.to_tensor(warm.integers(0, vocab, (b, s), dtype=np.int64))
    t0 = time.perf_counter()
    for _ in range(2):                  # the two eager calls of to_static
        jax.block_until_ready(step(small)._data)
    ctx.hbm("eager warm-ups")
    jax.block_until_ready(step(big)._data)      # compiles
    jax.block_until_ready(step(big)._data)
    progress(f"train: warm-ups and compile {time.perf_counter() - t0:.1f}s")
    if len(step._cache) != 1:
        raise RuntimeError(f"expected one compiled specialization, found "
                           f"{len(step._cache)}")
    ctx.hbm("compiled")

    state = {"model": model, "opt": opt, "step": step, "spec": spec,
             "layers": layers, "batch": (b, s), "big": big}
    state["first"] = first_steps(ctx, state, feed)
    state["feed"] = feed
    ctx.hbm("first steps")
    return state


def first_steps(ctx, state, feed) -> dict:
    """The state back to `ctx.seed`'s, then the first steps through the
    window's own call and feed: what `check` compares with the reference."""
    import paddle_tpu as paddle

    model, opt, step, spec = (state["model"], state["opt"], state["step"],
                              state["spec"])
    hp = ctx.mix["optimizer"]
    named = _named_params(model)
    weights.assign(model, weights.make(spec, ctx.seed))
    _reset_optimizer(model, opt)
    first = {"losses": [], "batches": []}
    for i in range(CHECKED_STEPS):
        ids = next(feed)
        loss = step(paddle.to_tensor(ids))
        first["losses"].append(float(loss))
        if i < 2:
            first["batches"].append(ids)
        # what the optimizer holds as the parameters: the float32 master
        # copy where the mix asks for one
        params = {k: opt._master_weights.get(id(p), p)._data
                  for k, p in named.items()}
        if i == 0:
            m1 = opt._accumulators["moment1"]
            first["grad_norm"] = _leaf_norms(
                {k: m1[id(p)]._data for k, p in named.items()},
                scale=1.0 / (1.0 - hp["beta1"]))
        if i == 1:
            p0 = weights.make(spec, ctx.seed)
            first["delta_norm"] = _leaf_norms(params, minus=p0)
            del p0
        del params
    progress(f"train: seed {ctx.seed}: first steps' losses "
             f"{first['losses']}")
    return first


def window(ctx, state) -> dict:
    import jax

    import paddle_tpu as paddle

    fam, cfg, mix = ctx.family, ctx.cfg, ctx.mix
    step, feed, (b, s) = state["step"], state["feed"], state["batch"]
    seconds = float(ctx.seconds)
    sl = {"on": False, "done": not ctx.trace, "t0": None, "t1": None,
          "start_at": seconds / 2.0, "steps_wanted": int(mix["trace_steps"]),
          "steps": 0, "span": None}
    walls, losses = [], []
    ctx.mark_window_start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        now = time.perf_counter() - t0
        if not sl["done"] and not sl["on"] and now >= sl["start_at"]:
            sl["span"] = open_slice(ctx)
            sl["on"], sl["t0"] = True, time.perf_counter() - t0
        t1 = time.perf_counter()
        with annotate("bench.fetch"):
            x = paddle.to_tensor(next(feed))
        with annotate("bench.train_step"):
            loss = step(x)
            jax.block_until_ready(loss._data)
        walls.append(time.perf_counter() - t1)
        losses.append(loss)
        if sl["on"]:
            sl["steps"] += 1
            if sl["steps"] >= sl["steps_wanted"]:
                sl["t1"] = time.perf_counter() - t0
                close_slice(sl["span"])
                sl["on"], sl["done"] = False, True
    window_s = time.perf_counter() - t0
    if sl["on"]:
        sl["t1"] = window_s
        close_slice(sl["span"])
    losses = [float(x) for x in losses]
    rec = {"window_s": window_s, "attempted": len(walls),
           "failed": int(sum(not np.isfinite(x) for x in losses)),
           "counts": {"tokens": float(len(walls) * b * s),
                      "steps": float(len(walls))},
           "samples": {"step_ms": [1e3 * w for w in walls]},
           "counters": {}, "first": state["first"]}
    if ctx.trace and sl["t1"] is not None:
        rec["slice"] = {
            "seconds": sl["t1"] - sl["t0"], "layers": state["layers"],
            "steps": sl["steps"], "batch": b, "seq": s,
            "model_flops": sl["steps"] * fam.train_flops_per_step(
                cfg, state["layers"], b, s)}
    progress(f"train: window {window_s:.2f}s, {len(walls)} steps of "
             f"{b}x{s} tokens, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return rec


def kernels_present(state) -> dict:
    """{kernel: count} in the compiled step's program text (lowered once
    more from its cached specialization: no device memory)."""
    (spec,) = state["step"]._cache.values()
    text = spec.executable.lower(
        [state["big"]._data], [t._data for t in spec.ro_caps],
        [t._data for t in spec.mut_caps]).as_text()
    return kernels_in(text)


def compiles_in_window(state) -> int:
    return len(state["step"]._cache) - 1


def release(state) -> None:
    state.clear()
    gc.collect()


def check(ctx, rec, precisions=("f32",)) -> list:
    """The first steps against the plain float32 trainer, from the same
    seed's weights and the same batches. With "fp8" in `precisions` the
    control (the reference with fp8 matmul operands) and the half-batch
    fault are read against the reference too."""
    fam, cfg, mix = ctx.family, ctx.cfg, ctx.mix
    layers = fam.depth(cfg, "train")
    first = rec["first"]
    w = weights.make(fam.weight_spec(cfg, layers), ctx.seed)
    hp = mix["optimizer"]
    ref = reference_train.run(fam, cfg, layers, w, first["batches"], hp)
    worst = {}
    got = reference_train.gaps(first, ref, worst)
    progress(f"train: reference losses {ref['losses']}, worst leaves "
             f"{worst}")
    limits = mix["check"]["limits"]
    out = [{"name": k, "value": v, "limit": limits.get(k)}
           for k, v in got.items() if k in limits]
    out += [{"name": k + ".not_compared", "value": v, "limit": None,
             "control": "not_compared"}
            for k, v in got.items() if k not in limits]
    if "fp8" in precisions:
        for tag, kw in (("control_fp8", {"precision": "fp8"}),
                        ("fault_half_batch", {"half_batch": True})):
            alt = reference_train.run(fam, cfg, layers, w,
                                      first["batches"], hp, **kw)
            out += [{"name": f"{tag}.{k}", "value": v,
                     "limit": limits.get(k), "control": tag}
                    for k, v in reference_train.gaps(alt, ref).items()]
    return out
