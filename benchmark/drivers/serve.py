"""Role `serve`: an open loop against `ServingEngine.add_request` / `step`
on one thread. Before each step every request now due is admitted; tokens
are stamped as `step` emits them. The benchmark keeps its own clock: a
request's time runs from when it was DUE, not from `add_request`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import traffic_gen, weights
from ..harness import annotate, close_slice, open_slice, progress


class Book:
    """What the benchmark knows of one request."""

    __slots__ = ("due", "prompt", "max_new", "rid", "req", "stamps",
                 "tokens", "done_at", "failed")

    def __init__(self, due, prompt, max_new):
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.rid = self.req = self.done_at = None
        self.stamps, self.tokens, self.failed = [], [], False


def setup(ctx) -> dict:
    from paddle_tpu import obs
    from paddle_tpu.inference.engine import ServingEngine

    fam, cfg, mix = ctx.family, ctx.cfg, ctx.mix
    layers = fam.depth(cfg, "serve")
    progress(f"serve: {cfg['name']} \"layers\": {layers}, \"of\": "
             f"{cfg['published'][fam.DEPTH_KEY]}")
    model = fam.build_model(cfg, layers, "serve")
    ctx.hbm("model built")
    n = weights.assign(model, weights.make(fam.weight_spec(cfg, layers),
                                           ctx.seed))
    progress(f"serve: {n:,} parameters made on the device from the seed")
    obs.clear_events()
    eng = ServingEngine(model, **mix["engine"])
    ctx.hbm("engine built")

    # warm every program the window can reach, on other tokens than the
    # window's: each distinct prompt length once (its prefill or chunk
    # ladder), then a full house of short requests that drains through
    # every decode bucket
    rng = np.random.default_rng(ctx.seed + 7919)
    vocab = cfg["vocab_size"]
    lengths = traffic_gen.warmup_lengths(mix, ctx.seconds)
    t0 = time.perf_counter()
    for ln in lengths:
        eng.add_request(rng.integers(0, vocab, ln), max_new_tokens=1)
        eng.run()
    slots = eng.max_slots
    for i in range(slots):
        eng.add_request(rng.integers(0, vocab, lengths[0]),
                        max_new_tokens=2 + i)
    eng.run()
    eng.finish_warmup()
    progress(f"serve: warmed {len(lengths)} prompt lengths and {slots} "
             f"slots in {time.perf_counter() - t0:.1f}s, "
             f"{len(obs.compile_events())} programs")
    ctx.hbm("warmed")
    return {"model": model, "engine": eng, "layers": layers}


def _counters(eng) -> dict:
    st = eng.stats()
    out = {k: float(st[k]) for k in (
        "steps", "decode_tokens", "prefill_tokens", "decode_time_s",
        "prefill_time_s", "admission_blocked", "prefill_chunks")}
    out["active_slot_steps"] = float(eng.active_slot_steps)
    out["slot_steps"] = float(eng.steps * eng.max_slots)
    return out


def window(ctx, state) -> dict:
    """The measured window: `ctx.seconds` of the open loop."""
    eng, fam, cfg, mix = state["engine"], ctx.family, ctx.cfg, ctx.mix
    layers = state["layers"]
    reqs = traffic_gen.serve_requests(mix, ctx.seed, ctx.seconds,
                                      cfg["vocab_size"])
    books = [Book(r["due_s"], r["prompt"], r["max_new_tokens"])
             for r in reqs]
    progress(f"serve: {len(books)} requests, prompt lengths "
             f"{traffic_gen.histogram(len(b.prompt) for b in books)}, "
             f"outputs {traffic_gen.histogram(b.max_new for b in books)}")
    by_rid, late, nxt = {}, [], 0
    seconds = float(ctx.seconds)
    sl = {"on": False, "done": not ctx.trace, "t0": None, "t1": None,
          "start_at": seconds / 2.0, "len": float(mix["trace_slice_s"]),
          "tokens": 0.0, "logit_rows": 0.0, "ctx_sum": 0.0,
          "decode_ctx_tokens": 0.0, "decode_tokens": 0.0, "span": None}
    before = _counters(eng)
    ctx.mark_window_start()
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if not sl["done"]:
            if not sl["on"] and now >= sl["start_at"]:
                sl["span"] = open_slice(ctx)
                sl["on"], sl["t0"] = True, time.perf_counter() - t0
            elif sl["on"] and now >= sl["t0"] + sl["len"]:
                sl["t1"] = time.perf_counter() - t0
                close_slice(sl["span"])
                sl["on"], sl["done"] = False, True
                continue
        if nxt < len(books) and books[nxt].due <= now:
            with annotate("bench.add_request"):
                while nxt < len(books) and books[nxt].due <= now:
                    b = books[nxt]
                    nxt += 1
                    late.append(now - b.due)
                    try:
                        b.rid = eng.add_request(b.prompt,
                                                max_new_tokens=b.max_new)
                    except ValueError as e:
                        b.failed = True
                        progress(f"serve: request refused: {e}")
                        continue
                    b.req = eng._waiting[-1]
                    by_rid[b.rid] = b
        if eng.has_work():
            with annotate("bench.engine_step"):
                emitted = eng.step()
            t = time.perf_counter() - t0
            for rid, tok, fin in emitted:
                b = by_rid[rid]
                if tok is not None:
                    if sl["on"]:
                        ctx_len = len(b.prompt) + len(b.tokens)
                        if b.tokens:            # a decode token
                            sl["decode_tokens"] += 1
                            sl["decode_ctx_tokens"] += ctx_len
                            sl["tokens"] += 1
                            sl["ctx_sum"] += ctx_len + 1
                        else:                   # its prompt was prefilled
                            p = len(b.prompt)
                            sl["tokens"] += p
                            sl["ctx_sum"] += p * (p + 1) / 2.0
                        sl["logit_rows"] += 1
                    b.stamps.append(t)
                    b.tokens.append(int(tok))
                if fin:
                    b.done_at = t
        else:
            wait = (books[nxt].due if nxt < len(books) else seconds) - now
            if sl["on"]:
                wait = min(wait, sl["t0"] + sl["len"] - now)
            with annotate("bench.gen_wait"):
                time.sleep(max(0.0, min(wait, seconds - now)))
    window_s = time.perf_counter() - t0
    if sl["on"]:                                # the window closed in it
        sl["t1"] = window_s
        close_slice(sl["span"])
    after = _counters(eng)

    # every request DUE in the window counts, offered or not: one the loop
    # never reached (a stall past its due time up to the close) has no
    # first token, which reads as the window's length
    due = [b for b in books if b.due < seconds]
    ttft, tpot, qwait = [], [], []
    for b in due:
        ttft.append((b.stamps[0] - b.due) if b.stamps else window_s)
        tpot += list(np.diff(b.stamps))
        if b.req is not None and b.req.admitted_s is not None:
            qwait.append(b.req.admitted_s - t0 - b.due)
    done = [b for b in due if b.done_at is not None]
    wrong = [b for b in done if len(b.tokens) != b.max_new]
    rec = {
        "window_s": window_s,
        "attempted": len(due),
        "failed": sum(b.failed for b in due) + len(wrong),
        "counts": {"output_tokens": float(sum(len(b.tokens) for b in due))},
        "samples": {"ttft_ms": [1e3 * x for x in ttft],
                    "tpot_ms": [1e3 * x for x in tpot],
                    "queue_wait_ms": [1e3 * x for x in qwait],
                    "generator_late_ms": [1e3 * x for x in late]},
        "counters": {k: after[k] - before[k] for k in after},
        "done": done,
    }
    if ctx.trace and sl["t1"] is not None:
        rec["slice"] = {
            "seconds": sl["t1"] - sl["t0"], "layers": layers,
            "decode_tokens": sl["decode_tokens"],
            "decode_ctx_tokens": sl["decode_ctx_tokens"],
            "model_flops": fam.serve_flops(cfg, layers, sl["tokens"],
                                           sl["logit_rows"], sl["ctx_sum"])}
    progress(f"serve: window {window_s:.2f}s, {len(due)} due, "
             f"{len(done)} completed, at the close {eng.num_waiting} "
             f"waiting and {eng.num_active} in slots, generator late p50/max "
             f"{np.median(late) * 1e3:.1f}/{max(late) * 1e3:.1f} ms, "
             f"counters {rec['counters']}")

    def pct(xs, qs):
        return [round(float(np.percentile(xs, q)), 1) for q in qs] \
            if len(xs) else []

    sm = rec["samples"]
    progress(f"serve: ttft ms p50/p70/p90/max "
             f"{pct(sm['ttft_ms'], (50, 70, 90, 100))} over {len(ttft)}, "
             f"tpot ms p50/p95/p99 {pct(sm['tpot_ms'], (50, 95, 99))} over "
             f"{len(tpot)}, queue wait ms p50/max "
             f"{pct(sm['queue_wait_ms'], (50, 100))}")
    progress(f"serve: ttft ms of each request due, ascending "
             f"{sorted(round(x, 1) for x in sm['ttft_ms'])}")
    return rec


def kernels_present(state) -> dict:
    """{program: {kernel: count}} of the decode programs the engine holds."""
    from paddle_tpu.inference import engine as engine_mod

    from ..harness import kernels_in

    return {f"{k[0]}/bucket{k[3]}": kernels_in(exe.as_text())
            for k, (exe, _) in engine_mod._SERVING_EXECUTABLES.items()
            if k[0] == "serving.decode"}


def compiles_in_window(state) -> int:
    from paddle_tpu import obs

    return int(obs.post_warmup_compiles())


def release(state) -> None:
    """Free the program's state before the reference runs."""
    from paddle_tpu.text import generation

    eng = state.pop("engine")
    eng.close()
    generation._STACK_CACHE.clear()
    state.clear()
    del eng
    gc.collect()


def check(ctx, rec, precisions=("f32",)) -> list:
    """The served tokens against the plain float32 reference: for a
    sample of the finished requests, drawn from the seed, the longest in
    it, the widest gap by which a served token's logit lies below the
    reference's best at its position, in units of that position's logit
    standard deviation. With "fp8" in `precisions` the control is read
    too: the gap of the token the lower precision puts first."""
    fam, cfg, mix = ctx.family, ctx.cfg, ctx.mix
    layers = fam.depth(cfg, "serve")
    done = rec["done"]
    out = []
    gap = {"served": 0.0, "control": 0.0}
    n_tok = 0
    if done:
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
            g = (best - ref[np.arange(len(toks)), toks]) / sd
            gap["served"] = max(gap["served"], float(g.max()))
            n_tok += len(toks)
            if "fp8" in precisions:
                low = fam.reference_rows(cfg, layers, w, ids, rows, "fp8")
                pick_low = low.argmax(axis=-1)
                gl = (best - ref[np.arange(len(toks)), pick_low]) / sd
                gap["control"] = max(gap["control"], float(gl.max()))
        del w
    limit = mix["check"]["gap_sigma_limit"]
    out.append({"name": "served_logit_gap_sigma", "value": gap["served"],
                "limit": limit, "tokens": n_tok, "requests": len(done)})
    if "fp8" in precisions:
        out.append({"name": "control_fp8.served_logit_gap_sigma",
                    "value": gap["control"], "limit": limit,
                    "control": "control_fp8"})
    out.append({"name": "requests_finished", "value": len(done),
                "limit": 1, "at_least": True})
    return out
