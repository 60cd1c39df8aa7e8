"""`run.py` as the driver starts it, and the pieces of a run driven at a
tiny size on the CPU through the harness (the test-only entry: `run.py`'s
own pieces called directly, the look for a chip skipped).

For each cell kind: the result object's keys, the lower-precision control
coming out as not correct, and the timed path broken underneath —
`correct` must come out false."""
import copy
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, reference_train

ROOT = harness.ROOT
CPU_PLANES = {"device_prefix": "/host:CPU", "ops_lines": ("tf_XLA",)}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_run_py_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", harness.manifest()["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_py_refuses_an_unknown_workload():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no-such-cell", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_py_alone_with_the_benchmark_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, the program is not there to measure: non-zero, no result."""
    import shutil

    man = harness.manifest()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, *man["command"][1:], "--workload",
         man["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "paddle_tpu" in p.stderr


# ------------------------------------------------------------------ serve

def _serve_ctx(seed=2 ** 31 + 5, trace=0, limit=0.02):
    man = harness.manifest()
    res = harness.resolve(man, "mistral-7b.serve-chat")
    cfg = dict(res["cfg"])
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               max_position_embeddings=256,
               num_hidden_layers={"serve": 2, "train": 2})
    mix = copy.deepcopy(res["mix"])
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt_tokens"].update(median=40, min=8, max=150)
    mix["output_tokens"].update(median=10, min=4, max=30)
    mix.update(max_total_tokens=256, trace_slice_s=1.0,
               engine={"max_slots": 4, "max_model_len": 256})
    mix["check"]["gap_sigma_limit"] = limit
    res["cfg"], res["mix"] = cfg, mix
    ctx = harness.Context("mistral-7b.serve-chat", seed, 3.0, trace, res,
                          time.time(), require_tpu=False)
    ctx.trace_planes, ctx.peaks = CPU_PLANES, CPU_PEAKS
    return ctx


@pytest.fixture(scope="module")
def serve_traced():
    return harness.run_cell(_serve_ctx(trace=1), precisions=("f32", "fp8"))


def test_serve_result_object(serve_traced):
    out = serve_traced
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == round(4.0 * 0.93 * 3.0)  # rate x due_within
    assert {"busy_s", "window_s", "memory_peak_bytes", "platform", "kind",
            "count"} <= set(out["device"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in out["breakdown"].values())
    got = set(out["metrics"])
    assert {"queue_wait_p50_ms", "slot_occupancy", "prefill_ms_per_ktok",
            "decode_tick_ms", "step_mfu.serve", "device_idle.serve"} <= got
    # no paged_decode kernel runs on the CPU: its roofline stays silent
    assert "paged_decode_roofline" not in got
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_serve_control_comes_out_not_correct(serve_traced):
    """The reference in fp8, put in the program's place, picks tokens
    whose float32 logit lies further below the best than the limit."""
    c = serve_traced["compared"]
    served = c["served_logit_gap_sigma"]
    control = c["control_fp8.served_logit_gap_sigma"]
    assert served["value"] <= served["limit"] < control["value"]
    assert serve_traced["controls_correct"] == {"control_fp8": False}


def test_serve_altered_token_is_caught(monkeypatch):
    """A token altered where it is produced: `correct` comes out false."""
    from paddle_tpu.inference.engine import ServingEngine

    real = ServingEngine.step
    state = {"armed": False}

    def step(self):
        out = real(self)
        if not state["armed"]:
            return out
        return [(rid, None if tok is None else (tok + 1 + n) % 256, fin)
                for n, (rid, tok, fin) in enumerate(out)]

    monkeypatch.setattr(ServingEngine, "step", step)
    ctx = _serve_ctx(seed=9)
    real_window = ctx.driver.window

    def window(c, s):
        state["armed"] = True
        try:
            return real_window(c, s)
        finally:
            state["armed"] = False

    monkeypatch.setattr(ctx.driver, "window", window)
    out = harness.run_cell(ctx)
    assert out["correct"] is False
    c = out["compared"]["served_logit_gap_sigma"]
    assert c["value"] > c["limit"]
    assert "metrics" in out and "setup_s" in out["metrics"]


# ------------------------------------------------------------------ train

LIMITS = {"loss_step1_rel": 1e-4, "loss_step2_rel": 1e-4,
          "grad_norm_worst_leaf": 3e-3, "delta_norm_worst_leaf": 1e-2}


def _train_ctx(family, trace=0, seed=2 ** 31 + 5):
    cfg_file, mix_file = {"llama": ("mistral-7b", "pretrain-4k"),
                          "gpt": ("cerebras-gpt-1.3b", "pretrain-2k")}[family]
    cfg = harness.load_json("configs", cfg_file + ".json")
    if family == "llama":
        cfg.update(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=4096, max_position_embeddings=64,
                   num_hidden_layers={"serve": 2, "train": 2})
    else:
        cfg.update(n_embd=64, n_head=4, n_inner=256, n_positions=64,
                   vocab_size=4099, n_layer={"train": 2})
    mix = harness.load_json("traffic", mix_file + ".json")
    mix.update(seq=64, tokens_per_step=256, warm_shape=[1, 16],
               trace_steps=3)
    mix["check"]["limits"] = dict(LIMITS)
    man = harness.manifest()
    res = {"cell": {"name": f"tiny-{family}", "chips": 1}, "cfg": cfg,
           "mix": mix,
           "end_to_end": [m for m in man["end_to_end"]
                          if m["name"] in ("setup_s", "train_tokens_per_s")],
           "per_layer": [m for m in man["per_layer"]
                         if m["name"].endswith(".train")
                         or m["name"].startswith("flash_")]}
    ctx = harness.Context(res["cell"]["name"], seed, 2.0, trace, res,
                          time.time(), require_tpu=False)
    ctx.trace_planes, ctx.peaks = CPU_PLANES, CPU_PEAKS
    return ctx


@pytest.fixture(scope="module", params=["llama", "gpt"])
def train_run(request):
    ctx = _train_ctx(request.param)
    return harness.run_cell(ctx, precisions=("f32", "fp8"))


def test_train_result_object(train_run):
    out = train_run
    assert KEYS <= set(out) and out["correct"] is True
    assert out["attempted"] > 3 and out["failed"] == 0
    c = out["compared"]
    for k, lim in LIMITS.items():
        assert c[k]["limit"] == lim and c[k]["value"] <= lim


def test_train_control_and_fault_come_out_not_correct(train_run):
    """fp8 matmul operands in the reference's place, and half of each
    batch left out with the mean taken over the rest: each fails at least
    one of the cell's numbers."""
    c = train_run["compared"]
    for tag in ("control_fp8", "fault_half_batch"):
        failed = [k for k in LIMITS
                  if c[f"{tag}.{k}"]["value"] > LIMITS[k]]
        assert failed, (tag, c)
    assert train_run["controls_correct"] == {"control_fp8": False,
                                             "fault_half_batch": False}
    assert c["fault_half_batch.grad_norm_worst_leaf"]["value"] > 0.2


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_broken_step_is_caught(monkeypatch, fault):
    """The timed path broken underneath: a step that returns its state
    unchanged, and a step that leaves out half of its batch."""
    import paddle_tpu as paddle

    ctx = _train_ctx("llama", seed=11)
    real_to_static = paddle.jit.to_static

    def to_static(fn, **kw):
        if fault == "half_batch":
            def broken(x):
                return fn(x[: x.shape[0] // 2])
            return real_to_static(broken, **kw)
        step = real_to_static(fn, **kw)
        calls = {"n": 0}

        def unchanged(x):
            calls["n"] += 1
            if calls["n"] <= 4:             # warm-ups and the compile
                return step(x)
            caps = step._cache and next(iter(step._cache.values()))
            # copies: the step donates the buffers it mutates
            keep = [(t, t._data + 0) for t in caps.mut_caps]
            loss = step(x)
            for t, old in keep:
                t._assign_raw(old)          # the state as it was
            return loss
        unchanged._cache = step._cache
        return unchanged

    monkeypatch.setattr(paddle.jit, "to_static", to_static)
    out = harness.run_cell(ctx)
    assert out["correct"] is False
    c = out["compared"]
    bad = [k for k in LIMITS if c[k]["value"] > c[k]["limit"]]
    assert bad
    if fault == "state_unchanged":
        assert c["delta_norm_worst_leaf"]["value"] == pytest.approx(1.0,
                                                                    abs=1e-3)


def test_train_traced_metrics():
    out = harness.run_cell(_train_ctx("llama", trace=1))
    got = set(out["metrics"])
    assert {"step_ms_p50.train", "step_mfu.train",
            "device_idle.train"} <= got
    assert not got & {"flash_fwd_roofline", "flash_bwd_roofline"}
    assert out["device"]["busy_s"] > 0 and out["breakdown"]["device_ops"]


def test_gaps_measure_norms_by_the_worst_leaf():
    ref = {"losses": [10.0, 9.0], "grad_norm": {"a": 1.0, "b": 2.0,
                                                "c": 1e-6},
           "delta_norm": {"a": 0.5, "b": 0.5, "c": 0.5}}
    prog = {"losses": [10.1, 9.0], "grad_norm": {"a": 1.0, "b": 2.2,
                                                 "c": 2e-6},
            "delta_norm": {"a": 0.5, "b": 0.25, "c": 0.0}}
    g = reference_train.gaps(prog, ref)
    assert g["loss_step1_rel"] == pytest.approx(0.01)
    assert g["loss_step2_rel"] == 0.0
    assert g["grad_norm_worst_leaf"] == pytest.approx(0.1)
    # leaf c's reference gradient is nought: left out of the change
    assert g["delta_norm_worst_leaf"] == pytest.approx(0.5)
