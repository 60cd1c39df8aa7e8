"""What PR 31 adds to the benchmark, at tiny shapes on the CPU: the
`cohere2_moe` family's counts against hand arithmetic, the two work
modules of the two-kind decode kernels, the `span_attr_ratio` reader on a
hand-written log, the `longdoc-open-loop` mix's quantiles, the
configuration file's cuts, and the cell itself driven through the harness
(the test-only entry of `test_bench_run.py`: the look for a chip
skipped)."""
import copy
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, traffic_gen
from benchmark.families import cohere2_moe as fam
from benchmark.kernels import paged_decode_full, paged_decode_window
from benchmark.readers import span_attr_ratio

CELL = "command-a-plus-05-2026.serve-longdoc"
MAN = harness.manifest()
CPU_PLANES = {"device_prefix": "/host:CPU", "ops_lines": ("tf_XLA",)}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

#: 4 heads over 2 KV heads of size 4, hidden 8, experts of width 16, 2 of
#: 8 experts held (rank 1 of 4), top-4, 2 shared, window 6, vocab 10
TINY = {"name": "tiny", "family": "cohere2_moe", "hidden_size": 8,
        "intermediate_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 4, "vocab_size": 10,
        "num_experts": 2, "num_experts_per_tok": 4, "num_shared_experts": 2,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 8},
        "sliding_window": 6,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


def test_matmul_params_count_the_expected_local_picks():
    # attention 8x16 + 2 x 8x8 + 16x8 = 384; one expert 3 x 8 x 16 = 384;
    # 2 shared = 768; router 8x8 = 64; routed 4 picks x 2/8 held = 1 expert
    assert fam.expert_params(TINY) == 384
    assert fam.matmul_params(TINY, 4) == 4 * (384 + 768 + 64 + 384)
    assert fam.head_params(TINY) == 80


def test_serve_flops_window_layers_see_the_window_at_most():
    # 5 tokens, 2 logit rows, 100 attended keys: the full layer counts 100,
    # each of the 3 sliding layers min(100, 5 x 6) = 30
    per_key = 4 * 4 * 4
    assert fam.serve_flops(TINY, 4, 5, 2, 100) == (
        2 * 4 * 1600 * 5 + 2 * 80 * 2 + per_key * 100 + 3 * per_key * 30)
    # below the window nothing is cut
    assert fam.serve_flops(TINY, 4, 5, 2, 20) == (
        2 * 4 * 1600 * 5 + 2 * 80 * 2 + 4 * per_key * 20)


def test_published_counts_agree_with_the_model_card():
    """218B total, 25B active, from the configuration file's own numbers."""
    cfg = harness.load_json("configs", "command-a-plus-05-2026.json")
    pub = dict(cfg, num_experts=cfg["published"]["num_experts"],
               expert_parallel={"chips": 1, "rank": 0,
                                "experts_total": 128})
    h, q, kv = 4096, 128 * 128, 8 * 128
    attn = 2 * h * q + 2 * h * kv
    per_layer = attn + 4 * fam.expert_params(pub) + h * 128
    assert round(per_layer / 1e6, 1) == 344.5
    total = 32 * (per_layer + 128 * fam.expert_params(pub)) \
        + fam.head_params(pub)
    active = 32 * (per_layer + 8 * fam.expert_params(pub)) \
        + fam.head_params(pub)
    assert round(total / 1e9) == 218 and round(active / 1e9) == 25
    # the cut: 4 layers with 16 experts held, and the embedding, in bf16
    held = 4 * (per_layer + 16 * fam.expert_params(cfg)) \
        + fam.head_params(cfg)
    assert round(2 * held / 1e9, 2) == 11.35
    assert sum(int(np.prod(s)) for _, s, _ in fam.weight_spec(cfg, 4)) \
        == held + 5 * h                     # the norm gains
    spec = fam.weight_spec(cfg, 4)
    assert spec[0][0] == "model.embed_tokens.weight"
    assert max(int(np.prod(s)) for _, s, _ in spec[1:]) < 0.54e9


def test_decode_work_by_layer_kind():
    sl = {"layers": 4, "decode_tokens": 3, "decode_ctx_tokens": 50}
    kv, qo = 2 * 2 * 4 * 2, 2 * 4 * 4 * 2
    # one full layer over the 50 live positions
    assert paged_decode_full.work(TINY, sl, 7) == (
        4 * 4 * 4 * 50, 50 * kv + 3 * qo)
    # three window layers, each token over a whole window of 6
    assert paged_decode_window.work(TINY, sl, 7) == (
        3 * 4 * 4 * 4 * 18, 3 * (18 * kv + 3 * qo))


def test_no_cell_that_reports_window_work_sends_a_prompt_below_the_window():
    """`paged_decode_window.work` counts a whole window a decode token:
    exact only where every context is at least the window."""
    cells = {w["name"]: w for w in MAN["workloads"]}
    configs = {c["name"]: c for c in MAN["configs"]}
    entry, = [m for m in MAN["per_layer"]
              if m["name"] == "paged_decode_window_roofline"]
    assert entry["workloads"]
    for name in entry["workloads"]:
        with open(os.path.join(harness.ROOT,
                               configs[cells[name]["config"]]["file"])) as f:
            cfg = json.load(f)
        mix = harness.load_json("traffic", cells[name]["traffic"] + ".json")
        assert mix["prompt_tokens"]["min"] >= cfg["sliding_window"], name


def _r(name, t0, dur, **attrs):
    return (name, t0, t0 + dur, None, attrs)


def test_span_attr_ratio_on_a_hand_written_log():
    log = [_r("serving.decode.run", 99.0, 0.1, moe_max_load=50,
              moe_local_picks=1),           # set-up: not read
           _r("serving.decode.run", 110.0, 0.1, moe_max_load=3,
              moe_local_picks=8, kv_bytes_held=4096, live_tokens=2),
           _r("serving.chunk.run", 110.2, 0.1, moe_max_load=99,
              moe_local_picks=99),          # another span: not read
           _r("serving.decode.run", 111.0, 0.1, moe_max_load=5,
              moe_local_picks=8, kv_bytes_held=8192, live_tokens=4)]
    p = {"phase": "window", "spans": ["serving.decode.run"],
         "num": "moe_max_load", "den": "moe_local_picks", "scale": 16.0}
    assert span_attr_ratio.compute(p, log, 0.0, 110.0, 120.0) == 8.0
    kb = {"phase": "window", "spans": ["serving.decode.run"],
          "num": "kv_bytes_held", "den": "live_tokens", "scale": 1 / 1024}
    assert span_attr_ratio.compute(kb, log, 0.0, 110.0, 120.0) == 2.0
    # an empty phase, a cut log, and spans without the attributes (a
    # program from before them): nothing, never 0
    assert span_attr_ratio.compute(p, log, 0.0, 130.0, 140.0) is None
    assert span_attr_ratio.compute(p, log, 110.5, 110.0, 120.0) is None
    bare = [_r("serving.decode.run", 110.0, 0.1, active=2)]
    assert span_attr_ratio.compute(p, bare, 0.0, 110.0, 120.0) is None


def test_longdoc_mix_quantiles():
    mix = harness.load_json("traffic", "longdoc-open-loop.json")
    cfg = harness.load_json("configs", "command-a-plus-05-2026.json")
    n = 4000
    prompts = traffic_gen.sizes(mix["prompt_tokens"], n)
    outs = traffic_gen.sizes(mix["output_tokens"], n)
    assert prompts.min() == 4096 >= cfg["sliding_window"]
    assert prompts.max() == 14336
    assert abs(np.median(prompts) - 6144) <= 8
    assert 6800 < prompts.mean() < 6950       # the clips pull it under 7.2k
    assert outs.min() == 16 and outs.max() == 512
    assert abs(np.median(outs) - 128) <= 1 and 170 < outs.mean() < 195
    assert (prompts.max() + outs.max() <= mix["max_total_tokens"]
            == mix["engine"]["max_model_len"]
            == cfg["max_position_embeddings"])
    reqs = traffic_gen.serve_requests(mix, 3, 51.0, cfg["vocab_size"])
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * 0.93 * 51)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 16384
               for r in reqs)


def test_config_file_copies_the_catalog_row_and_states_its_cuts():
    cfg = harness.load_json("configs", "command-a-plus-05-2026.json")
    pub = cfg["published"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "max_position_embeddings"}
    assert cfg["num_hidden_layers"] == {"serve": 4}
    assert fam.layer_kinds(cfg, 4) == ["sliding_attention"] * 3 + [
        "full_attention"]
    ep = cfg["expert_parallel"]
    assert (ep["chips"] * cfg["num_experts"] == ep["experts_total"]
            == pub["num_experts"])
    assert {"shared_expert_combination_strategy", "routed_scaling",
            "sliding_window", "intermediate_size", "first_k_dense_replace",
            "initializer", "vision_tower"} <= set(cfg["assumed"])


# ------------------------------------------------------- the cell, tiny

def _tiny_ctx(seed=2 ** 31 + 7, trace=0):
    res = harness.resolve(MAN, CELL)
    cfg = dict(res["cfg"])
    cfg.update(hidden_size=64, intermediate_size=32, num_attention_heads=8,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               num_experts=4, num_experts_per_tok=4, num_shared_experts=2,
               expert_parallel={"chips": 4, "rank": 1, "experts_total": 16},
               sliding_window=16, max_position_embeddings=128,
               dtype="float32", num_hidden_layers={"serve": 4})
    mix = copy.deepcopy(res["mix"])
    mix["arrivals"]["rate_per_s"] = 3.0
    mix["prompt_tokens"].update(median=40, min=16, max=90)
    mix["output_tokens"].update(median=10, min=4, max=30)
    mix.update(max_total_tokens=128, trace_slice_s=1.0,
               engine={"max_slots": 4, "max_model_len": 128,
                       "kv_block_size": 8, "chunked_prefill_tokens": 16})
    mix["check"].update(token_gap_sigma=0.02, off_share_limit=0.02)
    res["cfg"], res["mix"] = cfg, mix
    ctx = harness.Context(CELL, seed, 3.0, trace, res, time.time(),
                          require_tpu=False)
    ctx.trace_planes, ctx.peaks = CPU_PLANES, CPU_PEAKS
    return ctx


@pytest.fixture(scope="module")
def traced():
    return harness.run_cell(_tiny_ctx(trace=1), precisions=("f32", "fp8"))


def test_the_cell_runs_through_the_harness(traced):
    out = traced
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == round(3.0 * 0.93 * 3.0)
    got = out["metrics"]
    # every accepted serve metric the cell was appended to, and the two
    # span metrics PR 31 added; no decode kernel runs on the CPU, so the
    # two rooflines stay silent. Since PR 34 this cell holds `tpot_p95_ms`
    # to no bound (its p95 gap stands on an edge between two kinds of
    # tick): the metrics that moved it are here under `.ttft` names, and
    # the gap itself per layer as `decode_gap_p95_ms`
    for name in ("queue_wait_p50_ms", "slot_occupancy", "decode_tick_ms.ttft",
                 "step_mfu.serve.ttft", "device_idle.serve.ttft",
                 "decode_run_ms.ttft", "chunk_prefill_ms_per_ktok",
                 "engine_host_share.ttft", "step_host_ms_p95.serve.ttft",
                 "decode_gap_p95_ms", "moe_expert_imbalance",
                 "kv_bytes_per_live_token"):
        assert got[name]["value"] > 0, name
    assert not {"decode_tick_ms", "step_mfu.serve", "decode_run_ms",
                "tpot_p95_ms"} & set(got)
    assert "paged_decode_full_roofline" not in got
    assert "paged_decode_window_roofline" not in got
    assert "paged_decode_roofline" not in got
    # 4 held experts: the fullest holds at least a quarter of the picks
    assert 1.0 <= got["moe_expert_imbalance"]["value"] * 4 / 16 <= 4.0
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_the_fp8_control_comes_out_not_correct(traced):
    c = traced["compared"]
    served = c["served_tokens_off_share"]
    control = c["control_fp8.served_tokens_off_share"]
    assert served["value"] <= served["limit"] < control["value"]
    assert c["served_logit_gap_sigma"]["value"] \
        <= c["served_logit_gap_sigma"]["limit"]
    assert traced["controls_correct"] == {"control_fp8": False}


def test_the_sparse_check_counts_the_tokens_that_are_off():
    """`serve_sparse.check` on a reference written by hand: 3 requests,
    10 tokens; logits of standard deviation 1 whose best is token 0."""
    from types import SimpleNamespace

    from benchmark.drivers import serve_sparse

    ref_row = np.array([1.5, 1.47, 1.0, -1.0, -1.5, -1.47])
    ref_row = ref_row / ref_row.std()
    gap = {t: (ref_row[0] - ref_row[t]) for t in range(6)}
    low_row = np.roll(ref_row, 2)           # the control's best is token 2

    class Fam:
        depth = staticmethod(lambda cfg, role: 1)
        weight_spec = staticmethod(lambda cfg, layers: [])

        @staticmethod
        def reference_rows(cfg, layers, w, ids, rows, precision):
            row = ref_row if precision == "f32" else low_row
            return np.tile(row, (len(rows), 1))

    def book(prompt, tokens):
        return SimpleNamespace(prompt=np.arange(prompt), tokens=tokens)

    def over(comps):
        return [c["name"] for c in comps
                if not c.get("at_least") and c["value"] > c["limit"]]

    # the longest first, then both others: 7 tokens on the best, 2 within
    # 0.05 sigma of it, 1 at half a sigma
    done = [book(3, [0, 0, 1]), book(9, [0, 0, 0, 2]), book(5, [1, 0, 0])]
    ctx = SimpleNamespace(
        family=Fam, cfg={}, seed=5,
        mix={"max_total_tokens": 16,
             "check": {"requests": 3, "token_gap_sigma": 0.05,
                       "off_share_limit": 0.15, "gap_sigma_limit": 1.5}})
    comps = serve_sparse.check(ctx, {"done": done}, ("f32", "fp8"))
    by = {c["name"]: c for c in comps}
    assert gap[1] < 0.05 < gap[2]
    assert by["served_tokens_off_share"]["value"] == pytest.approx(0.1)
    assert by["served_tokens_off_share"]["tokens"] == 10
    assert by["served_logit_gap_sigma"]["value"] == pytest.approx(gap[2])
    assert by["control_fp8.served_tokens_off_share"]["value"] == 1.0
    assert harness.judge(comps) is True
    assert harness.control_verdicts(comps) == {"control_fp8": False}
    # two tokens of ten off: over the share's limit, the widest gap is not
    done[0].tokens[0] = 2
    comps = serve_sparse.check(ctx, {"done": done})
    assert harness.judge(comps) is False
    assert over(comps) == ["served_tokens_off_share"]
    # one token from foreign state: several sigma, the share's limit holds
    done[0].tokens[0] = 0
    done[1].tokens[3] = 4
    comps = serve_sparse.check(ctx, {"done": done})
    assert over(comps) == ["served_logit_gap_sigma"]


def test_untraced_run_reports_the_end_to_end_metrics():
    out = harness.run_cell(_tiny_ctx(seed=11))
    assert out["correct"] is True
    assert {"serve_tokens_per_s", "ttft_p70_ms",
            "setup_s"} == set(out["metrics"])


def test_new_metric_files_name_readers_that_exist():
    for name in ("paged_decode_full_roofline",
                 "paged_decode_window_roofline", "moe_expert_imbalance",
                 "kv_bytes_per_live_token"):
        spec = harness.load_json("metrics", name + ".json")
        importlib.import_module(f"benchmark.readers.{spec['reader']}")
        entry = [m for m in MAN["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL]
    assert os.path.exists(os.path.join(
        harness.HERE, "traffic", "longdoc-open-loop.json"))
