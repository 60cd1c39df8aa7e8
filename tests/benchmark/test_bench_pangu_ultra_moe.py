"""What PR 37 adds to the benchmark, at tiny shapes on the CPU: the
`pangu_ultra_moe` family's counts against hand arithmetic, the work of
decode over a latent cache, `chunk_ms_per_mpair` and
`moe_expert_imbalance.held8` on a hand-written span log, the
configuration file's cuts, the mix (longdoc-open-loop's lengths to the
digit), and the cell itself driven through the harness (the look for a
chip skipped)."""
import copy
import json
import time

import numpy as np
import pytest

from benchmark import harness, traffic_gen
from benchmark.families import pangu_ultra_moe as fam
from benchmark.kernels import paged_latent_decode
from benchmark.readers import program_span, span_attr_ratio

CELL = "openpangu-ultra-moe-718b.serve-longdoc"
MAN = harness.manifest()
CPU_PLANES = {"device_prefix": "/host:CPU", "ops_lines": ("tf_XLA",)}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

#: hidden 8, 2 heads of 4 + 2 (values 4), ranks 6 and 4, dense FFN 12,
#: experts of width 16: 2 of 8 held (rank 1 of 4), top-4, 1 shared, vocab 10
TINY = {"name": "tiny", "family": "pangu_ultra_moe", "hidden_size": 8,
        "intermediate_size": 12, "moe_intermediate_size": 16,
        "num_attention_heads": 2, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 6,
        "kv_lora_rank": 4, "vocab_size": 10, "first_k_dense_replace": 1,
        "n_routed_experts": 2, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-5,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 8}}


def test_matmul_params_count_the_published_form_once():
    # attention: q_a 8x6 + q_b 6x(2x6) + kv_a 8x(4+2) + kv_b 4x(2x8)
    # + o (2x4)x8 = 48 + 72 + 48 + 64 + 64 = 296
    assert fam.attn_params(TINY) == 296
    # one expert 3 x 8 x 16 = 384; the dense FFN 3 x 8 x 12 = 288; the
    # router 8 x 8 = 64; routed 4 picks x 2/8 held = 1 expert
    assert fam.expert_params(TINY) == 384
    assert fam.layer_kinds(TINY, 3) == ["dense", "experts", "experts"]
    assert fam.matmul_params(TINY, 3) == 3 * 296 + 288 + 2 * (
        384 + 64 + 384)
    assert fam.head_params(TINY) == 80


def test_serve_flops_by_hand():
    # 5 tokens, 2 logit rows, 100 attended pairs in each of 3 layers:
    # 2 x heads x (4 + 2 + 4) FLOPs a pair, neither the re-expansion of
    # a cached row nor the absorbed form's wider products
    params = 3 * 296 + 288 + 2 * 832
    assert fam.serve_flops(TINY, 3, 5, 2, 100) == (
        2 * params * 5 + 2 * 80 * 2 + 3 * 2 * 2 * 10 * 100)


def test_latent_decode_work_by_hand():
    sl = {"layers": 3, "decode_tokens": 3, "decode_ctx_tokens": 50}
    # a position: (4 + 2) values x 2 B read once; 2 heads x (6 + 4) x 2
    # FLOPs; a token: its absorbed query (6 a head) in, 4 a head out
    assert paged_latent_decode.work(TINY, sl, 7) == (
        3 * 2 * 2 * 10 * 50, 3 * (50 * 12 + 3 * 2 * 10 * 2))
    assert fam.kv_bytes_per_token_layer(TINY) == 12


def test_published_counts_agree_with_the_model_card():
    """719B total, about 40B active, and the cut's 10.95 GB, from the
    configuration file's own numbers."""
    cfg = harness.load_json("configs", "openpangu-ultra-moe-718b.json")
    pub = cfg["published"]
    assert round(fam.attn_params(cfg) / 1e6, 2) == 196.58
    expert, dense = fam.expert_params(cfg), 3 * 7680 * 18432
    assert round(expert / 1e6, 2) == 47.19
    assert round(dense / 1e6, 2) == 424.67
    outside = fam.attn_params(cfg) + expert + 7680 * 256
    emb = 2 * fam.head_params(cfg)
    total = 58 * (outside + 256 * expert) \
        + 3 * (fam.attn_params(cfg) + dense) + emb
    active = 58 * (outside + 8 * expert) \
        + 3 * (fam.attn_params(cfg) + dense) + emb
    assert round(total / 1e9) == 719 and round(active / 1e9) == 40
    assert pub["num_hidden_layers"] == 61 and pub["n_routed_experts"] == 256
    held = (fam.attn_params(cfg) + dense) + 4 * (outside + 8 * expert) + emb
    assert round(2 * held / 1e9, 2) == 10.95
    spec = fam.weight_spec(cfg, 5)
    gains = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert sum(int(np.prod(s)) for _, s, _ in spec) == held + gains
    assert [n for n, _, _ in spec[:2]] == ["model.embed_tokens.weight",
                                           "lm_head.weight"]
    assert max(int(np.prod(s)) for _, s, _ in spec[2:]) < 0.15e9


def _r(name, t0, dur, **attrs):
    return (name, t0, t0 + dur, None, attrs)


def test_the_two_span_metrics_on_a_hand_written_log():
    log = [_r("serving.chunk.run", 99.0, 9.0, attn_pairs=1),    # set-up
           _r("serving.chunk.run", 110.0, 0.06, attn_pairs=2_000_000),
           _r("serving.chunk.run", 111.0, 0.03, attn_pairs=1_000_000),
           _r("serving.decode.run", 112.0, 0.5, attn_pairs=7,
              moe_max_load=3, moe_local_picks=8),
           _r("serving.decode.run", 113.0, 0.5, moe_max_load=5,
              moe_local_picks=8)]
    pair = harness.load_json("metrics", "chunk_ms_per_mpair.json")
    assert pair["reader"] == "program_span"
    # 90 ms over 3 million attended pairs: 30 ms a million
    assert program_span.compute(pair["params"], log, 0.0, 110.0, 120.0) \
        == pytest.approx(30.0)
    held = harness.load_json("metrics", "moe_expert_imbalance.held8.json")
    assert held["reader"] == "span_attr_ratio"
    # 8 held experts x (3 + 5) / (8 + 8): the fullest's share of a tick
    assert span_attr_ratio.compute(held["params"], log, 0.0, 110.0,
                                   120.0) == pytest.approx(4.0)
    # a program from before the attribute (the parent): nothing, never 0
    bare = [_r("serving.chunk.run", 110.0, 0.06, tokens=512)]
    assert program_span.compute(pair["params"], bare, 0.0, 110.0,
                                120.0) is None
    twin = harness.load_json("metrics", "kv_bytes_per_live_token.latent.json")
    assert twin == harness.load_json("metrics",
                                     "kv_bytes_per_live_token.json")


def test_the_mix_offers_longdocs_lengths_to_the_digit():
    mine = harness.load_json("traffic", "longdoc-open-loop-mla.json")
    theirs = harness.load_json("traffic", "longdoc-open-loop.json")
    for key in ("prompt_tokens", "output_tokens", "max_total_tokens",
                "prompts", "role", "trace_slice_s"):
        assert mine[key] == theirs[key], key
    assert mine["arrivals"]["due_within"] == 0.93
    assert mine["schedule_seed"] == 37
    assert mine["engine"] == {"max_slots": 16, "max_model_len": 16384,
                              "chunked_prefill_tokens": 512}
    cfg = harness.load_json("configs", "openpangu-ultra-moe-718b.json")
    reqs = traffic_gen.serve_requests(mine, 2 ** 31 + 5, 51.0,
                                      cfg["vocab_size"])
    assert len(reqs) == round(mine["arrivals"]["rate_per_s"] * 0.93 * 51)
    assert all(len(r["prompt"]) + r["max_new_tokens"]
               <= cfg["max_position_embeddings"] for r in reqs)


def test_the_mix_offers_a_share_of_a_knee_that_was_swept():
    """As `test_bench_traffic.py` holds the accepted serve mixes: the two
    swept rates the knee lies between, 0.6-0.8 of the lower offered."""
    import re

    mix = harness.load_json("traffic", "longdoc-open-loop-mla.json")
    arr = mix["arrivals"]
    low, high = (float(x) for x in
                 re.findall(r"of (\d+\.?\d*)", mix["rate_from"])[:2])
    assert low < high
    assert 0.6 * low <= arr["rate_per_s"] <= 0.8 * low + 1e-9
    n = round(arr["rate_per_s"] * arr["due_within"] * 51)
    assert f"{n} requests" in mix["rate_from"]
    assert set(mix["check"]["why"]) == {"token_gap_sigma", "off_share_limit",
                                        "gap_sigma_limit"}


def test_config_file_copies_the_catalog_row_and_states_its_cuts():
    cfg = harness.load_json("configs", "openpangu-ultra-moe-718b.json")
    pub = cfg["published"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "max_position_embeddings"}
    assert cfg["num_hidden_layers"] == {"serve": 5}
    assert fam.layer_kinds(cfg, 5) == ["dense"] + ["experts"] * 4
    ep = cfg["expert_parallel"]
    assert (ep["chips"] * cfg["n_routed_experts"] == ep["experts_total"]
            == pub["n_routed_experts"])
    assert ep["experts_held"] == [0, 7] and ep["rank"] == 0
    assert {"router", "sandwich_norm", "rope", "softmax_scale",
            "n_shared_experts", "initializer", "mtp"} <= set(cfg["assumed"])
    assert "num_nextn_predict_layers" in pub
    entries = [m for m in MAN["per_layer"] if m["workloads"] == [CELL]]
    assert {m["name"] for m in entries} == {
        "paged_latent_decode_roofline", "chunk_ms_per_mpair",
        "moe_expert_imbalance.held8", "kv_bytes_per_live_token.latent"}


# ------------------------------------------------------- the cell, tiny

def _tiny_ctx(seed=2 ** 31 + 7, trace=0):
    res = harness.resolve(MAN, CELL)
    cfg = dict(res["cfg"])
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24,
               kv_lora_rank=32, vocab_size=256, n_routed_experts=4,
               num_experts_per_tok=4,
               expert_parallel={"chips": 4, "rank": 1, "experts_total": 16},
               max_position_embeddings=128, dtype="float32",
               num_hidden_layers={"serve": 3})
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
    # every accepted serve metric the cell was appended to, and the span
    # metrics PR 37 adds; no decode kernel runs on the CPU, so its
    # roofline stays silent
    for name in ("queue_wait_p50_ms", "slot_occupancy", "decode_tick_ms.ttft",
                 "step_mfu.serve.ttft", "device_idle.serve.ttft",
                 "decode_run_ms.ttft", "chunk_prefill_ms_per_ktok",
                 "engine_host_share.ttft", "step_host_ms_p95.serve.ttft",
                 "decode_gap_p95_ms", "chunk_ms_per_mpair",
                 "moe_expert_imbalance.held8",
                 "kv_bytes_per_live_token.latent"):
        assert got[name]["value"] > 0, name
    assert not {"tpot_p95_ms", "moe_expert_imbalance",
                "kv_bytes_per_live_token",
                "paged_latent_decode_roofline"} & set(got)
    # bfloat16 rows (`weights.make`'s dtype) of 32 + 8 values padded to
    # 128 lanes, 3 layers; blocks of 8 positions are held whole and for
    # the answer to come, so a live token costs a little more
    row_kb = 3 * 128 * 2 / 1024
    assert row_kb <= got["kv_bytes_per_live_token.latent"]["value"] \
        < 1.5 * row_kb
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_the_fp8_control_comes_out_not_correct(traced):
    c = traced["compared"]
    served = c["served_tokens_off_share"]
    control = c["control_fp8.served_tokens_off_share"]
    assert served["value"] <= served["limit"] < control["value"]
    assert c["served_logit_gap_sigma"]["value"] \
        <= c["served_logit_gap_sigma"]["limit"]
    assert traced["controls_correct"] == {"control_fp8": False}


def test_untraced_run_reports_the_end_to_end_metrics():
    out = harness.run_cell(_tiny_ctx(seed=11))
    assert out["correct"] is True
    assert {"serve_tokens_per_s", "ttft_p70_ms",
            "setup_s"} == set(out["metrics"])
