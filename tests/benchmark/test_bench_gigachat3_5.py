"""What PR 40 adds to the benchmark, at tiny shapes on the CPU: the
`gigachat3_5` family's counts against hand arithmetic, the work of a
decode step of the gated delta rule, `decode_ms_per_kstate_slot` on a
hand-written span log, the configuration file's cuts, the mix (held to
`test_bench_traffic.py`'s rules), and the cell itself driven through the
harness (the look for a chip skipped)."""
import copy
import json
import re
import time

import numpy as np
import pytest

from benchmark import harness, traffic_gen
from benchmark.families import gigachat3_5 as fam
from benchmark.kernels import gated_delta_decode
from benchmark.readers import program_span

CELL = "gigachat3.5-432b-a28b.serve-longgen"
MAN = harness.manifest()
CPU_PLANES = {"device_prefix": "/host:CPU", "ops_lines": ("tf_XLA",)}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BIG_SEED = 2 ** 31 + 12345

#: hidden 8; linear: 2 key heads and 4 value heads of 2, conv 4; MLA: 2
#: heads of 4 + 2 (values 4), ranks 6 and 4; dense FFN 12; experts of 16:
#: 2 of 8 held (rank 1 of 4), top-4, 1 shared; vocab 10; layers
#: (linear, dense), (linear, experts), (full, experts)
TINY = {"name": "tiny", "family": "gigachat3_5", "hidden_size": 8,
        "intermediate_size": 12, "moe_intermediate_size": 16,
        "num_attention_heads": 2, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 6,
        "kv_lora_rank": 4, "vocab_size": 10, "first_k_dense_replace": 1,
        "full_attention_layers": [2], "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 2,
        "linear_value_head_dim": 2, "linear_conv_kernel_dim": 4,
        "n_routed_experts": 2, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "swiglu_limit": 10, "rms_norm_eps": 1e-6,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 8}}


def test_matmul_params_count_each_kind_of_layer():
    # linear: qkvz 8 x (2*2*2 + 2*4*2) = 8 x 24 = 192, ba 8 x 8 = 64,
    # conv 4 x (2*2*2 + 4*2) = 64, out (4*2) x 8 = 64: 384
    assert fam.linear_params(TINY) == 384
    # MLA: q_a 8x6 + q_b 6x(2x6) + kv_a 8x(4+2) + kv_b 4x(2x8) + o (2x4)x8
    # + the gate 8x(2x4) = 48 + 72 + 48 + 64 + 64 + 64 = 360
    assert fam.mla_params(TINY) == 360
    assert fam.layer_kinds(TINY, 3) == [("linear", "dense"),
                                        ("linear", "experts"),
                                        ("full", "experts")]
    # dense 3 x 8 x 12 = 288; an expert layer: shared 384, router 64,
    # 4 picks x 2/8 held = 1 expert of 384
    assert fam.matmul_params(TINY, 3) == 384 + 288 + 384 + 832 + 360 + 832


def test_serve_flops_by_hand():
    # 5 tokens, 2 logit rows, 100 attended pairs in the MLA layer, the
    # recurrence 7 x 4 x 2 x 2 FLOPs a token in each of 2 linear layers
    params = fam.matmul_params(TINY, 3)
    assert fam.serve_flops(TINY, 3, 5, 2, 100) == (
        2 * params * 5 + 2 * 80 * 2 + 2 * 2 * 10 * 100 + 2 * 112 * 5)


def test_gated_delta_decode_work_by_hand():
    sl = {"layers": 3, "decode_tokens": 3, "decode_ctx_tokens": 50}
    # 2 linear layers x 3 tokens; a step: the state 4 x 2 x 2 float32 in
    # and out, q, k (2 key heads of 2), v, o (4 value heads of 2), beta, g
    steps = 6
    assert gated_delta_decode.work(TINY, sl, 7) == (
        steps * 7 * 16, steps * 4 * (2 * 16 + 2 * 4 + 2 * 8 + 2 * 4))
    assert fam.state_shape(TINY) == (4, 2, 2)


def test_published_counts_agree_with_the_model_card():
    """430.5B total (432B-A28B published), the cut's 4,931M parameters and
    its 9.86 GB, from the configuration file's own numbers."""
    cfg = harness.load_json("configs", "gigachat3.5-432b-a28b.json")
    assert round(fam.linear_params(cfg) / 1e6, 2) == 235.86
    assert round(fam.mla_params(cfg) / 1e6, 2) == 159.84
    expert, dense = fam.expert_params(cfg), 3 * 7168 * 18432
    assert round(expert / 1e6, 2) == 44.04 and round(dense / 1e6, 2) == 396.36
    router = 7168 * 256
    emb = 2 * fam.head_params(cfg)
    assert round(emb / 1e6, 2) == 1838.68
    total = (3 * (fam.linear_params(cfg) + dense)
             + 27 * (fam.linear_params(cfg) + router + 257 * expert)
             + 10 * (fam.mla_params(cfg) + router + 257 * expert) + emb)
    assert round(total / 1e9, 1) == 430.5
    held = (fam.linear_params(cfg) + dense
            + 3 * (fam.linear_params(cfg) + router + 9 * expert)
            + fam.mla_params(cfg) + router + 9 * expert + emb)
    assert round(held / 1e6) == 4931 and round(2 * held / 1e9, 2) == 9.86
    spec = fam.weight_spec(cfg, 5)
    gains = 5 * 4 * 7168 + 4 * (64 + 64 + 128) + 1536 + 512 + 7168
    assert sum(int(np.prod(s)) for _, s, _ in spec) == held + gains
    assert [n for n, _, _ in spec[:2]] == ["model.embed_tokens.weight",
                                           "lm_head.weight"]
    # a slot's state: 4 linear layers x 64 x 128 x 128 float32 = 16.8 MB
    assert 4 * 4 * int(np.prod(fam.state_shape(cfg))) == 16_777_216


def _r(name, t0, dur, **attrs):
    return (name, t0, t0 + dur, None, attrs)


def test_decode_ms_per_kstate_slot_on_a_hand_written_log():
    log = [_r("serving.decode.run", 99.0, 9.0, state_slots=1),   # set-up
           _r("serving.decode.run", 110.0, 0.02, state_slots=256),
           _r("serving.decode.run", 111.0, 0.01, state_slots=128),
           _r("serving.chunk.run", 112.0, 0.5, state_tokens=2048)]
    m = harness.load_json("metrics", "decode_ms_per_kstate_slot.json")
    assert m["reader"] == "program_span"
    # 30 ms over 384 slot-layers advanced: 78.125 ms a thousand
    assert program_span.compute(m["params"], log, 0.0, 110.0, 120.0) \
        == pytest.approx(78.125)
    # a program from before the attribute (the parent): nothing, never 0
    bare = [_r("serving.decode.run", 110.0, 0.02, active=8)]
    assert program_span.compute(m["params"], bare, 0.0, 110.0,
                                120.0) is None
    roof = harness.load_json("metrics", "gated_delta_decode_roofline.json")
    assert roof == {"reader": "kernel_roofline",
                    "params": {"events": ["gated_delta_decode"],
                               "work": "gated_delta_decode"}}


def test_config_file_copies_the_catalog_row_and_states_its_cuts():
    cfg = harness.load_json("configs", "gigachat3.5-432b-a28b.json")
    pub = cfg["published"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
        "n_routed_experts", "max_position_embeddings"}
    assert cfg["num_hidden_layers"] == {"serve": 5}
    assert fam.layer_kinds(cfg, 5) == [("linear", "dense")] + [
        ("linear", "experts")] * 3 + [("full", "experts")]
    assert pub["full_attention_layers"][:2] == [3, 7] \
        and pub["num_hidden_layers"] == 40
    ep = cfg["expert_parallel"]
    assert (ep["chips"] * cfg["n_routed_experts"] == ep["experts_total"]
            == pub["n_routed_experts"])
    assert ep["experts_held"] == [0, 7] and ep["rank"] == 0
    assert cfg["vocab_size"] == pub["vocab_size"] == 128256
    assert {"gate_scale", "layernorm_gating_weight", "norm_type",
            "key_heads", "gated_attention", "swiglu_limit", "yarn",
            "router", "state_dtype", "initializer", "mtp"} \
        <= set(cfg["assumed"])
    assert cfg["deployment"] and pub["num_nextn_predict_layers"] == 2
    entries = [m for m in MAN["per_layer"] if m["workloads"] == [CELL]]
    assert {m["name"] for m in entries} == {"gated_delta_decode_roofline",
                                            "decode_ms_per_kstate_slot"}
    conf = {c["name"]: c for c in MAN["configs"]}["gigachat3.5-432b-a28b"]
    assert conf["source"] == cfg["source"]


def test_the_mix_offers_a_share_of_a_knee_that_was_swept():
    """`test_bench_traffic.py`'s rules for a serve mix: `rate_from` names
    the two swept rates the knee lies between, the mix offers 0.6-0.8 of
    the lower, and a 51 s window is the same requests for every seed."""
    mix = harness.load_json("traffic", "longgen-open-loop-gdn.json")
    arr = mix["arrivals"]
    low, high = (float(x) for x in
                 re.findall(r"of (\d+\.?\d*)", mix["rate_from"])[:2])
    assert low < high
    assert 0.6 * low <= arr["rate_per_s"] <= 0.8 * low + 1e-9
    a, b = (traffic_gen.serve_requests(mix, seed, 51.0, 128256)
            for seed in (5, BIG_SEED))
    assert len(a) == round(arr["rate_per_s"] * arr["due_within"] * 51)
    assert f"{len(a)} requests" in mix["rate_from"]
    for r in a:
        assert 256 <= r["max_new_tokens"] <= 2048
        assert 256 <= len(r["prompt"]) <= 6144
        assert len(r["prompt"]) + r["max_new_tokens"] \
            <= mix["max_total_tokens"] <= mix["engine"]["max_model_len"]
    assert [(r["due_s"], len(r["prompt"])) for r in a] \
        == [(r["due_s"], len(r["prompt"])) for r in b]
    assert 0 < a[0]["due_s"] and a[-1]["due_s"] < arr["due_within"] * 51
    assert mix["engine"] == {"max_slots": 64, "max_model_len": 8192,
                             "chunked_prefill_tokens": 512}
    assert mix["schedule_seed"] == 40 and arr["due_within"] == 0.93
    assert set(mix["check"]["why"]) == {"token_gap_sigma", "off_share_limit",
                                        "gap_sigma_limit"}


# ------------------------------------------------------- the cell, tiny

def _tiny_ctx(seed=2 ** 31 + 7, trace=0):
    res = harness.resolve(MAN, CELL)
    cfg = dict(res["cfg"])
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24,
               kv_lora_rank=32, vocab_size=256, n_routed_experts=4,
               num_experts_per_tok=4, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16,
               expert_parallel={"chips": 4, "rank": 1, "experts_total": 16},
               max_position_embeddings=128, dtype="float32",
               num_hidden_layers={"serve": 5})
    mix = copy.deepcopy(res["mix"])
    mix["arrivals"]["rate_per_s"] = 3.0
    mix["prompt_tokens"].update(median=40, min=16, max=90)
    mix["output_tokens"].update(median=10, min=4, max=30)
    mix.update(max_total_tokens=128, trace_slice_s=1.0,
               engine={"max_slots": 4, "max_model_len": 128,
                       "kv_block_size": 8, "chunked_prefill_tokens": 16})
    # at hidden 64 four linear layers carry the bfloat16 program 0.1 of a
    # logit's standard deviation off the float32 reference (measured; the
    # output norm of a linear layer renormalises v - S^T k, a difference,
    # and at these widths its input is below the norm's eps): 3-6% of the
    # tokens lie past 0.05 sigma, 0-3% past 0.2; the fp8 control 49% past
    # 0.2. The cell's own limits are the chip's (PERF.md, PR 40)
    mix["check"].update(token_gap_sigma=0.2, off_share_limit=0.1)
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
    # metric PR 40 adds; no decode kernel runs on the CPU, so its
    # roofline stays silent
    for name in ("queue_wait_p50_ms", "decode_tick_ms.ttft",
                 "step_mfu.serve.ttft", "device_idle.serve.ttft",
                 "decode_run_ms.ttft", "chunk_prefill_ms_per_ktok",
                 "engine_host_share.ttft", "step_host_ms_p95.serve.ttft",
                 "decode_gap_p95_ms", "decode_ms_per_kstate_slot"):
        assert got[name]["value"] > 0, name
    # `serve_tokens_per_s` and what moves it are not the cell's: a third of
    # its answer tokens belong to answers the close cuts (PERF.md, PR 40)
    assert not {"tpot_p95_ms", "paged_latent_decode_roofline",
                "slot_occupancy",
                "chunk_ms_per_mpair", "moe_expert_imbalance.held8",
                "kv_bytes_per_live_token.latent",
                "gated_delta_decode_roofline"} & set(got)
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_the_fp8_control_comes_out_not_correct(traced):
    c = traced["compared"]
    served = c["served_tokens_off_share"]
    control = c["control_fp8.served_tokens_off_share"]
    assert served["value"] <= served["limit"] < control["value"]
    assert traced["controls_correct"] == {"control_fp8": False}


def test_untraced_run_reports_the_end_to_end_metrics():
    out = harness.run_cell(_tiny_ctx(seed=11))
    assert out["correct"] is True
    assert {"ttft_p70_ms", "setup_s"} == set(out["metrics"])
