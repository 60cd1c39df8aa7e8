"""What the Qwen3-Next configuration adds to the benchmark, at tiny shapes
on the CPU: the `qwen3_next` family's counts against hand arithmetic and
against the published 80B, the work of a decode over the full layers'
K/V pages (`paged_decode_hybrid`), the configuration file's cuts, the
mix, and the cell itself driven through the harness (the look for a chip
skipped)."""
import copy
import json
import time

import numpy as np
import pytest

from benchmark import harness, traffic_gen
from benchmark.families import qwen3_next as fam
from benchmark.kernels import gated_delta_decode, paged_decode_full, \
    paged_decode_hybrid

CELL = "qwen3-next-80b-a3b-instruct.serve-longctx"
MAN = harness.manifest()
# accepted metrics whose own tests hold their cell lists fixed, so the cell
# does not join them
UNLISTED = ("chunk_ms_per_mpair", "gated_delta_decode_roofline",
            "decode_ms_per_kstate_slot", "moe_experts_read_share",
            "decode_ahead_share")
CPU_PLANES = {"device_prefix": "/host:CPU", "ops_lines": ("tf_XLA",)}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

#: hidden 8; linear: 2 key heads and 4 value heads of 2, conv 4; full: 4
#: heads of 4 on 2 KV heads, 2 of them rotated; experts of 16 (shared
#: 12): 2 of 8 held (rank 1 of 4), top-4; vocab 10; layers (linear,
#: linear, linear, full) x experts
TINY = {"name": "tiny", "family": "qwen3_next", "hidden_size": 8,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 12,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
        "partial_rotary_factor": 0.5, "full_attention_interval": 4,
        "vocab_size": 10, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 2,
        "linear_value_head_dim": 2, "linear_conv_kernel_dim": 4,
        "num_experts": 2, "num_experts_per_tok": 4, "rms_norm_eps": 1e-6,
        "expert_parallel": {"chips": 4, "rank": 1, "experts_total": 8}}


def test_matmul_params_count_each_kind_of_layer():
    # linear: qkvz 8 x (2*2*2 + 2*4*2) = 192, ba 8 x 8 = 64, conv 4 x
    # (2*2*2 + 4*2) = 64, out (4*2) x 8 = 64: 384
    assert fam.linear_params(TINY) == 384
    # full: q and gate 8 x 4*2*4 = 256, k and v 2 x 8 x 8 = 128, o 16 x 8
    assert fam.full_params(TINY) == 256 + 128 + 128
    assert fam.layer_kinds(TINY, 4) == [("linear", "experts")] * 3 + [
        ("full", "experts")]
    # an expert layer: shared 3 x 8 x 12 = 288, its gate 8, router 8 x 8,
    # 4 picks x 2/8 held = 1 expert of 3 x 8 x 16 = 384
    sparse = 288 + 8 + 64 + 384
    assert fam.matmul_params(TINY, 4) == 3 * (384 + sparse) + 512 + sparse


def test_serve_flops_by_hand():
    # 5 tokens, 2 logit rows, 100 attended pairs in the full layer: QK and
    # PV over 4 dims, 4 heads; the recurrence 7 x 4 x 2 x 2 a token in
    # each of 3 linear layers
    params = fam.matmul_params(TINY, 4)
    assert fam.serve_flops(TINY, 4, 5, 2, 100) == (
        2 * params * 5 + 2 * 80 * 2 + 2 * 4 * 8 * 100 + 3 * 112 * 5)


def test_paged_decode_hybrid_work_by_hand():
    """One full layer of four: K and V of 2 heads x 4 (32 B a position in
    bfloat16) for each of 50 attended positions, q in and o out of 3
    tokens, QK^T and PV 2 x 4 heads x 8 FLOPs a position. The dense
    family's module cannot read this family's (attention, FFN) kinds."""
    sl = {"layers": 4, "decode_tokens": 3, "decode_ctx_tokens": 50}
    assert paged_decode_hybrid.work(TINY, sl, 7) == (
        2 * 4 * 8 * 50, 50 * 32 + 3 * 2 * 4 * 4 * 2)
    assert paged_decode_full.work(TINY, sl) == (0, 0)
    # and the linear layers' decode step the GDN roofline reads
    assert gated_delta_decode.work(TINY, sl, 7)[0] == 3 * 3 * 7 * 16


def test_published_counts_agree_with_the_model_card():
    """79.67B total (80B-A3B published), the cut's 3,988.8M parameters and
    its 7.98 GB, from the configuration file's own numbers."""
    cfg = harness.load_json("configs", "qwen3-next-80b-a3b-instruct.json")
    pub = cfg["published"]
    assert round(fam.linear_params(cfg) / 1e6, 2) == 33.72
    assert round(fam.full_params(cfg) / 1e6, 2) == 27.26
    expert = 3 * 2048 * 512
    side = 2048 * 512 + 3 * 2048 * 512 + 2048       # router, shared, its gate
    assert round(expert / 1e6, 3) == 3.146 and round(side / 1e6, 2) == 4.20
    emb = 2 * fam.head_params(cfg)
    assert round(emb / 1e6, 1) == 622.3
    total = (36 * fam.linear_params(cfg) + 12 * fam.full_params(cfg)
             + 48 * (side + 512 * expert) + emb)
    assert round(total / 1e9, 2) == 79.67 and pub["num_experts"] == 512
    held = (3 * fam.linear_params(cfg) + fam.full_params(cfg)
            + 4 * (side + 256 * expert) + emb)
    assert round(held / 1e6, 1) == 3988.8
    spec = fam.weight_spec(cfg, 4)
    gains = 4 * 2 * 2048 + 3 * (32 + 32 + 128) + 2 * 256 + 2048
    assert sum(int(np.prod(s)) for _, s, _ in spec) == held + gains
    assert [n for n, _, _ in spec[:2]] == ["model.embed_tokens.weight",
                                           "lm_head.weight"]
    # the K/V pages: 16 slots x 131072 positions x 2 KV heads x 256 x K, V
    # in bfloat16; a slot's state: 3 layers x 32 x 128 x 128 float32
    assert 16 * 131072 * fam.kv_bytes_per_token_layer(cfg) == 4_294_967_296
    assert 3 * 4 * int(np.prod(fam.state_shape(cfg))) == 6_291_456


def test_config_file_copies_the_catalog_row_and_states_its_cuts():
    cfg = harness.load_json("configs", "qwen3-next-80b-a3b-instruct.json")
    pub = cfg["published"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "max_position_embeddings"}
    assert cfg["num_hidden_layers"] == {"serve": 4}
    assert fam.layer_kinds(cfg, 4) == [("linear", "experts")] * 3 + [
        ("full", "experts")]
    ep = cfg["expert_parallel"]
    assert (ep["chips"] * cfg["num_experts"] == ep["experts_total"]
            == pub["num_experts"] == 512)
    assert ep["experts_held"] == [0, 255] and ep["rank"] == 0
    assert cfg["vocab_size"] == pub["vocab_size"] == 151936
    assert cfg["max_position_embeddings"] == 131072
    assert {"mtp", "initializer", "grouped_layout", "gated_norm",
            "attention", "router"} <= set(cfg["assumed"])
    assert cfg["deployment"]
    conf = {c["name"]: c for c in MAN["configs"]}[
        "qwen3-next-80b-a3b-instruct"]
    assert conf["source"] == cfg["source"]
    assert conf["reduced"] == sorted(cfg["reduced"], key=conf["reduced"].index)
    hybrid = [m for m in MAN["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in hybrid] == ["paged_decode_hybrid_roofline"]
    by = {m["name"]: m for m in MAN["per_layer"]}
    assert not any(CELL in by[name]["workloads"] for name in UNLISTED)


def test_the_mix_keeps_its_lengths_and_one_long_early_request():
    """Prompts 16k-96k and outputs 128-2048 whatever the seed, the same
    schedule for every seed, and among the first half's requests one of
    64k or more with an answer short enough to finish in the window (the
    check's longest request is then one of 64k or more)."""
    mix = harness.load_json("traffic", "longctx-open-loop-hybrid.json")
    arr = mix["arrivals"]
    a, b = (traffic_gen.serve_requests(mix, seed, 51.0, 151936)
            for seed in (5, 2 ** 31 + 99))
    assert len(a) == round(arr["rate_per_s"] * arr["due_within"] * 51)
    for r in a:
        assert 128 <= r["max_new_tokens"] <= 2048
        assert 16384 <= len(r["prompt"]) <= 98304
        assert len(r["prompt"]) + r["max_new_tokens"] \
            <= mix["max_total_tokens"] <= mix["engine"]["max_model_len"]
    assert [(r["due_s"], len(r["prompt"])) for r in a] \
        == [(r["due_s"], len(r["prompt"])) for r in b]
    assert any(len(r["prompt"]) >= 65536 and r["due_s"] < 25
               and r["max_new_tokens"] <= 600 for r in a)
    assert mix["engine"]["max_slots"] == 16 \
        and mix["engine"]["max_model_len"] == 131072


# ------------------------------------------------------- the cell, tiny

def _tiny_ctx(seed=2 ** 31 + 7, trace=0):
    res = harness.resolve(MAN, CELL)
    cfg = dict(res["cfg"])
    cfg.update(hidden_size=64, moe_intermediate_size=32,
               shared_expert_intermediate_size=48, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=256,
               num_experts=4, num_experts_per_tok=3,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               expert_parallel={"chips": 4, "rank": 1, "experts_total": 16},
               max_position_embeddings=128, dtype="float32")
    mix = copy.deepcopy(res["mix"])
    mix["arrivals"]["rate_per_s"] = 3.0
    mix["prompt_tokens"].update(median=40, min=16, max=90)
    mix["output_tokens"].update(median=10, min=4, max=30)
    mix.update(max_total_tokens=128, trace_slice_s=1.0,
               engine={"max_slots": 4, "max_model_len": 128,
                       "kv_block_size": 8, "chunked_prefill_tokens": 16})
    # the tiny float32 program against the float32 reference: the cell's
    # own limits are the chip's (PERF.md)
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
    assert out["attempted"] == round(3.0 * 0.9 * 3.0)
    got = out["metrics"]
    # every accepted serve metric the cell was appended to that reads a
    # span or the host; no kernel runs on the CPU, so the rooflines stay
    # silent
    for name in ("queue_wait_p50_ms", "ttft_p90_ms",
                 "chunk_prefill_ms_per_ktok", "decode_tick_ms.ttft",
                 "decode_run_ms.ttft", "device_idle.serve.ttft",
                 "step_mfu.serve.ttft", "engine_host_share.ttft",
                 "setup_compile_s", "step_host_ms_p95.serve.ttft"):
        assert got[name]["value"] > 0, name
    assert not {"tpot_p95_ms", "paged_decode_hybrid_roofline",
                "slot_occupancy"} & set(got)
    assert not set(UNLISTED) & set(got)
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_the_fp8_control_is_read_beside_the_program(traced):
    """The control's numbers are judged apart from the program's. At
    hidden 64 the pre-norm residual stream is mostly the embedding and
    fp8 rounding moves few argmaxes, so its verdict is the chip's (PERF.md
    section 6): here it only has to read no better than the program."""
    c = traced["compared"]
    for name in ("served_tokens_off_share", "served_logit_gap_sigma"):
        assert c["control_fp8." + name]["value"] >= c[name]["value"]
    assert set(traced["controls_correct"]) == {"control_fp8"}


def test_untraced_run_reports_the_end_to_end_metrics():
    out = harness.run_cell(_tiny_ctx(seed=11))
    assert out["correct"] is True
    assert {"ttft_p70_ms", "setup_s"} == set(out["metrics"])
