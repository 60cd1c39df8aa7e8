"""The yardstick's arithmetic against hand arithmetic at tiny shapes:
operations and bytes of the kernels' work, the model FLOP functions, the
readers that divide them by time and peaks."""
import numpy as np
import pytest

from benchmark.families import gpt, llama
from benchmark.kernels import flash_bwd, flash_fwd, paged_decode
from benchmark.readers import (counter_ratio, device_idle, kernel_roofline,
                               percentile, rate, step_mfu)

#: 2 heads over 1 KV head of size 4, hidden 8, FFN 16, vocab 10
LL = {"family": "llama", "hidden_size": 8, "intermediate_size": 16,
      "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
      "vocab_size": 10}
GP = {"family": "gpt", "n_embd": 8, "n_head": 2, "n_inner": 32,
      "vocab_size": 10}


class Ctx:
    cfg = LL
    cell = {"chips": 1}
    peaks = {"bf16_flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}


def test_llama_matmul_params_and_train_flops():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3x(8x16) = 576 a layer
    assert llama.matmul_params(LL, 2) == 2 * 576
    assert llama.head_params(LL) == 80
    # batch 1, seq 3: 3 tokens; causal pairs 1+2+3 = 6
    dense = 6 * (2 * 576 + 80) * 3
    attn = 3 * 2 * (4 * 2 * 4 * 6)
    assert llama.train_flops_per_step(LL, 2, 1, 3) == dense + attn


def test_gpt_matmul_params_and_train_flops():
    # qkv 8x24, out 8x8, fc_in 8x32, fc_out 32x8 = 768 a layer
    assert gpt.matmul_params(GP, 1) == 768
    dense = 6 * (768 + 80) * 4
    attn = 3 * 1 * (4 * 8 * (2 * 3))        # batch 2, seq 2: 2 x (1+2) pairs
    assert gpt.train_flops_per_step(GP, 1, 2, 2) == dense + attn


def test_serve_flops():
    # 5 tokens through 1 layer, 2 logit rows, 7 attended keys in all
    assert llama.serve_flops(LL, 1, 5, 2, 7) \
        == 2 * 576 * 5 + 2 * 80 * 2 + 4 * 2 * 4 * 7


@pytest.mark.parametrize("cfg,heads,kv", [(LL, 2, 1), (GP, 2, 2)])
def test_flash_fwd_and_bwd_work(cfg, heads, kv):
    sl = {"batch": 2, "seq": 3}
    pairs = 2 * (1 + 2 + 3)
    flops = 4 * heads * 4 * pairs
    nbytes = 2 * 2 * 3 * 4 * (2 * heads + 2 * kv)
    assert flash_fwd.work(cfg, sl, 1) == (flops, nbytes)
    assert flash_fwd.work(cfg, sl, 4) == (4 * flops, 4 * nbytes)
    # a dq call and a dkv call are ONE backward: 5 matmuls to forward's 2
    assert flash_bwd.work(cfg, sl, 2) == (2.5 * flops, 2.5 * nbytes)


def test_paged_decode_work_reads_live_kv_only():
    # 3 decode tokens that attended 10 live positions in all, 2 layers
    sl = {"decode_tokens": 3, "decode_ctx_tokens": 10, "layers": 2}
    flops, nbytes = paged_decode.work(LL, sl)
    assert flops == 2 * (4 * 2 * 4 * 10)
    kv = 2 * 1 * 4 * 2                      # K and V, one head of 4, bf16
    assert nbytes == 2 * (10 * kv + 3 * (2 * 2 * 4 * 2))


def test_kernel_roofline_reader_and_silence():
    ms = 1_000_000
    rec = {"slice": {"batch": 2, "seq": 3},
           "trace": {"devices": 1, "ops": {"d0": [
               ("flash_fwd.1", 0, 500 * ms), ("fusion.2", 0, 100 * ms),
               ("flash_fwd.1", 600 * ms, 500 * ms)]}}}
    params = {"events": ["flash_fwd"], "work": "flash_fwd"}
    flops, nbytes = flash_fwd.work(LL, rec["slice"], 2)
    least = max(flops / 1000.0, nbytes / 100.0)      # bytes bound here
    assert least == nbytes / 100.0
    assert kernel_roofline.read(params, rec, Ctx) \
        == pytest.approx(100.0 * least / 1.0)
    # a kernel that left the path leaves its roofline silent, never 0
    gone = {"events": ["no_such_kernel"], "work": "flash_fwd"}
    assert kernel_roofline.read(gone, rec, Ctx) is None
    assert kernel_roofline.read(params, {"counters": {}}, Ctx) is None


def test_step_mfu_idle_and_plain_readers():
    rec = {"slice": {"model_flops": 500.0, "seconds": 2.0},
           "trace": {"busy_s": 1.5, "window_s": 2.0},
           "window_s": 4.0, "counts": {"tokens": 100.0},
           "samples": {"ttft_ms": [1.0, 2.0, 3.0, 4.0, 5.0], "none": []},
           "counters": {"decode_time_s": 3.0, "steps": 60.0, "zero": 0.0}}
    assert step_mfu.read({}, rec, Ctx) == pytest.approx(25.0)
    assert device_idle.read({}, rec, Ctx) == pytest.approx(25.0)
    assert rate.read({"count": "tokens"}, rec, Ctx) == 25.0
    assert percentile.read({"samples": "ttft_ms", "q": 50}, rec, Ctx) == 3.0
    assert percentile.read({"samples": "none", "q": 50}, rec, Ctx) is None
    assert counter_ratio.read({"num": "decode_time_s", "den": "steps",
                               "scale": 1000.0}, rec, Ctx) == 50.0
    assert counter_ratio.read({"num": "steps", "den": "zero"}, rec,
                              Ctx) is None
    assert step_mfu.read({}, {}, Ctx) is None
    assert device_idle.read({}, {}, Ctx) is None


@pytest.mark.parametrize("q", [50, 70, 90])
def test_harrell_davis_percentile(q):
    """A weighted mean of the order statistics around the percentile:
    weights that sum to one, the value itself on equal samples, the
    median on symmetric ones, and less swing than one order statistic."""
    from benchmark.readers import percentile_hd

    assert percentile_hd.estimate([7.0] * 31, q / 100) == pytest.approx(7.0)
    x = np.arange(31.0)
    got = percentile_hd.estimate(x, q / 100)
    assert abs(got - np.percentile(x, q)) < 0.5
    if q == 50:
        assert got == pytest.approx(15.0)
    rng = np.random.default_rng(q)
    base = np.linspace(300, 1500, 31)
    runs = [base + rng.uniform(0, 120, 31) for _ in range(200)]
    assert np.std([percentile_hd.estimate(r, q / 100) for r in runs]) \
        < np.std([np.percentile(r, q) for r in runs])
    assert percentile_hd.read({"samples": "ttft_ms", "q": q},
                              {"samples": {}}, None) is None
