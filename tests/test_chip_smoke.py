"""chip_smoke.py rehearsed on the CPU at tiny sizes (on-chip-measurement
guide, section 2 steps 1 and 2): the phases' control flow, checks and
record shapes are held here so that a later PR cannot break the script
without a chip noticing first. What only a chip can show — that a kernel
is a `tpu_custom_call` in the compiled program, HBM in use — is steered in
the test, never through an option of the script; tests/test_chip_compile.py
asks the chip's compiler about the kernels.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

#: head_dim stays 128 (the size that routes to every kernel on the chip);
#: everything else is as small as the phases allow
TINY = cs.Sizes(
    model=dict(vocab_size=512, hidden_size=256, intermediate_size=512,
               num_attention_heads=2),
    train_layers=2, serve_layers=2, batch=2, seq=256, steps=4,
    requests=((16, 8), (16, 8), (24, 12), (50, 16), (64, 8), (300, 12)),
    max_model_len=512)


@pytest.fixture
def steered(monkeypatch):
    """No Mosaic custom call exists off the chip and the CPU reports no
    memory: both checks see what a chip would show them."""
    asked = []
    monkeypatch.setattr(cs, "require_kernels",
                        lambda text, names: asked.append(tuple(names))
                        or {n: 1 for n in names})
    monkeypatch.setattr(cs, "hbm", lambda devices=None: [
        {"in_use_gb": 1.0, "peak_gb": 1.0, "limit_gb": 16.0}
        for _ in devices or [0]])
    return asked


def test_fails_without_an_accelerator(capsys, monkeypatch):
    """The contract's first clause: no accelerator, no `ok` line, and an
    exit code other than 0 (here: the exception that ends the process)."""
    from paddle_tpu.core import compile_cache

    # main() would turn the persistent cache on for the rest of this worker
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        compile_cache.cache_dir)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_train_phase(steered):
    out = cs.phase_train(TINY, 0, cs.CacheCounter())
    json.dumps(out)                                   # one JSON line
    assert steered == [cs.TRAIN_KERNELS]
    assert out["model"]["head_dim"] == 128 and out["model"]["of"] == 32
    assert len(out["losses"]) == TINY.steps
    assert out["logits_vs_f32_reference"]["rel_l2"] <= cs.LOGITS_REL_L2


def test_serve_phase(steered):
    out = cs.phase_serve(TINY, 0, cs.CacheCounter())
    json.dumps(out)
    assert out["requests"] == len(TINY.requests)
    assert out["post_warmup_compiles"] == 0
    assert out["decode_kernels"] and all(
        names == ("paged_decode",) for names in steered)
    assert out["vs_static_engine"]["compared"] == 2


def test_four_chip_phase(steered):
    """On four of the suite's eight virtual CPU devices."""
    out = cs.phase_four_chips(TINY, 0, cs.CacheCounter())
    json.dumps(out)
    assert out["mesh"] == "data1xfsdp2xtp2"
    assert max(out["loss_rel_diff"]) <= cs.SHARDED_LOSS_REL
    assert out["collectives"]["all-gather"] > 0


@pytest.mark.parametrize("losses", [[2.0, 1.9, float("nan")],
                                    [2.0, 2.0, 1.9], [2.0, 1.9, 1.95]])
def test_check_losses_refuses(losses):
    with pytest.raises(AssertionError):
        cs.check_losses(losses)


def test_kernels_in_reads_the_pallas_name():
    def call(op):
        return ('%x.1 = bf16[8]{0} custom-call(%a), custom_call_target='
                '"tpu_custom_call", metadata={op_name="jit(pure)/' + op +
                '/pallas_call" stack_frame_id=2}')

    lowered = ('%0 = stablehlo.custom_call @tpu_custom_call(%arg0) '
               '{backend_config = "{}", kernel_name = "swiglu_bwd", '
               'operand_layouts = []} : (tensor<8xbf16>) -> tensor<8xbf16>')
    text = "\n".join([call("flash_fwd"), call("jvp(flash_fwd)"),
                      call("transpose(jvp(flash_bwd_dq))"), lowered,
                      '%y.2 = f32[8]{0} custom-call(%a), custom_call_target='
                      '"Sharding"'])
    assert cs.kernels_in(text) == {"flash_fwd": 2, "flash_bwd_dq": 1,
                                   "swiglu_bwd": 1}
    with pytest.raises(AssertionError, match="rope_qk_fwd"):
        cs.require_kernels(text, ("flash_fwd", "rope_qk_fwd"))
