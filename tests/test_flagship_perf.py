"""Round-6 flagship-perf machinery tests (ISSUE 1).

Covers the acceptance list: chunked fused CE numerics vs unchunked (both
chunk axes, ragged token counts, bf16), flash-resident remat-policy
gradient parity (+ the jaxpr proof that the policy keeps the forward flash
kernel out of the backward), long-seq autotune candidate validation and
cache hardening, the fused_momentum/adam interrupt-safe commit, and the
bench ladder's time-box contract.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.incubate.nn.functional import fused_linear_cross_entropy


def _plain_ce(h_np, w_np, lab_np, ignore_index=-100):
    h = paddle.to_tensor(h_np)
    h.stop_gradient = False
    w = paddle.to_tensor(w_np)
    w.stop_gradient = False
    loss = F.cross_entropy(h.matmul(w), paddle.to_tensor(lab_np),
                           reduction="mean", ignore_index=ignore_index)
    loss.backward()
    return float(loss), h.grad.numpy(), w.grad.numpy()


class TestChunkedFusedCE:
    """Sequence(token)-chunked fused CE vs the unchunked logits path."""

    @pytest.mark.parametrize("n,v,chunk", [(256, 1000, 128), (229, 1000, 64),
                                           (64, 50304, 64)])
    def test_token_chunk_matches_plain_f32(self, n, v, chunk):
        rs = np.random.RandomState(0)
        h_np = rs.randn(n, 64).astype("float32")
        w_np = (rs.randn(64, v) * 0.05).astype("float32")
        lab = rs.randint(0, v, (n,))
        lab[::7] = -100  # ignored rows excluded from mean AND grad
        lab_np = lab.astype("int64")
        ref_loss, ref_dh, ref_dw = _plain_ce(h_np, w_np, lab_np)

        h = paddle.to_tensor(h_np)
        h.stop_gradient = False
        w = paddle.to_tensor(w_np)
        w.stop_gradient = False
        loss = fused_linear_cross_entropy(h, w, paddle.to_tensor(lab_np),
                                          chunk_axis="tokens",
                                          token_chunk=chunk)
        loss.backward()
        assert abs(float(loss) - ref_loss) < 1e-5
        np.testing.assert_allclose(h.grad.numpy(), ref_dh, atol=2e-6)
        np.testing.assert_allclose(w.grad.numpy(), ref_dw, atol=2e-6)

    def test_token_chunk_matches_vocab_chunk(self):
        rs = np.random.RandomState(1)
        h_np = rs.randn(192, 32).astype("float32")
        w_np = (rs.randn(32, 1024) * 0.05).astype("float32")
        lab_np = rs.randint(0, 1024, (192,)).astype("int64")
        losses = {}
        for axis, kw in (("tokens", {"token_chunk": 64}),
                         ("vocab", {"chunk_size": 128})):
            h = paddle.to_tensor(h_np)
            w = paddle.to_tensor(w_np)
            losses[axis] = float(fused_linear_cross_entropy(
                h, w, paddle.to_tensor(lab_np), chunk_axis=axis, **kw))
        assert abs(losses["tokens"] - losses["vocab"]) < 1e-5

    def test_auto_axis_takes_token_path_for_50304(self):
        # GPT's 50304 has no usable multiple-of-128 divisor: auto must fuse
        # via the token axis instead of falling back to full logits
        from paddle_tpu.incubate.nn.functional.fused_loss import _best_chunk

        assert _best_chunk(50304, 8192) == 0
        assert _best_chunk(32000, 8192) == 6400
        rs = np.random.RandomState(2)
        h = paddle.to_tensor(rs.randn(32, 16).astype("float32"))
        w = paddle.to_tensor((rs.randn(16, 50304) * 0.05).astype("float32"))
        lab_np = rs.randint(0, 50304, (32,)).astype("int64")
        got = float(fused_linear_cross_entropy(h, w, paddle.to_tensor(lab_np),
                                               chunk_axis="auto"))
        ref, _, _ = _plain_ce(h.numpy(), w.numpy(), lab_np)
        assert abs(got - ref) < 1e-4

    def test_bf16_hidden_close_to_f32(self):
        rs = np.random.RandomState(3)
        h_np = rs.randn(128, 64).astype("float32")
        w_np = (rs.randn(64, 512) * 0.05).astype("float32")
        lab_np = rs.randint(0, 512, (128,)).astype("int64")
        ref, _, _ = _plain_ce(h_np, w_np, lab_np)
        h = paddle.to_tensor(h_np).astype("bfloat16")
        h.stop_gradient = False
        w = paddle.to_tensor(w_np).astype("bfloat16")
        w.stop_gradient = False
        loss = fused_linear_cross_entropy(h, w, paddle.to_tensor(lab_np),
                                          chunk_axis="tokens",
                                          token_chunk=128)
        loss.backward()
        assert abs(float(loss) - ref) / abs(ref) < 3e-2
        assert h.grad.dtype.name == "bfloat16"
        assert w.grad.dtype.name == "bfloat16"

    def test_all_labels_ignored_chunk(self):
        # a token chunk whose rows are all ignored must contribute nothing
        rs = np.random.RandomState(4)
        h = paddle.to_tensor(rs.randn(128, 16).astype("float32"))
        w = paddle.to_tensor((rs.randn(16, 256) * 0.1).astype("float32"))
        lab = rs.randint(0, 256, (128,))
        lab[64:] = -100  # second chunk fully ignored
        loss = fused_linear_cross_entropy(
            h, w, paddle.to_tensor(lab.astype("int64")),
            chunk_axis="tokens", token_chunk=64)
        ref, _, _ = _plain_ce(h.numpy(), w.numpy(), lab.astype("int64"))
        assert abs(float(loss) - ref) < 1e-5

    def test_gpt_loss_path_fused_matches_logits(self):
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=50304, hidden_size=32,
                        num_hidden_layers=1, num_attention_heads=2,
                        max_position_embeddings=64)
        m = GPTForCausalLM(cfg)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 50304, (2, 64)).astype("int64"))
        loss = m(ids, ids)
        logits = m(ids)
        ref = F.cross_entropy(logits.reshape([-1, 50304]), ids.reshape([-1]),
                              reduction="mean")
        assert abs(float(loss) - float(ref)) < 1e-4


class TestFlashResidentRemat:
    """Gradient parity of recompute(policy='flash_resident') and the proof
    that the policy keeps the forward flash kernel out of the backward."""

    def _grads(self, gran):
        from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, max_position_embeddings=128,
                          use_recompute=gran is not None,
                          recompute_granularity=gran or "full")
        m = LlamaForCausalLM(cfg)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 256, (2, 128)).astype("int64"))
        loss = m(ids, ids)
        loss.backward()
        return float(loss), [p.grad.numpy() for p in m.parameters()]

    def test_gradient_parity_vs_no_remat(self):
        l0, g0 = self._grads(None)
        l1, g1 = self._grads("flash_resident")
        assert abs(l0 - l1) < 1e-6
        assert len(g0) == len(g1)
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_policy_skips_flash_fwd_in_backward(self):
        # jaxpr-level proof: under save_only_these_names(flash residuals)
        # the rematerialized backward contains NO extra forward flash
        # kernel; plain full remat re-runs it once per checkpoint region
        from paddle_tpu.ops.pallas_attention import (FLASH_RESIDUAL_NAMES,
                                                     flash_attention_raw)

        rs = np.random.RandomState(0)
        q0 = jnp.asarray(rs.randn(1, 2, 256, 64).astype("float32"))
        w = jnp.asarray(np.eye(64, dtype="float32"))

        def chain(x, w):
            for _ in range(2):
                q = jnp.einsum("bhsd,de->bhse", x, w)
                x = jnp.tanh(flash_attention_raw(q, q, q, causal=True)) + x
            return jnp.sum(x ** 2)

        pol = jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES)
        full = str(jax.make_jaxpr(jax.grad(jax.checkpoint(chain)))(q0, w))
        res = str(jax.make_jaxpr(
            jax.grad(jax.checkpoint(chain, policy=pol)))(q0, w))
        # 2 layers: forward runs the fwd kernel twice in both; full remat
        # re-runs both in the backward, the policy none. The installed jax
        # prints a pallas_call by its `name=`, not its kernel function.
        assert full.count("name=flash_fwd") == 4
        assert res.count("name=flash_fwd") == 2
        assert res.count("name=flash_bwd_dq") == 2
        assert res.count("name=flash_bwd_dkv") == 2
        assert res.count("pallas_call[") == 6
        # ... because the policy still saves exactly the named residuals
        for name in FLASH_RESIDUAL_NAMES:
            assert res.count(f"name={name}]") == 2, name

    def test_unknown_policy_raises(self):
        from paddle_tpu.distributed.fleet.utils import _resolve_remat_policy

        with pytest.raises(ValueError):
            _resolve_remat_policy("no_such_policy")


class TestLongSeqAutotune:
    """Seq-keyed candidates, fwd/bwd split plumbing, and the hardened
    disk cache (validation + merge-on-store) — ADVICE r5 + VERDICT r5 #7."""

    def test_candidates_are_seq_keyed(self):
        from paddle_tpu.ops import pallas_attention as pa

        short = pa._tune_candidates(1024, 1024)
        long_ = pa._tune_candidates(8192, 8192)
        assert short == pa._TUNE_CANDIDATES
        assert long_ == pa._TUNE_CANDIDATES_LONG
        assert any(bk >= 2048 for _, bk in long_)
        # every candidate the tuner can emit passes its own load validation
        for cand in short + long_:
            assert pa._valid_blocks(cand)

    @pytest.mark.parametrize("bad", [
        (0, 512), (-512, 512), (100, 512), (512,), (512, 512, 512),
        (1 << 20, 128), ("512", 128), (True, 128), "512,512", None,
    ])
    def test_invalid_blocks_rejected(self, bad):
        from paddle_tpu.ops import pallas_attention as pa

        assert not pa._valid_blocks(bad)

    def test_poisoned_disk_entries_dropped_on_load(self, tmp_path,
                                                   monkeypatch):
        from paddle_tpu.ops import pallas_attention as pa

        path = str(tmp_path / "flash_tune_cache_v2.json")
        payload = {
            "flash|1024|1024|64|float32|True": [512, 1024, 512, 512],  # ok
            "flash|2048|2048|64|float32|True": [100, 512],     # not %128
            "flash|4096|4096|64|float32|True": [0, -512],      # non-positive
            "flashmask|8192|8192|128|bfloat16|True": [512, 512],  # ok (2)
            "bad key": [512, 512],                             # malformed
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        monkeypatch.setattr(pa, "_tune_cache_path", lambda: path)
        monkeypatch.setattr(pa, "_TUNE_CACHE", {})
        monkeypatch.setattr(pa, "_TUNE_DISK_LOADED", False)
        pa._tune_cache_load()
        assert pa._TUNE_CACHE == {
            ("flash", 1024, 1024, 64, "float32", True): (512, 1024, 512, 512),
            ("flashmask", 8192, 8192, 128, "bfloat16", True): (512, 512),
        }

    def test_store_merges_concurrent_entries(self, tmp_path, monkeypatch):
        from paddle_tpu.ops import pallas_attention as pa

        path = str(tmp_path / "flash_tune_cache_v2.json")
        other = {"flash|8192|8192|128|bfloat16|True": [1024, 2048, 512, 2048]}
        with open(path, "w") as f:
            json.dump(other, f)  # another process's tuning result
        monkeypatch.setattr(pa, "_tune_cache_path", lambda: path)
        key = ("flash", 1024, 1024, 64, "float32", True)
        monkeypatch.setattr(pa, "_TUNE_CACHE", {key: (512, 1024, 512, 512)})
        pa._tune_cache_store()
        with open(path) as f:
            stored = json.load(f)
        # both survive: ours AND the concurrent tuner's
        assert stored["flash|1024|1024|64|float32|True"] == [512, 1024,
                                                             512, 512]
        assert stored["flash|8192|8192|128|bfloat16|True"] == [1024, 2048,
                                                               512, 2048]

    def test_cache_dir_follows_the_compile_cache(self, monkeypatch):
        """One rule for both caches (core/compile_cache.py): where
        JAX_COMPILATION_CACHE_DIR says, else a fixed <checkout>/.jax_cache
        — never the home or temp directory, which differ from machine to
        machine. PADDLE_TPU_TUNE_CACHE_DIR still overrides the tune cache
        alone."""
        from paddle_tpu.core import compile_cache as cc
        from paddle_tpu.ops import pallas_attention as pa

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv("PADDLE_TPU_TUNE_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cc.cache_dir() == os.path.join(repo, ".jax_cache")
        assert os.path.dirname(pa._tune_cache_path()) == cc.cache_dir()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert cc.cache_dir() == "/placed/outside"
        assert pa._tune_cache_path().startswith("/placed/outside/")
        monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE_DIR", "/custom/dir")
        assert pa._tune_cache_path().startswith("/custom/dir/")
        assert cc.cache_dir() == "/placed/outside"

    def test_enable_compile_cache_sets_nothing_when_placed(self,
                                                           monkeypatch):
        from paddle_tpu.core import compile_cache as cc

        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert cc.enable_compile_cache() == "/placed/outside"
        assert "jax_compilation_cache_dir" not in seen
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert seen.get("jax_compilation_cache_dir") is None
        assert cc.enable_compile_cache() == cc.cache_dir()
        assert seen["jax_compilation_cache_dir"] == cc.cache_dir()

    def test_ensure_tuned_returns_split_pairs_off_tpu(self):
        from paddle_tpu.ops import pallas_attention as pa

        got = pa.ensure_tuned(1, 1, 1024, 1024, 64, jnp.float32, True)
        assert len(got) == 4

    def test_ensure_tuned_normalizes_legacy_two_tuple(self, monkeypatch):
        from paddle_tpu.ops import pallas_attention as pa

        key = ("flash", 2048, 2048, 64, "float32", True)
        monkeypatch.setitem(pa._TUNE_CACHE, key, (256, 512))
        got = pa.ensure_tuned(1, 1, 2048, 2048, 64, jnp.float32, True)
        assert got == (256, 512, 256, 512)


class TestFusedOptimizerInterruptSafety:
    """ADVICE r5: an interrupt between the donating jitted update and the
    _assign_raw loop must not leave optimizer state on deleted buffers."""

    def _model_and_ref(self, opt_cls, **kw):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                                   paddle.nn.Linear(16, 4))
        opt = opt_cls(learning_rate=0.1, parameters=net.parameters(), **kw)
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 4, (4,)).astype("int64"))
        return net, opt, x, y

    @pytest.mark.parametrize("opt_name", ["Momentum", "AdamW"])
    def test_interrupt_after_update_still_commits(self, opt_name,
                                                  monkeypatch):
        from paddle_tpu.optimizer import fused

        kw = {"use_multi_tensor": True}
        if opt_name == "Momentum":
            kw["momentum"] = 0.9
        opt_cls = getattr(paddle.optimizer, opt_name)

        def run(interrupt_step):
            net, opt, x, y = self._model_and_ref(opt_cls, **kw)
            for step in range(2):
                loss = F.cross_entropy(net(x), y)
                loss.backward()
                if step == interrupt_step:
                    def boom():
                        monkeypatch.setattr(fused, "_interrupt_test_hook",
                                            None)
                        raise KeyboardInterrupt
                    monkeypatch.setattr(fused, "_interrupt_test_hook", boom)
                    with pytest.raises(KeyboardInterrupt):
                        opt.step()
                else:
                    opt.step()
                opt.clear_grad()
            return [p.numpy() for p in net.parameters()]

        interrupted = run(interrupt_step=1)
        clean = run(interrupt_step=-1)
        # the interrupted step COMMITTED before the interrupt propagated:
        # params identical to an uninterrupted run, no dangling buffers
        for a, b in zip(interrupted, clean):
            np.testing.assert_array_equal(a, b)

    def test_state_usable_after_interrupt(self, monkeypatch):
        from paddle_tpu.optimizer import fused

        net, opt, x, y = self._model_and_ref(paddle.optimizer.Momentum,
                                             momentum=0.9,
                                             use_multi_tensor=True)
        loss = F.cross_entropy(net(x), y)
        loss.backward()

        def boom():
            monkeypatch.setattr(fused, "_interrupt_test_hook", None)
            raise KeyboardInterrupt
        monkeypatch.setattr(fused, "_interrupt_test_hook", boom)
        with pytest.raises(KeyboardInterrupt):
            opt.step()
        opt.clear_grad()
        # a further step must work on valid (non-donated-away) state
        loss = F.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        for p in net.parameters():
            assert np.all(np.isfinite(p.numpy()))


class TestBenchTimeBox:
    """VERDICT r5 Weak #2: the ladder must fit a wall-clock budget and
    record what it skipped, exiting rc 0 — while a rung that ran and
    failed exits non-zero, and a rung that writes a speed fails without a
    TPU instead of writing a CPU row under a speed's name."""

    def test_zero_budget_skips_everything_with_record(self, tmp_path,
                                                      monkeypatch):
        import bench

        monkeypatch.chdir(tmp_path)
        monkeypatch.setenv("BENCH_BUDGET_S", "0")
        # must not raise, must not spawn subprocesses; skipped != failed
        assert bench.main([]) == 0
        with open(tmp_path / "BENCH_DETAILS.json") as f:
            details = json.load(f)
        # every default-ladder config skipped, by name (no dupes, none run)
        assert sorted(details["skipped"]) == sorted(bench._COST_EST)
        assert details["results"] == {}

    def test_speed_rung_without_tpu_fails_the_run(self, tmp_path,
                                                  monkeypatch):
        import bench

        monkeypatch.chdir(tmp_path)
        assert bench.main(["decode_micro"]) == 1     # this suite is CPU-only
        with open(tmp_path / "BENCH_DETAILS.json") as f:
            row = json.load(f)["results"]["decode_micro"]
        assert "this rung measures the TPU" in row["error"], row
        assert "pallas_ms" not in row

    def test_headline_rebased_to_round4(self):
        import bench

        h = bench._headline({"llama_1b": {"tokens_per_sec": 19925.0}})
        assert h["vs_baseline"] == 1.0  # round-4 capture == the new base
        h2 = bench._headline({"llama_1b": {"tokens_per_sec": 23910.0}})
        assert h2["vs_baseline"] == 1.2
