"""paddle_tpu.analysis — per-detector fire/no-fire fixture pairs.

Every jaxpr detector (D1 dtype-stream, D2 donation, D3 host-sync, D4
fusion-miss, D5 vmem-budget, and the round-15 SPMD trio D9 sharding
coverage / D10 collective audit / D11 transfers) and every AST rule must
(a) fire on its intentionally-broken fixture and (b) stay silent on the
clean twin — the proof the lint gate actually gates. Jaxpr fixtures are
built directly with jax.make_jaxpr (no model compiles), AST fixtures
live in tests/lint_fixtures/.

Round 15 additionally pins the ProgramIndex refactor:
  * LEGACY PARITY — the pre-refactor detector implementations are frozen
    in tests/_legacy_jaxpr_audit.py; D1/D4/the callback scan must emit
    byte-identical findings on the real smoke programs and every micro
    fixture (the ISSUE-10 acceptance comparison).
  * SUB-JAXPR COVERAGE — every higher-order primitive appearing in the
    llama/gpt/bert/paged smoke jaxprs is either traversed by the walk or
    on the explicit stop-list; a jaxpr hidden anywhere in an eqn's
    params that the walk does not find is a failure.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import dataflow

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "lint_fixtures")


def _mesh42():
    return Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "mp"))


def _fx(name):
    return os.path.join(FIXTURES, name)


def _by_detector(findings, det):
    return [f for f in findings if f.detector == det]


# ------------------------------------------------------- D1 dtype-stream

def _stream_chain(x, promote):
    # bf16 [2,4,256] produced repeatedly = the inferred "residual stream"
    for _ in range(4):
        x = x + jnp.ones_like(x)
    if promote:
        x = x.astype(jnp.float32) * np.float32(2.0)   # silent re-widening
        x = x.astype(jnp.bfloat16)
    return x * 2


class TestD1DtypeStream:
    def _jaxpr(self, promote):
        x = jnp.ones((2, 4, 256), jnp.bfloat16)
        return jax.make_jaxpr(lambda a: _stream_chain(a, promote))(x)

    def test_fires_on_silent_promotion(self):
        fs = analysis.audit_dtype_stream(self._jaxpr(True),
                                         policy="bfloat16")
        assert fs, "f32-at-stream-shape must be detected"
        assert any("promotion" in f.message for f in fs)
        assert all(f.severity == "warning" for f in fs)
        assert all(f.data["shape"] == [2, 4, 256] for f in fs)

    def test_silent_on_clean_bf16_stream(self):
        assert analysis.audit_dtype_stream(self._jaxpr(False),
                                           policy="bfloat16") == []

    def test_f32_policy_permits_everything(self):
        assert analysis.audit_dtype_stream(self._jaxpr(True),
                                           policy="float32") == []

    def test_explicit_stream_shapes_override_inference(self):
        fs = analysis.audit_dtype_stream(
            self._jaxpr(True), policy="bfloat16",
            stream_shapes=[(9, 9, 9)])   # wrong shape: nothing matches
        assert fs == []


# ----------------------------------------------------------- D2 donation

class TestD2Donation:
    def _train_step(self, donate):
        paddle.seed(0)
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F

        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        X = paddle.to_tensor(np.random.randn(16, 8).astype("float32"))
        Y = paddle.to_tensor(np.random.randint(0, 4, (16,)).astype("int64"))

        def step(x, y):
            loss = F.cross_entropy(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        sf = paddle.jit.to_static(step, **(
            {} if donate else {"donate_buffers": False}))
        # donate_buffers is a CompiledFunction ctor arg
        from paddle_tpu.jit.api import CompiledFunction

        if not isinstance(sf, CompiledFunction):  # pragma: no cover
            raise AssertionError
        for _ in range(4):
            sf(X, Y)
        return sf

    def test_fires_when_donation_disabled(self):
        sf = self._train_step(donate=False)
        fs = analysis.audit_donation(sf)
        assert len(fs) == 1
        f = fs[0]
        assert f.severity == "warning"
        assert f.data["buffers"] > 0 and f.data["bytes"] > 0

    def test_silent_when_donated(self):
        sf = self._train_step(donate=True)
        assert analysis.audit_donation(sf) == []


# ---------------------------------------------------------- D3 host-sync

class TestD3HostSync:
    def test_fires_on_graph_break(self):
        def breaker(x):
            if float(x.sum().numpy()) > 0:   # concretization = flush site
                return x * 2
            return x * 3

        sf = paddle.jit.to_static(breaker)
        x = paddle.to_tensor(np.ones((3,), "float32"))
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for _ in range(4):
                sf(x)
        fs = analysis.audit_host_sync(sf)
        assert fs and all(f.detector == "host-sync" for f in fs)
        assert any("segment" in f.message or "EAGER" in f.message
                   for f in fs)

    def test_silent_on_compiled_function(self):
        @paddle.jit.to_static
        def clean(x):
            return (x * 2).sum()

        x = paddle.to_tensor(np.ones((3,), "float32"))
        for _ in range(4):
            clean(x)
        assert analysis.audit_host_sync(clean) == []

    def test_callback_primitive_detected(self):
        def chatty(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        jx = jax.make_jaxpr(chatty)(jnp.ones((4,)))
        fs = analysis.audit_callbacks(jx)
        assert fs and fs[0].severity == "warning"

    def test_no_callback_no_finding(self):
        jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,)))
        assert analysis.audit_callbacks(jx) == []


# -------------------------------------------------------- D4 fusion-miss

def _rms_composition(x, w):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + 1e-6)
            ).astype(x.dtype) * w


class TestD4FusionMiss:
    # 524288 elems: above BOTH the D4 reporting floor and the fused-kernel
    # routing threshold (1<<18), so "should have routed" is the verdict
    X = jnp.ones((8, 256, 256), jnp.bfloat16)
    W = jnp.ones((256,), jnp.bfloat16)

    def test_norm_composition_fires_as_warning_on_tpu(self):
        jx = jax.make_jaxpr(_rms_composition)(self.X, self.W)
        fs = _by_detector(
            analysis.audit_fusion_misses(jx, platform="tpu"), "fusion-miss")
        assert any(f.data["kind"] == "norm" and f.severity == "warning"
                   for f in fs), fs

    def test_norm_composition_is_note_off_tpu(self):
        jx = jax.make_jaxpr(_rms_composition)(self.X, self.W)
        fs = analysis.audit_fusion_misses(jx, platform="cpu")
        assert fs and all(f.severity == "note" for f in fs)
        assert all("not on TPU" in f.data["gate"] for f in fs)

    def test_small_tensor_below_floor_is_silent(self):
        x = jnp.ones((2, 4, 8), jnp.bfloat16)
        w = jnp.ones((8,), jnp.bfloat16)
        jx = jax.make_jaxpr(_rms_composition)(x, w)
        assert analysis.audit_fusion_misses(jx, platform="tpu") == []

    def test_pallas_routed_program_is_silent(self):
        from paddle_tpu.ops import pallas_norm as pn

        old = pn.FORCE_PALLAS
        pn.FORCE_PALLAS = True
        try:
            jx = jax.make_jaxpr(
                lambda a, b: pn.rms_norm_fused(a, b, 1e-6))(
                    self.X.astype(jnp.float32), self.W.astype(jnp.float32))
        finally:
            pn.FORCE_PALLAS = old
        fs = analysis.audit_fusion_misses(jx, platform="tpu")
        assert fs == [], ("the fused kernel's own rsqrt (inside "
                          "pallas_call) must not count as a miss")

    def test_swiglu_composition_fires(self):
        jx = jax.make_jaxpr(lambda g, u: jax.nn.silu(g) * u)(
            self.X, self.X)
        fs = analysis.audit_fusion_misses(jx, platform="tpu")
        assert any(f.data["kind"] == "swiglu/silu" for f in fs)

    def test_rotary_composition_fires_and_gqa_is_annotated(self):
        def rope(q, cos, sin):
            d = q.shape[-1] // 2
            rot = jnp.concatenate([-q[..., d:], q[..., :d]], axis=-1)
            return q * cos + rot * sin

        q = jnp.ones((2, 64, 8, 64), jnp.float32)
        c = jnp.ones((1, 64, 1, 64), jnp.float32)
        jx = jax.make_jaxpr(rope)(q, c, c)
        fs = analysis.audit_fusion_misses(jx, platform="tpu")
        assert any(f.data["kind"] == "rotary" for f in fs)

        # GQA: rotate q and a k with FEWER heads -> mismatch annotation
        def rope_qk(q, k, cos, sin):
            return rope(q, cos, sin) + 0 * q.sum(), rope(k, cos, sin)

        k = jnp.ones((2, 64, 2, 64), jnp.float32)
        jx2 = jax.make_jaxpr(rope_qk)(q, k, c, c)
        fs2 = analysis.audit_fusion_misses(jx2, platform="tpu")
        ropes = [f for f in fs2 if f.data["kind"] == "rotary"]
        assert ropes and all("GQA" in f.data["gate"] for f in ropes)

    def test_dropout_add_composition_fires(self):
        key = jax.random.PRNGKey(0)

        def dro(x, y):
            m = (jax.random.uniform(key, x.shape) > 0.1).astype(x.dtype)
            return x * m * (1 / 0.9) + y

        x = jnp.ones((4, 64, 256), jnp.float32)
        jx = jax.make_jaxpr(dro)(x, x)
        fs = analysis.audit_fusion_misses(jx, platform="tpu")
        assert any(f.data["kind"] == "dropout-add" for f in fs)


class TestD4DecodeAttention:
    """Round-10: the gather-over-cache + seq-1-query softmax anchor
    (paged decode composition -> "should have routed to pallas_decode"
    with the REAL gating reason)."""

    @staticmethod
    def _decode_jaxpr(s=8, hq=16, hkv=4, d=128, bs=16, pages=32, n=128,
                      dtype=jnp.bfloat16):
        from paddle_tpu.ops.pallas_decode import paged_decode_attention_xla

        q = jnp.zeros((s, hq, d), dtype)
        kc = jnp.zeros((n, hkv, bs, d), dtype)
        tabs = jnp.zeros((s, pages), jnp.int32)
        lens = jnp.ones((s,), jnp.int32)
        return jax.make_jaxpr(paged_decode_attention_xla)(q, kc, kc, tabs,
                                                          lens)

    def test_fires_as_warning_on_tpu(self):
        # 8*16*512 = 65536 score elements: above floor AND kernel threshold
        fs = [f for f in analysis.audit_fusion_misses(self._decode_jaxpr(),
                                                      platform="tpu")
              if f.data.get("kind") == "decode-attn"]
        assert fs and fs[0].severity == "warning", fs
        assert "pallas_decode" in fs[0].data["gate"] \
            or "Pallas decode" in fs[0].data["gate"], fs[0].data

    def test_off_tpu_is_a_note_with_real_reason(self):
        fs = [f for f in analysis.audit_fusion_misses(self._decode_jaxpr(),
                                                      platform="cpu")
              if f.data.get("kind") == "decode-attn"]
        assert fs and fs[0].severity == "note"
        assert "not on TPU" in fs[0].data["gate"]

    def test_unaligned_head_dim_is_a_note(self):
        fs = [f for f in analysis.audit_fusion_misses(
            self._decode_jaxpr(d=64, pages=64), platform="tpu")
            if f.data.get("kind") == "decode-attn"]
        assert fs and fs[0].severity == "note"
        assert "lane-aligned" in fs[0].data["gate"]

    def test_small_scores_below_floor_silent(self):
        fs = [f for f in analysis.audit_fusion_misses(
            self._decode_jaxpr(s=1, hq=4, hkv=4, pages=4, n=8),
            platform="tpu") if f.data.get("kind") == "decode-attn"]
        assert fs == []

    def test_pallas_kernel_path_is_silent(self):
        from paddle_tpu.ops.pallas_decode import paged_decode_attention_raw

        q = jnp.zeros((8, 16, 128), jnp.bfloat16)
        kc = jnp.zeros((128, 4, 16, 128), jnp.bfloat16)
        tabs = jnp.zeros((8, 32), jnp.int32)
        lens = jnp.ones((8,), jnp.int32)
        jx = jax.make_jaxpr(paged_decode_attention_raw)(q, kc, kc, tabs,
                                                        lens)
        fs = [f for f in analysis.audit_fusion_misses(jx, platform="tpu")
              if f.data.get("kind") == "decode-attn"]
        assert fs == [], ("scores computed inside pallas_call must not "
                          "count as a decode miss")

    def test_serving_step_program_audits_clean_off_tpu(self):
        """The engine's real decode step program on CPU: the decode
        composition is the INTENDED fallback -> notes only, gate passes
        (what tools/graft_lint.py's paged smoke asserts)."""
        import paddle_tpu as paddle
        from paddle_tpu.inference.engine import ServingEngine
        from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=64)
        m = LlamaForCausalLM(cfg)
        m.eval()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8)
        jx = eng.decode_program_jaxpr()
        fs = analysis.audit_fusion_misses(jx, platform="cpu")
        assert all(f.severity == "note" for f in fs), fs
        fs_cb = analysis.audit_callbacks(jx)
        assert fs_cb == []


class TestD5DecodeConfig:
    def test_default_decode_config_fits(self):
        assert analysis.audit_decode_config(128, 16) == []

    @pytest.mark.parametrize("kv_heads,group,itemsize", [
        (8, 4, 2), (32, 1, 2), (16, 1, 2), (8, 4, 1)],
        ids=["mistral_gqa", "llama2_mha", "cerebras", "mistral_int8"])
    def test_served_geometries_fit(self, kv_heads, group, itemsize):
        # the kernel cuts its compute block to its share: every served
        # head geometry sits well under the budget at the default page
        assert analysis.audit_decode_config(
            128, 16, group=group, itemsize=itemsize, kv_heads=kv_heads,
            seq_pages=256, pool_blocks=4097, slots=16) == []
        est = analysis.decode_vmem_bytes(128, 16, group, itemsize, kv_heads,
                                         256)
        assert 4 * 2**20 < est < 8 * 2**20

    def test_oversized_block_fires(self):
        fs = analysis.audit_decode_config(128, 32768)
        assert fs and fs[0].severity == "warning"
        assert "FLAGS_kv_block_size" in fs[0].message
        # the kernel was already down to a page a step
        assert fs[0].data["pages_per_step"] == 1

    def test_estimate_reads_the_kernels_own_sizing(self):
        from paddle_tpu.ops import pallas_decode

        # the serve cell: 32 pages a step, K and V double-buffered = 4 MiB
        assert pallas_decode.pages_per_step(256, 16, 8, 128, 2) == 32
        est = analysis.decode_vmem_bytes(128, 16, 4, 2, 8, 256)
        assert est > pallas_decode._STREAM_VMEM_BYTES == 4 * 32 * 8 * 16 \
            * 128 * 2
        # a short table caps the block, and the estimate with it
        assert analysis.decode_vmem_bytes(128, 16, 4, 2, 8, 4) < est / 4

    def test_estimator_monotonic_in_block_size(self):
        # decode_vmem_bytes(head_dim, block_size, ...) — same order as
        # audit_decode_config. Past the stream share (one page a step) a
        # larger page is a larger working set
        a = analysis.decode_vmem_bytes(128, 8192)
        b = analysis.decode_vmem_bytes(128, 16384)
        assert b > a


# -------------------------------------------------------- D5 vmem budget

class TestD5VmemBudget:
    def test_poisoned_tune_entry_fires(self):
        entries = {("flash", 8192, 8192, 256, "float32", True):
                   (4096, 4096, 4096, 4096)}
        fs = analysis.audit_tune_cache(entries=entries)
        assert fs and any(f.severity == "warning" for f in fs)
        assert all(f.detector == "vmem-budget" for f in fs)

    def test_default_blocks_fit(self):
        entries = {("flash", 1024, 1024, 128, "bfloat16", True):
                   (512, 1024, 512, 1024)}
        assert analysis.audit_tune_cache(entries=entries) == []

    def test_malformed_entry_is_a_warning(self):
        # non-sequence, wrong-arity, and out-of-range values must all be
        # findings, never unpack crashes (the lint's whole point is that
        # poisoned entries fail LINT, not a later run)
        for bad in ({("flash", 1): "junk"},
                    {("flash", 8192, 8192, 256, "float32", True):
                     (4096, 4096, 4096)},
                    {("flash", 8192, 8192, 256, "float32", True): 7},
                    {("flash", 1024, 1024, 128, "bfloat16", True):
                     (513, 1024)}):
            fs = analysis.audit_tune_cache(entries=bad)
            assert fs and fs[0].severity == "warning", bad
            assert "malformed" in fs[0].message, bad

    def test_norm_config_width_ladder(self):
        # the kernels size their own row block from the width, so every
        # width audits clean at the default ...
        for h in (4096, 8192, 16384):
            assert analysis.audit_norm_config(h, itemsize=2) == []
        # ... while the fixed 256 rows the kernels used to take do NOT fit
        # at the 7B width (the chip's compiler: 16.25 MiB over 16) — the
        # finding tells the caller to shrink block_rows
        fs = analysis.audit_norm_config(4096, itemsize=2, block_rows=256)
        assert fs and fs[0].severity == "warning"
        assert "block_rows" in fs[0].message
        assert analysis.audit_norm_config(8192, itemsize=2,
                                          block_rows=64) == []

    def test_estimator_monotonic(self):
        a = analysis.flash_vmem_bytes(512, 1024, 128, 2)
        b = analysis.flash_vmem_bytes(1024, 2048, 128, 2)
        assert b[0] > a[0] and b[1] > a[1]


# ------------------------------------------------------------- AST rules

class TestAstLint:
    def test_x64_fixture_fires_everywhere(self):
        fs = _by_detector(analysis.lint_file(_fx("fx_x64_toggle.py")),
                          "ast-x64")
        kinds = {f.data["kind"] for f in fs}
        assert len(fs) >= 3 and {"enable_x64(...) call",
                                 'config.update("jax_enable_x64", ...)',
                                 "import of enable_x64"} <= kinds

    def test_vjp_saves_fixture_fires_on_leaked_operand(self):
        fs = _by_detector(analysis.lint_file(_fx("fx_vjp_saves.py")),
                          "ast-vjp-saves")
        assert len(fs) == 1 and fs[0].data["extra"] == ["x"]

    def test_dy2static_fixture_fires_on_each_construct(self):
        fs = _by_detector(analysis.lint_file(_fx("fx_dy2static.py")),
                          "ast-dy2static")
        constructs = {f.data["construct"] for f in fs}
        assert "`return`" in constructs
        assert any("attribute store" in c for c in constructs)
        assert any("subscript store" in c for c in constructs)
        assert all(f.severity == "note" for f in fs)

    def test_clean_fixture_is_silent(self):
        assert analysis.lint_file(_fx("fx_clean.py")) == []

    def test_sanctioned_x64_site_exempt(self):
        path = os.path.join(REPO, "paddle_tpu", "ops", "_pallas_common.py")
        assert _by_detector(analysis.lint_file(path), "ast-x64") == []

    def test_repo_flags_doc_in_sync(self):
        assert analysis.audit_flags_doc(REPO) == []

    def test_flags_doc_catches_missing(self, tmp_path):
        (tmp_path / "paddle_tpu" / "core").mkdir(parents=True)
        (tmp_path / "paddle_tpu" / "core" / "flags.py").write_text(
            'define_flag("FLAGS_ghost", True, "undocumented behavior")\n'
            'define_flag("FLAGS_mute", 1)\n')
        (tmp_path / "README.md").write_text("# no flags table\nFLAGS_mute\n")
        fs = analysis.audit_flags_doc(str(tmp_path))
        msgs = " | ".join(f.message for f in fs)
        assert "FLAGS_ghost" in msgs and "missing from" in msgs
        assert "FLAGS_mute" in msgs and "doc string" in msgs

    def test_real_pallas_norm_declarations_hold(self):
        path = os.path.join(REPO, "paddle_tpu", "ops", "pallas_norm.py")
        assert _by_detector(analysis.lint_file(path), "ast-vjp-saves") == []


# ---------------------------------------------------- baseline + gate

class TestBaselineAndGate:
    def _mk(self, det, sev, loc="a.py:1", msg="boom"):
        return analysis.Finding(det, sev, loc, msg)

    def test_gate_counts_warning_and_error_not_notes(self):
        fs = [self._mk("d", "note"), self._mk("d", "warning"),
              self._mk("d", "error")]
        assert len(analysis.gate_failures(fs)) == 2

    def test_baseline_suppresses_by_detector_and_substring(self, tmp_path):
        p = tmp_path / "base.json"
        p.write_text(json.dumps({"suppressions": [
            {"detector": "d1", "match": "a.py", "reason": "known"}]}))
        fs = [self._mk("d1", "warning", loc="a.py:3"),
              self._mk("d2", "warning", loc="a.py:3")]
        analysis.apply_baseline(fs, analysis.load_baseline(str(p)))
        assert fs[0].suppressed and not fs[1].suppressed
        assert len(analysis.gate_failures(fs)) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert analysis.load_baseline(str(tmp_path / "nope.json")) == []

    def test_corrupt_baseline_raises(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"suppressions": [{"detector": "x"}]}')
        with pytest.raises(ValueError):
            analysis.load_baseline(str(p))

    def test_json_payload_shape(self):
        fs = [self._mk("d", "warning")]
        payload = analysis.to_json(fs)
        assert payload["gate_failures"] == 1 and not payload["clean"]
        assert payload["findings"][0]["detector"] == "d"


# ------------------------------------------------------------ CLI + gate

@pytest.mark.slow
def test_cli_full_model_audit_is_clean():
    """The acceptance command: every smoke config audits clean at default
    flags through the real CLI (subprocess: own jax session)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graft_lint.py"),
         "--models", "llama,gpt,bert,paged", "--json"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert payload["clean"]


def test_cli_ast_and_vmem_clean():
    """Fast CI shape of the gate: AST lint + tune-cache audit via the CLI."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graft_lint.py"),
         "--json"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert payload["clean"]
    # the sanctioned x64 site is visibly suppressed, not hidden
    assert payload["suppressed"] >= 1


def test_scoreboard_grew_the_lint_gate():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_scoreboard

    assert hasattr(check_scoreboard, "lint_gate")
    src = open(os.path.join(REPO, "tools", "check_scoreboard.py")).read()
    assert "lint_gate()" in src.split("def main")[1], \
        "check_scoreboard.main must run the lint gate"
    # round-10: the serving step program is part of the audited model set
    assert "paged" in check_scoreboard.lint_gate.__defaults__[0]


def test_paged_serving_smoke_audits_clean():
    """graft_lint's `paged` smoke (the serving decode step program) must
    come back clean at default flags — the round-10 acceptance gate,
    in-process so the quick tier covers it without a subprocess."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import graft_lint

    findings = graft_lint.audit_serving()
    bad = [f for f in findings if f.severity in ("warning", "error")]
    assert bad == [], bad


# ------------------------------------ round 15: ProgramIndex framework

@pytest.fixture(scope="module")
def smoke_jaxprs():
    """The real smoke programs (compiled ONCE per module): llama forward
    + train step, gpt/bert forward, and the paged decode step program —
    the corpus for legacy parity and the sub-jaxpr coverage meta-test."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from report_graph_breaks import SMOKES

    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    out = {}
    paddle.set_flags({"FLAGS_jit_debug_program": True})
    try:
        for name in ("llama", "gpt", "bert"):
            fwd_fn, args = SMOKES[name]()
            sfwd = paddle.jit.to_static(fwd_fn)
            for _ in range(3):
                sfwd(*args)
            out[f"{name}/forward"] = sfwd.program_jaxpr()
            if name == "llama":   # one train step covers the grad HOPs
                model = fwd_fn.__self__
                opt = paddle.optimizer.AdamW(
                    learning_rate=1e-4, parameters=model.parameters())

                @paddle.jit.to_static
                def train_step(*a):
                    loss = fwd_fn(*a)
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                    return loss

                for _ in range(4):
                    train_step(*args)
                out["llama/train_step"] = train_step.program_jaxpr()
    finally:
        paddle.set_flags({"FLAGS_jit_debug_program": False})

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    eng = ServingEngine(m, max_slots=2, kv_block_size=8)
    out["paged/decode_step"] = eng.decode_program_jaxpr()
    return out


def _load_legacy():
    """The pre-refactor jaxpr_audit, frozen at the round-14 commit.
    Loaded under the analysis package name so its relative import of
    .findings resolves — same Finding class, so to_dict() comparisons
    are exact."""
    path = os.path.join(HERE, "_legacy_jaxpr_audit.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.analysis._legacy_jaxpr_audit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestProgramIndex:
    def _scan_prog(self):
        def f(x):
            def body(c, t):
                return c + t.sum(), c * t.sum()

            acc, ys = jax.lax.scan(body, x.sum(), x)
            return jax.lax.cond(acc > 0, lambda v: v * 2, lambda v: v, ys)

        return jax.make_jaxpr(jax.jit(f))(jnp.ones((4, 8), jnp.float32))

    def test_single_walk_indexes_sub_jaxprs(self):
        idx = analysis.build_index(self._scan_prog())
        assert len(idx.levels) > 1
        assert "scan" in idx.eqns_by_prim or any(
            "scan" in lv.path for lv in idx.levels)
        assert idx.hop_entered, "higher-order prims must be entered"

    def test_walk_stops_at_pallas_call(self):
        from paddle_tpu.ops import pallas_norm as pn

        old = pn.FORCE_PALLAS
        pn.FORCE_PALLAS = True
        try:
            jx = jax.make_jaxpr(
                lambda a, b: pn.rms_norm_fused(a, b, 1e-6))(
                    jnp.ones((8, 256, 256), jnp.float32),
                    jnp.ones((256,), jnp.float32))
        finally:
            pn.FORCE_PALLAS = old
        idx = analysis.build_index(jx)
        assert idx.hop_stopped.get("pallas_call", 0) >= 1
        assert all("pallas_call" not in lv.path for lv in idx.levels), \
            "kernel bodies must not become walked levels"

    def test_detectors_accept_prebuilt_index(self):
        x = jnp.ones((2, 4, 256), jnp.bfloat16)
        jx = jax.make_jaxpr(lambda a: _stream_chain(a, True))(x)
        idx = analysis.build_index(jx)
        direct = [f.to_dict() for f in analysis.audit_dtype_stream(
            jx, policy="bfloat16")]
        via_idx = [f.to_dict() for f in analysis.audit_dtype_stream(
            idx, policy="bfloat16")]
        assert direct == via_idx and direct

    def test_var_info_carries_shape_sharding_provenance(self):
        mesh = _mesh42()

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2, NamedSharding(mesh, P("dp", None))) + 1

        jx = jax.make_jaxpr(f)(jnp.ones((8, 16), jnp.float32))
        idx = analysis.build_index(jx)
        (level, eqn), = idx.eqns_by_prim["sharding_constraint"]
        info = idx.var_info(eqn.outvars[0], level)
        assert info.shape == (8, 16) and info.dtype == "float32"
        assert info.size == 128 and info.path == "root"
        assert info.sharding is not None
        assert info.sharding.axes_used == {"dp"}
        assert idx.mesh_axes.get("dp") == 4 and idx.mesh_axes.get("mp") == 2

    def test_stream_shape_inference_shared_with_d1(self):
        x = jnp.ones((2, 4, 256), jnp.bfloat16)
        jx = jax.make_jaxpr(lambda a: _stream_chain(a, False))(x)
        idx = analysis.build_index(jx)
        assert analysis.infer_stream_shapes(idx) == [(2, 4, 256)]
        # D9 widens the same inference to f32
        xf = jnp.ones((2, 4, 256), jnp.float32)
        jxf = jax.make_jaxpr(lambda a: _stream_chain(a, False))(xf)
        idxf = analysis.build_index(jxf)
        assert analysis.infer_stream_shapes(idxf) == []
        assert idxf.stream_shapes(dtypes=("float32",)) == [(2, 4, 256)]


class TestLegacyParity:
    """ISSUE-10 acceptance: D1/D4/callbacks produce IDENTICAL findings
    before and after the ProgramIndex refactor, on the real smoke
    programs and on every micro fixture."""

    @staticmethod
    def _dicts(findings):
        return [f.to_dict() for f in findings]

    def _assert_parity(self, legacy, jx, stream_policy="bfloat16"):
        for platform in ("tpu", "cpu"):
            assert self._dicts(
                legacy.audit_fusion_misses(jx, platform=platform)) == \
                self._dicts(
                    analysis.audit_fusion_misses(jx, platform=platform))
        assert self._dicts(legacy.audit_callbacks(jx)) == \
            self._dicts(analysis.audit_callbacks(jx))
        assert self._dicts(
            legacy.audit_dtype_stream(jx, policy=stream_policy)) == \
            self._dicts(analysis.audit_dtype_stream(jx,
                                                    policy=stream_policy))
        assert list(legacy.infer_stream_shapes(jx)) == \
            list(analysis.infer_stream_shapes(jx))

    def test_smoke_program_parity(self, smoke_jaxprs):
        legacy = _load_legacy()
        for name, jx in smoke_jaxprs.items():
            self._assert_parity(legacy, jx)

    def test_micro_fixture_parity(self):
        legacy = _load_legacy()
        fixtures = []
        x = jnp.ones((2, 4, 256), jnp.bfloat16)
        fixtures.append(jax.make_jaxpr(
            lambda a: _stream_chain(a, True))(x))
        fixtures.append(jax.make_jaxpr(_rms_composition)(
            TestD4FusionMiss.X, TestD4FusionMiss.W))
        fixtures.append(jax.make_jaxpr(
            lambda g, u: jax.nn.silu(g) * u)(TestD4FusionMiss.X,
                                             TestD4FusionMiss.X))
        fixtures.append(TestD4DecodeAttention._decode_jaxpr())

        def chatty(v):
            jax.debug.print("v={v}", v=v.sum())
            return v * 2

        fixtures.append(jax.make_jaxpr(chatty)(jnp.ones((4,))))
        for jx in fixtures:
            self._assert_parity(legacy, jx)


#: primitives that are call-like by name even when the generic param
#: scan finds their body some other way
_CALL_LIKE = {"jit", "scan", "while", "cond", "shard_map", "remat",
              "checkpoint", "named_call", "core_call", "closed_call",
              "custom_lin"}

#: call-like primitives ALLOWED to carry no sub-jaxpr in their params
#: (their body lives behind a thunk/linearization jax never re-traces —
#: nothing for a detector to miss). Keep this list tight: a new entry
#: means a new blind spot was consciously accepted.
_ALLOWED_LEAF_CALLS = {"custom_lin"}


def _deep_jaxpr_scan(obj, found, depth=0):
    if depth > 6:
        return
    if hasattr(obj, "eqns") or hasattr(getattr(obj, "jaxpr", None),
                                       "eqns"):
        found.append(obj)
        return
    if isinstance(obj, (tuple, list)):
        for x in obj:
            _deep_jaxpr_scan(x, found, depth + 1)
    elif isinstance(obj, dict):
        for x in obj.values():
            _deep_jaxpr_scan(x, found, depth + 1)


class TestSubJaxprCoverage:
    """Satellite 1: every higher-order primitive in the smoke jaxprs is
    traversed by the walk or on the explicit stop-list — a call-like
    primitive that silently hides eqns from every detector is exactly
    the bug class this meta-test exists to catch."""

    def test_every_hop_traversed_or_stopped(self, smoke_jaxprs):
        seen_hops = set()
        for name, jx in smoke_jaxprs.items():
            idx = analysis.build_index(jx)
            for level, eqn in idx.eqns:
                prim = eqn.primitive.name
                shallow = dataflow._sub_jaxprs(eqn.params)
                deep: list = []
                for v in eqn.params.values():
                    _deep_jaxpr_scan(v, deep)
                if prim in dataflow.STOP_PRIMS:
                    continue
                assert len(deep) <= len(shallow), \
                    (f"{name}: '{prim}' hides {len(deep) - len(shallow)} "
                     f"jaxpr(s) in nested params the walk does not find")
                call_like = (prim.endswith("call") or prim in _CALL_LIKE)
                if call_like:
                    seen_hops.add(prim)
                    assert shallow or prim in _ALLOWED_LEAF_CALLS, \
                        (f"{name}: call-like '{prim}' carries no "
                         "sub-jaxpr the walk can traverse and is not on "
                         "the allowed leaf-call list")
                if shallow:
                    assert prim in idx.hop_entered, \
                        f"{name}: '{prim}' has sub-jaxprs but was not " \
                        "entered"
        assert "jit" in seen_hops, \
            "smoke corpus lost its higher-order primitives — the " \
            "meta-test is no longer testing anything"


# ------------------------------------------- D9 sharding coverage (spmd)

def _f32_stream(x, constrain=None):
    for i in range(4):
        x = x + 1.0
        if constrain is not None:
            x = constrain(x, i)
    return x


class TestD9ShardingCoverage:
    X = jnp.ones((8, 32, 64), jnp.float32)

    def test_fires_on_explicitly_replicated_stream(self):
        mesh = _mesh42()
        sh = NamedSharding(mesh, P(None, None, None))
        jx = jax.make_jaxpr(lambda a: _f32_stream(
            a, lambda v, i: jax.lax.with_sharding_constraint(v, sh)))(
                self.X)
        fs = analysis.audit_sharding_coverage(jx, mesh=mesh)
        warns = [f for f in fs if f.severity == "warning"]
        assert warns, fs
        assert set(warns[0].data["uncovered_axes"]) == {"dp", "mp"}

    def test_fires_on_unannotated_program_under_declared_mesh(self):
        jx = jax.make_jaxpr(lambda a: _f32_stream(a))(self.X)
        fs = analysis.audit_sharding_coverage(
            jx, mesh={"dp": 4, "mp": 2})
        warns = [f for f in fs if f.severity == "warning"]
        assert warns and "NO sharding annotation" in warns[0].message

    def test_silent_when_every_axis_covered(self):
        mesh = _mesh42()

        def constrain(v, i):
            spec = P("dp", None, None) if i % 2 else P(None, None, "mp")
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, spec))

        jx = jax.make_jaxpr(lambda a: _f32_stream(a, constrain))(self.X)
        fs = analysis.audit_sharding_coverage(jx, mesh=mesh)
        assert [f for f in fs if f.severity == "warning"] == [], fs
        assert any("coverage ok" in f.message for f in fs)

    def test_partial_coverage_names_the_missing_axis(self):
        mesh = _mesh42()
        sh = NamedSharding(mesh, P(None, None, "mp"))
        jx = jax.make_jaxpr(lambda a: _f32_stream(
            a, lambda v, i: jax.lax.with_sharding_constraint(v, sh)))(
                self.X)
        warns = [f for f in analysis.audit_sharding_coverage(jx,
                                                             mesh=mesh)
                 if f.severity == "warning"]
        assert warns and warns[0].data["uncovered_axes"] == ["dp"]

    def test_no_mesh_no_findings(self):
        jx = jax.make_jaxpr(lambda a: _f32_stream(a))(self.X)
        assert analysis.audit_sharding_coverage(jx) == []

    def test_trivial_axes_exempt(self):
        jx = jax.make_jaxpr(lambda a: _f32_stream(a))(self.X)
        assert analysis.audit_sharding_coverage(
            jx, mesh={"dp": 1, "pp": 1}) == []

    def test_replicated_local_gather_next_to_sharded_twin_is_note(self):
        # the real tp x dp train step's shape: gather_output-style P()
        # constraints coexist with sharded constraints at the SAME shape
        mesh = _mesh42()

        def constrain(v, i):
            spec = P(None, None, "mp") if i < 2 else P(None, None, None)
            if i == 3:
                spec = P("dp", None, None)
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, spec))

        jx = jax.make_jaxpr(lambda a: _f32_stream(a, constrain))(self.X)
        fs = analysis.audit_sharding_coverage(jx, mesh=mesh)
        assert [f for f in fs if f.severity == "warning"] == [], fs
        assert any("fully-replicated" in f.message for f in fs)


# ---------------------------------------------- D10 collectives (spmd)

class TestD10Collectives:
    def _shardmapped(self, body, in_specs, out_specs):
        return jax.make_jaxpr(shard_map(
            body, mesh=_mesh42(), in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    def test_gratuitous_all_gather_fires(self):
        def body(x):     # gathered output only feeds elementwise ops
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            return g * 2.0 + 1.0

        jx = self._shardmapped(body, P("mp"), P())(
            jnp.ones((128, 256), jnp.float32))
        fs = analysis.audit_collectives(jx)
        warns = [f for f in fs if f.severity == "warning"]
        assert warns and warns[0].data["accidental"]
        assert warns[0].data["axes"] == ["mp"]
        assert warns[0].data["bytes"] == 128 * 256 * 4

    def test_psum_of_scalar_loss_is_a_note(self):
        def body(x):     # the legitimate grad/loss reduction
            return jax.lax.psum((x ** 2).sum(), "dp")

        jx = self._shardmapped(body, P("dp"), P())(
            jnp.ones((128, 256), jnp.float32))
        fs = analysis.audit_collectives(jx)
        assert fs and all(f.severity == "note" for f in fs), fs
        assert any(f.data.get("prim") == "psum" for f in fs)

    def test_fsdp_reduce_scatter_is_a_note(self):
        def body(g):     # ZeRO-style grad shard reduction
            s = jax.lax.psum_scatter(g, "dp", scatter_dimension=0,
                                     tiled=True)
            return s * 0.01

        jx = self._shardmapped(body, P(), P("dp"))(
            jnp.ones((128, 256), jnp.float32))
        fs = analysis.audit_collectives(jx)
        assert fs and all(f.severity == "note" for f in fs), fs
        assert any(f.data.get("prim") == "reduce_scatter" for f in fs)

    def test_all_gather_feeding_matmul_is_justified(self):
        def body(x, w):  # the contraction NEEDS the materialized axis
            g = jax.lax.all_gather(x, "mp", axis=1, tiled=True)
            return g @ w

        jx = self._shardmapped(body, (P(None, "mp"), P()), P())(
            jnp.ones((128, 256), jnp.float32),
            jnp.ones((256, 64), jnp.float32))
        fs = analysis.audit_collectives(jx)
        assert fs and all(f.severity == "note" for f in fs), fs

    def test_warning_floor_applies(self):
        def body(x):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            return g * 2.0

        jx = self._shardmapped(body, P("mp"), P())(
            jnp.ones((128, 256), jnp.float32))
        fs = analysis.audit_collectives(jx, min_bytes=1 << 30)
        assert all(f.severity == "note" for f in fs), fs

    def test_no_collectives_no_findings(self):
        jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,)))
        assert analysis.audit_collectives(jx) == []

    def test_collective_bytes_summary(self):
        def body(x):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            s = jax.lax.psum(x.sum(), "dp")
            return g.sum() + s

        jx = self._shardmapped(body, P("mp"), P())(
            jnp.ones((64, 64), jnp.float32))
        vol = analysis.jaxpr_collective_bytes(jx)
        assert vol["sites"] == 2
        assert set(vol["per_axis"]) == {"dp", "mp"}
        assert vol["per_prim"]["all_gather"] == 64 * 64 * 4
        assert vol["total"] == sum(vol["per_prim"].values())

    def test_ledger_row_carries_collective_bytes(self):
        from paddle_tpu.obs import costs as obs_costs

        e = obs_costs.record_program("test.spmd", "g", "collective_row",
                                     collective_bytes=4096)
        try:
            assert e.collective_bytes == 4096
            assert e.to_dict()["collective_bytes"] == 4096
            # idempotent re-record keeps/backfills the volume
            e2 = obs_costs.record_program("test.spmd", "g",
                                          "collective_row",
                                          collective_bytes=4096)
            assert e2 is e and e2.collective_bytes == 4096
        finally:
            obs_costs._ledger.pop("test.spmd|collective_row", None)


# ------------------------------------------------ D11 transfers (spmd)

class TestD11Transfers:
    def test_device_put_inside_program_fires(self):
        mesh = _mesh42()

        def f(x):
            return jax.device_put(
                x * 2.0, NamedSharding(mesh, P())) + 1.0

        jx = jax.make_jaxpr(f)(jnp.ones((8, 8)))
        fs = analysis.audit_transfers(jx)
        assert len(fs) == 1 and fs[0].severity == "warning"
        assert fs[0].data["shape"] == [8, 8]

    def test_plain_program_silent(self):
        jx = jax.make_jaxpr(lambda x: (x * 2).sum())(jnp.ones((8, 8)))
        assert analysis.audit_transfers(jx) == []

    def test_sharding_constraint_does_not_fire(self):
        mesh = _mesh42()

        def f(x):
            return jax.lax.with_sharding_constraint(
                x * 2, NamedSharding(mesh, P("dp", None)))

        jx = jax.make_jaxpr(f)(jnp.ones((8, 8)))
        assert analysis.audit_transfers(jx) == []


# --------------------------------------------- stale suppressions + CLI

class TestStaleSuppressions:
    def _mk(self, det, sev="warning", loc="a.py:1", msg="boom"):
        return analysis.Finding(det, sev, loc, msg)

    def test_apply_baseline_tracks_matches(self):
        base = [{"detector": "d1", "match": "a.py"},
                {"detector": "ghost", "match": "nowhere"}]
        analysis.apply_baseline([self._mk("d1")], base)
        stale = analysis.stale_suppressions(base)
        assert len(stale) == 1 and stale[0]["detector"] == "ghost"

    def _baseline_file(self, tmp_path, extra=True):
        entries = [{"detector": "ast-x64",
                    "match": "paddle_tpu/__init__.py",
                    "reason": "sanctioned"}]
        if extra:
            entries.append({"detector": "ghost", "match": "never-matches",
                            "reason": "dead entry"})
        p = tmp_path / "base.json"
        p.write_text(json.dumps({"suppressions": entries}))
        return str(p)

    def test_partial_run_reports_stale_as_note(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        fs = graft_lint.run(models=(), ast=True,
                            baseline_path=self._baseline_file(tmp_path))
        stale = [f for f in fs if f.detector == "stale-suppression"]
        assert len(stale) == 1 and stale[0].severity == "note"
        assert "ghost" in stale[0].message

    def test_full_run_reports_stale_as_warning(self, tmp_path,
                                               monkeypatch):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        for name in ("audit_serving", "audit_obs", "audit_ckpt",
                     "audit_spmd", "audit_conc", "audit_router"):
            monkeypatch.setattr(graft_lint, name, lambda: [])
        monkeypatch.setattr(graft_lint, "audit_model", lambda n: [])
        fs = graft_lint.run(models=graft_lint.CI_MODELS, ast=True,
                            baseline_path=self._baseline_file(tmp_path))
        stale = [f for f in fs if f.detector == "stale-suppression"]
        assert len(stale) == 1 and stale[0].severity == "warning"
        assert analysis.gate_failures(stale), \
            "a stale suppression must fail the full-coverage gate"

    def test_prune_baseline_rewrites_file(self, tmp_path, monkeypatch):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        for name in ("audit_serving", "audit_obs", "audit_ckpt",
                     "audit_spmd", "audit_conc", "audit_router"):
            monkeypatch.setattr(graft_lint, name, lambda: [])
        monkeypatch.setattr(graft_lint, "audit_model", lambda n: [])
        path = self._baseline_file(tmp_path)
        fs = graft_lint.run(models=graft_lint.CI_MODELS, ast=True,
                            baseline_path=path, prune_baseline=True)
        kept = json.load(open(path))["suppressions"]
        assert [e["detector"] for e in kept] == ["ast-x64"]
        assert all("_matched" not in e for e in kept)
        stale = [f for f in fs if f.detector == "stale-suppression"]
        assert stale and all(f.severity == "note" for f in stale)
        assert not analysis.gate_failures(stale)

    def test_prune_on_partial_run_refuses(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        path = self._baseline_file(tmp_path)
        fs = graft_lint.run(models=(), ast=True, baseline_path=path,
                            prune_baseline=True)
        errs = [f for f in fs if f.detector == "stale-suppression"
                and f.severity == "error"]
        assert errs, "pruning on a partial run must refuse loudly"
        assert json.load(open(path))["suppressions"][-1]["detector"] \
            == "ghost", "the file must not be rewritten"

    def test_live_baseline_has_no_stale_entries_on_ast_run(self):
        """The committed baseline's entries all match on a plain AST
        run — if this fails, tools/lint_baseline.json accumulated dead
        entries; run --prune-baseline with the full model set."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        fs = graft_lint.run(models=(), ast=True)
        assert [f for f in fs if f.detector == "stale-suppression"] == []


def test_spmd_smoke_audits_clean():
    """graft_lint's `spmd` smoke: the tp x dp hybrid train step audits
    clean through D1-D11 at default flags on the 8-device virtual mesh,
    and the D9/D10/D11 fire fixtures all still produce warnings — the
    round-15 acceptance gate, in-process so the quick tier covers it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import graft_lint

    findings = graft_lint.audit_spmd()
    bad = [f for f in findings if f.severity in ("warning", "error")]
    assert bad == [], bad
    # round 18: a 4th fixture — D9 through the declarative-partitioner
    # path (all-replicated rule table must still warn)
    fired = [f for f in findings if f.loc == "spmd/fire-fixtures"]
    assert len(fired) == 4 and all(f.severity == "note" for f in fired)
    part = [f for f in findings if f.loc == "spmd/partitioner_step"]
    assert part and not [f for f in part
                         if f.severity in ("warning", "error")]


def test_lint_gate_model_list_includes_spmd():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_scoreboard

    assert "spmd" in check_scoreboard.lint_gate.__defaults__[0]


def test_registered_in_quick_tier():
    src = open(os.path.join(HERE, "conftest.py")).read()
    assert '"test_analysis.py"' in src.split("QUICK_MODULES")[1], \
        "tests/test_analysis.py must be registered in QUICK_MODULES"
