"""`ServingEngine(PanguUltraMoEForCausalLM(cfg))`: chunk prefill and then
decode through the paged latent cache against the reference's full forward
pass, `paged_latent_decode` in the interpreter against its XLA
composition, the chunk kernel (`latent_chunk_attn`) forced through the
interpreter against the composition's tokens, the latent pool's bytes, the
spans' new attributes, and the options this architecture refuses by name.
Small sizes, seeded weights, the CPU backend (Pallas in the interpreter)."""
import functools
import time
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_engine_cohere2_moe import _kernel_calls
from test_pangu_ultra_moe import LAYERS, TINY, tiny_model

from benchmark.families import pangu_ultra_moe as fam
from paddle_tpu.inference import layered
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.ops import pallas_decode as pd
from paddle_tpu.ops import pallas_latent_chunk as plc
from paddle_tpu.text.models import latent_block as lb
from paddle_tpu.text.paged_cache import blocks_for, latent_row_width

CHUNK, BS, MAX_LEN = 16, 8, 128


def _engine(model, **kw):
    args = dict(max_slots=3, kv_block_size=BS, max_model_len=MAX_LEN,
                chunked_prefill_tokens=CHUNK)
    args.update(kw)
    return ServingEngine(model, **args)


def _served_logits_gap(w, prompt, toks):
    """(widest gap by which a served token's reference logit lies below
    the reference's best at its position, tokens that are not the
    reference's argmax) — teacher-forced on what was served."""
    seq = np.concatenate([prompt, toks[:-1]])
    ids = np.zeros(MAX_LEN, np.int64)
    ids[:len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(toks))
    ref = fam.reference_rows(TINY, LAYERS, w, ids, rows)
    gap = ref.max(-1) - ref[np.arange(len(toks)), toks]
    return float(gap.max()), int((ref.argmax(-1) != toks).sum())


#: prompts that end inside a page and a chunk (40 + ..., 7, 53), ON a
#: chunk boundary (32 = 2 chunks = 4 pages), on a page boundary inside a
#: chunk (24) and of one whole chunk (16)
PROMPTS = (40, 7, 53, 32, 24, 16)
NEW = (30, 12, 20, 8, 9, 17)


@pytest.fixture(scope="module")
def served(built, forget_programs):
    """Six greedy requests through one engine of three slots: slots are
    reused, decode crosses page boundaries, the context blocks of the
    chunk attention (patched to 32 positions) are merged online."""
    model, w = built(21)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(lb, "CTX_BLOCK", 32)
        eng = _engine(model)
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 256, n) for n in PROMPTS]
        rids = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts, NEW)]
        held = []
        while eng.has_work():
            eng.step()
            held.append((eng.allocator.num_blocks - 1
                         - eng.allocator.available,
                         sum(len(b) for b in eng._slot_blocks)))
    forget_programs()               # programs traced with CTX_BLOCK 32
    return {"w": w, "eng": eng, "prompts": prompts,
            "tokens": [eng.completed[r] for r in rids], "held": held}


def test_prefill_by_chunks_then_decode_agree_with_the_reference(served):
    """Every served position's logits: the served token is the float32
    reference's argmax at its position (a token is the argmax of its
    logits, so one wrong cached row anywhere in its context would change
    it; the direct logit comparison is the Layer's)."""
    for prompt, toks, n in zip(served["prompts"], served["tokens"], NEW):
        assert len(toks) == n
        gap, wrong = _served_logits_gap(served["w"], prompt, toks)
        assert wrong == 0 and gap == 0.0, (len(prompt), gap, wrong)


@pytest.mark.parametrize("fault", ["rotary", "sandwich"])
def test_a_planted_fault_fails_the_served_comparison(monkeypatch, fault,
                                                     fresh_programs, built):
    """The comparison above fails with the rotary part of the score left
    out (`q_rope . kr` = 0: neither cached nor attended) and with the
    sandwich norms skipped, through chunk prefill and decode alike."""
    model, w = built(21)
    if fault == "rotary":
        monkeypatch.setattr(lb, "rope_half",
                            lambda x, cos, sin: jnp.zeros_like(x))
    else:
        monkeypatch.setattr(lb, "sandwich_add",
                            lambda x, y, gain, eps: x + y.astype(x.dtype))
    fresh_programs()                # the planted fault is traced anew
    eng = _engine(model)
    prompt = np.random.default_rng(4).integers(0, 256, 53)
    rid = eng.add_request(prompt, max_new_tokens=20)
    toks = eng.run()[rid]
    gap, wrong = _served_logits_gap(w, prompt, toks)
    assert wrong > 0 and gap > 1e-3


def test_the_latent_pool_holds_one_row_a_position(served):
    """One array a layer, `[N, 1, block_size, W]` with W the 32 + 8 values
    of a position rounded up to 128 lanes, no V, no ring; its bytes a
    token; the allocator's pages follow the requests and all come back."""
    eng = served["eng"]
    c = eng.cache
    assert eng.ring is None and c.kinds == ("latent",) * LAYERS
    assert c.v == (None,) * LAYERS
    assert len(c.k) == LAYERS and c.sliding == (False,) * LAYERS
    w = latent_row_width(TINY["kv_lora_rank"] + TINY["qk_rope_head_dim"])
    assert w == 128 and latent_row_width(576) == 640
    for pool in c.k:
        assert pool.shape == (1 + 3 * (MAX_LEN // BS), 1, BS, w)
    assert c.bytes_per_token(False) == LAYERS * w * 4
    assert c.bytes_per_token(True) == 0
    assert c.hbm_bytes == LAYERS * (1 + 3 * 16) * BS * w * 4
    used, tabled = zip(*served["held"])
    assert max(used) == max(tabled) > 0 and used[-1] == tabled[-1] == 0
    assert eng.allocator.available == eng.allocator.num_blocks - 1
    m = {k: v["samples"][0]["value"] for k, v in eng.metrics().items()
         if k.startswith(("serving_kv_", "serving_moe_", "serving_latent"))}
    assert m["serving_kv_full_blocks_used"] == 0
    assert m["serving_kv_latent_bytes_held"] == 0
    assert m["serving_kv_window_bytes_held"] == 0
    # every prompt position attends the positions up to its own, every
    # decode token its whole context, once a layer
    want = sum(p * (p + 1) // 2 + sum(p + j for j in range(1, n))
               for p, n in zip(PROMPTS, NEW))
    assert m["serving_latent_ctx_tokens_total"] == LAYERS * want
    assert m["serving_latent_chunk_kernel_blocks_total"] == 0
    # two expert layers of three; 4 of 16 experts held, top-4: ~1 pick
    tokens = sum(PROMPTS) + sum(n - 1 for n in NEW)
    assert m["serving_moe_routed_tokens_total"] == 2 * tokens
    share = (m["serving_moe_local_picks_total"]
             / (m["serving_moe_routed_tokens_total"] * 4))
    assert 0.1 < share < 0.5


def test_a_reused_slot_serves_the_next_request_exactly():
    """One slot: a long request, then a short one in the same slot and
    pages. Nothing stale is visible: the second is served as by a fresh
    engine, and as the reference has it."""
    model, w = tiny_model(33)
    rng = np.random.default_rng(33)
    long_p, short_p = rng.integers(0, 256, 70), rng.integers(0, 256, 9)
    eng = _engine(model, max_slots=1)
    first = eng.add_request(long_p, max_new_tokens=40)
    eng.run()
    second = eng.add_request(short_p, max_new_tokens=25)
    toks = eng.run()[second]
    assert len(eng.completed[first]) == 40
    fresh = _engine(model, max_slots=1)
    rid = fresh.add_request(short_p, max_new_tokens=25)
    np.testing.assert_array_equal(toks, fresh.run()[rid])
    gap, wrong = _served_logits_gap(w, short_p, toks)
    assert wrong == 0 and gap == 0.0


def test_spans_carry_the_latent_attributes_and_one_transfer_a_decode():
    from paddle_tpu import obs

    model, _ = tiny_model(5)
    eng = _engine(model)
    t0 = time.perf_counter()
    eng.add_request(np.arange(30) % 256, max_new_tokens=6)
    eng.run()
    mine = [r for r in obs.span_events() if r.start >= t0]
    runs = [r for r in mine
            if r.name in ("serving.decode.run", "serving.chunk.run")]
    assert {r.name for r in runs} == {"serving.decode.run",
                                      "serving.chunk.run"}
    per_tok = LAYERS * 128 * 4
    for r in runs:
        a = r.attrs
        assert {"moe_tokens", "moe_local_picks", "moe_max_load",
                "moe_experts_read", "moe_experts_held", "kv_bytes_held",
                "live_tokens"} <= set(a)
        # two expert layers; one slot live: its pages, held ahead
        assert a["moe_tokens"] == 2 * (a.get("tokens") or a["active"])
        assert 0 <= a["moe_max_load"] <= a["moe_local_picks"] \
            <= a["moe_tokens"] * 4
        # 2 expert layers x 4 held (the dense layer holds none); the scan
        # (the CPU's path) reads every one
        assert a["moe_experts_held"] == 8
        assert a["moe_experts_read"] == 8
        assert a["kv_bytes_held"] == blocks_for(36, BS) * BS * per_tok
    chunks = [r.attrs for r in runs if r.name == "serving.chunk.run"]
    assert [c["attn_pairs"] for c in chunks] == [
        LAYERS * (16 * 17 // 2), LAYERS * (14 * 16 + 14 * 15 // 2)]
    # off the chip the composition runs: no block went through the kernel
    assert [c["attn_kernel_blocks"] for c in chunks] == [0, 0]
    assert all("ctx_tokens" not in c for c in chunks)
    decodes = [r.attrs for r in runs if r.name == "serving.decode.run"]
    assert [d["ctx_tokens"] for d in decodes] == [
        LAYERS * (31 + j) for j in range(5)]
    # every host value reaches a program as the one packed operand (a
    # chunk's ids are an operand of their own)
    builds = {n: [r.attrs["h2d"] for r in mine if r.name == n]
              for n in ("serving.decode.build", "serving.chunk.build")}
    assert builds["serving.decode.build"][1:] == [1] * 4
    assert builds["serving.chunk.build"][1:] == [2]


# ------------------------------------------------------ the chunk kernel

@pytest.fixture
def chunk_kernel_forced(monkeypatch, fresh_programs):
    """The chunk kernel in the interpreter where the router would keep the
    composition (the CPU backend, widths of 16 and 8), a step of it 32
    context positions so that a prompt's later chunks merge blocks. The
    choice is no part of a program's key: the executables are dropped on
    both sides."""
    monkeypatch.setattr(plc, "use_latent_chunk_kernel",
                        lambda *a, **k: True)
    monkeypatch.setattr(plc, "_CTX_BLOCKS", (32,))
    fresh_programs()                # the forced choice is traced anew


def test_the_chunk_kernel_serves_the_compositions_tokens(
        served, chunk_kernel_forced, built):
    """The same six requests with every chunk's attention through the
    kernel: the tokens the composition served, which are the float32
    reference's argmax at every position."""
    model, w = built(21)
    eng = _engine(model)
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(served["prompts"], NEW)]
    eng.run()
    for rid, prompt, toks in zip(rids, served["prompts"],
                                 served["tokens"]):
        np.testing.assert_array_equal(eng.completed[rid], toks)
        gap, wrong = _served_logits_gap(w, prompt, eng.completed[rid])
        assert wrong == 0 and gap == 0.0, (len(prompt), gap, wrong)


def test_chunk_spans_count_the_kernels_blocks(chunk_kernel_forced):
    """`attn_kernel_blocks` on every `serving.chunk.run`: the blocks of 32
    positions that hold [0, start + 16), the padded chunk's end, once a
    latent layer; host arithmetic beside `attn_pairs`."""
    from paddle_tpu import obs

    model, _ = tiny_model(5)
    eng = _engine(model)
    t0 = time.perf_counter()
    eng.add_request(np.arange(70) % 256, max_new_tokens=2)
    eng.run()
    chunks = [r.attrs for r in obs.span_events()
              if r.start >= t0 and r.name == "serving.chunk.run"]
    assert [c["start"] for c in chunks] == [0, 16, 32, 48, 64]
    assert [c["attn_kernel_blocks"] for c in chunks] == [
        LAYERS * b for b in (1, 1, 2, 2, 3)]
    assert all(c["attn_pairs"] > 0 for c in chunks)
    total = eng.metrics()["serving_latent_chunk_kernel_blocks_total"]
    assert total["samples"][0]["value"] == LAYERS * 9


def test_the_chunk_program_names_its_kernel(chunk_kernel_forced):
    """One `latent_chunk_attn` call a layer with five operands (the
    chunk's first position, both query parts, Wkvb, the table's rows) in
    the chunk program, with and without the first token: no hidden
    fallback. On the composition's route the program holds no kernel."""
    model, _ = tiny_model(1)
    eng = _engine(model)
    lp, c = eng.programs, eng.cache
    ids = jnp.zeros((1, CHUNK), jnp.int32)
    ints = jnp.zeros(5 + eng.pages, jnp.int32)
    samp = eng._samp([], 1, False)

    def calls(emit_token):
        fn = functools.partial(layered._chunk_impl, lp.spec, False,
                               emit_token, eng.pages)
        return _kernel_calls(jax.make_jaxpr(fn)(
            eng.params, ids, ints, c.k, c.v, eng._last, samp, eng._key))

    for emit_token in (False, True):
        assert calls(emit_token) == {plc.NAME: [5] * LAYERS}
    with mock.patch.object(plc, "use_latent_chunk_kernel",
                           lambda *a, **k: False):
        assert calls(False) == {}


# ----------------------------------------------------- the decode kernel

def _latent_case(dtype, seed=0):
    """5 slots over 12 pages of 16 rows, 256 wide with 128 value columns;
    ragged lengths, a row of length 1, and a padding row whose table is
    the trash block and whose length is 0."""
    rng = np.random.default_rng(seed)
    s, h, w, bs, p = 6, 16, 256, 16, 12
    n = 1 + s * p
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    tabs = 1 + rng.permutation(s * p).reshape(s, p)
    tabs[5] = 0
    return (mk(s, h, w), mk(n, 1, bs, w), jnp.asarray(tabs, jnp.int32),
            jnp.asarray([p * bs, 100, 37, 64, 1, 0], jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_latent_decode_matches_the_composition(dtype, tol):
    """The kernel in the interpreter against the XLA composition, and the
    composition against attention written out. bf16: both round the
    probabilities to bf16 before the second product, in another order of
    summation (2e-2 on outputs of size ~1); float32: summation order."""
    q, pool, tabs, lens = _latent_case(dtype)
    vc, scale = 128, 0.05
    want = pd.paged_latent_decode_xla(q, pool, tabs, lens, vc, scale)
    rows = pool[tabs[1]].reshape(-1, 256)[:100].astype(jnp.float32)
    for h in (0, 9):
        sc = rows @ q[1, h].astype(jnp.float32) * scale
        o = jax.nn.softmax(sc) @ rows[:, :vc]
        np.testing.assert_allclose(np.asarray(want[1, h], np.float32),
                                   np.asarray(o), atol=10 * tol, rtol=0)
    for pps in (None, 2, 4):
        got = pd.paged_latent_decode_raw(q, pool, tabs, lens, vc, scale,
                                         pages_per_step_=pps)
        assert got.shape == (6, 16, vc) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got[:5], np.float32),
                                   np.asarray(want[:5], np.float32),
                                   atol=tol, rtol=0)
        assert np.isfinite(np.asarray(got[5], np.float32)).all()


def test_the_gate_knows_the_latent_widths():
    ok = pd.decode_gate_reason(1 << 20, "bfloat16", "tpu", head_dim=640,
                               block_size=16, latent_cols=512)
    assert ok[1] == "warning"
    for kw in ({"head_dim": 576}, {"latent_cols": 500},
               {"dtype": "int8"}, {"block_size": 12}):
        args = dict(n_elems=1 << 20, dtype="bfloat16", platform="tpu",
                    head_dim=640, block_size=16, latent_cols=512)
        args.update(kw)
        assert pd.decode_gate_reason(**args)[1] == "note", kw
    assert pd.decode_gate_reason(1 << 20, "bfloat16", "cpu", head_dim=640,
                                 block_size=16, latent_cols=512)[1] == "note"


def test_the_decode_program_names_its_kernel():
    """One `paged_latent_decode` call a layer with four operands (table,
    lengths, q, the ONE pool), no `paged_decode`: no hidden fallback. The
    name does not contain the dense kernels' (`trace_reduce.kernel_ns`
    matches event names by substring)."""
    model, _ = tiny_model(1)
    eng = _engine(model)
    with mock.patch.object(pd, "use_pallas_latent_decode",
                           lambda *a, **k: True):
        calls = _kernel_calls(eng.decode_program_jaxpr(bucket=2))
    assert calls == {"paged_latent_decode": [4] * LAYERS}
    assert "paged_decode" not in "paged_latent_decode"
    assert "paged_latent_decode" not in "paged_window_decode"


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("kwargs,names,why", [
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'", "latent pool"),
    ({"kv_cache_dtype": "int4"}, "kv_cache_dtype='int4'", "latent pool"),
    ({"weight_quant": "int8"}, "weight_quant='int8'", "own buffers"),
    ({"weight_quant": "int4"}, "weight_quant='int4'", "own buffers"),
    ({"spec_decode": "ngram"}, "spec_decode='ngram'", "latent cache"),
    ({"prefix_cache": True}, "prefix_cache=True", "latent pages"),
    ({"chunked_prefill_tokens": 0}, "chunked_prefill_tokens=0",
     "by chunks"),
])
def test_unsupported_options_raise_by_name(kwargs, names, why):
    """What this architecture does not get yet is refused at
    construction, by the option's name, with a reason that is true of a
    latent cache (no ring, no window); nothing falls back."""
    model, _ = tiny_model(1)
    with pytest.raises(ValueError,
                       match="not supported for pangu_ultra_moe") as e:
        _engine(model, **kwargs)
    assert names in str(e.value) and why in str(e.value)
    assert "window" not in str(e.value) and "ring" not in str(e.value)


def test_the_refusals_of_a_window_model_still_name_its_ring():
    from paddle_tpu.text.models import cohere2_moe_tiny_config

    why = layered.refusals(cohere2_moe_tiny_config().block_spec())
    assert "ring has no per-block scales" in why["kv_cache_dtype"]
    assert "two-kind" in why["spec_decode"]
    assert "ring is sized by the chunk" in why["chunked_prefill_tokens"]


def test_the_static_engine_refuses_the_architecture():
    from paddle_tpu.text import generation

    model, _ = tiny_model(1)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="pangu_ultra_moe"):
        generation.generate(model, ids, max_new_tokens=2)
    with pytest.raises(ValueError, match="static"):
        model.generate(ids, max_new_tokens=2, engine="static")
    out = model.generate(ids, max_new_tokens=3, kv_block_size=BS,
                         max_model_len=MAX_LEN,
                         chunked_prefill_tokens=CHUNK)
    assert out.shape == (1, 3)


def test_defaults_serve_the_architecture():
    """`ServingEngine(model)` with no option: the flags' defaults (prefix
    cache on by flag, pages of 16, chunks of 256 over a table of 128
    positions) do not refuse it; the prefix cache is simply off."""
    model, _ = tiny_model(1)
    eng = ServingEngine(model)
    assert eng.prefix_cache_enabled is False and eng.layered is not None
    rid = eng.add_request(np.arange(20) % 256, max_new_tokens=4)
    assert len(eng.run()[rid]) == 4
