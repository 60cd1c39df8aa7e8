"""`ServingEngine(Cohere2MoeForCausalLM(cfg))`: chunk prefill and then
decode through the two-kind cache against the reference's full forward
pass, the window ring, `paged_decode_attention(kv_start=)`, and the
options this architecture refuses by name. Small sizes, seeded weights,
the CPU backend (Pallas in the interpreter)."""
import time
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_cohere2_moe import LAYERS, TINY, tiny_model

from benchmark.families import cohere2_moe as fam
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.ops import pallas_decode as pd
from paddle_tpu.text.models import parallel_block as pb
from paddle_tpu.text.paged_cache import WindowRing, blocks_for

WINDOW, CHUNK, BS, MAX_LEN = TINY["sliding_window"], 16, 8, 128


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


@pytest.fixture(scope="module")
def served(built):
    """Four greedy requests through one engine of three slots: prompts
    below the window, across it inside one chunk, over several chunks
    (several ring turns), and one that crosses the window while
    decoding; the fourth reuses a slot a longer request left."""
    model, w = built(21)
    eng = _engine(model)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, n) for n in (40, 7, 53, 20)]
    new = [30, 12, 20, 8]
    rids = [eng.add_request(p, max_new_tokens=m)
            for p, m in zip(prompts, new)]
    held = []
    while eng.has_work():
        eng.step()
        held.append((eng.allocator.num_blocks - 1
                     - eng.allocator.available,
                     sum(len(b) for b in eng._slot_blocks)))
    return {"w": w, "eng": eng, "prompts": prompts, "new": new,
            "tokens": [eng.completed[r] for r in rids], "held": held}


def test_prefill_by_chunks_then_decode_agree_with_the_reference(served):
    """(b) Every served position's logits: the served token is the
    float32 reference's argmax at its position (a token is the argmax of
    its logits, so one wrong logit row anywhere past the window would
    change it; the direct logit comparison is the Layer's, test (a))."""
    assert max(len(p) for p in served["prompts"]) > 3 * WINDOW
    for prompt, toks, m in zip(served["prompts"], served["tokens"],
                               served["new"]):
        assert len(toks) == m
        gap, wrong = _served_logits_gap(served["w"], prompt, toks)
        assert wrong == 0 and gap == 0.0, (len(prompt), gap, wrong)


@pytest.mark.parametrize("site", ["chunk", "decode"])
def test_a_dropped_window_mask_is_seen_at_each_site(monkeypatch, site,
                                                    fresh_programs, built):
    """The comparison of (b) fails if the window mask is taken out of
    either attention site the engine has for this architecture."""
    model, w = built(21)
    if site == "chunk":
        monkeypatch.setattr(
            pb, "visible",
            lambda q, k, kind, window: q[:, None] >= k[None, :])
    else:
        real = pd.paged_decode_attention

        def no_start(*a, kv_start=None, name="paged_decode", **kw):
            return real(*a, **kw)

        from paddle_tpu.inference import layered
        monkeypatch.setattr(layered, "paged_decode_attention", no_start)
    fresh_programs()                # the planted fault is traced anew
    eng = _engine(model)
    prompt = np.random.default_rng(4).integers(0, 256, 53)
    rid = eng.add_request(prompt, max_new_tokens=20)
    toks = eng.run()[rid]
    gap, wrong = _served_logits_gap(w, prompt, toks)
    assert wrong > 0 and gap > 1e-3


def test_cache_manager_holds_a_window_and_returns_everything(served):
    """(d) Window pages a slot never pass ceil((W + chunk)/bs) + 1
    whatever the length; the full layers' pages follow the requests and
    all come back; a finished engine holds nothing of either kind."""
    eng = served["eng"]
    bound = blocks_for(WINDOW + CHUNK, BS) + 1
    assert eng.ring.pages == bound
    n_window = sum(eng.cache.sliding)
    assert n_window == 6 and len(eng.cache.k) == LAYERS
    for k, is_sliding in zip(eng.cache.k, eng.cache.sliding):
        want = 1 + 3 * bound if is_sliding else 1 + 3 * (MAX_LEN // BS)
        assert k.shape[0] == want
    # the pool of a window layer is smaller than a full layer's, however
    # long the sequences (73 tokens here against a ring of 40)
    assert eng.ring.tokens_reserved() == bound * BS < 53 + 20
    used, tabled = zip(*served["held"])
    assert max(used) == max(tabled) > 0 and used[-1] == tabled[-1] == 0
    assert eng.allocator.available == eng.allocator.num_blocks - 1
    m = {k: v["samples"][0]["value"] for k, v in eng.metrics().items()
         if k.startswith(("serving_kv_", "serving_moe_"))}
    assert m["serving_kv_full_blocks_used"] == 0
    assert m["serving_kv_window_bytes_held"] == 0
    assert m["serving_moe_routed_tokens_total"] > 0
    share = (m["serving_moe_local_picks_total"]
             / (m["serving_moe_routed_tokens_total"] * 4))
    assert 0.1 < share < 0.5              # 4 of 16 experts held: ~0.25


def test_ring_view_is_the_ring_in_logical_order():
    ring = WindowRing(slots=2, window=16, chunk=16, block_size=8)
    assert ring.pages == 5 and ring.num_blocks == 11
    row, base = ring.view(1, last_pos=7)
    assert base == 0 and list(row) == [6, 7, 8, 9, 10]
    for last in (39, 40, 95, 1000):
        row, base = ring.view(0, last)
        assert base == last // 8 - 4
        # entry i holds absolute page base + i, at ring page (base+i) % 5
        assert list(row) == [1 + (base + i) % 5 for i in range(5)]
        # the window and a chunk before `last` lie inside the view
        assert base * 8 <= last - 16 - 16 + 2


def test_a_reused_slot_serves_the_next_request_exactly():
    """(d) One slot: a long request, then a short one in the same slot
    and ring. Nothing stale is visible: the second is served as by a
    fresh engine, and as the reference has it."""
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


def test_spans_carry_the_expert_and_cache_attributes():
    from paddle_tpu import obs

    model, _ = tiny_model(5, layers=4)
    eng = _engine(model)
    t0 = time.perf_counter()
    eng.add_request(np.arange(30) % 256, max_new_tokens=6)
    eng.run()
    runs = [r for r in obs.span_events() if r.start >= t0
            and r.name in ("serving.decode.run", "serving.chunk.run")]
    assert {r.name for r in runs} == {"serving.decode.run",
                                      "serving.chunk.run"}
    for r in runs:
        a = r.attrs
        assert {"moe_tokens", "moe_local_picks", "moe_max_load",
                "moe_experts_read", "moe_experts_held", "kv_bytes_held",
                "live_tokens"} <= set(a)
        assert a["moe_tokens"] == 4 * (a.get("tokens") or a["active"])
        assert 0 <= a["moe_max_load"] <= a["moe_local_picks"] \
            <= a["moe_tokens"] * 4
        # 4 expert layers x 4 held; the scan (the CPU's path) reads every
        # one (the kernel's count: tests/test_pallas_grouped_experts.py)
        assert a["moe_experts_held"] == 16
        assert a["moe_experts_read"] == 16
        # one slot live: its full pages + 3 window layers' rings
        per_tok = 2 * 2 * 16 * 4
        assert a["kv_bytes_held"] == (
            blocks_for(36, BS) * BS * per_tok
            + 3 * eng.ring.tokens_reserved() * per_tok)
    read = eng.metrics()["serving_moe_experts_read_total"]
    assert read["samples"][0]["value"] == sum(
        r.attrs["moe_experts_read"] for r in runs) > 0


# ------------------------------------------------------------ the kernel

def _decode_case(dtype, seed=0):
    rng = np.random.default_rng(seed)
    s, hq, hkv, d, bs, p = 5, 8, 2, 128, 16, 12
    n = 1 + s * p
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    return (mk(s, hq, d), mk(n, hkv, bs, d), mk(n, hkv, bs, d),
            jnp.asarray(1 + rng.permutation(s * p).reshape(s, p),
                        jnp.int32),
            jnp.asarray([p * bs, 100, 37, 64, 1], jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("starts", [
    [5, 3, 20, 47, 0],          # inside a block
    [32, 64, 16, 48, 0],        # at a block boundary
    [100, 95, 36, 63, 0],       # past boundaries, up to the last position
], ids=["inside", "at", "past"])
def test_paged_decode_kv_start_matches_the_composition(dtype, tol, starts):
    """(e) The kernel in the interpreter against the XLA composition:
    pages before `kv_start` are skipped, the first live one is masked
    from below. bf16: both round the probabilities to bf16 before the
    second product, in another order of summation (2e-2 on outputs of
    size ~1); float32: summation order alone."""
    q, kc, vc, tabs, lens = _decode_case(dtype)
    st = jnp.asarray(starts, jnp.int32)
    want = pd.paged_decode_attention_xla(q, kc, vc, tabs, lens, kv_start=st)
    # the composition itself against attention written out, row 1
    k1 = jnp.swapaxes(kc[tabs[1]], 1, 2).reshape(-1, 2, 128)
    v1 = jnp.swapaxes(vc[tabs[1]], 1, 2).reshape(-1, 2, 128)
    lo, hi = starts[1], int(lens[1])
    for h in (0, 5):
        sc = (k1[lo:hi, h // 4].astype(jnp.float32)
              @ q[1, h].astype(jnp.float32)) / np.sqrt(128)
        o = jax.nn.softmax(sc) @ v1[lo:hi, h // 4].astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(want[1, h], np.float32),
                                   np.asarray(o), atol=10 * tol, rtol=0)
    for pps in (None, 2, 4):
        got = pd.paged_decode_attention_raw(
            q, kc, vc, tabs, lens, kv_start=st, pages_per_step_=pps,
            name="paged_window_decode")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=0)


def _kernel_calls(jaxpr) -> dict:
    """{kernel name: [operands of its call]} of the Pallas calls in a
    program's jaxpr."""
    found = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.setdefault(eqn.params["name"], []).append(
                    len(eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_without_kv_start_the_dense_models_decode_program_is_unchanged():
    """(e) The LLaMA-family decode program holds the `paged_decode` call
    it held before — one in the layer scan, its five operands (table,
    lengths, q, k, v: no `kv_start`) — and none by the windowed name."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=256,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=2048)
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, max_slots=4, kv_block_size=16,
                        max_model_len=2048, prefix_cache=False)
    with mock.patch.object(pd, "use_pallas_decode", lambda *a, **k: True):
        calls = _kernel_calls(eng.decode_program_jaxpr(bucket=4))
    assert calls == {"paged_decode": [5]}


def test_layered_decode_program_names_its_two_kernels():
    """A window layer's call carries `paged_window_decode` and a sixth
    operand (`kv_start`); the full layer's is the dense models' call. The
    new name does not contain the old: `trace_reduce.kernel_ns` matches
    event names by substring."""
    model, _ = tiny_model(1, layers=4)
    eng = _engine(model)
    with mock.patch.object(pd, "use_pallas_decode", lambda *a, **k: True):
        calls = _kernel_calls(eng.decode_program_jaxpr(bucket=2))
    assert calls == {"paged_window_decode": [6, 6, 6], "paged_decode": [5]}
    assert "paged_decode" not in "paged_window_decode"


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("kwargs,names", [
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'"),
    ({"kv_cache_dtype": "int4"}, "kv_cache_dtype='int4'"),
    ({"weight_quant": "int8"}, "weight_quant='int8'"),
    ({"weight_quant": "int4"}, "weight_quant='int4'"),
    ({"spec_decode": "ngram"}, "spec_decode='ngram'"),
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"chunked_prefill_tokens": 0}, "chunked_prefill_tokens=0"),
])
def test_unsupported_options_raise_by_name(kwargs, names):
    """(f) What this architecture does not get yet is refused at
    construction, by the option's name; nothing falls back."""
    model, _ = tiny_model(1, layers=4)
    with pytest.raises(ValueError, match="not supported for cohere2_moe") \
            as e:
        _engine(model, **kwargs)
    assert names in str(e.value)


def test_the_static_engine_refuses_the_architecture():
    from paddle_tpu.text import generation

    model, _ = tiny_model(1, layers=4)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="cohere2_moe"):
        generation.generate(model, ids, max_new_tokens=2)
    with pytest.raises(ValueError, match="static"):
        model.generate(ids, max_new_tokens=2, engine="static")
    out = model.generate(ids, max_new_tokens=3, kv_block_size=BS,
                         max_model_len=MAX_LEN,
                         chunked_prefill_tokens=CHUNK)
    assert out.shape == (1, 3)


def test_defaults_serve_the_architecture():
    """`ServingEngine(model)` with no option: the flags' defaults (prefix
    cache on by flag) do not refuse it; the prefix cache is simply off."""
    model, _ = tiny_model(1, layers=4)
    eng = ServingEngine(model)
    assert eng.prefix_cache_enabled is False and eng.layered is not None
    rid = eng.add_request(np.arange(20) % 256, max_new_tokens=4)
    assert len(eng.run()[rid]) == 4
