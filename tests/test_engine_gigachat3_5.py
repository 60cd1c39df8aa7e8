"""`ServingEngine(GigaChat35ForCausalLM(cfg))`: chunk prefill (chunk edges
inside and on the WY form's sub-chunk edges) and then decode through the
slot states and the latent pages against the reference's full forward
pass; a stale or unreset state, the decay and beta left out, each failing
it; bucket rows and chunk padding leaving every other slot's state bit
for bit; a reused slot serving as a fresh one; `gated_delta_decode`
forced through the interpreter against the composition's tokens; the
spans' and registry's state counts; and the options refused by name.
Small sizes, seeded weights, the CPU backend (Pallas in the
interpreter)."""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest
from test_engine_cohere2_moe import _kernel_calls
from test_gigachat3_5 import LAYERS, TINY, tiny_model

from benchmark.families import gigachat3_5 as fam
from paddle_tpu.inference import layered
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.ops import pallas_gated_delta as pgd
from paddle_tpu.text.models import GigaChat35Config
from paddle_tpu.text.models import gated_delta_block as gd

CHUNK, BS, MAX_LEN, WY = 16, 8, 128, 8


@pytest.fixture
def wy8(monkeypatch, fresh_programs):
    """The WY form 8 rows a sub-chunk (a chunk of 16 is two of them):
    the engine's chunks then end inside and on sub-chunk edges."""
    real = GigaChat35Config.block_spec
    monkeypatch.setattr(GigaChat35Config, "block_spec",
                        lambda self: dataclasses.replace(real(self),
                                                         wy_chunk=WY))
    fresh_programs()                # the patched spec is traced anew


def _engine(model, **kw):
    args = dict(max_slots=3, kv_block_size=BS, max_model_len=MAX_LEN,
                chunked_prefill_tokens=CHUNK)
    args.update(kw)
    return ServingEngine(model, **args)


def _served_logits_gap(w, prompt, toks):
    """(widest gap by which a served token's reference logit lies below
    the reference's best at its position, tokens that are not the
    reference's argmax): teacher-forced on what was served."""
    seq = np.concatenate([prompt, toks[:-1]])
    ids = np.zeros(MAX_LEN, np.int64)
    ids[:len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(toks))
    ref = fam.reference_rows(TINY, LAYERS, w, ids, rows)
    gap = ref.max(-1) - ref[np.arange(len(toks)), toks]
    return float(gap.max()), int((ref.argmax(-1) != toks).sum())


#: prompts that end inside a chunk and a sub-chunk (37, 5, 53), on a
#: chunk's edge (32), on a sub-chunk's edge inside a chunk (24), of one
#: whole chunk (16)
PROMPTS = (37, 5, 53, 32, 24, 16)
NEW = (30, 12, 20, 8, 9, 17)


@pytest.fixture(scope="module")
def served(built, forget_programs):
    """Six greedy requests through one engine of three slots, slow decays
    (`dt_bias` -4): slots are reused, a state carries far."""
    with pytest.MonkeyPatch.context() as m:
        real = GigaChat35Config.block_spec
        m.setattr(GigaChat35Config, "block_spec",
                  lambda self: dataclasses.replace(real(self), wy_chunk=WY))
        forget_programs()           # the patched spec is traced anew
        model, w = built(21, dt_bias=-4.0)
        eng = _engine(model)
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 256, n) for n in PROMPTS]
        rids = [eng.add_request(p, max_new_tokens=n)
                for p, n in zip(prompts, NEW)]
        eng.run()
    forget_programs()               # nothing traced with WY 8 outlives it
    return {"w": w, "eng": eng, "prompts": prompts,
            "tokens": [eng.completed[r] for r in rids]}


def test_prefill_by_chunks_then_decode_agree_with_the_reference(served):
    """Every served position: the served token is the float32 reference's
    argmax (one wrong state or cached row anywhere in its past changes
    the logits; the direct comparison of logits is the Layer's)."""
    for prompt, toks, n in zip(served["prompts"], served["tokens"], NEW):
        assert len(toks) == n
        gap, wrong = _served_logits_gap(served["w"], prompt, toks)
        assert wrong == 0 and gap == 0.0, (len(prompt), gap, wrong)


@pytest.mark.parametrize("fault", ["decay", "beta", "stale_state"])
def test_a_planted_fault_fails_the_served_comparison(monkeypatch, wy8,
                                                     fault, fresh_programs,
                                                     built):
    """The comparison above fails with the decay g left out (alpha = 1),
    with beta left out (= 1), and with a slot's state NOT reset where a
    new request's first chunk starts (the last request's state and conv
    inputs carried into the next): one slot, two requests."""
    model, w = built(21, dt_bias=-4.0)
    if fault == "stale_state":
        monkeypatch.setattr(layered, "_slot_state",
                            lambda pool, slot, fresh: pool[slot])
    else:
        real = gd.delta_inputs

        def planted(*a, **k):
            q, kk, v, beta, g = real(*a, **k)
            if fault == "decay":
                return q, kk, v, beta, jnp.zeros_like(g)
            return q, kk, v, jnp.ones_like(beta), g
        monkeypatch.setattr(gd, "delta_inputs", planted)
    fresh_programs()                # the planted fault is traced anew
    eng = _engine(model, max_slots=1)
    rng = np.random.default_rng(4)
    eng.add_request(rng.integers(0, 256, 40), max_new_tokens=12)
    prompt = rng.integers(0, 256, 11)
    rid = eng.add_request(prompt, max_new_tokens=16)
    toks = eng.run()[rid]
    gap, wrong = _served_logits_gap(w, prompt, toks)
    assert wrong > 0 and gap > 1e-3


def test_other_slots_states_are_untouched_bit_for_bit(wy8):
    """Four slots hold what four finished requests left; three new ones
    take slots 0-2: every decode tick runs a bucket of 4 with a row that
    holds no request (it names the trash slot), every chunk is padded
    past its prompt. Slot 3's state and conv inputs do not move by a
    bit; the trash slot is the pool's last."""
    from paddle_tpu import obs

    model, _ = tiny_model(8)
    eng = _engine(model, max_slots=4)
    rng = np.random.default_rng(8)
    for n in (9, 12, 5, 7):
        eng.add_request(rng.integers(0, 256, n), max_new_tokens=3)
    eng.run()
    lin = [i for i, k in enumerate(eng.cache.kinds) if k == "recurrent"]
    assert len(lin) == 3 and eng.cache.k[lin[0]].shape[0] == 4 + 1
    before = [(np.asarray(eng.cache.k[i][3]), np.asarray(eng.cache.v[i][3]))
              for i in lin]
    assert all(np.abs(st).max() > 0 for st, _ in before)
    t0 = time.perf_counter()
    for n in (21, 9, 30):
        eng.add_request(rng.integers(0, 256, n), max_new_tokens=6)
    eng.run()
    ticks = [r.attrs for r in obs.span_events()
             if r.start >= t0 and r.name == "serving.decode.run"]
    assert any(t["active"] == 3 and t["bucket"] == 4 for t in ticks)
    for i, (st, conv) in zip(lin, before):
        np.testing.assert_array_equal(np.asarray(eng.cache.k[i][3]), st)
        np.testing.assert_array_equal(np.asarray(eng.cache.v[i][3]), conv)


def test_a_reused_slot_serves_the_next_request_exactly(wy8):
    """One slot: a long request, then a short one in the same slot. The
    second is served as by a fresh engine, and as the reference has it."""
    model, w = tiny_model(33, dt_bias=-4.0)
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


def test_the_decode_kernel_serves_the_compositions_tokens(monkeypatch,
                                                          wy8,
                                                          fresh_programs):
    """`gated_delta_decode` forced through the interpreter where the
    router keeps the composition (the CPU backend, values of 16 lanes):
    the same tokens, and the decode program names the kernel."""
    model, _ = tiny_model(12, dt_bias=-4.0)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 256, n) for n in (20, 7, 33)]

    def serve():
        eng = _engine(model)
        rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
        eng.run()
        return eng, [eng.completed[r] for r in rids]

    _, plain = serve()
    monkeypatch.setattr(pgd, "use_kernel", lambda shape: True)
    fresh_programs()                # the forced kernel is traced anew
    eng, forced = serve()
    for a, b in zip(plain, forced):
        np.testing.assert_array_equal(a, b)
    calls = _kernel_calls(eng.decode_program_jaxpr(bucket=4))
    # slots, beta, alpha, k|q, v, the state pool: one call a linear layer
    assert calls["gated_delta_decode"] == [6] * 3


def test_spans_and_the_registry_count_the_state():
    from paddle_tpu import obs

    model, _ = tiny_model(5)
    eng = _engine(model)
    t0 = time.perf_counter()
    eng.add_request(np.arange(30) % 256, max_new_tokens=6)
    eng.run()
    mine = [r for r in obs.span_events() if r.start >= t0]
    chunks = [r.attrs for r in mine if r.name == "serving.chunk.run"]
    decodes = [r.attrs for r in mine if r.name == "serving.decode.run"]
    # 3 linear layers: a chunk's prompt tokens, a tick's one slot
    assert [c["state_tokens"] for c in chunks] == [3 * 16, 3 * 14]
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert [d["state_slots"] for d in decodes] == [3] * 5
    assert {d["state_bytes_held"] for d in decodes} == {per_slot}
    # 3 expert layers x 4 held; the scan (the CPU's path) reads every one
    for a in chunks + decodes:
        assert a["moe_experts_held"] == 12
        assert a["moe_experts_read"] == 12
    assert eng.cache.state_bytes_per_slot() == per_slot
    # the MLA layer alone attends latent rows
    assert [c["attn_pairs"] for c in chunks] == [
        16 * 17 // 2, 14 * 16 + 14 * 15 // 2]
    m = {k: v["samples"][0]["value"] for k, v in eng.metrics().items()
         if k.startswith("serving_state") or k.startswith(
             "serving_recurrent")}
    assert m["serving_state_tokens_total"] == 3 * 30
    assert m["serving_state_slot_steps_total"] == 3 * 5
    assert m["serving_recurrent_state_bytes_held"] == 0      # drained
    read = eng.metrics()["serving_moe_experts_read_total"]
    assert read["samples"][0]["value"] == sum(
        a["moe_experts_read"] for a in chunks + decodes)
    # every host value of a tick is the one packed operand
    builds = [r.attrs["h2d"] for r in mine
              if r.name == "serving.decode.build"]
    assert builds[1:] == [1] * 4


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("kwargs,names,why", [
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'", "recurrent state"),
    ({"weight_quant": "int8"}, "weight_quant='int8'", "own buffers"),
    ({"spec_decode": "ngram"}, "spec_decode='ngram'", "recurrent state"),
    ({"prefix_cache": True}, "prefix_cache=True", "recurrent state"),
    ({"chunked_prefill_tokens": 0}, "chunked_prefill_tokens=0",
     "recurrent state"),
])
def test_unsupported_options_raise_by_name(kwargs, names, why):
    """What this architecture does not get yet is refused at
    construction, by the option's name, with the reasons of BOTH kinds of
    layer state it keeps (the recurrent state and the latent pages)."""
    model, _ = tiny_model(1)
    with pytest.raises(ValueError,
                       match="not supported for gigachat3_5") as e:
        _engine(model, **kwargs)
    assert names in str(e.value) and why in str(e.value)


def test_refusals_join_the_reasons_of_every_kind():
    spec = GigaChat35Config(num_hidden_layers=4,
                            full_attention_layers=(3,)).block_spec()
    why = layered.refusals(spec)
    assert "recurrent state" in why["prefix_cache"] \
        and "latent pages" in why["prefix_cache"]
    assert "float32 matrix" in why["kv_cache_dtype"] \
        and "latent pool" in why["kv_cache_dtype"]
    assert "rolled back" in why["spec_decode"]
    linear_only = dataclasses.replace(
        spec, attn_types=("linear_attention",) * 4)
    assert "latent" not in layered.refusals(linear_only)["prefix_cache"]


def test_the_static_engine_refuses_the_architecture():
    from paddle_tpu.text import generation

    model, _ = tiny_model(1)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="gigachat3_5"):
        generation.generate(model, ids, max_new_tokens=2)
    with pytest.raises(ValueError, match="static"):
        model.generate(ids, max_new_tokens=2, engine="static")
    out = model.generate(ids, max_new_tokens=3, kv_block_size=BS,
                         max_model_len=MAX_LEN,
                         chunked_prefill_tokens=CHUNK)
    assert out.shape == (1, 3)


def test_defaults_serve_the_architecture():
    """`ServingEngine(model)` with no option: the flags' defaults do not
    refuse it; the prefix cache is simply off."""
    model, _ = tiny_model(1)
    eng = ServingEngine(model)
    assert eng.prefix_cache_enabled is False and eng.layered is not None
    rid = eng.add_request(np.arange(20) % 256, max_new_tokens=4)
    assert len(eng.run()[rid]) == 4
