"""`ServingEngine(Qwen3NextForCausalLM(cfg))`: chunk prefill (the full
layer attending its K/V pages a context block at a time, a prompt over
three or more blocks) and then decode through the slot states and the
pages, against the reference's full forward pass, at several chunk sizes
and context blocks; the spans' counts; the options refused by name; and
the programs of the other per-layer models lowering to the text they
lowered to before the hybrid block took a second kind of full layer.
Small sizes, seeded weights, the CPU backend (Pallas in the
interpreter)."""
import hashlib
import importlib
import time

import jax
import numpy as np
import pytest
from test_qwen3_next import LAYERS, TINY, tiny_model

from benchmark import weights
from benchmark.families import qwen3_next as fam
from paddle_tpu.inference import layered
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.text.models import gated_attention_block as ga

BS, MAX_LEN = 8, 128


def _engine(model, **kw):
    args = dict(max_slots=3, kv_block_size=BS, max_model_len=MAX_LEN,
                chunked_prefill_tokens=16)
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


#: prompts that end inside a chunk (37, 5), on a chunk's edge (32) and
#: past three context blocks of 16 (70: its last chunks attend 5 blocks)
PROMPTS = (37, 5, 70, 32)
NEW = (20, 12, 9, 14)


@pytest.mark.parametrize("chunk,block", [(16, 16), (32, 16), (16, 64)])
def test_prefill_by_chunks_then_decode_agree_with_the_reference(
        chunk, block, monkeypatch, fresh_programs, built):
    """Every served position: the served token is the float32 reference's
    argmax (one wrong state or cached row anywhere in its past changes
    the logits), with chunks of 16 and 32 and context blocks of 16 and
    64 positions (`gated_attention_block.CTX_BLOCK`), slow decays so that
    a state carries far, and four requests over three slots (one slot
    reused)."""
    monkeypatch.setattr(ga, "CTX_BLOCK", block)
    fresh_programs()                # the block size is traced anew
    model, w = built(21, dt_bias=-4.0)
    eng = _engine(model, chunked_prefill_tokens=chunk)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, n) for n in PROMPTS]
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, NEW)]
    eng.run()
    for prompt, rid, n in zip(prompts, rids, NEW):
        toks = eng.completed[rid]
        assert len(toks) == n
        gap, wrong = _served_logits_gap(w, prompt, toks)
        assert wrong == 0 and gap == 0.0, (len(prompt), gap, wrong)


def test_a_slot_state_not_reset_fails_the_served_comparison(
        monkeypatch, fresh_programs, built):
    """The comparison above fails where a slot's state is NOT reset at a
    new request's first chunk: one slot, two requests."""
    model, w = built(21, dt_bias=-4.0)
    monkeypatch.setattr(layered, "_slot_state",
                        lambda pool, slot, fresh: pool[slot])
    fresh_programs()                # the planted fault is traced anew
    eng = _engine(model, max_slots=1)
    rng = np.random.default_rng(4)
    eng.add_request(rng.integers(0, 256, 40), max_new_tokens=12)
    prompt = rng.integers(0, 256, 11)
    rid = eng.add_request(prompt, max_new_tokens=16)
    toks = eng.run()[rid]
    gap, wrong = _served_logits_gap(w, prompt, toks)
    assert wrong > 0 and gap > 1e-3


def test_one_cache_holds_pages_beside_the_state_and_the_spans_count_them():
    """`LayeredKVCache`: three recurrent layers' slot states beside the
    full layer's K and V pages (2 KV heads x 32); one chunk program for
    every context length; the spans carry the full layer's attended
    pairs and decode context, the state counts, the held experts."""
    from paddle_tpu import obs

    model, _ = tiny_model(5)
    eng = _engine(model)
    assert eng.cache.kinds == ("recurrent",) * 3 + ("full_attention",)
    pool = eng.cache.k[3]
    assert pool.shape == (1 + 3 * MAX_LEN // BS, 2, BS, 32)
    assert eng.cache.v[3].shape == pool.shape
    assert eng.cache.k[0].shape == (4, 4, 16, 16)
    lp = eng.programs
    assert lp.blocked_full_layers == 1
    assert lp.chunk_buckets(16, 20) == lp.chunk_buckets(16, 100) \
        == (16, MAX_LEN // BS)
    t0 = time.perf_counter()
    eng.add_request(np.arange(30) % 256, max_new_tokens=6)
    eng.run()
    mine = [r for r in obs.span_events() if r.start >= t0]
    chunks = [r.attrs for r in mine if r.name == "serving.chunk.run"]
    decodes = [r.attrs for r in mine if r.name == "serving.decode.run"]
    assert [c["attn_pairs"] for c in chunks] == [
        16 * 17 // 2, 14 * 16 + 14 * 15 // 2]
    assert [c["state_tokens"] for c in chunks] == [3 * 16, 3 * 14]
    assert [d["ctx_tokens"] for d in decodes] == [31, 32, 33, 34, 35]
    assert [d["state_slots"] for d in decodes] == [3] * 5
    for a in chunks + decodes:
        assert a["moe_experts_held"] == 4 * 4
        assert a["moe_experts_read"] == 4 * 4
    assert {d["ahead_slots"] for d in decodes[1:]} <= {0, 1}


@pytest.mark.parametrize("kwargs,names,why", [
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'", "recurrent state"),
    ({"spec_decode": "ngram"}, "spec_decode='ngram'", "rolled back"),
    ({"prefix_cache": True}, "prefix_cache=True", "recurrent state"),
])
def test_unsupported_options_raise_by_name(kwargs, names, why):
    """What this architecture does not get yet is refused at
    construction, by the option's name: int8/int4 pages, speculative
    decoding, the prefix cache over recurrent state."""
    model, _ = tiny_model(1)
    with pytest.raises(ValueError,
                       match="not supported for qwen3_next") as e:
        _engine(model, **kwargs)
    assert names in str(e.value) and why in str(e.value)


def test_the_static_engine_refuses_the_architecture():
    model, _ = tiny_model(1)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="static"):
        model.generate(ids, max_new_tokens=2, engine="static")
    out = model.generate(ids, max_new_tokens=3, kv_block_size=BS,
                         max_model_len=MAX_LEN, chunked_prefill_tokens=16)
    assert out.shape == (1, 3)


# ------------------------------ the other per-layer models' programs

#: sha256 (first 16 hex) of the decode program (a bucket of 4) and of
#: the chunk program (chunks of 16) of each tiny per-layer engine, as
#: lowered before the hybrid block took a GQA full layer and the expert
#: layer its softmax router and gated shared expert: those options off,
#: every other model's programs are the same text (under this suite's
#: conftest: matmul precision "highest")
LOWERED = {
    "cohere.bfloat16.chunk": "81eb34e985722a4a",
    "cohere.bfloat16.decode": "645681bde79f321b",
    "cohere.float32.chunk": "89ea9268316c6ef6",
    "cohere.float32.decode": "60209e747305ee46",
    "gigachat.bfloat16.chunk": "4e618e41967b7a66",
    "gigachat.bfloat16.decode": "aa3a2a35c32420e9",
    "gigachat.float32.chunk": "25a135a0a6b40fdc",
    "gigachat.float32.decode": "4dfcd5e200d2595b",
    "pangu.bfloat16.chunk": "ce4dea55409fafbb",
    "pangu.bfloat16.decode": "4822a62c83d95c5e",
    "pangu.float32.chunk": "06517ec3e0d026ab",
    "pangu.float32.decode": "166b94e2f2f078fa"}


def _lowered(name, module, max_len, dtype):
    """(decode text, chunk text) of the tiny model of test `module`."""
    t = importlib.import_module(module)
    fam_ = importlib.import_module("benchmark.families." + t.TINY["family"])
    model = fam_.build_model(t.TINY, t.LAYERS, "serve")
    weights.assign(model, weights.make(fam_.weight_spec(t.TINY, t.LAYERS),
                                       0, dtype))
    eng = ServingEngine(model, max_slots=4, kv_block_size=8,
                        max_model_len=max_len, chunked_prefill_tokens=16)
    lp = eng.programs

    def shapes(*a):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), a)

    ints = np.zeros((4, lp._ints_width()), np.int32)
    decode = layered.decode_step.lower(lp.spec, False, *shapes(
        eng.params, ints, lp.cache.k, lp.cache.v, eng._last,
        eng._samp([], 4, False), eng._key)).as_text()
    c, pages = lp.chunk_buckets(16, 40)
    cints = np.zeros((4 + eng.pages + lp.spec.window_pages + 1,), np.int32)
    chunk = layered.chunk_step.lower(lp.spec, False, True, pages, *shapes(
        eng.params, np.zeros((1, c), np.int32), cints, lp.cache.k,
        lp.cache.v, eng._last, eng._samp([], 0, False), eng._key)).as_text()
    eng.close()
    return decode, chunk


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name,module,max_len", [
    ("cohere", "test_cohere2_moe", 128),
    ("pangu", "test_pangu_ultra_moe", 512),
    ("gigachat", "test_gigachat3_5", 512)])
def test_the_other_models_programs_lower_as_before(name, module, max_len,
                                                   dtype):
    decode, chunk = _lowered(name, module, max_len, dtype)
    for what, text in (("decode", decode), ("chunk", chunk)):
        digest = hashlib.sha256(text.encode()).hexdigest()[:16]
        assert digest == LOWERED[f"{name}.{dtype}.{what}"], what
