"""Continuous-batching serving engine tests (round 10).

The paged engine (inference/engine.py + text/paged_cache.py) must be
token-identical to the single-program engine under greedy sampling, and
the scheduler must actually do continuous batching: freed slots refill
mid-flight, admission control holds requests the block pool can't cover,
and blocks come back on finish (copy-free release).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import ServingEngine, generate_paged
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.text.paged_cache import (BlockAllocator, PagedKVCache,
                                         blocks_for)


def _tiny(vocab=128, kv_heads=None, max_pos=64, layers=2):
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=layers, num_attention_heads=4,
                      num_key_value_heads=kv_heads,
                      max_position_embeddings=max_pos)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _tiny_gpt(layers=2):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_hidden_layers=layers,
                    num_attention_heads=4, max_position_embeddings=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)          # block 0 reserved
        assert a.available == 7
        ids = a.alloc(3)
        assert len(ids) == 3 and 0 not in ids
        assert a.available == 4
        a.free(ids)
        assert a.available == 7

    def test_all_or_nothing(self):
        a = BlockAllocator(4)
        assert a.alloc(5) is None      # over-ask leaves the pool intact
        assert a.available == 3

    def test_double_free_and_trash_guard(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError):
            a.free([ids[0]])
        with pytest.raises(ValueError):
            a.free([0])                # the trash block is never yours

    def test_blocks_for(self):
        assert blocks_for(1, 16) == 1
        assert blocks_for(16, 16) == 1
        assert blocks_for(17, 16) == 2

    def test_cache_block_size_alignment(self):
        with pytest.raises(ValueError):
            PagedKVCache(1, 4, 2, 12, 16, "float32")


class TestPagedEngineParity:
    """Greedy generations must be TOKEN-IDENTICAL to the single-program
    engine (acceptance criterion)."""

    def test_llama_greedy_token_identical(self):
        m = _tiny()
        prompt = np.random.RandomState(0).randint(0, 128,
                                                  (2, 5)).astype("int64")
        out_s = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=6)._data)
        out_p = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=6,
                                      engine="paged")._data)
        np.testing.assert_array_equal(out_s, out_p)

    def test_llama_gqa_greedy_token_identical(self):
        m = _tiny(vocab=64, kv_heads=2)
        prompt = np.random.RandomState(1).randint(0, 64,
                                                  (2, 4)).astype("int64")
        out_s = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=5)._data)
        out_p = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=5,
                                      engine="paged")._data)
        np.testing.assert_array_equal(out_s, out_p)

    def test_gpt_greedy_token_identical(self):
        m = _tiny_gpt()
        prompt = np.random.RandomState(2).randint(0, 96,
                                                  (2, 5)).astype("int64")
        out_s = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=6)._data)
        out_p = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=6,
                                      engine="paged")._data)
        np.testing.assert_array_equal(out_s, out_p)

    def test_eos_semantics_match_static(self):
        m = _tiny()
        prompt = np.random.RandomState(4).randint(0, 128,
                                                  (1, 4)).astype("int64")
        first = np.asarray(m.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=1)._data)[0, -1]
        out = np.asarray(m.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=8, engine="paged",
                                    eos_token_id=int(first))._data)
        assert out.shape[1] == prompt.shape[1] + 1
        assert out[0, -1] == first

    def test_1d_prompt(self):
        m = _tiny()
        out = m.generate(paddle.to_tensor(np.array([1, 2, 3], "int64")),
                         max_new_tokens=3, engine="paged")
        assert tuple(out.shape) == (1, 6)

    def test_sampling_in_engine_is_deterministic(self):
        m = _tiny()
        prompt = np.random.RandomState(5).randint(0, 128,
                                                  (2, 4)).astype("int64")
        kw = dict(max_new_tokens=4, do_sample=True, top_k=10, seed=7,
                  engine="paged")
        s1 = np.asarray(m.generate(paddle.to_tensor(prompt), **kw)._data)
        s2 = np.asarray(m.generate(paddle.to_tensor(prompt), **kw)._data)
        np.testing.assert_array_equal(s1, s2)

    def test_unseeded_sampling_is_fresh(self):
        """seed=None must draw from the framework rng stream like the
        static engine — repeated unseeded sampling calls differ."""
        m = _tiny()
        prompt = np.random.RandomState(5).randint(0, 128,
                                                  (2, 6)).astype("int64")
        kw = dict(max_new_tokens=8, do_sample=True, temperature=1.5,
                  engine="paged")
        s1 = np.asarray(m.generate(paddle.to_tensor(prompt), **kw)._data)
        s2 = np.asarray(m.generate(paddle.to_tensor(prompt), **kw)._data)
        assert not np.array_equal(s1, s2)

    def test_int8_kv_cache_close(self):
        m = _tiny()
        prompt = np.random.RandomState(6).randint(0, 128,
                                                  (2, 6)).astype("int64")
        fp = generate_paged(m, prompt, 6)
        i8 = generate_paged(m, prompt, 6, kv_cache_dtype="int8")
        assert fp.shape == i8.shape
        # per-block int8 cache on a tiny random model: most tokens agree
        assert (fp == i8).mean() > 0.7, (fp, i8)

    def test_weight_quant_on_paged(self):
        """Round 20: weight-only quantization is a first-class paged-engine
        mode (it used to raise NotImplementedError here) — and a bogus
        mode still fails fast at the API."""
        m = _tiny()
        prompt = np.random.RandomState(11).randint(0, 128,
                                                   (2, 5)).astype("int64")
        fp = generate_paged(m, prompt, 5)
        q8 = generate_paged(m, prompt, 5, weight_quant="int8")
        # per-channel int8 on a tiny random model: most tokens agree with
        # the full-precision engine
        assert q8.shape == fp.shape and (fp == q8).mean() > 0.7, (fp, q8)
        # 15 int4 levels on 32-wide random weights flip near-tied argmaxes
        # (the draw decides how many), so int4 is held to what it must
        # equal: the full-precision engine over the dequantized weights
        from paddle_tpu.ops.quantized import dequant_int4, quantize_int4

        q4 = generate_paged(m, prompt, 5, weight_quant="int4")
        ref = _tiny()
        for name, p in ref.named_parameters():
            if p.ndim == 2 and "embed" not in name:
                packed, scale = quantize_int4(p._data)
                p._assign_raw(dequant_int4(packed, scale, p.shape[0],
                                           p._data.dtype))
        np.testing.assert_array_equal(q4, generate_paged(ref, prompt, 5))
        with pytest.raises(ValueError):
            m.generate(paddle.to_tensor(np.zeros((1, 4), "int64")),
                       max_new_tokens=2, engine="paged",
                       weight_quant="int2")

    def test_bad_engine_name(self):
        m = _tiny()
        with pytest.raises(ValueError):
            m.generate(paddle.to_tensor(np.zeros((1, 4), "int64")),
                       max_new_tokens=2, engine="vllm")

    def test_block_rounded_context_gap_raises_at_api(self):
        """max_position_embeddings=40 rounds to 32 usable paged tokens at
        block 16: a request in the gap must fail AT generate() with the
        block-rounding explanation, not deep inside admission (the static
        engine still serves it)."""
        m = _tiny(max_pos=40)
        prompt = np.random.RandomState(11).randint(0, 128,
                                                   (1, 30)).astype("int64")
        out = m.generate(paddle.to_tensor(prompt), max_new_tokens=5)
        assert tuple(out.shape) == (1, 35)
        with pytest.raises(ValueError, match="usable context"):
            m.generate(paddle.to_tensor(prompt), max_new_tokens=5,
                       engine="paged")


class TestContinuousBatching:
    def test_slots_refill_mid_flight(self):
        """5 mixed-length requests over 2 slots: finished slots must be
        re-admitted into while others are mid-flight (the continuous-
        batching property), and every request completes with its exact
        token budget."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8)
        rs = np.random.RandomState(3)
        want = {}
        for ln, nt in ((3, 4), (7, 6), (2, 9), (5, 3), (4, 5)):
            rid = eng.add_request(rs.randint(0, 128, (ln,)),
                                  max_new_tokens=nt)
            want[rid] = nt
        saw_mixed_admission = False
        while eng.has_work():
            before_active = eng.num_active
            eng.step()
            if 0 < before_active < 2 and eng.num_active == 2:
                saw_mixed_admission = True  # a freed slot was refilled
        done = {r: len(v) for r, v in eng.completed.items()}
        assert done == want
        assert saw_mixed_admission, "no slot was refilled mid-flight"
        st = eng.stats()
        assert st["slot_utilization"] > 0.8
        assert len(st["ttft_s"]) == 5

    def test_admission_control_against_pool(self):
        """A pool of 5 usable blocks (block_size 8): a 40-token request
        takes all 5; the second request must WAIT (not crash, not OOM)
        until the first finishes, then run to completion."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            num_kv_blocks=6)
        rs = np.random.RandomState(4)
        big = eng.add_request(rs.randint(0, 128, (30,)), max_new_tokens=10)
        small = eng.add_request(rs.randint(0, 128, (4,)), max_new_tokens=4)
        eng.step()
        assert eng.num_active == 1 and eng.num_waiting == 1
        done = eng.run()
        assert len(done[big]) == 10 and len(done[small]) == 4

    def test_impossible_request_rejected(self):
        m = _tiny()
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            num_kv_blocks=3)
        with pytest.raises(ValueError):            # pool can never cover
            eng.add_request(np.arange(30) % 16, max_new_tokens=10)
        with pytest.raises(ValueError):            # context too small
            eng.add_request(np.arange(60) % 16, max_new_tokens=60)

    def test_blocks_released_on_finish(self):
        """Release is copy-free and leak-free: with the prefix cache on
        (default), full blocks park REUSABLE in the refcount-0 LRU and
        the rest free-list — allocatable capacity is fully restored."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            num_kv_blocks=9)
        free0 = eng.allocator.available
        rs = np.random.RandomState(5)
        eng.add_request(rs.randint(0, 128, (5,)), max_new_tokens=4)
        eng.add_request(rs.randint(0, 128, (9,)), max_new_tokens=6)
        eng.run()
        assert eng.prefix_cache.available == free0   # nothing leaked
        assert eng.prefix_cache.referenced_blocks == 0
        assert eng.num_active == 0 and eng.num_waiting == 0

    def test_blocks_released_to_free_list_when_cache_off(self):
        """With the prefix cache disabled the round-10 contract holds
        bit-for-bit: every block returns to the free list."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            num_kv_blocks=9, prefix_cache=False)
        free0 = eng.allocator.available
        rs = np.random.RandomState(5)
        eng.add_request(rs.randint(0, 128, (5,)), max_new_tokens=4)
        eng.add_request(rs.randint(0, 128, (9,)), max_new_tokens=6)
        eng.run()
        assert eng.allocator.available == free0
        assert eng.prefix_cache.cached_blocks == 0

    def test_static_admission_is_waves(self):
        """admission="static" (the bench baseline) must never admit into
        a partially-busy engine."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            admission="static")
        rs = np.random.RandomState(6)
        for ln, nt in ((3, 3), (4, 8), (5, 4)):
            eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
        max_active_seen = 0
        admitted_into_busy = False
        while eng.has_work():
            before = eng.num_active
            eng.step()
            if before not in (0, 2) and eng.num_active > before:
                admitted_into_busy = True
            max_active_seen = max(max_active_seen, eng.num_active)
        assert not admitted_into_busy
        assert max_active_seen == 2
        assert len(eng.completed) == 3

    def test_continuous_beats_static_utilization(self):
        """The acceptance property, in miniature: on a mixed-length
        stream, continuous batching's slot utilization beats the static-
        wave baseline."""
        m = _tiny()
        rs = np.random.RandomState(7)
        stream = [(rs.randint(2, 8), rs.randint(2, 12)) for _ in range(6)]

        def run(mode):
            eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                                admission=mode)
            r2 = np.random.RandomState(8)
            for ln, nt in stream:
                eng.add_request(r2.randint(0, 128, (ln,)),
                                max_new_tokens=nt)
            eng.run()
            return eng.stats()["slot_utilization"]

        cont, stat = run("continuous"), run("static")
        assert cont > stat, (cont, stat)


class TestRequestDeadline:
    """Per-request deadline (robustness round 12): an expired request
    finishes with reason "timeout", releases its blocks to the free
    list, and counts in serving_requests_timeout_total — a stuck-long
    request can't hold slots/pool forever."""

    def test_stuck_request_cannot_hold_slot_forever(self):
        m = _tiny()
        eng = ServingEngine(m, max_slots=1, kv_block_size=8)
        rs = np.random.RandomState(0)
        free0 = eng.allocator.available
        # a would-run-very-long request with a ~1ms budget, and a normal
        # one queued behind it on the ONLY slot
        stuck = eng.add_request(rs.randint(0, 128, (4,)),
                                max_new_tokens=40, max_time_ms=1.0)
        quick = eng.add_request(rs.randint(0, 128, (4,)), max_new_tokens=3)
        out = eng.run()
        assert eng.finish_reasons[stuck] == "timeout"
        assert len(out[stuck]) < 40            # cut off by the deadline
        assert eng.finish_reasons[quick] == "length"
        assert len(out[quick]) == 3            # the queue drained
        assert eng.allocator.available == free0    # blocks all released
        snap = eng.metrics()
        t = [s for s in snap["serving_requests_timeout_total"]["samples"]]
        assert t and t[0]["value"] >= 1

    def test_queued_request_can_expire_before_admission(self):
        m = _tiny()
        eng = ServingEngine(m, max_slots=1, kv_block_size=8)
        rs = np.random.RandomState(1)
        hog = eng.add_request(rs.randint(0, 128, (4,)), max_new_tokens=8)
        doomed = eng.add_request(rs.randint(0, 128, (4,)),
                                 max_new_tokens=8, max_time_ms=0.5)
        import time

        time.sleep(0.002)
        out = eng.run()
        assert eng.finish_reasons[doomed] == "timeout"
        assert len(out[doomed]) == 0           # never admitted
        assert len(out[hog]) == 8

    def test_timeout_emits_terminal_event_from_step(self):
        """Streaming consumers track completion via the finished flag;
        a deadline finish must emit (rid, None, True) from step() like
        eos/length finishes emit (rid, token, True)."""
        m = _tiny()
        eng = ServingEngine(m, max_slots=1, kv_block_size=8)
        rs = np.random.RandomState(3)
        rid = eng.add_request(rs.randint(0, 128, (4,)),
                              max_new_tokens=40, max_time_ms=1.0)
        events = []
        for _ in range(200):
            if not eng.has_work():
                break
            events.extend(eng.step())
        assert (rid, None, True) in events
        # and every request sees exactly one terminal event
        finals = [e for e in events if e[2]]
        assert len(finals) == 1

    def test_no_deadline_is_unchanged(self):
        m = _tiny()
        rs = np.random.RandomState(2)
        prompt = rs.randint(0, 128, (5,))
        eng = ServingEngine(m, max_slots=2, kv_block_size=8)
        rid = eng.add_request(prompt, max_new_tokens=4)
        out = eng.run()
        np.testing.assert_array_equal(
            out[rid], generate_paged(_tiny(), prompt[None], 4)[0])
        assert eng.finish_reasons[rid] == "length"

    def test_bad_deadline_rejected(self):
        eng = ServingEngine(_tiny(), max_slots=1, kv_block_size=8)
        with pytest.raises(ValueError, match="max_time_ms"):
            eng.add_request(np.arange(4), max_new_tokens=2, max_time_ms=0)


class TestServingPredictor:
    def test_predictor_wraps_engine(self):
        from paddle_tpu.inference import Config, create_serving_predictor

        m = _tiny()
        cfg = Config("unused_prefix")
        cfg.enable_paged_serving(slots=2, kv_block_size=8)
        pred = create_serving_predictor(cfg, model=m)
        rs = np.random.RandomState(9)
        outs = pred.generate([rs.randint(0, 128, (4,)),
                              rs.randint(0, 128, (6,))],
                             max_new_tokens=3)
        assert [len(o) for o in outs] == [3, 3]
        assert pred.get_stats()["decode_tokens"] > 0

    def test_predictor_matches_direct_engine(self):
        from paddle_tpu.inference import Config, create_serving_predictor

        m = _tiny()
        prompt = np.random.RandomState(10).randint(0, 128, (5,))
        cfg = Config("unused_prefix")
        cfg.enable_paged_serving(slots=1, kv_block_size=8)
        pred = create_serving_predictor(cfg, model=m)
        got = pred.generate([prompt], max_new_tokens=4)[0]
        want = generate_paged(m, prompt[None], 4)[0]
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------ pools by offset (PR 32)
# The stacked programs see the pools `[L, N, H_kv, bs, D]` as
# `[L * N, ...]`, carry them through the layer scan and address layer l's
# blocks at `l * N + id`: no slice of a pool leaves the donated buffer.

def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("program", ["decode", "verify"])
def test_pools_ride_the_layer_scan_as_carry(program):
    m = _tiny(kv_heads=2)
    eng = ServingEngine(m, max_slots=2, kv_block_size=8, prefix_cache=False)
    closed = eng.decode_program_jaxpr() if program == "decode" \
        else eng.verify_program_jaxpr()
    layers, blocks = eng.cache.k.shape[:2]
    flat = (layers * blocks,) + eng.cache.k.shape[2:]
    scans = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == layers]
    assert len(scans) == 1
    scan = scans[0]
    nc, ncar = scan.params["num_consts"], scan.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in scan.invars]
    assert shapes[nc:nc + ncar].count(flat) == 2          # k and v
    pool_like = [sh for sh in shapes[:nc] + shapes[nc + ncar:]
                 + [tuple(v.aval.shape) for v in scan.outvars[ncar:]]
                 if sh[-3:] == flat[-3:]]
    assert pool_like == [], "a pool rides the scan as a constant, xs or ys"
    scatters = [e for e in _eqns(scan.params["jaxpr"].jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert scatters, "the layer writes no K/V"
    for e in scatters:
        dims = e.params["dimension_numbers"].scatter_dims_to_operand_dims
        assert len(dims) == 1, f"a scatter over dimensions {dims}"


def _dense_kv(eng, tokens):
    """Every layer's K and V of `tokens` by the static engine's dense
    causal forward: [L, S, H_kv, D] each."""
    import jax.numpy as jnp

    from paddle_tpu.text.models import dense_block

    ids = jnp.asarray(np.asarray(tokens, np.int32))[None]
    _, ks, vs = dense_block.forward_sequence(eng.params, ids, eng.spec)
    return np.asarray(ks[:, 0], np.float32), np.asarray(vs[:, 0],
                                                        np.float32)


#: how far a cache row may lie from the dense forward's, as a share of the
#: largest magnitude in the prompt's K (or V): float pools differ by the
#: paged attention's rounding, int8 by half a step of 1/127, int4 by half
#: a step of 1/7 and what re-quantising a block on every append adds
_ROW_TOL = {"model": 1e-4, "int8": 0.02, "int4": 0.25}


@pytest.mark.parametrize("mode", ["model", "int8", "int4"])
@pytest.mark.parametrize("arch", ["llama", "gpt"])
def test_each_layer_writes_and_reads_its_own_pages(arch, mode):
    """A prompt by chunks, a whole short prompt, a copy-on-write prefix
    hit and some decode ticks, three layers with K/V of their own: layer
    l's pages hold layer l's rows, every block nobody was given is as it
    was made, each layer's block 0 took that layer's masked writes, and
    the float cache's tokens are the static engine's."""
    from paddle_tpu.text.paged_cache import gather_context

    m = _tiny_gpt(layers=3) if arch == "gpt" \
        else _tiny(vocab=96, kv_heads=2, layers=3)
    bs, new = 8, 12
    eng = ServingEngine(m, max_slots=4, kv_block_size=bs, num_kv_blocks=48,
                        prefix_cache=True, chunked_prefill_tokens=16,
                        kv_cache_dtype=None if mode == "model" else mode)
    rs = np.random.RandomState(11)
    shared, by_chunks, short = (rs.randint(0, 96, (n,))
                                for n in (16, 40, 9))
    given = set()

    def step():
        out = eng.step()
        for blocks in eng._slot_blocks:
            given.update(blocks)
        return out

    # the shared prompt once to its end: its two blocks enter the cache
    first = eng.add_request(shared, max_new_tokens=new)
    while eng.has_work():
        step()
    # then three requests at once, and three slots of a bucket of four
    prompts = {eng.add_request(p, max_new_tokens=new): p
               for p in (by_chunks, short, shared)}
    while eng.num_waiting or min(len(r.tokens) for r in eng._slot_req
                                 if r is not None) < 3:
        step()
    st = eng.stats()
    assert st["prefill_chunks"] >= 4 and st["prefix_blocks_hit"] == 2
    assert eng.num_active == 3

    c = eng.cache
    int4 = mode == "int4"
    for slot, req in enumerate(eng._slot_req):
        if req is None:
            continue
        n = int(eng._slot_pos[slot])             # positions in the cache
        seq = np.concatenate([req.prompt, req.tokens])[:n]
        for pool, scale, want in zip(
                (c.k, c.v), (c.k_scale, c.v_scale), _dense_kv(eng, seq)):
            tol = _ROW_TOL[mode] * np.abs(want).max()
            for layer in range(3):
                got = np.asarray(gather_context(
                    pool[layer], None if scale is None else scale[layer],
                    eng._tables[slot], eng.pages, int4=int4),
                    np.float32)[:n]
                err = [np.abs(got - want[other]).max()
                       for other in range(3)]
                assert err[layer] <= tol, (slot, layer, err, tol)
                assert min(err[:layer] + err[layer + 1:]) > 2 * tol, \
                    "the layers' rows are too alike to tell apart"

    never = sorted(set(range(1, c.num_blocks)) - given)
    assert len(never) > 10
    for pool in (c.k, c.v):
        pool = np.asarray(pool, np.float32)
        assert not pool[:, never].any(), "a block nobody was given"
        # the padded fourth row of the decode bucket wrote every layer's
        # own trash block, `l * N` of the flat pool
        assert all(pool[layer, 0].any() for layer in range(3))
    if c.k_scale is not None:
        for scale in (c.k_scale, c.v_scale):
            assert np.all(np.asarray(scale)[:, never] == np.float32(1e-8))

    while eng.has_work():
        step()
    prompts[first] = shared
    same = []
    for rid, prompt in prompts.items():
        want = np.asarray(m.generate(
            paddle.to_tensor(prompt[None].astype("int64")),
            max_new_tokens=new)._data)[0, prompt.size:]
        got = eng.completed[rid]
        if mode == "model" or prompt is short:
            np.testing.assert_array_equal(got, want)
        same.extend(got == want)
    # a quantized cache rounds what a long prompt's last chunk and every
    # later tick read: most tokens are the float cache's, not all
    assert np.mean(same) >= 0.5, (mode, same)


def test_registered_in_quick_tier():
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    src = open(os.path.join(here, "conftest.py")).read()
    assert '"test_serving.py"' in src.split("QUICK_MODULES")[1], \
        "tests/test_serving.py must be registered in QUICK_MODULES"
