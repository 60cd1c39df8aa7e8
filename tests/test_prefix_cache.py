"""Prefix caching + chunked prefill (round 13, serving tier 2).

Contracts under test:
  * PrefixCache bookkeeping — refcounts, LRU, eviction touches ONLY
    refcount-0 blocks, release-to-cache vs free-list, the max-blocks cap;
  * token-identical greedy parity with the cache ON vs OFF (llama, gpt,
    GQA, int8-KV) when a request stream actually shares prefixes;
  * copy-on-write: a whole-prompt hit recomputes only the final token
    into a private copy, and the shared source block stays intact for
    later requests;
  * chunked prefill emits the same first token as monolithic prefill and
    interleaves with in-flight decode instead of blocking it;
  * admission accounting credits cached blocks (a mostly-cached request
    admits into a pool that could not hold it cold);
  * the D7 cache-defeated finding fires on an identical-prompt stream
    with zero hits and stays quiet on a healthy one.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.text.paged_cache import (BlockAllocator, PrefixCache,
                                         hash_blocks)


def _tiny(vocab=128, kv_heads=None, max_pos=128):
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=kv_heads,
                      max_position_embeddings=max_pos)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _tiny_gpt():
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=128)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestHashChain:
    def test_full_blocks_only(self):
        assert len(hash_blocks(np.arange(15), 16)) == 0
        assert len(hash_blocks(np.arange(16), 16)) == 1
        assert len(hash_blocks(np.arange(33), 16)) == 2

    def test_chained_identity(self):
        """A block's hash covers its whole prefix: same second block
        after a different first block must hash differently."""
        a = hash_blocks(np.r_[np.full(16, 1), np.full(16, 9)], 16)
        b = hash_blocks(np.r_[np.full(16, 2), np.full(16, 9)], 16)
        assert a[0] != b[0] and a[1] != b[1]

    def test_namespace_partitions(self):
        t = np.arange(16)
        assert hash_blocks(t, 16, namespace=1) != hash_blocks(
            t, 16, namespace=2)


class TestPrefixCache:
    def test_release_to_cache_then_hit(self):
        pc = PrefixCache(BlockAllocator(8))
        h = hash_blocks(np.arange(32), 16)
        ids = pc.allocate(2)
        pc.register(h, ids)
        pc.release(ids)
        assert pc.evictable == 2 and pc.cached_blocks == 2
        assert pc.lookup(h) == ids and pc.hits == 2
        assert pc.evictable == 0           # referenced again

    def test_unmapped_blocks_free_list(self):
        alloc = BlockAllocator(8)
        pc = PrefixCache(alloc)
        ids = pc.allocate(3)
        pc.release(ids)
        assert alloc.available == 7 and pc.cached_blocks == 0

    def test_eviction_is_lru_and_refcount0_only(self):
        alloc = BlockAllocator(6)          # 5 usable
        pc = PrefixCache(alloc)
        held = pc.allocate(2)
        pc.register(hash_blocks(np.arange(32), 16), held)   # refcount 1
        parked = pc.allocate(2)
        pc.register(hash_blocks(np.arange(100, 132), 16), parked)
        pc.release(parked)                 # refcount 0 -> LRU
        # pressure: 3 blocks needed, 1 free + 2 evictable
        got = pc.allocate(3)
        assert got is not None and pc.evictions == 2
        assert pc.refcount(held[0]) == 1   # referenced blocks untouched
        assert pc.cached_blocks == 2       # held registrations survive
        # now only the held refs remain — over-ask must refuse, never
        # evict referenced blocks
        assert pc.allocate(1) is None

    def test_max_cached_blocks_cap(self):
        pc = PrefixCache(BlockAllocator(10), max_cached_blocks=2)
        ids = pc.allocate(4)
        pc.register(hash_blocks(np.arange(64), 16), ids)
        pc.release(ids)
        assert pc.evictable == 2 and pc.evictions == 2

    def test_cancel_lookup_rolls_back(self):
        pc = PrefixCache(BlockAllocator(8))
        h = hash_blocks(np.arange(32), 16)
        ids = pc.allocate(2)
        pc.register(h, ids)
        pc.release(ids)
        found = pc.lookup(h + [12345])
        pc.cancel_lookup(found, 3)
        assert pc.hits == 0 and pc.misses == 0
        assert pc.evictable == 2

    def test_double_release_raises(self):
        pc = PrefixCache(BlockAllocator(4))
        ids = pc.allocate(1)
        pc.release(ids)
        with pytest.raises(ValueError):
            pc.release(ids)


def _drive_pair(model, prompts, gens, cache_on, **kw):
    """Sequential requests through ONE engine (so later requests can hit
    prefixes registered by earlier ones); returns outputs in order."""
    eng = ServingEngine(model, max_slots=2, kv_block_size=8,
                        prefix_cache=cache_on, **kw)
    outs = []
    for p, g in zip(prompts, gens):
        rid = eng.add_request(p, max_new_tokens=g)
        eng.run()
        outs.append(eng.completed[rid])
    return eng, outs


class TestCacheParity:
    """Greedy outputs must be TOKEN-IDENTICAL cache-on vs cache-off on
    streams that share prefixes (the acceptance criterion)."""

    def _parity(self, model, **kw):
        rs = np.random.RandomState(0)
        vocab = model.config.vocab_size
        shared = rs.randint(0, vocab, (20,))
        prompts = [np.concatenate([shared, rs.randint(0, vocab, (k,))])
                   for k in (3, 5, 2)]
        gens = [5, 4, 6]
        e_on, on = _drive_pair(model, prompts, gens, True, **kw)
        e_off, off = _drive_pair(model, prompts, gens, False, **kw)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
        assert e_on.stats()["prefix_blocks_hit"] >= 4   # 2 blocks x 2 reqs
        assert e_off.stats()["prefix_blocks_hit"] == 0
        return e_on

    def test_llama(self):
        eng = self._parity(_tiny())
        assert eng.stats()["prefill_chunks"] >= 2

    def test_gpt(self):
        self._parity(_tiny_gpt())

    def test_llama_gqa(self):
        self._parity(_tiny(vocab=64, kv_heads=2))

    def test_llama_int8_kv(self):
        self._parity(_tiny(), kv_cache_dtype="int8")


class TestCopyOnWrite:
    def test_whole_prompt_hit_cow_parity_and_source_intact(self):
        """A byte-identical block-aligned prompt hits every full block;
        the final token recomputes into a COW copy. Outputs match the
        cache-off engine, and the SHARED source block survives for a
        third identical request (which must also match)."""
        m = _tiny()
        rs = np.random.RandomState(3)
        p = rs.randint(0, 128, (16,))      # exactly 2 blocks of 8
        eng, outs = _drive_pair(m, [p, p, p], [4, 4, 4], True)
        off, outs_off = _drive_pair(m, [p, p, p], [4, 4, 4], False)
        for a, b in zip(outs, outs_off):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(outs[1], outs[0])
        assert eng.stats()["prefix_blocks_hit"] >= 4
        assert eng.prefix_cache.referenced_blocks == 0  # no ref leaks

    def test_cow_releases_source_ref(self):
        m = _tiny()
        rs = np.random.RandomState(4)
        p = rs.randint(0, 128, (16,))
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=True)
        free0 = eng.prefix_cache.available
        for _ in range(2):
            eng.add_request(p, max_new_tokens=3)
            eng.run()
        assert eng.prefix_cache.available == free0
        assert eng.prefix_cache.referenced_blocks == 0


class TestChunkedPrefill:
    def test_first_token_matches_monolithic(self):
        """Bitwise-identical first (and all greedy) tokens: chunked
        prefill (4 chunks) vs monolithic on the same prompt."""
        for model in (_tiny(), _tiny_gpt()):
            vocab = model.config.vocab_size
            p = np.random.RandomState(5).randint(0, vocab, (50,))
            ec, chunked = _drive_pair(model, [p], [6], False,
                                      chunked_prefill_tokens=16)
            em, mono = _drive_pair(model, [p], [6], False,
                                   chunked_prefill_tokens=0)
            np.testing.assert_array_equal(chunked[0], mono[0])
            assert ec.stats()["prefill_chunks"] == 4
            assert em.stats()["prefill_chunks"] == 0

    def test_int8_chunk_spanning_page_boundary(self):
        """A chunk shorter than a block that starts mid-block still spans
        TWO pages; the int8 scatter must size its page window for the
        offset case or the spilled tokens' KV silently routes to the drop
        index and later attention reads garbage (regression: p_t was
        c//bs+1 = 1 for chunk [12, 20) at bs=16, dropping tokens 16-19).
        Every chunk token must gather back within quantization error."""
        import jax.numpy as jnp

        from paddle_tpu.text.paged_cache import (gather_context,
                                                 scatter_chunk_int8)
        bs, nb, hkv, d = 16, 8, 2, 4
        cache = jnp.zeros((nb, hkv, bs, d), jnp.int8)
        scale = jnp.full((nb,), 1e-8, jnp.float32)
        table = jnp.array([3, 5, 0, 0], jnp.int32)
        ks = jnp.asarray(np.random.RandomState(7).randn(8, hkv, d),
                         jnp.float32)              # chunk [12, 20)
        cache, scale = scatter_chunk_int8(cache, scale, ks, 12, 20,
                                          table, bs)
        got = np.asarray(gather_context(cache, scale, table, 2))[12:20]
        np.testing.assert_allclose(got, np.asarray(ks), atol=0.05)

    def test_chunks_interleave_with_decode(self):
        """A long prompt chunk-prefills ONE chunk per tick while another
        slot keeps decoding — the head-of-line property."""
        m = _tiny()
        rs = np.random.RandomState(6)
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            prefix_cache=False, chunked_prefill_tokens=8)
        short = eng.add_request(rs.randint(0, 128, (4,)),
                                max_new_tokens=20)
        eng.step()                          # short admitted + decoding
        long_r = eng.add_request(rs.randint(0, 128, (40,)),
                                 max_new_tokens=4)
        long_req = eng._waiting[0]
        decoded_during_prefill = 0
        for _ in range(50):
            before = len(eng._slot_req[0].tokens) \
                if eng._slot_req[0] is not None else None
            eng.step()
            if not long_req.prefill_done and before is not None:
                after = len(eng._slot_req[0].tokens)
                decoded_during_prefill += after - before
            if long_req.prefill_done:
                break
        assert eng.stats()["prefill_chunks"] == 5        # ceil(40/8)
        assert decoded_during_prefill >= 3, \
            "decode stalled while the long prompt prefilled"
        out = eng.run()
        assert len(out[long_r]) == 4 and len(out[short]) == 20

    def test_cache_hit_suffix_rides_chunk_program(self):
        m = _tiny()
        rs = np.random.RandomState(7)
        shared = rs.randint(0, 128, (24,))
        p1 = np.concatenate([shared, rs.randint(0, 128, (4,))])
        p2 = np.concatenate([shared, rs.randint(0, 128, (6,))])
        # chunking globally off: the hit suffix still computes chunked
        eng, _ = _drive_pair(m, [p1, p2], [3, 3], True,
                             chunked_prefill_tokens=0)
        st = eng.stats()
        assert st["prefix_blocks_hit"] == 3 and st["prefill_chunks"] == 1


class TestAdmissionAccounting:
    def test_cached_request_admits_with_tiny_budget(self):
        """Pool of 7 usable blocks; a cold 32-token request needs 4. Two
        cold requests cannot run concurrently — but the second request
        sharing the whole prompt needs only its COW + decode blocks, so
        with the cache ON both run at once."""
        m = _tiny()
        rs = np.random.RandomState(8)
        p = rs.randint(0, 128, (24,))

        def overlap(cache_on):
            eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                                num_kv_blocks=8, prefix_cache=cache_on)
            eng.add_request(p, max_new_tokens=8)
            eng.step()                     # r1 prefilled + registered
            eng.add_request(p, max_new_tokens=8)
            both = False
            while eng.has_work():
                eng.step()
                both |= eng.num_active == 2
            return both

        assert overlap(True)
        assert not overlap(False)

    def test_blocked_lookup_does_not_leak(self):
        """A head-of-line request blocked on the pool must not leak
        refcounts or inflate hit counters across retries."""
        m = _tiny()
        rs = np.random.RandomState(9)
        p = rs.randint(0, 128, (16,))
        eng = ServingEngine(m, max_slots=2, kv_block_size=8,
                            num_kv_blocks=7, prefix_cache=True)
        eng.add_request(p, max_new_tokens=20)          # 5 of 6 blocks
        eng.step()
        # same prefix, but needs more than the 1 free block -> blocked
        eng.add_request(np.concatenate([p, rs.randint(0, 128, (8,))]),
                        max_new_tokens=20)
        for _ in range(5):
            eng.step()
        assert eng.num_waiting == 1
        hits_while_blocked = eng.prefix_cache.hits
        out = eng.run()
        assert len(out) == 2
        assert eng.prefix_cache.referenced_blocks == 0
        assert eng.prefix_cache.hits >= hits_while_blocked


class TestTimeoutRelease:
    def test_timeout_mid_chunk_prefill_releases_everything(self):
        m = _tiny()
        rs = np.random.RandomState(10)
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=True, chunked_prefill_tokens=8)
        free0 = eng.prefix_cache.available
        rid = eng.add_request(rs.randint(0, 128, (48,)), max_new_tokens=4,
                              max_time_ms=1.0)
        import time

        eng.step()                          # admit + first chunk
        time.sleep(0.003)
        eng.run()
        assert eng.finish_reasons[rid] == "timeout"
        assert eng.prefix_cache.available == free0
        assert eng.prefix_cache.referenced_blocks == 0


class TestMultiTurn:
    def test_prompt_plus_completion_hits_generated_blocks(self):
        """finish registers FULL blocks of prompt+generation, so a
        follow-up turn whose prompt extends the last turn's conversation
        hits blocks the DECODE wrote."""
        m = _tiny()
        rs = np.random.RandomState(11)
        p1 = rs.randint(0, 128, (10,))
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=True)
        r1 = eng.add_request(p1, max_new_tokens=8)
        eng.run()
        turn2 = np.concatenate([p1, eng.completed[r1][:6]])  # 2 blocks
        r2 = eng.add_request(turn2, max_new_tokens=4)
        eng.run()
        assert eng.stats()["prefix_blocks_hit"] == 2
        off = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=False)
        r3 = off.add_request(turn2, max_new_tokens=4)
        off.run()
        np.testing.assert_array_equal(eng.completed[r2], off.completed[r3])


class TestD7Detector:
    def test_fires_on_defeated_cache(self):
        from paddle_tpu import analysis

        m = _tiny()
        rs = np.random.RandomState(12)
        p = rs.randint(0, 128, (16,))
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=True)
        eng.add_request(p, max_new_tokens=2)
        eng.run()
        eng._prefix_namespace += 1          # the defeat: namespace drift
        eng.add_request(p, max_new_tokens=2)
        eng.run()
        finds = analysis.audit_prefix_cache(eng)
        assert [f for f in finds if f.severity == "warning"
                and "DEFEATED" in f.message]

    def test_quiet_on_healthy_cache(self):
        from paddle_tpu import analysis

        m = _tiny()
        rs = np.random.RandomState(13)
        p = rs.randint(0, 128, (16,))
        eng = ServingEngine(m, max_slots=1, kv_block_size=8,
                            prefix_cache=True)
        for _ in range(2):
            eng.add_request(p, max_new_tokens=2)
            eng.run()
        finds = analysis.audit_prefix_cache(eng)
        assert all(f.severity == "note" for f in finds)
        assert "healthy" in finds[0].message

    def test_notes_when_disabled(self):
        from paddle_tpu import analysis

        eng = ServingEngine(_tiny(), max_slots=1, kv_block_size=8,
                            prefix_cache=False)
        finds = analysis.audit_prefix_cache(eng)
        assert finds[0].severity == "note" and "disabled" in finds[0].message


class TestObsAndRouting:
    def test_new_metrics_present_and_counting(self):
        m = _tiny()
        rs = np.random.RandomState(14)
        p = rs.randint(0, 128, (20,))
        eng, _ = _drive_pair(m, [p, p], [3, 3], True)
        snap = eng.metrics()
        for name in ("serving_prefix_blocks_hit_total",
                     "serving_prefix_blocks_missed_total",
                     "serving_prefill_chunks_total",
                     "serving_prefix_cache_blocks",
                     "serving_prefix_cache_referenced_blocks",
                     "serving_prefix_cache_evictions_total"):
            assert name in snap, name
        assert snap["serving_prefix_blocks_hit_total"]["samples"][0][
            "value"] >= 2
        assert snap["serving_prefill_chunks_total"]["samples"][0][
            "value"] >= 1

    def test_cache_gauges_follow_the_cache(self):
        # the gauges are set from the cache's own counts whenever the
        # pool's are (a presence check alone passes on gauges left at 0)
        rs = np.random.RandomState(16)
        p = rs.randint(0, 128, (20,))
        eng, _ = _drive_pair(_tiny(), [p, p], [3, 3], True)
        snap = eng.metrics()
        cached = snap["serving_prefix_cache_blocks"]["samples"][0]["value"]
        assert cached == eng.prefix_cache.cached_blocks > 0

    def test_generate_prefix_cache_kwarg(self):
        m = _tiny()
        prompt = np.random.RandomState(15).randint(0, 128,
                                                   (2, 6)).astype("int64")
        a = np.asarray(m.generate(paddle.to_tensor(prompt),
                                  max_new_tokens=4, engine="paged",
                                  prefix_cache=True)._data)
        b = np.asarray(m.generate(paddle.to_tensor(prompt),
                                  max_new_tokens=4, engine="paged",
                                  prefix_cache=False)._data)
        np.testing.assert_array_equal(a, b)
        with pytest.raises(ValueError, match="paged"):
            m.generate(paddle.to_tensor(prompt), max_new_tokens=4,
                       prefix_cache=True)

    def test_d5_pool_budget_accounts_cached_blocks(self):
        from paddle_tpu import analysis

        # pool holds 2x16 pages cold -> fine
        assert not analysis.audit_decode_config(
            64, 16, pool_blocks=33, slots=2, seq_pages=16)
        # undersized pool fires ...
        f = analysis.audit_decode_config(
            64, 16, pool_blocks=17, slots=2, seq_pages=16)
        assert f and "cannot hold" in f[0].message
        # ... unless shared prefix blocks cover the gap
        assert not analysis.audit_decode_config(
            64, 16, pool_blocks=17, slots=2, seq_pages=16,
            cached_blocks=16)


def test_registered_in_quick_tier():
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    src = open(os.path.join(here, "conftest.py")).read()
    assert '"test_prefix_cache.py"' in src.split("QUICK_MODULES")[1], \
        "tests/test_prefix_cache.py must be registered in QUICK_MODULES"
