"""Speculative decoding tests (round 16).

The tentpole contract is the GREEDY PARITY ORACLE: a speculating paged
engine must emit tokens bitwise-identical to the non-speculative engine
on every model/dtype combination — acceptance rate changes throughput,
never content. On top of that: the Leviathan accept/reject rule keeps
the SAMPLED output distribution unchanged (seeded distribution check),
cache rewind leaves prefix-cache block contents bit-identical, mixed
speculating/plain slots coexist in one tick, eos/length finish honors
mid-window acceptance, timeouts release blocks cleanly, and TPOT is
observed once per emitted token (not once per multi-token tick).
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.engine import ServingEngine, _verify_tokens
from paddle_tpu.inference.speculative import (AlwaysRejectProposer,
                                              NgramProposer, ReplayProposer,
                                              SpecConfig, propose_ngram)
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(vocab=128, kv_heads=None, max_pos=64):
    # geometry matches tests/test_serving.py's _tiny exactly, so in one
    # tier-1 process the per-bucket programs are already compiled
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
                    num_attention_heads=4, max_position_embeddings=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _repetitive(vocab, motif=4, tiles=5, seed=0):
    rs = np.random.RandomState(seed)
    return np.tile(rs.randint(0, vocab, (motif,)), tiles).astype("int64")


def _drive(model, prompts, spec, nt=24, **req_kw):
    """Run one engine over `prompts`, return (per-prompt outputs, engine)."""
    eng = ServingEngine(model, max_slots=2, spec_decode=spec)
    rids = [eng.add_request(p, max_new_tokens=nt, **req_kw)
            for p in prompts]
    out = eng.run()
    return [out[r] for r in rids], eng


class TestNgramProposal:
    def test_tiled_motif_full_k(self):
        ctx = np.tile([7, 3, 9, 5], 6)
        prop = propose_ngram(ctx, 4)
        # the motif's continuation, full k wide
        assert prop.tolist() == [7, 3, 9, 5]

    def test_prefers_full_continuation_over_latest(self):
        # the latest suffix match sits at the very end (1 token left);
        # an earlier tile still has k tokens to give
        ctx = np.tile([1, 2, 3, 4, 5, 6, 7, 8], 3)[:-4]
        prop = propose_ngram(ctx, 6)
        assert len(prop) == 6

    def test_no_match_is_empty(self):
        assert propose_ngram(np.arange(20), 4).size == 0

    def test_short_context(self):
        assert propose_ngram(np.array([5]), 4).size == 0


class TestGreedyParity:
    """Token-identical to the plain paged engine — the in-repo oracle."""

    def _check(self, model, vocab, spec):
        prompts = [_repetitive(vocab, seed=s) for s in (0, 1)]
        base, _ = _drive(model, prompts, None)
        out, eng = _drive(model, prompts, spec)
        assert eng.spec_stats()["windows"] > 0, \
            "spec engine never speculated — parity held vacuously"
        for b, o in zip(base, out):
            assert np.array_equal(b, o), (b, o)
        return eng

    def test_llama_ngram(self):
        eng = self._check(_tiny(), 128, "ngram")
        assert eng.spec_stats()["accepted_tokens"] > 0

    def test_gpt_ngram(self):
        self._check(_tiny_gpt(), 96, "ngram")

    def test_gqa_ngram(self):
        self._check(_tiny(kv_heads=2), 128, "ngram")

    def test_int8_kv_ngram(self):
        model = _tiny()
        prompts = [_repetitive(128, seed=s) for s in (0, 1)]
        eng_b = ServingEngine(model, max_slots=2, kv_cache_dtype="int8")
        rb = [eng_b.add_request(p, max_new_tokens=24) for p in prompts]
        ob = eng_b.run()
        eng_s = ServingEngine(model, max_slots=2, kv_cache_dtype="int8",
                              spec_decode="ngram")
        rs_ = [eng_s.add_request(p, max_new_tokens=24) for p in prompts]
        os_ = eng_s.run()
        assert eng_s.spec_stats()["windows"] > 0
        for b, s in zip(rb, rs_):
            assert np.array_equal(ob[b], os_[s])

    def test_draft_model_self_accepts_all(self):
        # the target as its own draft: proposals ARE the argmax stream,
        # so every window accepts all K — pins the draft proposer's
        # position/ingest bookkeeping exactly
        model = _tiny()
        spec = SpecConfig(method="draft", k=4, draft_model=model)
        eng = self._check(model, 128, spec)
        assert eng.spec_stats()["accept_rate"] == pytest.approx(1.0)

    def test_draft_model_distinct_parity(self):
        paddle.seed(3)
        cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2,
                          max_position_embeddings=64)
        draft = LlamaForCausalLM(cfg)
        draft.eval()
        self._check(_tiny(), 128,
                    SpecConfig(method="draft", k=3, draft_model=draft))

    def test_always_reject_parity_via_correction(self):
        # worst case: every proposal rejected — output must still match
        # through the correction token path
        eng = self._check(
            _tiny(), 128, SpecConfig(proposer=AlwaysRejectProposer(4)))
        assert eng.spec_stats()["accept_rate"] < 0.2


class TestRejectionSampling:
    def test_output_marginal_matches_target(self):
        # Leviathan guarantee: accept-or-resample leaves the emitted
        # marginal equal to the target distribution regardless of what
        # the (deterministic) draft proposed
        import jax
        import jax.numpy as jnp

        rs = np.random.RandomState(0)
        b, v = 4000, 8
        lg = jnp.asarray(rs.randn(b, 2, v).astype(np.float32))
        proposed = jnp.asarray(rs.randint(0, v, (b, 1)).astype(np.int32))
        samp = {"do_sample": jnp.ones(b, bool),
                "temperature": jnp.full(b, 1.0, jnp.float32),
                "top_k": jnp.zeros(b, jnp.int32),
                "top_p": jnp.ones(b, jnp.float32)}
        acc, tgt, _ = _verify_tokens(lg, proposed, samp,
                                     jax.random.PRNGKey(0), True)
        emitted = np.where(np.asarray(acc)[:, 0],
                           np.asarray(proposed)[:, 0],
                           np.asarray(tgt)[:, 0])
        emp = np.bincount(emitted, minlength=v) / b
        exp = np.asarray(jax.nn.softmax(lg[:, 0], axis=-1)).mean(0)
        assert np.abs(emp - exp).max() < 0.03, (emp, exp)

    def test_seeded_determinism_and_greedy_rows(self):
        import jax
        import jax.numpy as jnp

        rs = np.random.RandomState(1)
        b, v = 8, 16
        lg = jnp.asarray(rs.randn(b, 3, v).astype(np.float32))
        proposed = jnp.asarray(rs.randint(0, v, (b, 2)).astype(np.int32))
        samp = {"do_sample": jnp.asarray([True, False] * 4),
                "temperature": jnp.full(b, 0.9, jnp.float32),
                "top_k": jnp.full(b, 5, jnp.int32),
                "top_p": jnp.full(b, 0.95, jnp.float32)}
        a1, t1, _ = _verify_tokens(lg, proposed, samp,
                                   jax.random.PRNGKey(7), True)
        a2, t2, _ = _verify_tokens(lg, proposed, samp,
                                   jax.random.PRNGKey(7), True)
        assert np.array_equal(a1, a2) and np.array_equal(t1, t2)
        # greedy rows (do_sample=False) accept iff proposal == argmax
        greedy = np.argmax(np.asarray(lg), axis=-1)
        for i in range(1, b, 2):
            assert np.array_equal(
                np.asarray(a1)[i],
                np.asarray(proposed)[i] == greedy[i, :2])
            assert np.array_equal(np.asarray(t1)[i], greedy[i])

    def test_sampled_spec_run_drains(self):
        out, eng = _drive(_tiny(), [_repetitive(128)], "ngram", nt=12,
                          do_sample=True, temperature=0.8, top_k=20)
        assert len(out[0]) == 12


class TestCacheRewind:
    def test_prefix_cache_bit_identical(self):  # to an ulp: see below
        # rejected candidates' K/V must never leak into registered
        # prefix blocks: the published block contents after a spec run
        # equal the non-spec run's. Tokens a K+1 verify window wrote went
        # through other matmul shapes than one-token decode, so the CPU
        # backend may round them an ulp apart (4.8e-7 seen); a leaked
        # candidate is another token's K/V and differs at O(1).
        model = _tiny()
        prompt = _repetitive(128)
        engs = {}
        for tag, spec in (("plain", None), ("spec", "ngram")):
            eng = ServingEngine(model, max_slots=2, spec_decode=spec)
            eng.add_request(prompt, max_new_tokens=24)
            eng.run()
            engs[tag] = eng
        pc_p = engs["plain"].prefix_cache
        pc_s = engs["spec"].prefix_cache
        assert engs["spec"].spec_stats()["windows"] > 0
        shared = set(pc_p._map) & set(pc_s._map)
        assert shared, "no common registered prefix blocks to compare"
        kp = np.asarray(engs["plain"].cache.k)
        ks = np.asarray(engs["spec"].cache.k)
        vp = np.asarray(engs["plain"].cache.v)
        vs = np.asarray(engs["spec"].cache.v)
        for h in shared:
            bp, bs_ = pc_p._map[h], pc_s._map[h]
            np.testing.assert_allclose(kp[:, bp], ks[:, bs_], atol=1e-5)
            np.testing.assert_allclose(vp[:, bp], vs[:, bs_], atol=1e-5)

    def test_prefix_hit_after_spec_run_stays_token_identical(self):
        model = _tiny()
        prompt = _repetitive(128)
        eng = ServingEngine(model, max_slots=2, spec_decode="ngram")
        r1 = eng.add_request(prompt, max_new_tokens=24)
        eng.run()
        r2 = eng.add_request(prompt, max_new_tokens=24)
        out = eng.run()
        assert eng.prefix_cache.hits > 0
        assert np.array_equal(out[r1], out[r2])


class TestScheduling:
    def test_mixed_spec_and_optout_slots(self):
        model = _tiny()
        prompt = _repetitive(128)
        eng = ServingEngine(model, max_slots=2, spec_decode="ngram")
        r_spec = eng.add_request(prompt, max_new_tokens=16)
        r_plain = eng.add_request(prompt, max_new_tokens=16,
                                  speculative=False)
        out = eng.run()
        assert eng.spec_stats()["windows"] > 0
        base, _ = _drive(model, [prompt], None, nt=16)
        assert np.array_equal(out[r_spec], base[0])
        assert np.array_equal(out[r_plain], base[0])

    def test_mid_window_eos(self):
        model = _tiny()
        prompt = _repetitive(128)
        base, _ = _drive(model, [prompt], None, nt=16)
        eos = int(base[0][7])
        b_eos, _ = _drive(model, [prompt], None, nt=16, eos_token_id=eos)
        s_eos, eng = _drive(model, [prompt], "ngram", nt=16,
                            eos_token_id=eos)
        assert np.array_equal(b_eos[0], s_eos[0])
        assert len(s_eos[0]) < 16          # eos actually cut the window

    def test_timeout_during_verify_releases_blocks(self):
        import time

        model = _tiny()
        eng = ServingEngine(model, max_slots=2, spec_decode="ngram")
        free0 = eng.allocator.available
        r = eng.add_request(_repetitive(128), max_new_tokens=40,
                            max_time_ms=1.0)
        time.sleep(0.005)
        for _ in range(60):
            if not eng.has_work():
                break
            eng.step()
        assert eng.finish_reasons[r] == "timeout"
        assert eng.allocator.available == free0


class TestTpotAccounting:
    def test_accepts_all_k4_observes_per_token(self):
        # K=4 accepts-all: each tick emits 5 tokens. TPOT must be
        # observed once PER TOKEN at tick_wall/5 — one observation per
        # tick would report a fake 5x TPOT win
        model = _tiny()
        prompt = _repetitive(128)
        base, _ = _drive(model, [prompt], None, nt=20)
        replay = ReplayProposer(4, {0: base[0]})
        eng = ServingEngine(model, max_slots=2,
                            spec_decode=SpecConfig(proposer=replay))
        r = eng.add_request(prompt, max_new_tokens=20)
        out = eng.run()
        ss = eng.spec_stats()
        assert np.array_equal(out[r], base[0])
        assert ss["accept_rate"] == pytest.approx(1.0)
        # one observation per DECODE-emitted token (prefill emits the
        # first of the 20, so 19 decode tokens across ~4 ticks)
        assert eng._m_tpot.count == eng.stats()["decode_tokens"] == 19
        assert eng._m_decode_step.count == ss["windows"]
        assert ss["windows"] < 19           # multi-token ticks happened

    def test_plain_engine_tpot_count_unchanged(self):
        model = _tiny()
        _, eng = _drive(model, [_repetitive(128)], None, nt=12)
        assert eng._m_tpot.count == eng.stats()["decode_tokens"] == 11


class TestAuditAndTrend:
    def test_d16_fire_on_collapse(self):
        from paddle_tpu.analysis import audit_spec_decode

        model = _tiny()
        eng = ServingEngine(
            model, max_slots=2,
            spec_decode=SpecConfig(proposer=AlwaysRejectProposer(4)))
        eng.add_request(_repetitive(128), max_new_tokens=12)
        eng.run()
        eng.finish_warmup()
        eng.add_request(_repetitive(128, seed=2), max_new_tokens=12)
        eng.run()
        f = audit_spec_decode(eng)
        assert f[0].severity == "warning" and "collapsed" in f[0].message

    def test_d16_healthy_parity_and_disabled(self):
        from paddle_tpu.analysis import audit_spec_decode

        model = _tiny()
        _, eng = _drive(model, [_repetitive(128)], "ngram")
        eng.finish_warmup()
        f = audit_spec_decode(eng, parity=True)
        assert f[0].severity == "note" and "healthy" in f[0].message
        assert audit_spec_decode(eng, parity=False)[0].severity == "error"
        _, plain = _drive(model, [_repetitive(128)], None, nt=4)
        assert audit_spec_decode(plain)[0].severity == "note"

    def test_bench_trend_accept_is_higher_better(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            from bench_trend import lower_is_better
        finally:
            sys.path.pop(0)
        assert not lower_is_better("ngram_k4_repetitive_accept")
        assert not lower_is_better("spec.accept_rate")
        assert lower_is_better("ttft_ms_p95")

    def test_spec_metrics_registered(self):
        _, eng = _drive(_tiny(), [_repetitive(128)], "ngram", nt=8)
        names = set(eng.registry.names())
        for n in ("serving_spec_windows_total",
                  "serving_spec_proposed_tokens_total",
                  "serving_spec_accepted_tokens_total",
                  "serving_spec_accept_rate",
                  "serving_spec_accepted_per_window"):
            assert n in names, n


class TestStaticEngine:
    def test_static_ngram_parity(self):
        model = _tiny()
        prompt = _repetitive(128).reshape(1, -1)
        t = paddle.to_tensor(prompt)
        base = np.asarray(model.generate(t, max_new_tokens=16)._data)
        spec = np.asarray(model.generate(
            t, max_new_tokens=16, spec_decode="ngram")._data)
        assert np.array_equal(base, spec)

    def test_static_spec_rejects_sampling(self):
        model = _tiny()
        t = paddle.to_tensor(np.zeros((1, 8), "int64"))
        with pytest.raises(NotImplementedError):
            model.generate(t, max_new_tokens=4, spec_decode="ngram",
                           do_sample=True)


class TestSpecConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            SpecConfig(method="magic")
        with pytest.raises(ValueError):
            SpecConfig(k=0)
        with pytest.raises(ValueError):
            SpecConfig(method="draft")          # draft needs a model

    def test_flag_selects_proposer(self):
        paddle.set_flags({"FLAGS_spec_decode": "ngram"})
        try:
            eng = ServingEngine(_tiny(), max_slots=2)
            assert isinstance(eng.proposer, NgramProposer)
        finally:
            paddle.set_flags({"FLAGS_spec_decode": "off"})
        assert ServingEngine(_tiny(), max_slots=2).proposer is None
