"""One decode tick in flight (`ServingEngine.step`): the host dispatches
tick n+1 before it fetches tick n's tokens, each slot's last token kept
on the device. For the dense model and each per-layer cache kind
(windowed, latent, recurrent): the parent commit's tokens, greedy and
sampled, for requests that join and leave mid-stream; a request ended by
eos or by its deadline while a tick is in flight emits nothing after; the
pages and slot it freed serve the next request correctly; `run`, `drain`
and `generate_paged` return every token; a verify tick is synchronous;
`ahead_slots` and `serving_decode_ahead_total` count the ticks
dispatched ahead. Small sizes, the CPU backend."""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.inference.engine import ServingEngine, generate_paged

KINDS = ("dense", "window", "latent", "recurrent")


@functools.cache
def _model(kind):
    """(model, vocabulary) of a kind, built once a test process."""
    if kind == "dense":
        from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128))
        m.eval()
        return m, 128
    module = {"window": "test_cohere2_moe", "latent": "test_pangu_ultra_moe",
              "recurrent": "test_gigachat3_5"}[kind]
    model, _ = __import__(module).tiny_model(21)
    return model, 256


def _engine(kind, **kw):
    args = dict(max_slots=4, kv_block_size=8, max_model_len=128,
                chunked_prefill_tokens=16, seed=3)
    args.update(kw)
    return ServingEngine(_model(kind)[0], **args)


SAMPLE_A = dict(do_sample=True, temperature=0.8, top_k=5)
SAMPLE_B = dict(do_sample=True, temperature=1.3, top_p=0.9)

#: (step it joins at, prompt length, max_new_tokens, sampling): four join
#: while others decode (whole prompts and chunk ladders of 1-3 chunks),
#: leave by length at different steps, and a fifth joins a slot one of
#: them freed
PLAN = ((0, 20, 14, {}), (0, 9, 16, SAMPLE_A), (2, 37, 10, SAMPLE_B),
        (4, 5, 6, {}), (16, 12, 6, {}))


def _serve(eng, plan, vocab):
    """Drive `eng` step by step, each request added at its step; the
    tokens each request completed with, in plan order."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, vocab, (n,)) for _, n, _, _ in plan]
    rids = [None] * len(plan)
    step = 0
    while step <= max(p[0] for p in plan) or eng.has_work():
        for i, (at, _, new, samp) in enumerate(plan):
            if at == step:
                rids[i] = eng.add_request(prompts[i], max_new_tokens=new,
                                          **samp)
        eng.step()
        step += 1
    return [eng.completed[r].tolist() for r in rids]


#: what the parent commit (each tick's tokens fetched before the next
#: was built) served for `PLAN`, by kind: generated by running `_serve`
#: on that tree, CPU backend
PARENT_TOKENS = {
    "dense": [
        [123, 36, 32, 126, 32, 36, 87, 16, 19, 32, 36, 99, 3, 85],
        [125, 9, 35, 35, 96, 35, 55, 24, 93, 68, 29, 61, 0, 41, 106, 19],
        [95, 121, 83, 123, 95, 117, 66, 68, 93, 60],
        [122, 59, 9, 61, 100, 123],
        [40, 86, 23, 123, 93, 86],
    ],
    "window": [
        [151, 151, 151, 151, 151, 77, 77, 77, 77, 77, 77, 151, 151, 151],
        [220, 222, 105, 46, 13, 220, 220, 220, 220, 219, 36, 44, 79, 79, 79,
         79],
        [155, 43, 101, 242, 177, 188, 168, 127, 114, 98],
        [158, 158, 158, 158, 158, 158],
        [196, 195, 15, 133, 15, 133],
    ],
    "latent": [
        [249, 68, 129, 36, 202, 208, 178, 192, 145, 24, 202, 185, 10, 146],
        [206, 122, 244, 1, 202, 170, 185, 131, 202, 170, 179, 123, 123, 140,
         45, 140],
        [154, 88, 96, 122, 153, 20, 168, 209, 89, 253],
        [47, 208, 47, 106, 106, 47],
        [0, 87, 47, 106, 61, 243],
    ],
    "recurrent": [
        [121, 164, 250, 80, 200, 78, 2, 93, 238, 24, 2, 129, 13, 93],
        [167, 43, 182, 235, 182, 170, 250, 138, 134, 218, 133, 36, 94, 147, 79,
         230],
        [45, 136, 32, 219, 177, 97, 168, 212, 100, 86],
        [47, 195, 80, 18, 140, 192],
        [5, 197, 77, 114, 149, 108],
    ],
}


@pytest.mark.parametrize("kind", KINDS)
def test_requests_that_join_and_leave_serve_the_parents_tokens(kind):
    """Greedy and sampled rows side by side, requests joining while
    others decode and leaving by length: token for token what the parent
    served. The same ticks hold the same rows, so the key splits and the
    sampled draws are the parent's."""
    got = _serve(_engine(kind), PLAN, _model(kind)[1])
    assert [len(t) for t in got] == [p[2] for p in PLAN]
    assert got == PARENT_TOKENS[kind]


def _forward_gap(kind, prompt, toks):
    """Widest gap by which a served token's logit lies below the best
    logit of the model's own forward over the served sequence."""
    model = _model(kind)[0]
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int64)[None]
    lg = np.asarray(model(paddle.to_tensor(seq))._data, np.float32)[0]
    rows = lg[len(prompt) - 1:]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


def _until(eng, cond, events, limit=200):
    for _ in range(limit):
        if cond():
            return
        events.extend(eng.step())
    raise AssertionError("condition never held")


@pytest.mark.parametrize("kind", KINDS)
def test_eos_in_flight_drops_the_overrun_row_and_frees_good_pages(kind):
    """Request R ends by eos in tick n while tick n+1, which holds a row
    for it, is already dispatched: R emits nothing after its eos, and a
    request admitted next into R's slot and freed pages (the pool holds
    no others) serves the model's own tokens."""
    vocab = _model(kind)[1]
    rs = np.random.RandomState(7)
    r_prompt, s_prompt, u_prompt = (rs.randint(0, vocab, (n,))
                                    for n in (11, 6, 10))

    def engine():
        # R's and S's blocks, no more (+ the trash block)
        return _engine(kind, max_slots=2, num_kv_blocks=1 + 4 + 4)

    # R's tokens without an eos, beside the same S
    first = engine()
    rid = first.add_request(r_prompt, max_new_tokens=20)
    first.add_request(s_prompt, max_new_tokens=26)
    toks = first.run()[rid].tolist()
    k = next(i for i in range(3, 20) if toks[i] not in toks[:i])
    eos = toks[k]
    eng = engine()
    events = []
    r = eng.add_request(r_prompt, max_new_tokens=20, eos_token_id=eos)
    s = eng.add_request(s_prompt, max_new_tokens=26)
    _until(eng, lambda: r in eng.completed, events)
    # the tick after R's eos was in flight when the eos arrived
    assert eng._ticks and r in [q.rid for q in eng._ticks[0].reqs]
    u = eng.add_request(u_prompt, max_new_tokens=21)
    eng.run()
    mine = [(t, fin) for rid_, t, fin in events if rid_ == r]
    assert [t for t, _ in mine] == toks[:k + 1]
    assert [fin for _, fin in mine] == [False] * k + [True]
    assert eng.completed[r].tolist() == toks[:k + 1]
    assert eng.finish_reasons[r] == "eos"
    assert len(eng.completed[u]) == 21 and len(eng.completed[s]) == 26
    assert _forward_gap(kind, u_prompt, eng.completed[u]) < 1e-3
    assert _forward_gap(kind, s_prompt, eng.completed[s]) < 1e-3
    assert eng.prefix_cache.available == eng.allocator.num_blocks - 1


@pytest.mark.parametrize("kind", KINDS)
def test_deadline_while_a_tick_is_in_flight(kind):
    """A request whose deadline passes while its tick is in flight ends
    with the tokens it had: its terminal event comes once, from the step
    that expires it, with no token after it; the other request runs to
    its end."""
    vocab = _model(kind)[1]
    rs = np.random.RandomState(9)
    eng = _engine(kind)
    r = eng.add_request(rs.randint(0, vocab, (7,)), max_new_tokens=30,
                        max_time_ms=600_000)
    s_prompt = rs.randint(0, vocab, (13,))
    s = eng.add_request(s_prompt, max_new_tokens=12)
    events = []
    req = eng._waiting[0]
    _until(eng, lambda: len(req.tokens) >= 3 and req.in_flight, events)
    had = list(req.tokens)
    req.deadline_s = 0.0                        # long past
    while eng.has_work():
        events.extend(eng.step())
    mine = [e for e in events if e[0] == r]
    assert mine[-1] == (r, None, True)
    assert sum(fin for _, _, fin in mine) == 1
    assert [t for _, t, _ in mine[:-1]] == had
    assert eng.completed[r].tolist() == had
    assert eng.finish_reasons[r] == "timeout"
    assert len(eng.completed[s]) == 12
    assert _forward_gap(kind, s_prompt, eng.completed[s]) < 1e-3
    assert eng.num_active == 0


def test_run_drain_and_generate_paged_return_every_token():
    """A tick left in flight is taken before each of them returns."""
    rs = np.random.RandomState(4)
    eng = _engine("dense")
    rids = [eng.add_request(rs.randint(0, 128, (n,)), max_new_tokens=m)
            for n, m in ((5, 9), (23, 4), (12, 1), (30, 7))]
    done = eng.run()
    assert [len(done[r]) for r in rids] == [9, 4, 1, 7]
    assert not eng._ticks and not eng.has_work()
    # drain: admission stops, the engine is drained only once the tick in
    # flight is taken
    eng = _engine("dense")
    rids = [eng.add_request(rs.randint(0, 128, (n,)), max_new_tokens=m)
            for n, m in ((7, 6), (18, 5))]
    eng.step()
    eng.step()
    eng.drain()
    steps = 0
    while not eng.drained:
        assert eng._ticks or eng.num_active
        eng.step()
        steps += 1
    assert steps and not eng._ticks
    assert [len(eng.completed[r]) for r in rids] == [6, 5]
    # close takes a tick still in flight
    eng = _engine("dense")
    r = eng.add_request(np.arange(5), max_new_tokens=3)
    eng.step()
    eng.step()
    assert eng._ticks
    eng.close()
    assert not eng._ticks and len(eng.completed[r]) == 3
    # generate_paged: a rectangular batch, every row its whole budget
    model = _model("dense")[0]
    ids = rs.randint(0, 128, (3, 9))
    out = generate_paged(model, ids, 11, seed=1, kv_block_size=8)
    assert out.shape == (3, 11) and (out >= 0).all()
    for row, toks in zip(ids, out):
        assert _forward_gap("dense", row, toks) < 1e-3


def _decode_spans(evs):
    steps = sorted((e for e in evs if e.name == "serving.step"),
                   key=lambda e: e.start)

    def step_of(e):
        return next(i for i, s in enumerate(steps)
                    if s.start <= e.start and e.end <= s.end)
    builds = sorted((e for e in evs if e.name == "serving.decode.build"),
                    key=lambda e: e.start)
    runs = sorted((e for e in evs if e.name == "serving.decode.run"),
                  key=lambda e: e.start)
    return [(step_of(b), step_of(r), r.attrs) for b, r in zip(builds, runs)]


def _ahead_total(eng):
    return eng.metrics()["serving_decode_ahead_total"]["samples"][0][
        "value"]


def test_a_verify_tick_is_synchronous():
    """With a proposer every tick's tokens are taken in the step that
    dispatched it (the proposer reads them), and nothing is left in
    flight between steps; the stream is what it was (the mixed-bucket
    test's parent tokens hold that)."""
    eng = _engine("dense", spec_decode="ngram")
    pat = np.array([3, 4, 5, 6] * 6)
    obs.clear_spans()
    eng.add_request(pat, max_new_tokens=12)
    eng.add_request(np.arange(9), max_new_tokens=7)
    while eng.has_work():
        eng.step()
        assert not eng._ticks
    spans = _decode_spans(obs.span_events())
    assert spans and all(b == r and a["ahead_slots"] == 0
                         for b, r, a in spans)
    assert _ahead_total(eng) == 0
    assert eng.stats()["spec_windows"] > 0
    # a request that opts out decodes ahead on the same engine
    eng.add_request(np.arange(9), max_new_tokens=7, speculative=False)
    obs.clear_spans()
    eng.run()
    assert [a["ahead_slots"] for _, _, a in
            _decode_spans(obs.span_events())] == [0] + [1] * 5


@pytest.mark.parametrize("kind", KINDS)
def test_ahead_slots_count_steady_ticks(kind):
    """`ahead_slots` is a tick's live rows where an earlier tick's tokens
    were still on the device when it was dispatched, 0 on the first tick
    after an idle engine; the counter is their sum; a tick's `.run` span
    lies in the step after its build; a greedy call hands over one
    packed operand (the layered chunk two: its ids are their own)."""
    vocab = _model(kind)[1]
    eng = _engine(kind)
    rs = np.random.RandomState(2)
    for m in (2, 4):                            # buckets 2 and 1 warm
        eng.add_request(rs.randint(0, vocab, (6,)), max_new_tokens=m)
    eng.run()
    before = _ahead_total(eng)
    obs.clear_spans()
    for n, m in ((6, 8), (9, 6)):
        eng.add_request(rs.randint(0, vocab, (n,)), max_new_tokens=m)
    eng.run()
    evs = obs.span_events()
    spans = _decode_spans(evs)
    assert spans[0][2]["ahead_slots"] == 0
    assert all(a["ahead_slots"] == a["active"] for _, _, a in spans[1:])
    assert all(r == b + 1 for b, r, _ in spans)
    assert _ahead_total(eng) - before == sum(
        a["ahead_slots"] for _, _, a in spans) > 0
    two = 1 if kind == "dense" else 2
    for e in evs:
        if e.name == "serving.decode.build":
            assert e.attrs["h2d"] == 1
        elif e.name == "serving.chunk.build":
            assert e.attrs["h2d"] == two
