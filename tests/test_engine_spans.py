"""The spans inside `ServingEngine.step` and the `to_static` call, read
back from the one span log (`obs.span_events()`), and the engine metrics
repaired beside them: a request's clock running from `arrival_s`, TPOT as
the request's user sees it, the bounded TTFT / queue-wait samples.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.inference.engine import ServingEngine
from paddle_tpu.obs.metrics import DEFAULT_EXACT_CAP

RUNS = ("serving.prefill.run", "serving.chunk.run", "serving.decode.run",
        "serving.verify.run")


def _tiny_llama(max_pos=128):
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=max_pos)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def served():
    """A tiny engine run that takes every path of a tick: short prompts
    prefilled whole inside admission, long ones through the chunk ladder,
    decode ticks with one to three slots live, a request left waiting."""
    eng = ServingEngine(_tiny_llama(), max_slots=3, kv_block_size=8,
                        chunked_prefill_tokens=16)
    rs = np.random.RandomState(0)
    obs.clear_spans()
    before = eng.stats()
    for ln, nt in ((5, 4), (40, 6), (12, 3), (33, 5), (7, 2)):
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    eng.run()
    return eng, obs.span_events(), before, eng.stats()


def _children(evs, parent):
    """The records that lie inside `parent` and name it as their parent."""
    return sorted((e for e in evs if e.parent == parent.name
                   and parent.start <= e.start and e.end <= parent.end),
                  key=lambda e: e.start)


def test_every_record_is_well_formed(served):
    _, evs, _, _ = served
    assert evs and all(isinstance(e, obs.SpanRecord) for e in evs)
    assert all(e.start <= e.end for e in evs)
    assert {e.name.split(".")[0] for e in evs} == {"serving"}
    names = {e.name for e in evs}
    assert {"serving.step", "serving.expire", "serving.admit",
            "serving.prefill.build", "serving.prefill.run",
            "serving.chunk.build", "serving.chunk.run",
            "serving.decode.build", "serving.decode.run",
            "serving.decode.emit"} <= names
    assert "serving.verify.run" not in names     # no speculation here


def test_children_lie_inside_their_step_and_do_not_overlap(served):
    eng, evs, _, _ = served
    steps = [e for e in evs if e.name == "serving.step"]
    assert len(steps) >= eng.steps > 0
    assert all(s.parent is None for s in steps)
    seen = 0
    for st in steps:
        kids = _children(evs, st)
        seen += len(kids)
        assert [k.name for k in kids[:2]] == ["serving.expire",
                                              "serving.admit"]
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start, (a, b)
        assert set(st.attrs) == {"active", "waiting"}
    assert seen == sum(e.parent == "serving.step" for e in evs)
    # a short prompt is prefilled inside the admission pass
    for e in evs:
        if e.name.startswith("serving.prefill."):
            assert e.parent == "serving.admit"
    admitted = sum(e.attrs["admitted"] for e in evs
                   if e.name == "serving.admit")
    assert admitted == 5


def test_prefill_spans_count_the_prefill_counters_tokens(served):
    _, evs, before, after = served
    runs = [e for e in evs if e.name in ("serving.chunk.run",
                                         "serving.prefill.run")]
    assert sum(e.attrs["tokens"] for e in runs) \
        == after["prefill_tokens"] - before["prefill_tokens"] \
        == 5 + 40 + 12 + 33 + 7
    chunks = [e for e in evs if e.name == "serving.chunk.run"]
    assert len(chunks) == after["prefill_chunks"] - before["prefill_chunks"]
    assert all(set(e.attrs) == {"rid", "tokens", "start", "last", "bucket"}
               for e in chunks)
    # request 1's 40 tokens: chunks of 16, 16, 8, the last one flagged
    mine = sorted((e for e in chunks if e.attrs["rid"] == 1),
                  key=lambda e: e.start)
    assert [(e.attrs["start"], e.attrs["tokens"], e.attrs["last"])
            for e in mine] == [(0, 16, False), (16, 16, False),
                               (32, 8, True)]


def test_decode_spans_are_the_decode_step_observation(served):
    """`decode.build` + `decode.run` of a tick equals what
    `serving_decode_step_seconds` observed for it, to the microsecond."""
    eng, evs, _, _ = served
    build = sorted((e for e in evs if e.name == "serving.decode.build"),
                   key=lambda e: e.start)
    run = sorted((e for e in evs if e.name == "serving.decode.run"),
                 key=lambda e: e.start)
    emit = [e for e in evs if e.name == "serving.decode.emit"]
    assert len(build) == len(run) == len(emit) == eng._m_decode_step.count
    ticks = [(b.end - b.start) + (r.end - r.start)
             for b, r in zip(build, run)]
    assert sorted(ticks) == pytest.approx(sorted(eng._m_decode_step._exact),
                                          abs=1e-6)
    assert sum(ticks) == pytest.approx(eng._m_decode_step.sum, abs=1e-6)
    for b, r in zip(build, run):
        assert b.end <= r.start
        assert set(r.attrs) == {"active", "bucket", "live_pages",
                                "kv_steps", "ahead_slots"}
        assert r.attrs["ahead_slots"] in (0, r.attrs["active"])
        # the build says besides how many host arrays it handed over
        assert b.attrs == {**r.attrs, "h2d": b.attrs["h2d"]}
        assert 1 <= b.attrs["active"] <= b.attrs["bucket"] <= 4
        # at least a page a live slot, at most every page of each
        assert b.attrs["active"] <= b.attrs["live_pages"] \
            <= b.attrs["active"] * eng.pages
        assert b.attrs["kv_steps"] == 0      # CPU: the XLA composition


def test_decode_span_counts_live_pages_and_the_kernels_grid(monkeypatch):
    """`live_pages` is the sum over live slots of `pos // block_size + 1`;
    `kv_steps` is the `paged_decode` grid at the tick's slot bucket, from
    the function that sizes the kernel's compute block."""
    from paddle_tpu.ops import pallas_decode

    eng = ServingEngine(_tiny_llama(), max_slots=4, kv_block_size=8)
    for ln in (5, 9, 17):
        eng.add_request(np.arange(ln) % 128, max_new_tokens=3)
    eng.step()                                  # admits and prefills all 3
    obs.clear_spans()
    eng.step()
    run, = [e for e in obs.span_events() if e.name == "serving.decode.run"]
    # a slot's pos is one past its prompt's after the tick; read before it
    assert run.attrs["live_pages"] == 5 // 8 + 1 + 9 // 8 + 1 + 17 // 8 + 1
    assert run.attrs["kv_steps"] == 0
    monkeypatch.setattr(pallas_decode, "use_pallas_decode",
                        lambda *a, **k: True)
    # 16 pages of 8 rows x 4 heads x 8 wide fit one compute block: a step
    # a slot of the 4-slot bucket
    assert eng._kv_steps(4) == 4 == pallas_decode.kv_steps(
        4, eng.pages, 8, 4, 8, eng.cache.k.dtype.itemsize)
    # room for K and V, double-buffered, of 4 pages: 4 blocks of 4 a slot
    page = 4 * 8 * 8 * eng.cache.k.dtype.itemsize
    monkeypatch.setattr(pallas_decode, "_STREAM_VMEM_BYTES", 4 * 4 * page)
    assert eng.pages == 16 and eng._kv_steps(4) == 4 * 4


def test_flight_recorder_reads_the_spans_clock(served, tmp_path):
    """One pair of clock reads per interval: the flight's program spans
    and decode ticks ARE the `.run` spans' start and end, and the dump's
    TTFT tiling assertion keeps holding."""
    eng, evs, _, _ = served
    runs = {(e.start, e.end) for e in evs if e.name in RUNS}
    ticks = [t for t in eng.flight._ticks if t[0] == "decode_tick"]
    assert ticks and all((t0, t1) in runs for _, t0, t1, _ in ticks)
    for fl in eng.flight.flights():
        assert fl.spans and all((t0, t1) in runs
                                for _, t0, t1, _ in fl.spans)
        assert fl.first_token_s in {end for _, end in runs}
    path = str(tmp_path / "trace.json")
    eng.dump_trace(path)                     # raises if the tiling broke
    summary = obs.validate_trace(path)
    assert summary["requests"] == summary["tiled_requests"] == 5
    assert sorted(eng.stats()["ttft_s"]) == sorted(
        fl.first_token_s - fl.arrival_s for fl in eng.flight.flights())


def test_compile_span_on_a_program_miss(monkeypatch):
    from paddle_tpu.inference import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_SERVING_EXECUTABLES", {})
    monkeypatch.setattr(engine_mod, "_SEEN_SERVING_PROGRAMS", set())
    obs.clear_events()
    eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8)
    obs.clear_spans()
    eng.add_request(np.arange(5), max_new_tokens=3)
    eng.run()
    comp = [e for e in obs.span_events() if e.name == "serving.compile"]
    assert [(e.attrs["site"], e.parent) for e in comp] == [
        ("serving.prefill", "serving.prefill.build"),
        ("serving.decode", "serving.decode.build")]
    walls = sorted(e.wall_s for e in obs.compile_events()
                   if e.site.startswith("serving."))
    assert walls == sorted(e.end - e.start for e in comp)
    # a second engine finds the programs: no compile span
    obs.clear_spans()
    eng2 = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8)
    eng2.add_request(np.arange(5), max_new_tokens=3)
    eng2.run()
    assert not [e for e in obs.span_events() if e.name == "serving.compile"]


def test_verify_window_shares_the_spans_clock():
    eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8,
                        spec_decode="ngram")
    obs.clear_spans()
    pat = np.array([3, 4, 5, 6] * 6)
    eng.add_request(pat, max_new_tokens=12)
    eng.run()
    ver = [e for e in obs.span_events() if e.name == "serving.verify.run"]
    wins = [t for t in eng.flight._ticks if t[0] == "verify_window"]
    assert wins and len(ver) == len(wins)
    assert sorted((e.start, e.end) for e in ver) \
        == sorted((t0, t1) for _, t0, t1, _ in wins)
    assert all(set(e.attrs) == {"active", "k"} for e in ver)


# ------------------------------------------- one packed operand a call

def _tiny_gpt(max_pos=128):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=max_pos))
    m.eval()
    return m


ARCH_KV = [(a, kv) for a in ("llama", "gpt")
           for kv in ("model", "int8", "int4")]
BUILDS = ("serving.prefill.build", "serving.chunk.build",
          "serving.decode.build")


def _engine(arch, kv, **kw):
    model = _tiny_llama() if arch == "llama" else _tiny_gpt()
    return ServingEngine(model, max_slots=4, kv_block_size=8,
                         chunked_prefill_tokens=16, kv_cache_dtype=kv,
                         seed=7, **kw)


@pytest.mark.parametrize("arch,kv", ARCH_KV)
def test_a_greedy_call_hands_the_device_one_packed_operand(arch, kv):
    """`h2d` on every `.build` span is the number of host arrays its call
    handed to the device: on greedy traffic ONE (tokens, positions, block
    tables, ids and the scalars packed into one int32 array; the sampling
    operands a cached device dict), plus the four arrays of that dict the
    first time a bucket size is seen."""
    eng = _engine(arch, kv)
    rs = np.random.RandomState(3)
    obs.clear_spans()
    for ln, nt in ((5, 4), (40, 6), (12, 3), (33, 5), (7, 2), (21, 4)):
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    eng.run()
    builds = sorted((e for e in obs.span_events() if e.name in BUILDS),
                    key=lambda e: e.start)
    assert {e.name for e in builds} == set(BUILDS)
    sizes = set()
    for e in builds:
        # the sampling operands' size: the slot bucket, or one request
        size = e.attrs["bucket"] if e.name == BUILDS[2] else 1
        assert e.attrs["h2d"] == (1 if size in sizes else 5), e
        sizes.add(size)
    assert len(sizes) >= 3 and eng._h2d == len(builds) + 4 * len(sizes)
    # steady state: the same traffic again transfers one array a call
    obs.clear_spans()
    for ln, nt in ((5, 4), (40, 6), (12, 3)):
        eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
    eng.run()
    again = [e for e in obs.span_events() if e.name in BUILDS]
    assert again and all(e.attrs["h2d"] == 1 for e in again)


@pytest.mark.parametrize("arch,kv", ARCH_KV)
def test_greedy_calls_share_one_sampling_dict_a_size(arch, kv):
    """Consecutive greedy calls of one bucket size are handed the
    IDENTICAL device dict (nothing is built or transferred for them), no
    program donates it, and a sampling call gets arrays of its own."""
    eng = _engine(arch, kv)
    seen = []
    program = eng._program

    def recording(site, jitted, n_static, bucket, any_sample, extra, args):
        seen.append((site, int(bucket), bool(any_sample), args[-2]))
        return program(site, jitted, n_static, bucket, any_sample, extra,
                       args)

    eng._program = recording
    for ln in (6, 30, 9):
        eng.add_request(np.arange(ln) % 128, max_new_tokens=6)
    eng.run()
    decodes = [s for s in seen if s[0] == "serving.decode"]
    assert len(decodes) >= 5
    by_size = {}
    for site, bucket, any_sample, samp in seen:
        assert not any_sample
        size = bucket if site == "serving.decode" else 1
        assert by_size.setdefault(size, samp) is samp
        assert samp is eng._greedy_samp[size]
    assert len(by_size) == len(eng._greedy_samp) >= 2
    for size, samp in by_size.items():          # alive after every call
        assert set(samp) == {"do_sample", "temperature", "top_k", "top_p"}
        assert not any(a.is_deleted() for a in samp.values())
        assert samp["do_sample"].shape == (size,)
        assert not np.asarray(samp["do_sample"]).any()
    # a sampled request: its calls are handed fresh arrays, the greedy
    # dicts stay what they were
    seen.clear()
    eng.add_request(np.arange(7), max_new_tokens=3, do_sample=True,
                    temperature=0.7, top_k=5)
    eng.run()
    sampled = [s for s in seen if s[2]]
    assert sampled and all(s[3] is not by_size.get(1) for s in sampled)
    assert len({id(s[3]) for s in sampled}) == len(sampled)
    assert all(eng._greedy_samp[k] is v for k, v in by_size.items())


#: what the parent commit (unpacked operands, `_samp_arrays` on every
#: call) served for `_mixed_traffic`, by (arch, kv_mode, spec_decode): generated by
#: running `_mixed_traffic` on that tree, CPU backend
PARENT_TOKENS = {
    ("llama", "model", None): [
        [105, 12, 77, 3, 62, 116, 41, 108],
        [42, 50, 43, 110, 126, 89, 115, 72],
        [108, 90, 58, 1, 74, 18, 11, 97],
        [57, 48, 117, 35, 96, 84, 1, 126],
    ],
    ("llama", "int8", None): [
        [105, 12, 77, 3, 62, 116, 41, 108],
        [42, 50, 43, 110, 126, 89, 115, 72],
        [108, 90, 58, 1, 74, 18, 11, 97],
        [57, 48, 117, 35, 96, 84, 1, 126],
    ],
    ("llama", "int4", None): [
        [105, 12, 77, 126, 119, 9, 82, 34],
        [42, 50, 82, 23, 3, 93, 84, 89],
        [108, 90, 58, 1, 74, 18, 11, 97],
        [57, 48, 19, 92, 96, 84, 1, 70],
    ],
    ("gpt", "model", None): [
        [4, 57, 30, 67, 24, 80, 57, 71],
        [77, 38, 97, 103, 121, 77, 109, 57],
        [108, 38, 1, 70, 60, 18, 15, 97],
        [13, 73, 1, 76, 1, 65, 0, 24],
    ],
    ("gpt", "int8", None): [
        [4, 57, 30, 67, 24, 80, 57, 71],
        [77, 38, 97, 103, 121, 77, 109, 57],
        [108, 38, 1, 70, 60, 18, 15, 97],
        [13, 73, 1, 76, 1, 65, 0, 24],
    ],
    ("gpt", "int4", None): [
        [4, 57, 30, 67, 24, 80, 57, 71],
        [77, 38, 97, 103, 0, 115, 26, 89],
        [108, 38, 1, 70, 60, 18, 15, 97],
        [13, 73, 1, 76, 1, 65, 0, 24],
    ],
    ("llama", "model", "ngram"): [
        [105, 12, 77, 3, 62, 116, 41, 108],
        [42, 50, 43, 110, 126, 89, 113, 123],
        [108, 32, 94, 74, 94, 103, 31, 38],
        [57, 48, 117, 35, 96, 84, 1, 126],
    ],
}


def _mixed_traffic(arch, kv, **kw):
    """Greedy and sampled requests side by side in one engine: a whole
    short prompt and a chunked one of each kind, so that the prefill, the
    last chunk and the decode bucket all see a mixed `do_sample`."""
    eng = _engine(arch, kv, **kw)
    rs = np.random.RandomState(11)
    rids = [
        eng.add_request(rs.randint(0, 128, (5,)), max_new_tokens=8),
        eng.add_request(rs.randint(0, 128, (12,)), max_new_tokens=8,
                        do_sample=True, temperature=0.8, top_k=5),
        eng.add_request(rs.randint(0, 128, (40,)), max_new_tokens=8,
                        do_sample=True, temperature=1.3, top_p=0.9),
        eng.add_request(rs.randint(0, 128, (27,)), max_new_tokens=8),
    ]
    done = eng.run()
    return [done[r].tolist() for r in rids]


@pytest.mark.parametrize("arch,kv,spec", [(*a, None) for a in ARCH_KV]
                         + [("llama", "model", "ngram")])
def test_a_mixed_bucket_serves_the_parents_tokens(arch, kv, spec):
    """The sampling path is what it was: for a fixed engine key a bucket
    that mixes greedy and sampled rows serves the tokens the parent
    commit served (through the verify window too), and its greedy rows
    are those of an all-greedy run."""
    got = _mixed_traffic(arch, kv, **({"spec_decode": spec} if spec else {}))
    assert got == PARENT_TOKENS[arch, kv, spec]
    eng = _engine(arch, kv)
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 128, (n,)) for n in (5, 12, 40, 27)]
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    done = eng.run()
    assert got[0] == done[rids[0]].tolist()
    assert got[3] == done[rids[3]].tolist()
    assert got[1] != done[rids[1]].tolist() \
        or got[2] != done[rids[2]].tolist()      # sampling did sample


# ------------------------------------------------------------ arrival_s

def test_arrival_in_the_past_reads_as_queue_wait():
    """A request that reached the system 0.2 s before `add_request` has
    waited those 0.2 s: queue wait, TTFT, the flight's enqueue mark and
    the deadline all run from `arrival_s`."""
    eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8)
    arrived = time.perf_counter() - 0.2
    rid = eng.add_request(np.arange(6), max_new_tokens=2,
                          arrival_s=arrived, max_time_ms=60_000)
    req = eng._waiting[-1]
    assert req.arrival_s == arrived
    assert req.deadline_s == pytest.approx(arrived + 60.0)
    eng.run()
    st = eng.stats()
    assert st["queue_wait_s"][0] >= 0.2 and st["ttft_s"][0] >= 0.2
    assert eng._m_queue_wait.sum >= 0.2 and eng._m_ttft.sum >= 0.2
    fl = eng.flight.get(rid)
    assert fl.arrival_s == arrived and fl.ttft_s == st["ttft_s"][0]
    eng.flight._check_tiling()
    # without it the clock runs from the call, as before
    eng.add_request(np.arange(6), max_new_tokens=2)
    eng.run()
    assert eng.stats()["queue_wait_s"][1] < 0.2


def test_arrival_in_the_past_can_expire_before_admission():
    eng = ServingEngine(_tiny_llama(), max_slots=1, kv_block_size=8)
    rid = eng.add_request(np.arange(6), max_new_tokens=2, max_time_ms=50,
                          arrival_s=time.perf_counter() - 0.2)
    assert (rid, None, True) in eng.step()
    assert eng.finish_reasons[rid] == "timeout"


def test_routed_request_ttft_holds_its_inbox_wait():
    """A request the router took while the replica's driver was inside a
    step: the engine's TTFT and queue wait hold the time it sat in the
    inbox, because the clock runs from when `Router.submit` took it."""
    import threading

    from paddle_tpu.serving import Router

    eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8)
    real_step, real_add = eng.step, eng.add_request
    in_step, added = threading.Event(), {}

    def slow_first_step():
        if not in_step.is_set():
            in_step.set()
            time.sleep(0.3)
        return real_step()

    def add_request(prompt, **kw):
        added[len(prompt)] = time.perf_counter()
        return real_add(prompt, **kw)

    eng.step, eng.add_request = slow_first_step, add_request
    router = Router([eng])
    try:
        assert router.wait_ready(120)
        first = router.submit(np.arange(5), max_new_tokens=2)
        assert in_step.wait(120)             # the driver is in its step
        t_submit = time.perf_counter()
        second = router.submit(np.arange(7), max_new_tokens=2)
        first.result(timeout=120)
        second.result(timeout=120)
        in_inbox = added[7] - t_submit
        assert in_inbox >= 0.2
        fl = eng.flight.flights()[1]
        assert fl.prompt_len == 7
        assert t_submit <= fl.arrival_s <= t_submit + 0.05
        assert fl.ttft_s >= fl.admitted_s - fl.arrival_s >= in_inbox
        assert max(eng.stats()["queue_wait_s"]) >= in_inbox
    finally:
        router.close()


# ------------------------------------------------- the engine's own metrics

def test_tpot_is_the_gap_a_request_sees_not_the_tick_over_the_batch():
    """With 4 slots decoding, `serving_tpot_seconds` reads about one tick
    a token (each request gets one token a tick), not a quarter of it."""
    eng = ServingEngine(_tiny_llama(), max_slots=4, kv_block_size=8)
    rs = np.random.RandomState(1)
    for _ in range(4):                        # warm the programs
        eng.add_request(rs.randint(0, 128, (6,)), max_new_tokens=3)
    eng.run()
    tp0, st0 = len(eng._m_tpot._exact), len(eng._m_decode_step._exact)
    for _ in range(4):
        eng.add_request(rs.randint(0, 128, (6,)), max_new_tokens=24)
    obs.clear_spans()
    eng.run()
    steps = [e for e in obs.span_events() if e.name == "serving.step"
             and e.attrs["active"] == 4]
    tick = float(np.median([e.end - e.start for e in steps]))
    tpot = float(np.median(eng._m_tpot._exact[tp0:]))
    assert len(eng._m_tpot._exact) - tp0 == 4 * 23      # one a token
    assert abs(tpot - tick) <= 0.2 * tick, (tpot, tick)
    decode = float(np.median(eng._m_decode_step._exact[st0:]))
    assert tpot > 0.8 * decode                # not decode / 4


def test_ttft_and_queue_wait_samples_are_bounded():
    eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8)
    assert eng.ttfts.maxlen == eng.queue_waits.maxlen == DEFAULT_EXACT_CAP
    eng.ttfts.extend(range(DEFAULT_EXACT_CAP + 10))
    eng.add_request(np.arange(5), max_new_tokens=2)
    eng.run()
    st = eng.stats()
    assert len(st["ttft_s"]) == DEFAULT_EXACT_CAP
    assert isinstance(st["ttft_s"], list) and st["ttft_s"][-1] < 100
    assert len(st["queue_wait_s"]) == 1


# ------------------------------------------------------------------- jit

def test_to_static_spans_of_a_fresh_function():
    """One `jit.warmup`, one `jit.discover`, one `jit.compile`, then every
    call a `jit.call` around a `jit.dispatch`."""
    import paddle_tpu.nn as nn

    paddle.seed(0)
    net = nn.Linear(4, 3)

    def fwd_for_spans(x):
        return net(x).sum()

    step = paddle.jit.to_static(fwd_for_spans)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    obs.clear_spans()
    outs = [float(step(x)) for _ in range(6)]
    assert len(set(outs)) == 1
    evs = [e for e in obs.span_events()
           if e.attrs.get("fn") == "fwd_for_spans"]
    by = {}
    for e in evs:
        by.setdefault(e.name, []).append(e)
    assert {k: len(v) for k, v in by.items()} == {
        "jit.call": 6, "jit.warmup": 1, "jit.discover": 1,
        "jit.compile": 1, "jit.dispatch": 3}
    calls = sorted(by["jit.call"], key=lambda e: e.start)
    inner = [by["jit.warmup"][0], by["jit.discover"][0],
             by["jit.compile"][0], *sorted(by["jit.dispatch"],
                                           key=lambda e: e.start)]
    for c, i in zip(calls, inner):
        assert i.parent == "jit.call"
        assert c.start <= i.start and i.end <= c.end
    for a, b in zip(calls, calls[1:]):
        assert a.end <= b.start


def test_train_flight_dispatch_is_the_dispatch_span():
    """The training flight recorder takes the `jit.dispatch` span's own
    start and end for its `dispatch:<fn>` span."""
    import paddle_tpu.nn as nn
    from paddle_tpu.obs import train_flight

    net = nn.Linear(4, 3)

    def fwd_for_flight(x):
        return net(x).sum()

    step = paddle.jit.to_static(fwd_for_flight)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(3):
        step(x)
    rec = obs.TrainFlightRecorder(registry=obs.Registry())
    obs.clear_spans()
    prev = train_flight.set_current(rec)
    try:
        t = time.perf_counter()
        rec.step_begin(0, 0, t, t)
        step(x)
        t1 = time.perf_counter()
        rec.step_end(t1, t1 - t)
    finally:
        train_flight.set_current(prev)
    (disp,) = [e for e in obs.span_events() if e.name == "jit.dispatch"]
    spans = [s for st in rec.steps() for s in st.spans
             if s[0] == "dispatch:fwd_for_flight"]
    assert [(s[1], s[2]) for s in spans] == [(disp.start, disp.end)]
