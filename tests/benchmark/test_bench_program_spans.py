"""The per-layer metrics that read the program's span log
(`readers/program_span.py`): the arithmetic on a hand-written log, the
cases that read nothing, the tiny CPU cells reporting every one of them
in a traced run, and `span_gaps.py` naming the idle inside the benchmark's
`bench.engine_step` by the program's own spans."""
import time
from types import SimpleNamespace

import pytest
from test_bench_run import _serve_ctx, _train_ctx

from benchmark import harness, span_gaps
from benchmark.readers import program_span

MAN = harness.manifest()
SERVE = ["chunk_prefill_ms_per_ktok", "decode_run_ms", "engine_host_share",
         "step_host_ms_p95.serve", "setup_compile_s"]
TRAIN = ["to_static_call_ms", "setup_warmup_s", "setup_compile_s"]

# set-up is [100, 110), the window [110, 120); one clock
SETUP, WINDOW = (100.0, 110.0), (110.0, 120.0)


def _r(name, t0, dur, parent=None, **attrs):
    return (name, t0, t0 + dur, parent, attrs)


LOG = [
    # ---- set-up
    _r("jit.warmup", 100.5, 3.0, "jit.call", fn="step"),
    _r("jit.discover", 103.5, 2.0, "jit.call", fn="step"),
    _r("jit.compile", 106.0, 3.0, "jit.call", fn="step"),
    _r("serving.compile", 101.0, 0.5, "serving.decode.build", bucket=2),
    _r("serving.compile", 102.0, 1.0, "serving.chunk.build", bucket=256),
    _r("serving.step", 105.0, 0.1), _r("serving.decode.run", 105.01, 0.08),
    _r("jit.call", 109.5, 0.4, fn="step"),
    # ---- the window: a tick that admits and prefills a short prompt,
    # runs a chunk of another and decodes; a decode-only tick; a tick with
    # a chunk and a decode
    _r("serving.step", 110.2, 0.100, active=2, waiting=1),
    _r("serving.admit", 110.201, 0.027, "serving.step", admitted=1),
    _r("serving.prefill.run", 110.205, 0.020, "serving.admit", rid=7,
       tokens=100, bucket=128),
    _r("serving.chunk.run", 110.23, 0.020, "serving.step", rid=5,
       tokens=256, start=256, last=False, bucket=256),
    _r("serving.decode.build", 110.251, 0.002, "serving.step"),
    _r("serving.decode.run", 110.253, 0.040, "serving.step", active=2),
    _r("serving.decode.emit", 110.2935, 0.0004, "serving.step"),
    _r("serving.step", 111.0, 0.050, active=3, waiting=0),
    _r("serving.decode.run", 111.005, 0.040, "serving.step", active=3),
    _r("serving.step", 112.0, 0.200, active=3, waiting=0),
    _r("serving.chunk.run", 112.01, 0.060, "serving.step", rid=5,
       tokens=144, start=512, last=True, bucket=256),
    _r("serving.decode.run", 112.08, 0.100, "serving.step", active=3),
    _r("jit.call", 113.0, 0.004, fn="step"),
    _r("jit.dispatch", 113.001, 0.002, "jit.call", fn="step"),
    _r("jit.call", 114.0, 0.002, fn="step"),
    _r("jit.call", 115.0, 0.003, fn="step"),
    # ---- after the window (the check's own work): never read
    _r("serving.step", 121.0, 0.5), _r("serving.decode.run", 121.1, 0.3),
    _r("serving.chunk.run", 121.0, 0.05, tokens=7), _r("jit.call", 122, 1.0),
    _r("jit.compile", 123.0, 9.0), _r("jit.warmup", 133.0, 9.0),
]

EXPECTED = {
    # 1e6 x (0.020 + 0.020 + 0.060) s / (100 + 256 + 144) tokens
    "chunk_prefill_ms_per_ktok": 200.0,
    "decode_run_ms": 40.0,                  # p50 of 40, 40, 100 ms
    # ticks 0.35 s, their .run spans 0.08 + 0.04 + 0.16 s
    "engine_host_share": 20.0,
    "step_host_ms_p95.serve": 38.0,         # p95 of 20, 10, 40 ms
    "to_static_call_ms": 3.0,               # p50 of 4, 2, 3 ms
    "setup_warmup_s": 5.0,
    "setup_compile_s": 4.5,
}


def _params(name):
    spec = harness.load_json("metrics", name + ".json")
    assert spec["reader"] == "program_span"
    return spec["params"]


def _phase(params):
    return SETUP if params["phase"] == "setup" else WINDOW


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_arithmetic_on_a_hand_written_log(name):
    p = _params(name)
    assert program_span.compute(p, LOG, 0.0, *_phase(p)) \
        == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_cut_log_reads_nothing(name):
    """The ring dropped records after the phase began: no partial sum."""
    p = _params(name)
    a, b = _phase(p)
    assert program_span.compute(p, LOG, a + 0.25, a, b) is None
    assert program_span.compute(p, LOG, a, a, b) is not None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_an_empty_phase_reads_nothing_and_never_zero(name):
    p = _params(name)
    a, b = _phase(p)
    other = [r for r in LOG if not a <= r[1] < b]
    assert program_span.compute(p, other, 0.0, a, b) is None
    assert program_span.compute(p, [], 0.0, a, b) is None


def test_prefill_with_no_token_count_reads_nothing():
    p = _params("chunk_prefill_ms_per_ktok")
    log = [_r("serving.chunk.run", 111.0, 0.1)]
    assert program_span.compute(p, log, 0.0, *WINDOW) is None


def _ctx_20s_ago():
    """A run that started 20 s ago: 10 s of set-up, 10 s of window."""
    ctx = SimpleNamespace(process_start=time.time() - 20.0, setup_s=10.0)
    return ctx, {"window_s": 10.0}


def _log_from(base):
    return [(n, s - 100.0 + base, e - 100.0 + base, p, a)
            for n, s, e, p, a in LOG]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_read_maps_the_harness_clock_to_the_logs(monkeypatch, name):
    """`read` selects by phase on the harness's `time.time()` marks, the
    log is on `perf_counter`: one offset at read time."""
    ctx, rec = _ctx_20s_ago()
    log = _log_from(time.perf_counter() - 20.0)
    monkeypatch.setattr(program_span, "program_log", lambda: (log, 0.0))
    assert program_span.read(_params(name), rec, ctx) \
        == pytest.approx(EXPECTED[name], rel=1e-6)
    # before the window opened the harness has no set-up time: nothing
    ctx.setup_s = None
    assert program_span.read(_params(name), rec, ctx) is None


def test_read_finds_the_programs_own_log(monkeypatch):
    from paddle_tpu import obs

    ctx, rec = _ctx_20s_ago()
    obs.clear_spans()
    with obs.span("jit.call", fn="f"):
        time.sleep(0.002)
    p = _params("to_static_call_ms")
    # the span began after the window's 10 s: the phase is empty
    assert program_span.read(p, rec, ctx) is None
    rec["window_s"] = 11.0
    # cleared a moment ago: the log is cut for a window 10 s old
    assert program_span.read(p, rec, ctx) is None
    monkeypatch.setattr(obs.trace, "_cleared_at", 0.0)
    assert 2.0 <= program_span.read(p, rec, ctx) < 50.0
    obs.clear_spans()


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    """The parent commit's `obs` has no `span_log_start` and its records
    no start: the reader returns nothing and does not raise."""
    from paddle_tpu import obs

    ctx, rec = _ctx_20s_ago()
    monkeypatch.delattr(obs, "span_log_start")
    for name in EXPECTED:
        assert program_span.read(_params(name), rec, ctx) is None


def _cell_kinds():
    """{cell: "serve" | "train"} by its traffic's role (`serve` and
    `serve_sparse` are one loop), in the manifest's order."""
    return {w["name"]: harness.load_json(
        "traffic", w["traffic"] + ".json")["role"].split("_")[0]
        for w in MAN["workloads"]}


@pytest.mark.parametrize("kinds, names", [
    (("serve",), SERVE[:-1]), (("train",), TRAIN[:-1]),
    (("serve", "train"), ["setup_compile_s"])],
    ids=["serve", "train", "both"])
def test_manifest_entries_are_the_issues(kinds, names):
    """Every span metric lists exactly the cells whose traffic has its
    role, however many cells there are, with the source, direction, key
    set and `moves` PR 28 gave it. Where a cell does not report the
    end-to-end metric it moves (PR 34: `tpot_p95_ms` is held to a bound in
    the chat cell alone), the cell reads the same quantity under the name
    `<metric>.ttft`, from a copy of the metric's file, moving
    `ttft_p70_ms`: the two entries together list every such cell once."""
    cells = _cell_kinds()
    assert cells["mistral-7b.serve-chat"] == "serve"
    assert cells["mistral-7b.train-4k"] == "train" \
        == cells["cerebras-gpt-1.3b.train-2k"]
    mine = [c for c, kind in cells.items() if kind in kinds]
    by = {m["name"]: m for m in MAN["per_layer"]}
    for name in names:
        m, twin = by[name], by.get(name + ".ttft")
        listed = m["workloads"] + (twin["workloads"] if twin else [])
        assert sorted(listed, key=mine.index) == mine
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["moves"] == "setup_s") == name.startswith("setup_")
        for entry in filter(None, (m, twin)):
            for cell in entry["workloads"]:
                assert entry["moves"] in {
                    e["name"] for e in harness.resolve(MAN, cell)["end_to_end"]}
        if twin:
            assert twin["moves"] == "ttft_p70_ms" != m["moves"]
            assert {k: twin[k] for k in ("unit", "better", "source", "layer")} \
                == {k: m[k] for k in ("unit", "better", "source", "layer")}
            assert harness.load_json("metrics", name + ".ttft.json") \
                == harness.load_json("metrics", name + ".json")


# ------------------------------------------------- the tiny cells, traced

@pytest.fixture(scope="module")
def serve_gaps():
    """The tiny CPU serve cell, traced, through `span_gaps.run`. Fresh
    program caches, so that its set-up compiles whatever ran before."""
    from paddle_tpu import obs
    from paddle_tpu.inference import engine as engine_mod

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_SERVING_EXECUTABLES", {})
        mp.setattr(engine_mod, "_SEEN_SERVING_PROGRAMS", set())
        obs.clear_spans()
        ctx = _serve_ctx(trace=1, seed=2 ** 31 + 11)
        return dict(span_gaps.run(ctx), setup_s=ctx.setup_s)


def test_tiny_serve_cell_reports_every_new_metric(serve_gaps):
    got = serve_gaps["metrics"]
    assert set(SERVE) <= set(got), sorted(got)
    for name in SERVE:
        assert got[name]["value"] > 0
        assert got[name]["unit"] == {"engine_host_share": "%",
                                     "setup_compile_s": "s"}.get(name, "ms")
    assert serve_gaps["correct"] is True
    # what the spans time is what the engine's own counters time
    assert got["decode_run_ms"]["value"] <= got["decode_tick_ms"]["value"]
    assert got["chunk_prefill_ms_per_ktok"]["value"] \
        <= got["prefill_ms_per_ktok"]["value"]
    assert 0 < got["engine_host_share"]["value"] < 100
    assert got["setup_compile_s"]["value"] < serve_gaps["setup_s"]


def test_widened_prefix_names_the_idle_inside_engine_step(serve_gaps):
    """With the program's spans read from the same capture, less than 5%
    of the idle inside `bench.engine_step` is left to that name, and
    `serving.step` covers `bench.engine_step` to within 2%."""
    idle = dict(serve_gaps["idle_gaps_by_span"])
    inside = {k: v for k, v in idle.items()
              if k == "bench.engine_step" or k.startswith("serving.")}
    assert any(k.startswith("serving.") for k in inside)
    assert idle.get("bench.engine_step", 0.0) < 0.05 * sum(inside.values())
    secs = serve_gaps["span_seconds"]
    assert secs["serving.step"] <= secs["bench.engine_step"]
    assert secs["serving.step"] >= 0.98 * secs["bench.engine_step"]
    # the driver's own table keeps its ten rows
    assert len(serve_gaps["breakdown"]["idle_gaps"]) <= 10
    # and the reducer is left as it was found
    from benchmark import trace_reduce

    assert trace_reduce.SPAN_PREFIX == "bench."
    assert trace_reduce.reduce.__name__ == "reduce"


@pytest.fixture(scope="module")
def train_gaps():
    ctx = _train_ctx("llama", trace=1, seed=2 ** 31 + 11)
    ctx.per_layer = ctx.per_layer + [m for m in MAN["per_layer"]
                                     if m["name"] in TRAIN]
    return dict(span_gaps.run(ctx), setup_s=ctx.setup_s)


def test_tiny_train_cell_reports_every_new_metric(train_gaps):
    got = train_gaps["metrics"]
    assert set(TRAIN) <= set(got), sorted(got)
    assert all(got[n]["value"] > 0 for n in TRAIN)
    assert got["setup_warmup_s"]["value"] + got["setup_compile_s"]["value"] \
        < train_gaps["setup_s"]
    assert got["to_static_call_ms"]["value"] \
        <= got["step_ms_p50.train"]["value"]
    secs = train_gaps["span_seconds"]
    assert 0 < secs["jit.dispatch"] <= secs["jit.call"] \
        <= secs["bench.train_step"]
    assert not any(k.startswith("serving.") for k in secs)
