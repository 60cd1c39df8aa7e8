"""Round-11 observability subsystem (paddle_tpu.obs).

Covers the tentpole contract end to end: registry semantics (labels incl.
the cardinality cap, histogram exact-vs-bucket quantiles), the JSONL and
Prometheus exporters round-tripping, span nesting, the structured logger's
rate limiting, the compile watchdog's fire/no-fire pairs — including the
acceptance pair where intentionally breaking generation-length bucketing
(exact-length keying, the round-10 failure) makes the recompile-storm
finding fire — and the serving-engine instrumentation: required metrics,
the queue-wait/prefill TTFT decomposition, and the regression test that
20 steady-state paged-decode steps after warmup record ZERO compiles.
"""
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.obs.metrics import Histogram

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


# ------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_gauge_basics(self):
        r = obs.Registry("t")
        c = r.counter("reqs_total", "requests", ("kind",))
        c.labels("a").inc()
        c.labels("a").inc(2)
        c.labels(kind="b").inc()
        assert c.labels("a").value == 3
        assert c.labels("b").value == 1
        with pytest.raises(ValueError):
            c.labels("a").inc(-1)          # counters are monotonic
        g = r.gauge("depth", "queue depth")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4
        # same name re-registration returns the same object; a kind or
        # label mismatch is an error, not a silent second metric
        assert r.counter("reqs_total", "requests", ("kind",)) is c
        with pytest.raises(ValueError):
            r.gauge("reqs_total", "boom")
        with pytest.raises(ValueError):
            r.counter("reqs_total", "boom", ("other",))

    def test_label_arity_checked(self):
        r = obs.Registry("t")
        c = r.counter("x_total", "", ("a", "b"))
        with pytest.raises(ValueError):
            c.labels("only-one")
        with pytest.raises(ValueError):
            c.labels(a="1", c="2")

    def test_label_cardinality_cap(self):
        r = obs.Registry("t")
        c = r.counter("bomb_total", "", ("rid",), label_cap=4)
        for i in range(10):
            c.labels(str(i)).inc()
        # 4 real children + the shared overflow child soaking the rest
        keys = {k for k, _ in c.samples()}
        assert (obs.OVERFLOW,) in keys
        assert len(keys) == 5
        assert c.dropped_label_sets == 6
        overflow = dict(c.samples())[(obs.OVERFLOW,)]
        assert overflow.value == 6          # every dropped inc landed here

    def test_histogram_exact_quantiles(self):
        h = Histogram("lat", "")
        vals = [i / 100 for i in range(1, 101)]     # 0.01 .. 1.00
        for v in vals:
            h.observe(v)
        assert h.exact
        assert h.quantile(0.5) == pytest.approx(0.5, abs=0.011)
        assert h.quantile(0.95) == pytest.approx(0.95, abs=0.011)
        assert h.quantile(1.0) == 1.0
        assert h.mean() == pytest.approx(np.mean(vals))

    def test_histogram_bucket_quantiles_match_exact(self):
        """Past the exact-sample cap the histogram degrades to bucket
        interpolation — the two estimators must agree to bucket width."""
        rs = np.random.RandomState(0)
        vals = rs.uniform(0.001, 2.0, size=2000)
        hx = Histogram("a", "", exact_cap=4000)      # stays exact
        hb = Histogram("b", "", exact_cap=100)       # ring overflows
        for v in vals:
            hx.observe(v)
            hb.observe(v)
        assert hx.exact and not hb.exact
        for q in (0.5, 0.9, 0.95):
            exact = hx.quantile(q)
            approx = hb.quantile(q)
            # tolerance: the enclosing fixed-bucket width
            assert abs(approx - exact) < 0.8, (q, exact, approx)

    def test_prometheus_round_trip(self):
        r = obs.Registry("pt")
        r.counter("c_total", "a counter", ("site",)).labels("x").inc(3)
        h = r.histogram("h_seconds", "a histogram", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = r.render_prometheus()
        lines = dict(
            ln.rsplit(" ", 1) for ln in text.splitlines()
            if ln and not ln.startswith("#"))
        assert lines['pt_c_total{site="x"}'] == "3"
        assert lines['pt_h_seconds_bucket{le="0.1"}'] == "1"
        assert lines['pt_h_seconds_bucket{le="1"}'] == "2"
        assert lines['pt_h_seconds_bucket{le="+Inf"}'] == "3"
        assert lines["pt_h_seconds_count"] == "3"
        assert float(lines["pt_h_seconds_sum"]) == pytest.approx(5.55)
        assert "# TYPE pt_h_seconds histogram" in text
        # label values escape quotes/newlines
        r.counter("e_total", "", ("p",)).labels('a"b\n').inc()
        assert r'p="a\"b\n"' in r.render_prometheus()

    def test_histogram_bucket_ladder_mismatch_raises(self):
        r = obs.Registry()
        r.histogram("h_seconds", "", buckets=(0.1, 1.0))
        assert r.histogram("h_seconds", "", buckets=(1.0, 0.1)) is not None
        with pytest.raises(ValueError):      # a DIFFERENT ladder is an error
            r.histogram("h_seconds", "", buckets=(0.5, 2.0))

    def test_to_dict_snapshot(self):
        r = obs.Registry()
        r.histogram("h", "").observe(2.0)
        snap = r.to_dict()
        assert snap["h"]["kind"] == "histogram"
        s = snap["h"]["samples"][0]
        assert s["count"] == 1 and s["p95"] == 2.0
        json.dumps(snap)                    # JSON-able end to end


# ---------------------------------------------------------------- JSONL
class TestJsonl:
    def test_event_log_round_trip(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        paddle.set_flags({"FLAGS_obs_log_path": path})
        try:
            assert obs.log_event("compile", site="test", key="k1")
            r = obs.Registry()
            r.counter("c_total", "").inc(7)
            assert obs.dump_registry(r)
        finally:
            paddle.set_flags({"FLAGS_obs_log_path": ""})
        assert not obs.log_event("compile", site="dropped")  # flag off
        recs = [json.loads(ln) for ln in open(path)]
        assert [r["kind"] for r in recs] == ["compile", "metrics"]
        assert recs[0]["site"] == "test" and "t" in recs[0]
        assert recs[1]["metrics"]["c_total"]["samples"][0]["value"] == 7


# ---------------------------------------------------------------- spans
class TestSpans:
    def test_record_holds_start_end_parent_attrs(self):
        """(was test_nesting_paths) the record of the one span log."""
        obs.clear_spans()
        with obs.span("outer", rid=7, site="s"):
            with obs.span("inner"):
                pass
        evs = obs.span_events(clear=True)
        assert [e.name for e in evs] == ["inner", "outer"]  # as they ended
        inner, outer = evs
        assert isinstance(inner, obs.SpanRecord)
        assert inner.parent == "outer" and outer.parent is None
        assert outer.attrs == {"rid": 7, "site": "s"} and inner.attrs == {}
        assert all(e.start <= e.end for e in evs)
        assert outer.start <= inner.start and inner.end <= outer.end
        assert tuple(outer) == ("outer", outer.start, outer.end, None,
                                outer.attrs)

    def test_span_object_carries_its_clock_reads(self):
        """(was test_span_feeds_histogram) whoever times the interval a
        span times takes the span's own reads; attrs known only at the end
        reach the log."""
        obs.clear_spans()
        h = Histogram("span_h", "")
        with obs.span("timed") as sp:
            sp.attrs["admitted"] = 2
        h.observe(sp.end - sp.start)
        (rec,) = obs.span_events(clear=True)
        assert (rec.start, rec.end) == (sp.start, sp.end)
        assert rec.attrs == {"admitted": 2}
        assert h.count == 1 and h.sum == rec.end - rec.start

    def test_log_says_from_when_it_is_whole(self, monkeypatch):
        """(was test_step_span_off_tpu) a reader can tell a cut log from
        a whole one: clearing and wrapping both move span_log_start()."""
        from collections import deque

        from paddle_tpu.obs import trace

        t0 = time.perf_counter()
        obs.clear_spans()
        cleared = obs.span_log_start()
        assert t0 <= cleared <= time.perf_counter()
        monkeypatch.setattr(trace, "SPAN_LOG_CAP", 4)
        monkeypatch.setattr(trace, "_span_log", deque(maxlen=4))
        for i in range(3):
            with obs.span("s", i=i):
                pass
        assert obs.span_log_start() == cleared     # nothing dropped yet
        for i in range(3, 9):
            with obs.span("s", i=i):
                pass
        evs = obs.span_events()
        assert [e.attrs["i"] for e in evs] == [5, 6, 7, 8]
        # whatever was dropped ended before the oldest record kept did
        assert obs.span_log_start() == evs[0].end > cleared

    def test_span_shows_in_a_cpu_xplane(self, tmp_path):
        """The annotation is emitted on every backend: a capture on the
        CPU holds the span, nested as the log has it, with its attrs."""
        from jax.profiler import ProfileData

        from benchmark import trace_reduce

        with obs.capture_trace(str(tmp_path)):
            with obs.span("t_obs.outer", rid=3):
                with obs.span("t_obs.inner", site="here"):
                    time.sleep(0.002)
        got = {}
        path = trace_reduce.find_xplane(str(tmp_path))
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("t_obs."):
                        got[e.name] = (e.start_ns, e.start_ns
                                       + e.duration_ns, dict(e.stats))
        assert set(got) == {"t_obs.outer", "t_obs.inner"}
        o, i = got["t_obs.outer"], got["t_obs.inner"]
        assert o[0] <= i[0] and i[1] <= o[1] and i[1] - i[0] >= 2e6
        assert o[2] == {"rid": 3} and i[2] == {"site": "here"}

    def test_span_pops_its_parent_on_an_exception(self):
        obs.clear_spans()
        with pytest.raises(KeyError):
            with obs.span("a"):
                with obs.span("b"):
                    raise KeyError("x")
        with obs.span("c"):
            pass
        assert [(e.name, e.parent) for e in obs.span_events(clear=True)] \
            == [("b", "a"), ("a", None), ("c", None)]


# -------------------------------------------------------------- logging
class TestLogging:
    def test_vlog_level_gated(self, capsys):
        log = obs.get_logger("tests.vlog")
        log.reset()
        paddle.set_flags({"FLAGS_log_level": 0})
        assert not log.vlog(1, "hidden")
        paddle.set_flags({"FLAGS_log_level": 2})
        try:
            assert log.vlog(2, "shown", key="s1")
        finally:
            paddle.set_flags({"FLAGS_log_level": 0})
        err = capsys.readouterr().err
        assert "hidden" not in err
        assert "[paddle_tpu:tests.vlog] V2: shown" in err

    def test_rate_limit_and_suppression_report(self, capsys):
        log = obs.get_logger("tests.rate")
        log.reset()
        assert log.warning("spam", key="k")
        for _ in range(5):
            assert not log.warning("spam", key="k")   # inside the window
        assert log.suppressed_total == 5
        # a new window reports how many were dropped
        log._last["k"] -= 100.0
        assert log.warning("spam", key="k")
        assert "[5 similar suppressed]" in capsys.readouterr().err

    def test_also_warn_keeps_warning_contract(self):
        import warnings

        log = obs.get_logger("tests.alsowarn")
        log.reset()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            log.warning("graph break in 'f'", key="w1", also_warn=True)
            # rate-limited on stderr, but the warning still fires: the
            # catch_warnings contract survives the logger migration
            log.warning("graph break in 'f'", key="w1", also_warn=True)
        assert sum("graph break" in str(m.message) for m in w) == 2


# ------------------------------------------------------------- watchdog
class TestWatchdog:
    def test_record_and_counters(self):
        obs.clear_events()
        before = obs.default_registry().counter(
            "compiles_total", "", ("site",)).labels("testsite").value
        obs.record_compile("testsite", "fam", "k1", bucket=4, wall_s=0.25,
                           donated=True)
        evs = obs.compile_events("testsite")
        assert len(evs) == 1 and evs[0].bucket == 4
        assert obs.compile_counts()["testsite"] == 1
        after = obs.default_registry().counter(
            "compiles_total", "", ("site",)).labels("testsite").value
        assert after == before + 1
        obs.clear_events()
        assert obs.compile_counts() == {}

    def test_storm_fires_on_distinct_keys(self):
        evs = [obs.CompileEvent("generate", "generate/llama", f"g{i}")
               for i in range(6)]
        fs = obs.audit_recompiles(evs, threshold=3)
        storms = [f for f in fs if f.detector == "recompile-storm"
                  and f.severity == "warning"]
        assert len(storms) == 1
        assert storms[0].data["distinct"] == 6

    def test_no_storm_under_threshold(self):
        evs = [obs.CompileEvent("generate", "generate/llama", f"g{i}")
               for i in range(3)]
        fs = obs.audit_recompiles(evs, threshold=3)
        assert all(f.severity == "note" for f in fs)

    def test_same_key_repeat_is_thrash(self):
        evs = [obs.CompileEvent("to_static", "step@1", "k")] * 2
        fs = obs.audit_recompiles(evs, threshold=8)
        assert any(f.severity == "warning" and "cache thrash"
                   in f.message for f in fs)

    def test_eager_distinct_keys_are_by_design(self):
        # per-(statics, diff-mask) specialization growth must NOT storm;
        # an eager same-key re-BUILD (eviction thrash) still does
        evs = [obs.CompileEvent("eager", "matmul", f"k{i}")
               for i in range(50)]
        fs = obs.audit_recompiles(evs, threshold=3)
        assert all(f.severity == "note" for f in fs)
        fs = obs.audit_recompiles(
            evs + [obs.CompileEvent("eager", "matmul", "k0")], threshold=3)
        assert any(f.severity == "warning" for f in fs)

    def test_post_warmup_compile_fires(self):
        evs = [obs.CompileEvent("serving.decode", "d", "k", warm=True)]
        fs = obs.audit_recompiles(evs, threshold=8)
        warm = [f for f in fs if f.detector == "post-warmup-compile"]
        assert len(warm) == 1 and warm[0].severity == "warning"

    def test_analysis_reexport(self):
        from paddle_tpu import analysis

        fs = analysis.audit_recompiles(
            [obs.CompileEvent("s", "g", "k", warm=True)])
        assert any(f.detector == "post-warmup-compile" for f in fs)


# ----------------------------------------- generation bucketing (D6 pair)
def _nano_llama():
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


class TestGenerationBucketingWatchdog:
    """The acceptance pair: with generation-length bucketing intact a
    stream of varied max_new_tokens compiles few programs (no finding);
    re-introducing exact-length keying (the round-10 bug) makes the
    recompile-storm finding FIRE."""

    LENGTHS = (3, 4, 5, 6, 7)

    def _drive(self, model):
        from paddle_tpu.text import generation as gen_mod

        obs.clear_events()
        # clear the host-side program-key mirror so THIS stream's keys
        # all record (other tests may share the nano spec/shapes)
        saved = set(gen_mod._seen_gen_programs)
        gen_mod._seen_gen_programs.clear()
        try:
            ids = np.full((1, 4), 7, dtype="int64")
            for mnt in self.LENGTHS:
                model.generate(paddle.to_tensor(ids), max_new_tokens=mnt)
        finally:
            gen_mod._seen_gen_programs.update(saved)
        return [e for e in obs.compile_events("generate")
                if e.group == "generate/llama"]

    def test_bucketed_no_fire(self):
        model = _nano_llama()
        evs = self._drive(model)
        # mnt 3..7 buckets to {4, 8}: at most 2 generation-length keys
        fs = obs.audit_recompiles(evs, threshold=3)
        assert not [f for f in fs if f.severity != "note"], fs
        assert len({e.key for e in evs}) <= 3

    def test_exact_length_keying_fires(self, monkeypatch):
        from paddle_tpu.jit import api as jit_api

        model = _nano_llama()
        # the round-10 bug, reintroduced: every length is its own bucket
        monkeypatch.setattr(jit_api, "default_buckets", lambda n: n)
        evs = self._drive(model)
        assert len({e.key for e in evs}) >= len(self.LENGTHS)
        fs = obs.audit_recompiles(evs, threshold=3)
        storms = [f for f in fs if f.detector == "recompile-storm"
                  and f.severity == "warning"]
        assert storms, "exact-length keying must trip the watchdog"


# ------------------------------------------------------- serving metrics
def _tiny_llama():
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


class TestServingObs:
    def test_required_metrics_exist_and_count(self):
        from graft_lint import (MUST_COUNT_SERVING_METRICS,
                                REQUIRED_SERVING_METRICS)
        from paddle_tpu.inference.engine import ServingEngine

        eng = ServingEngine(_tiny_llama(), max_slots=2)
        rs = np.random.RandomState(0)
        for ln, nt in ((3, 3), (6, 4)):
            eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
        eng.run()
        snap = eng.metrics()
        assert not [m for m in REQUIRED_SERVING_METRICS if m not in snap]
        for m in MUST_COUNT_SERVING_METRICS:
            assert any(s.get("count") or s.get("value")
                       for s in snap[m]["samples"]), m
        # stats() stays the thin view over the SAME numbers
        st = eng.stats()
        dec = snap["serving_decode_tokens_total"]["samples"][0]["value"]
        assert st["decode_tokens"] == int(dec)
        assert "paddle_tpu_serving_ttft_seconds_count" \
            in eng.render_prometheus()

    def test_ttft_decomposes_into_queue_wait_plus_prefill(self):
        """Satellite-6 fix: a request blocked on the pool accrues
        queue_wait, not prefill — and ttft == queue_wait + prefill."""
        from paddle_tpu.inference.engine import ServingEngine

        eng = ServingEngine(_tiny_llama(), max_slots=2, kv_block_size=8,
                            num_kv_blocks=6)
        rs = np.random.RandomState(4)
        big = eng.add_request(rs.randint(0, 128, (30,)), max_new_tokens=10)
        small = eng.add_request(rs.randint(0, 128, (4,)), max_new_tokens=4)
        done = eng.run()
        assert len(done[big]) == 10 and len(done[small]) == 4
        st = eng.stats()
        assert len(st["ttft_s"]) == len(st["queue_wait_s"]) == 2
        # the blocked request's queue wait covers the wall the first one
        # spent decoding — it must NOT be attributed to prefill
        assert st["queue_wait_s"][1] > st["queue_wait_s"][0]
        assert st["admission_blocked"] >= 1
        snap = eng.metrics()
        pf = snap["serving_prefill_seconds"]["samples"][0]
        qw = snap["serving_queue_wait_seconds"]["samples"][0]
        tt = snap["serving_ttft_seconds"]["samples"][0]
        assert tt["sum"] == pytest.approx(pf["sum"] + qw["sum"], rel=1e-6)

    def test_zero_post_warmup_compiles_20_steady_steps(self):
        """ACCEPTANCE regression: after warmup, 20 steady-state
        paged-decode steps record ZERO compile events (warm or not) at
        serving sites — a steady-state tick never traces."""
        from paddle_tpu.inference.engine import ServingEngine

        model = _tiny_llama()
        eng = ServingEngine(model, max_slots=2)
        rs = np.random.RandomState(0)
        # warm every bucket this workload uses: prompt bucket 16 (both
        # prompts), decode buckets {1, 2}
        for ln, nt in ((3, 2), (6, 3)):
            eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
        eng.run()
        eng.finish_warmup()
        obs.clear_events()
        for ln, nt in ((4, 25), (5, 22)):
            eng.add_request(rs.randint(0, 128, (ln,)), max_new_tokens=nt)
        steps = 0
        while eng.has_work() and steps < 30:
            eng.step()
            steps += 1
        assert steps >= 20, "stream ended before 20 steady-state steps"
        serving_evs = [e for e in obs.compile_events()
                       if e.site.startswith("serving")]
        assert serving_evs == [], [e.to_dict() for e in serving_evs]
        assert obs.post_warmup_compiles() == 0

    def test_post_warmup_compile_is_recorded_when_forced(self):
        """Fire direction of the warmup barrier: a NEW bucket after
        finish_warmup records a warm compile event + counter."""
        from paddle_tpu.inference import engine as eng_mod
        from paddle_tpu.inference.engine import ServingEngine

        eng = ServingEngine(_tiny_llama(), max_slots=2)
        rs = np.random.RandomState(1)
        eng.add_request(rs.randint(0, 128, (3,)), max_new_tokens=2)
        eng.run()
        eng.finish_warmup()
        obs.clear_events()
        # force unseen program keys: wipe the host-side mirror so the
        # next tick's programs count as fresh compiles
        saved = set(eng_mod._SEEN_SERVING_PROGRAMS)
        eng_mod._SEEN_SERVING_PROGRAMS.clear()
        try:
            eng.add_request(rs.randint(0, 128, (3,)), max_new_tokens=2)
            eng.run()
        finally:
            eng_mod._SEEN_SERVING_PROGRAMS.update(saved)
        warm = [e for e in obs.compile_events() if e.warm]
        assert warm, "forced post-warmup compile was not recorded"
        fs = obs.audit_recompiles()
        assert any(f.detector == "post-warmup-compile"
                   and f.severity == "warning" for f in fs)
        obs.clear_events()

    def test_http_metrics_endpoint(self):
        reg = obs.Registry("pt")
        reg.counter("up_total", "").inc()
        srv = obs.serve_metrics(0, reg)       # port 0: OS-assigned
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as resp:
                body = resp.read().decode()
                assert resp.status == 200
            assert "pt_up_total 1" in body
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz") as resp:
                assert resp.read() == b"ok\n"
        finally:
            srv.close()

    def test_engines_share_one_http_port(self):
        """FLAGS_obs_http_port names ONE fixed port: every engine in
        the process registers on the SHARED endpoint (round 16) — both
        registries scrape through /metrics with an engine="..." label
        instead of the pre-round-16 first-binder-wins behavior — and
        /healthz is a READINESS probe: 503 while any registered engine
        has not finished warmup, 200 once all have."""
        from paddle_tpu.inference.engine import ServingEngine

        probe = obs.serve_metrics(0, obs.Registry())   # grab a free port
        port = probe.port
        probe.close()
        model = _tiny_llama()
        paddle.set_flags({"FLAGS_obs_http_port": port})
        try:
            e1 = ServingEngine(model, max_slots=1)
            e2 = ServingEngine(model, max_slots=1)     # must not raise
            assert e1._metrics_server is e2._metrics_server
            srv = e1._metrics_server
            assert len(srv.engines()) == 2
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                body = resp.read().decode()
            assert f'serving_slots{{engine="{e1._engine_name}"}}' in body
            assert f'serving_slots{{engine="{e2._engine_name}"}}' in body
            # readiness: 503 until EVERY engine passed finish_warmup
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz")
            assert ei.value.code == 503
            e1.finish_warmup()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz")
            # round 20: PER-ENGINE readiness — ?engine=NAME answers for
            # that replica alone (a router admits warmed replica A while
            # B still warms), aggregate contract above unchanged
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz"
                    f"?engine={e1._engine_name}") as resp:
                assert resp.status == 200
                assert resp.read() == b"ready\n"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz"
                    f"?engine={e2._engine_name}")
            assert ei.value.code == 503
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz?engine=nope")
            assert ei.value.code == 404
            e2.finish_warmup()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz") as resp:
                assert resp.status == 200
                assert resp.read() == b"ready\n"
            # close() detaches the engine, not the shared endpoint
            e2.close()
            assert srv.engines() == [e1._engine_name]
        finally:
            paddle.set_flags({"FLAGS_obs_http_port": 0})
            e1.close()
            e2.close()
            srv.close()

    def test_serving_predictor_metrics(self):
        from paddle_tpu.inference import Config, create_serving_predictor

        pred = create_serving_predictor(Config(), model=_tiny_llama())
        rs = np.random.RandomState(0)
        pred.generate([rs.randint(0, 128, (4,))], max_new_tokens=3)
        snap = pred.metrics()
        assert snap["serving_decode_tokens_total"]["samples"][0]["value"] \
            >= 2
        assert "serving_ttft_seconds" in pred.render_prometheus()


# ------------------------------------------------------ train callback
class TestTelemetryCallback:
    def test_fit_records_step_metrics(self):
        import paddle_tpu.nn as nn

        reg = obs.Registry()
        net = nn.Linear(4, 2)
        model = paddle.hapi.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        model.prepare(opt, nn.MSELoss())
        rs = np.random.RandomState(0)
        data = [(rs.randn(4).astype("float32"),
                 rs.randn(2).astype("float32")) for _ in range(8)]
        cb = paddle.hapi.TelemetryCallback(registry=reg, batch_tokens=16)
        model.fit(data, batch_size=4, epochs=1, verbose=0, callbacks=[cb])
        assert reg.get("train_steps_total").value == 2
        assert reg.get("train_step_seconds").count == 2
        assert reg.get("train_loss").value > 0       # MSE of random data
        assert reg.get("train_tokens_per_sec").value > 0

    def test_auto_attach_behind_flag(self):
        from paddle_tpu.hapi.callbacks import (TelemetryCallback,
                                               config_callbacks)

        has = lambda cl: any(isinstance(c, TelemetryCallback)  # noqa: E731
                             for c in cl.callbacks)
        assert not has(config_callbacks(model=None, verbose=0))
        paddle.set_flags({"FLAGS_obs_metrics": True})
        try:
            assert has(config_callbacks(model=None, verbose=0))
        finally:
            paddle.set_flags({"FLAGS_obs_metrics": False})

    def test_lazy_flush_counter_wired(self):
        from paddle_tpu.core.lazy import flush_info

        assert set(flush_info()) >= {"flushes", "entries", "hits",
                                     "misses"}


# --------------------------------------------------- overhead discipline
class TestOverheadDiscipline:
    def test_metrics_off_by_default_outside_serving(self):
        assert paddle.get_flags("FLAGS_obs_metrics")["FLAGS_obs_metrics"] \
            is False
        assert not obs.metrics_enabled()

    def test_hot_path_is_attribute_updates(self):
        """The per-sample path must stay allocation-light: one observe is
        bounded by ~20us even on a loaded CI host (the real budget is
        the <2% tok/s A/B in PERF.md round 11; this is the smoke that a
        lock or I/O never sneaks into the hot path)."""
        import time

        h = Histogram("hot", "")
        c = obs.Registry().counter("hot_total", "")
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            h.observe(0.001)
            c.inc()
        per = (time.perf_counter() - t0) / n
        assert per < 20e-6, f"{per * 1e6:.1f}us per sample"


# ----------------------------------------------------------------- peaks
class TestPeaks:
    """obs/peaks.py: one table keyed by device_kind; an unknown device is
    an error unless the flag says what to divide by."""

    @pytest.fixture
    def no_flags(self):
        old = paddle.get_flags(["FLAGS_obs_peak_gbps",
                                "FLAGS_obs_peak_tflops"])
        paddle.set_flags({"FLAGS_obs_peak_gbps": 0.0,
                          "FLAGS_obs_peak_tflops": 0.0})
        yield old
        paddle.set_flags(old)

    @pytest.mark.parametrize("fn", [obs.peak_gbps, obs.peak_tflops])
    def test_unknown_device_raises(self, no_flags, fn):
        # this suite's device is the CPU, which is in no table
        with pytest.raises(LookupError, match="device_kind"):
            fn()

    def test_flag_says_what_to_divide_by(self, no_flags):
        # conftest passes both flags for the whole suite
        assert no_flags["FLAGS_obs_peak_gbps"] > 0
        assert no_flags["FLAGS_obs_peak_tflops"] > 0
        paddle.set_flags({"FLAGS_obs_peak_gbps": 7.0,
                          "FLAGS_obs_peak_tflops": 3.0})
        assert obs.peak_gbps() == 7.0 and obs.peak_tflops() == 3.0

    def test_v5e_row(self):
        row = obs.device_peaks("TPU v5 lite")
        assert row == {"bf16_tflops": 197.0, "int8_tops": 393.0,
                       "hbm_gbps": 819.0, "hbm_gb": 16.0}
        with pytest.raises(LookupError):
            obs.device_peaks("TPU v0")


def test_quick_tier_registration():
    """test_obs.py must ride the quick tier (conftest QUICK_MODULES)."""
    import conftest

    assert "test_obs.py" in conftest.QUICK_MODULES
