"""Concurrency auditor (round 17): D13 lock-discipline lint, D14 runtime
lockdep, D15 thread contracts — fire/no-fire pairs per detector, the
deterministic lock-order-cycle fixture, a 4-thread serving/scrape/ckpt
stress that must audit clean, and the race-fix regressions the
annotation sweep surfaced (Registry.unregister/clear under lock, the
comm-watchdog singleton, the rpc serve-thread start ordering, idempotent
engine/endpoint teardown)."""
import ast
import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis, obs
from paddle_tpu.core import lockdep

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "lint_fixtures")


def _fx(name):
    return os.path.join(FIXTURES, name)


def _warns(findings, det=None):
    return [f for f in findings if f.severity == "warning"
            and (det is None or f.detector == det)]


def _lint_file_src(path):
    src = open(path).read()
    return analysis.lint_guarded_by(ast.parse(src), src,
                                    os.path.basename(path)), src


@pytest.fixture(autouse=True)
def _clean_lockdep():
    lockdep.reset()
    yield
    lockdep.disable()
    lockdep.reset()
    paddle.set_flags({"FLAGS_debug_thread_checks": False})


# ===================================================== D13 guarded-by

class TestGuardedBy:
    def test_fire_fixture(self):
        fs, _ = _lint_file_src(_fx("fx_conc_guarded.py"))
        fs = _warns(fs, "conc-guarded-by")
        assert len(fs) == 3
        msgs = " ".join(f.message for f in fs)
        assert "_items" in msgs            # attr mutated outside lock
        assert "_REGISTRY" in msgs         # global mutated outside lock
        assert "requires-lock" in msgs     # unlocked requires-lock call

    def test_no_fire_on_clean_twin(self):
        fs, _ = _lint_file_src(_fx("fx_clean.py"))
        assert _warns(fs, "conc-guarded-by") == []

    def test_annotation_on_preceding_comment_line(self, tmp_path):
        src = ("import threading\n"
               "_L = threading.Lock()\n"
               "# guarded-by: _L\n"
               "_T: dict = {}\n"
               "def bad():\n"
               "    _T['k'] = 1\n")
        fs = analysis.lint_guarded_by(ast.parse(src), src, "m.py")
        assert len(_warns(fs, "conc-guarded-by")) == 1

    def test_init_is_exempt_and_unguarded_ok_escapes(self):
        src = ("import threading\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._xs: list = []   # guarded-by: _lock\n"
               "    def hot(self, v):\n"
               "        self._xs.append(v)  # unguarded-ok: bench-only\n")
        fs = analysis.lint_guarded_by(ast.parse(src), src, "m.py")
        assert _warns(fs, "conc-guarded-by") == []

    def test_repo_is_clean(self):
        """The annotated framework source itself passes D13 — every
        `# guarded-by:` mutation sits under its lock (the satellite-1
        sweep property)."""
        fs = analysis.lint_tree(REPO)
        conc = _warns(fs, "conc-guarded-by")
        assert conc == [], conc


# =================================================== D13 shared-state

class TestSharedState:
    def test_fire_and_threadsafe_no_fire(self):
        fs = analysis.audit_shared_state([_fx("fx_conc_shared.py")],
                                         FIXTURES)
        fs = _warns(fs, "conc-shared-state")
        assert len(fs) == 1
        assert "_PENDING" in fs[0].message
        assert "_SAFE_EVENTS" not in fs[0].message

    def test_repo_is_clean(self):
        fs = analysis.audit_concurrency(REPO)
        assert _warns(fs) == [], _warns(fs)

    def test_main_thread_only_mutation_is_silent(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text("_CACHE: dict = {}\n"
                     "def put(k, v):\n"
                     "    _CACHE[k] = v\n")
        fs = analysis.audit_shared_state([str(p)], str(tmp_path))
        assert _warns(fs, "conc-shared-state") == []


# ====================================================== D14 lockdep

class TestLockdep:
    def test_deterministic_cycle_fixture(self):
        lockdep.enable()
        a = lockdep.make_lock("t14.A")
        b = lockdep.make_lock("t14.B")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        lockdep.disable()
        cycles = lockdep.find_cycles()
        assert cycles, "the two-lock inversion must produce a cycle"
        fs = _warns(analysis.audit_lock_order(loc="t"), "conc-lock-order")
        assert len(fs) == 1
        assert "t14.A" in fs[0].message and "t14.B" in fs[0].message

    def test_consistent_order_is_acyclic_note(self):
        lockdep.enable()
        a = lockdep.make_lock("t14c.A")
        b = lockdep.make_lock("t14c.B")
        for _ in range(3):
            with a:
                with b:
                    pass
        lockdep.disable()
        fs = analysis.audit_lock_order(loc="t")
        assert len(fs) == 1 and fs[0].severity == "note"
        assert lockdep.lock_graph() and not lockdep.find_cycles()

    def test_reentrant_rlock_records_no_self_edge(self):
        lockdep.enable()
        r = lockdep.make_rlock("t14.R")
        with r:
            with r:
                pass
        lockdep.disable()
        assert ("t14.R", "t14.R") not in lockdep.lock_graph()
        assert not lockdep.find_cycles()

    def test_blocking_under_hot_lock_fires(self):
        lockdep.enable()
        hot = lockdep.make_lock("t14.hot", hot=True)
        with hot:
            lockdep.note_blocking("fsync", "/tmp/x")
        lockdep.disable()
        fs = _warns(analysis.audit_lock_order(loc="t"),
                    "conc-blocking-under-lock")
        assert len(fs) == 1 and "fsync" in fs[0].message

    def test_blocking_under_cold_lock_or_allowed_is_silent(self):
        lockdep.enable()
        cold = lockdep.make_lock("t14.cold")          # hot=False
        hot = lockdep.make_lock("t14.own", hot=True)
        with cold:
            lockdep.note_blocking("fsync", "x")
        with hot:       # a sink's own lock legitimately guards its IO
            lockdep.note_blocking("fsync", "x", allow=("t14.own",))
        lockdep.disable()
        assert lockdep.blocking_violations() == []

    def test_disabled_records_nothing(self):
        a = lockdep.make_lock("t14.off")
        with a:
            lockdep.note_blocking("fsync", "x")
        assert lockdep.lock_graph() == {}
        assert lockdep.locks_seen() == {}
        assert lockdep.blocking_violations() == []


# ================================================= D15 thread contract

class TestThreadContract:
    def test_binds_then_second_thread_raises_and_records(self):
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        c = lockdep.ThreadContract("T15")
        c.check("op")
        caught = []

        def other():
            try:
                c.check("op")
            except lockdep.ConcurrencyContractError as e:
                caught.append(e)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert caught and "owner-thread contract" in str(caught[0])
        fs = _warns(analysis.audit_thread_contracts(loc="t"),
                    "conc-thread-contract")
        assert len(fs) == 1

    def test_rebind_hands_ownership_off(self):
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        c = lockdep.ThreadContract("T15r")
        c.check("op")
        c.rebind()
        ok = []

        def other():
            c.check("op")        # rebinds to this thread, no raise
            ok.append(True)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert ok
        with pytest.raises(lockdep.ConcurrencyContractError):
            c.check("op")        # the MAIN thread is now the intruder

    def test_flag_off_is_noop(self):
        c = lockdep.ThreadContract("T15off")
        c.check("op")
        err = []

        def other():
            try:
                c.check("op")
            except lockdep.ConcurrencyContractError as e:
                err.append(e)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert not err and lockdep.contract_violations() == []

    def test_static_fixture_fires_and_main_use_is_silent(self):
        fs = analysis.audit_contract_callsites(
            [_fx("fx_conc_contract.py")], FIXTURES)
        fs = _warns(fs, "conc-thread-contract")
        assert len(fs) == 1
        assert ".step" in fs[0].message or "step" in fs[0].data["method"]

    def test_engine_objects_declare_contracts(self):
        from paddle_tpu.inference.engine import ServingEngine
        from paddle_tpu.text.paged_cache import (BlockAllocator,
                                                 PagedKVCache, PrefixCache)

        for cls in (ServingEngine, BlockAllocator, PrefixCache):
            assert getattr(cls, "_thread_contract"), cls
        alloc = BlockAllocator(4)
        assert alloc.contract.name == "BlockAllocator"
        cache = PagedKVCache(1, 4, 1, 8, 8, "float32")
        assert cache.contract.name == "PagedKVCache"


def _tiny_engine():
    from paddle_tpu.inference.engine import ServingEngine
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return ServingEngine(model, max_slots=2)


class TestEngineContract:
    def test_second_thread_step_raises_under_flag(self):
        eng = _tiny_engine()
        rs = np.random.RandomState(0)
        eng.add_request(rs.randint(0, 128, (3,)), max_new_tokens=2)
        eng.run()                      # binds... only under the flag
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        eng.add_request(rs.randint(0, 128, (3,)), max_new_tokens=1)
        caught = []

        def intruder():
            try:
                eng.step()
            except lockdep.ConcurrencyContractError as e:
                caught.append(e)

        t = threading.Thread(target=intruder)
        t.start()
        t.join()
        assert caught, "engine.step from a second thread must raise"
        eng.run()                      # the owner thread still works
        fs = _warns(analysis.audit_thread_contracts(loc="t"),
                    "conc-thread-contract")
        assert fs
        eng.close()

    def test_close_idempotent_and_concurrent(self):
        eng = _tiny_engine()
        srv = obs.shared_server(0)
        srv.register_engine("tconc", eng.registry, ready=lambda: True)
        eng._metrics_server = srv
        eng._engine_name = "tconc"
        threads = [threading.Thread(target=eng.close) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.close()                    # and again, after the fact
        assert "tconc" not in srv.engines()
        srv.close()
        srv.close()                    # MetricsServer.close idempotent

    def test_shared_server_close_concurrent(self):
        srv = obs.shared_server(0)
        threads = [threading.Thread(target=srv.close) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()


# ================================================= 4-thread stress

class TestStress:
    def test_scrape_save_tick_stress_audits_clean(self, tmp_path):
        """Serving ticks (owner thread) + /metrics scrapes (HTTP server
        threads) + overlapped async checkpoint commits (saver thread) +
        a comm-watchdog scan loop, all with lockdep recording and
        contract checks ON: the lock-order graph must come back acyclic
        with zero blocking-under-hot-lock and zero contract violations."""
        from paddle_tpu import ckpt
        from paddle_tpu.distributed.comm_watchdog import CommTaskManager

        eng = _tiny_engine()
        rs = np.random.RandomState(0)
        eng.add_request(rs.randint(0, 128, (3,)), max_new_tokens=2)
        eng.run()                              # warm programs first
        lockdep.reset()
        lockdep.enable()
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        srv = obs.shared_server(0)
        srv.register_engine("stress", eng.registry, ready=lambda: True)
        mgr = CommTaskManager(scan_interval=0.01,
                              default_timeout=60.0).start()
        saver = ckpt.AsyncCheckpointer(str(tmp_path))
        stop = threading.Event()
        errors, scrapes = [], [0]

        def scrape():
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            try:
                while not stop.is_set():
                    for path in ("/metrics", "/healthz"):
                        conn.request("GET", path)
                        conn.getresponse().read()
                        scrapes[0] += 1
            except Exception as e:
                errors.append(e)
            finally:
                conn.close()

        scraper = threading.Thread(target=scrape, daemon=True)
        scraper.start()
        tree = {"w": rs.randn(32).astype("float32")}
        try:
            with mgr.watch("stress"):
                for i in range(3):
                    eng.add_request(rs.randint(0, 128, (3 + i,)),
                                    max_new_tokens=2)
                    while eng.has_work():
                        eng.step()
                    saver.save(i + 1, tree)
            saver.wait()
        finally:
            stop.set()
            scraper.join(timeout=10)
            lockdep.disable()
            paddle.set_flags({"FLAGS_debug_thread_checks": False})
            saver.close()
            mgr.shutdown()
            srv.close()
        assert not errors, errors
        assert scrapes[0] >= 2, "scraper never ran concurrently"
        assert len(lockdep.locks_seen()) >= 3, lockdep.locks_seen()
        findings = analysis.audit_lock_order(loc="stress")
        findings += analysis.audit_thread_contracts(loc="stress")
        assert analysis.gate_failures(findings) == [], findings


# ============================================ race-fix regressions

class TestReviewRegressions:
    def test_registry_unregister_clear_hold_the_lock(self):
        """Round-17 D13 fix: Registry.unregister/clear raced
        _get_or_make's double-checked insert. Hammer both sides; the
        registry must stay consistent and never throw."""
        reg = obs.Registry("t")
        stop = threading.Event()
        errors = []

        def churn():
            try:
                while not stop.is_set():
                    reg.counter("c", "x").inc()
                    reg.histogram("h", "y").observe(1.0)
            except Exception as e:
                errors.append(e)

        t = threading.Thread(target=churn)
        t.start()
        try:
            for _ in range(200):
                reg.unregister("c")
                reg.clear()
        finally:
            stop.set()
            t.join()
        assert not errors, errors

    def test_comm_watchdog_singleton_is_raced_once(self):
        import paddle_tpu.distributed.comm_watchdog as cw

        old = cw._manager
        cw._manager = None
        try:
            got = []
            barrier = threading.Barrier(4)

            def grab():
                barrier.wait()
                got.append(cw.get_comm_task_manager())

            threads = [threading.Thread(target=grab) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len({id(m) for m in got}) == 1
            got[0].shutdown()
        finally:
            cw._manager = old

    def test_rpc_worker_table_published_before_serve_thread(self,
                                                           monkeypatch):
        """Round-17 race fix: init_rpc used to start the serve thread
        BEFORE the worker table existed — an early inbound RPC observed
        a half-initialized registry. Pin the ordering: at the moment the
        serve thread starts, the table and pool are already published."""
        from paddle_tpu.distributed import rpc as rpc_pkg
        from paddle_tpu.distributed.rpc import rpc as rpc_mod

        seen = {}
        real_thread = rpc_mod.threading.Thread

        class SnoopThread(real_thread):
            def start(self):
                if self._target is rpc_mod._serve:
                    seen["workers"] = dict(rpc_mod._state["workers"])
                    seen["pool"] = rpc_mod._state["pool"]
                    seen["inited"] = rpc_mod._state["inited"]
                super().start()

        monkeypatch.setattr(rpc_mod.threading, "Thread", SnoopThread)
        rpc_pkg.init_rpc("w0")
        try:
            assert seen, "serve thread never started"
            assert "w0" in seen["workers"]
            assert seen["pool"] is not None and seen["inited"]
            # and the server actually works
            assert rpc_pkg.rpc_sync("w0", max, args=(2, 3)) == 3
        finally:
            rpc_pkg.shutdown()

    def test_global_mesh_memo_rebuilds_under_lock(self):
        from paddle_tpu.distributed import parallel_env as pe

        old = pe._state["mesh"]
        pe._state["mesh"] = None
        try:
            got = []
            threads = [threading.Thread(
                target=lambda: got.append(pe.global_mesh()))
                for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len({id(m) for m in got}) == 1
        finally:
            pe._state["mesh"] = old


# ============================================ review-pass regressions

class TestReviewPass:
    def test_multiline_annotations_bind(self):
        """Round-17 review fix: `_ann_text` only read ONE comment line
        above a definition, so every wrapped `# thread-safe:` block in
        this very diff silently failed to bind. Pin that the repo's own
        multi-line annotations register."""
        from paddle_tpu.analysis.concurrency import _GuardInfo

        for rel, names in (
                ("paddle_tpu/obs/watchdog.py",
                 ("_events", "_post_warmup_total", "_ckpt_events")),
                ("paddle_tpu/inference/engine.py",
                 ("_SEEN_SERVING_PROGRAMS", "_SERVING_EXECUTABLES")),
                ("paddle_tpu/obs/trace.py",
                 ("_span_log", "_cleared_at"))):
            src = open(os.path.join(REPO, rel)).read()
            info = _GuardInfo(ast.parse(src), src.splitlines(), src)
            for name in names:
                assert name in info.threadsafe, (rel, name,
                                                 info.threadsafe)

    def test_same_class_cross_instance_nesting_records_self_edge(self):
        """Round-17 review fix: same-NAMED locks from different
        instances were treated as reentrant re-acquires, hiding
        same-class A->B/B->A inversions. Two instances of one lock
        class nested must record the (name, name) self-edge (kernel
        lockdep semantics); the same OBJECT reentrantly stays silent."""
        lockdep.enable()
        a = lockdep.make_lock("t17.same")
        b = lockdep.make_lock("t17.same")
        with a:
            with b:
                pass
        lockdep.disable()
        assert ("t17.same", "t17.same") in lockdep.lock_graph()
        assert lockdep.find_cycles()
        fs = _warns(analysis.audit_lock_order(loc="t"), "conc-lock-order")
        assert len(fs) == 1

    def test_contract_first_bind_race_has_one_winner(self):
        """Round-17 review fix: the first-bind check-then-set was
        unsynchronized — two threads racing the FIRST check could both
        pass. Under the locked bind, exactly one of N simultaneous
        first callers wins; every other raises and records."""
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        c = lockdep.ThreadContract("T17race")
        n = 8
        barrier = threading.Barrier(n)
        ok, bad = [], []

        def racer():
            barrier.wait()
            try:
                c.check("op")
                ok.append(threading.get_ident())
            except lockdep.ConcurrencyContractError:
                bad.append(threading.get_ident())

        threads = [threading.Thread(target=racer) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(ok) == 1 and len(bad) == n - 1, (ok, bad)
        assert len(lockdep.contract_violations()) == n - 1

    def test_shared_state_sees_nested_def_mutations(self, tmp_path):
        """Round-17 review fix: a mutation inside a NESTED helper was
        attributed to the nested bare name, which no closure contains
        (nested defs are not graph-defined) — the exact thread-root
        mutation pattern D13 exists for came back clean."""
        p = tmp_path / "m.py"
        p.write_text(
            "import threading\n"
            "_PENDING: list = []\n"
            "def _worker():\n"
            "    def _inner():\n"
            "        _PENDING.append(1)\n"
            "    _inner()\n"
            "def start():\n"
            "    threading.Thread(target=_worker, daemon=True).start()\n")
        fs = analysis.audit_shared_state([str(p)], str(tmp_path))
        fs = _warns(fs, "conc-shared-state")
        assert len(fs) == 1 and "_PENDING" in fs[0].message, fs

    def test_release_clears_held_entry_while_disabled(self):
        """Round-17 review fix: release() only popped the held-set when
        recording was ON — a lock released after disable() left a
        phantom entry that fabricated false order edges on the next
        enable()."""
        lockdep.enable()
        a = lockdep.make_lock("t17.phantom")
        a.acquire()
        lockdep.disable()
        a.release()                   # must clear the entry regardless
        lockdep.reset()
        lockdep.enable()
        b = lockdep.make_lock("t17.after")
        with b:
            pass
        lockdep.disable()
        assert all("t17.phantom" not in k for k in lockdep.lock_graph()), \
            lockdep.lock_graph()

    def test_cache_swap_is_contract_checked(self):
        """Round-17 review fix: PagedKVCache advertised a contract but
        enforced nothing — `swap` is now the sanctioned mutation point
        and the engine routes every step write-back through it."""
        from paddle_tpu.text.paged_cache import PagedKVCache

        assert PagedKVCache._thread_contract == ("swap",)
        paddle.set_flags({"FLAGS_debug_thread_checks": True})
        cache = PagedKVCache(1, 4, 1, 8, 8, "float32")
        cache.swap(cache.k, cache.v)          # binds this thread
        caught = []

        def intruder():
            try:
                cache.swap(cache.k, cache.v)
            except lockdep.ConcurrencyContractError as e:
                caught.append(e)

        t = threading.Thread(target=intruder)
        t.start()
        t.join()
        assert caught
        # and the engine actually calls it (write-backs route through)
        src = open(os.path.join(
            REPO, "paddle_tpu", "inference", "engine.py")).read()
        assert src.count("c.swap(") >= 3
        assert "c.k, c.v, c.k_scale, c.v_scale, self._key = out" not in src


# ======================================================= CI wiring

class TestCIWiring:
    def test_conc_in_ci_model_set(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import check_scoreboard
        import graft_lint

        assert "conc" in graft_lint.CI_MODELS
        assert hasattr(graft_lint, "audit_conc")
        assert "conc" in check_scoreboard.lint_gate.__defaults__[0]
        covered = {m for grp, _ast in check_scoreboard.LINT_GROUPS
                   for m in grp.split(",")}
        assert set(graft_lint.CI_MODELS) <= covered, \
            "every CI smoke must belong to a parallel gate group"
        assert any(with_ast for _g, with_ast in check_scoreboard.LINT_GROUPS)

    def test_conc_fire_fixture_selftest_is_wired(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        fs = graft_lint._audit_conc_fixtures()
        errs = [f for f in fs if f.severity == "error"]
        assert errs == [], errs
        assert len(fs) == 6          # one self-test note per detector leg

    def test_baseline_suppression_covers_conc_detectors(self):
        """The generic baseline machinery must reach the new detectors:
        a conc-guarded-by suppression suppresses the matching finding
        (and registers a match, so it is not stale); an unmatched conc
        entry reads as stale."""
        fs, _src = _lint_file_src(_fx("fx_conc_guarded.py"))
        baseline = [
            {"detector": "conc-guarded-by", "match": "fx_conc_guarded.py",
             "reason": "fixture"},
            {"detector": "conc-lock-order", "match": "nowhere",
             "reason": "dead"}]
        analysis.apply_baseline(fs, baseline)
        assert all(f.suppressed for f in fs
                   if f.detector == "conc-guarded-by")
        assert analysis.gate_failures(fs) == []
        stale = analysis.stale_suppressions(baseline)
        assert [e["detector"] for e in stale] == ["conc-lock-order"]

    def test_defer_stale_payload_carries_match_counts(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import graft_lint

        base = tmp_path / "b.json"
        base.write_text(json.dumps({"suppressions": [
            {"detector": "ast-x64", "match": "paddle_tpu/__init__.py",
             "reason": "sanctioned"},
            {"detector": "ghost", "match": "nowhere", "reason": "dead"}]}))
        fs = graft_lint.run(models=(), ast=True, baseline_path=str(base),
                            defer_stale=True)
        assert not [f for f in fs if f.detector == "stale-suppression"]
        counts = {(e["detector"], e["match"]): e.get("_matched", 0)
                  for e in graft_lint.LAST_BASELINE}
        assert counts[("ast-x64", "paddle_tpu/__init__.py")] >= 1
        assert counts[("ghost", "nowhere")] == 0


def test_registered_in_quick_tier():
    from conftest import QUICK_MODULES

    assert "test_concurrency.py" in QUICK_MODULES, \
        "tests/test_concurrency.py must be registered in QUICK_MODULES"
