"""One serving replica: a ``ServingEngine`` owned by its driver thread.

The engine declares a single-owner thread contract (D15): every driving
call — ``add_request``/``step``/``run``/``finish_warmup``/``drain`` —
must come from one thread. The Replica IS that thread: the router never
touches the engine's scheduler directly, it enqueues submissions into
the replica's inbox (a ``queue.Queue``) and the driver loop admits them
at tick boundaries. Results flow back through ``RouterFuture``s the
driver completes — the only cross-thread hand-offs are the thread-safe
queue, the future's event, and the engine's documented read-only
surfaces (``stats()``, ``warmed``).

Lifecycle: ``warming`` (driver runs the warmup fn, then
``finish_warmup()``) → ``ready`` (accepting placements) → ``draining``
(``Router.drain``: the engine rejects new admissions, in-flight
requests finish under the round-12 deadline path) → ``stopped`` (driver
exited after ``contract.rebind()`` — ownership handed back for
teardown). A driver crash lands in ``dead``: the inbox leftovers and
every in-flight submission are handed to the router's reroute callback,
so a replica loss never loses a request (the futures complete on a
surviving replica instead).
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..core import lockdep
from ..core.flags import flag


class RouterFuture:
    """Handle for one routed request. ``result()`` blocks for the
    generated tokens; ``finish_reason``/``replica`` are set once done.
    Completes EXACTLY once — later attempts only bump ``completions``
    (the rolling-restart test's zero-duplicate witness)."""

    def __init__(self):
        self._evt = threading.Event()
        self._mu = threading.Lock()     # per-request; not a tracked lock
        self._tokens = None
        self._exc = None
        self.finish_reason = None
        self.replica = None
        #: completion attempts observed (must end at exactly 1)
        self.completions = 0

    def done(self) -> bool:
        return self._evt.is_set()

    def finish(self, tokens, reason: str, replica: str):
        with self._mu:
            self.completions += 1
            if self._evt.is_set():
                return                  # first completion wins
            self._tokens = tokens
            self.finish_reason = reason
            self.replica = replica
            self._evt.set()

    def fail(self, exc: BaseException):
        with self._mu:
            self.completions += 1
            if self._evt.is_set():
                return
            self._exc = exc
            self._evt.set()

    def result(self, timeout=None) -> np.ndarray:
        if not self._evt.wait(timeout):
            raise TimeoutError("request not complete")
        if self._exc is not None:
            raise self._exc
        return self._tokens


class Submission:
    """Router-side record of one request: what to run, where results
    go, the placement inputs (prefix fingerprint, session), and when the
    router took it (`arrival_s`, a `perf_counter` time: the engine's TTFT
    and deadline run from it, so they hold the wait in the mailbox and
    every re-placement)."""

    __slots__ = ("rid", "prompt", "kwargs", "session", "fingerprint",
                 "future", "attempts", "arrival_s")

    def __init__(self, rid, prompt, kwargs, session, fingerprint,
                 arrival_s):
        self.arrival_s = float(arrival_s)
        self.rid = rid
        self.prompt = prompt
        self.kwargs = kwargs
        self.session = session
        self.fingerprint = fingerprint
        self.future = RouterFuture()
        self.attempts = 0


class Replica:
    """Driver-thread wrapper around one ``ServingEngine``."""

    def __init__(self, name: str, engine, warmup=None, on_reroute=None):
        self.name = str(name)
        self.engine = engine
        self._warmup_fn = warmup
        self._on_reroute = on_reroute
        self._inbox: queue.Queue = queue.Queue()
        self._lock = lockdep.make_lock("serving.Replica._lock")
        # lifecycle: warming|ready|draining|stopped|dead
        self._state = "warming"         # guarded-by: _lock
        self._stop_flag = False         # guarded-by: _lock
        #: placements accepted (read by fleet_stats / D17 skew)
        self.routed = 0                 # guarded-by: _lock
        self._ready_evt = threading.Event()
        self._stopped_evt = threading.Event()
        self.error = None               # set once by the dying driver
        # engine-rid -> Submission; DRIVER-THREAD ONLY (the crash path
        # _die also runs on the driver thread)
        self._live: dict = {}
        # prefix fingerprint index: block hash -> None, LRU-bounded.
        # Router-thread only — every touch is serialized by the router's
        # placement lock, the driver never reads it.
        self._fp_index = {}
        self._fp_cap = int(flag("FLAGS_router_fingerprint_blocks"))
        self._thread = None

    # ---------------------------------------------------------- control
    def start(self):
        """Spawn the driver. Ownership of the engine is explicitly
        handed to the new thread: ``rebind()`` clears whatever thread
        drove the engine before (a caller that pre-warmed it), and the
        driver's first call binds the contract to itself."""
        self.engine.contract.rebind()
        self._thread = threading.Thread(
            target=self._loop, name=f"replica-{self.name}", daemon=True)
        self._thread.start()
        return self

    def wait_ready(self, timeout=None) -> bool:
        """True once the driver finished warmup (``engine.warmed``)."""
        if not self._ready_evt.wait(timeout):
            return False
        return self.state == "ready" and bool(self.engine.warmed)

    def wait_stopped(self, timeout=None) -> bool:
        return self._stopped_evt.wait(timeout)

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    @property
    def accepting(self) -> bool:
        return self.state == "ready"

    def submit(self, sub: Submission):
        """Enqueue one placement (router thread). Raises RuntimeError
        when the replica can no longer take work — the router re-places
        on a survivor. The state check and the put are atomic against
        the crash path's leftover collection, so a submission is either
        rejected here or guaranteed to reach the reroute callback."""
        with self._lock:
            if self._state not in ("warming", "ready"):
                raise RuntimeError(
                    f"replica {self.name} is {self._state}")
            self.routed += 1
            self._inbox.put(("sub", sub))

    def drain(self, deadline_ms=None):
        """Begin drain (router thread): placements stop immediately,
        the driver tells the engine to reject new admissions and clamps
        in-flight deadlines, then exits once ``engine.drained``."""
        with self._lock:
            if self._state in ("stopped", "dead"):
                return
            self._state = "draining"
            self._inbox.put(("drain", deadline_ms))

    def stop(self, reroute: bool = True):
        """Hard stop (router teardown, not a graceful drain): the
        driver exits at the next tick boundary; unfinished submissions
        are rerouted, or failed when ``reroute`` is False (the whole
        fleet is going away) or no reroute callback is set."""
        with self._lock:
            if self._state in ("stopped", "dead"):
                return
            if not reroute:
                self._on_reroute = None
            self._stop_flag = True
            self._state = "draining"
            self._inbox.put(("stop", None))

    # ------------------------------------------------- placement inputs
    def load(self):
        """(queue depth, -free KV blocks): inbox + engine queue + active
        slots, free-block budget from the engine's thread-safe
        ``stats()`` view as the tiebreak. Lexicographic min = least
        loaded."""
        eng = self.engine
        depth = self._inbox.qsize() + eng.num_waiting + eng.num_active
        return (depth, -int(eng.stats()["kv_pool_free"]))

    def queue_depth(self) -> int:
        eng = self.engine
        return self._inbox.qsize() + eng.num_waiting + eng.num_active

    def fingerprint_score(self, fingerprint) -> int:
        """Leading block hashes of ``fingerprint`` this replica has
        served before — the prefix its cache can cover. Router-thread
        only (serialized by the router's placement lock)."""
        score = 0
        for h in fingerprint:
            if h not in self._fp_index:
                break
            score += 1
        return score

    def record_fingerprint(self, fingerprint):
        """Remember a placed prompt's block hashes (router-thread only,
        LRU-bounded by FLAGS_router_fingerprint_blocks)."""
        if self._fp_cap <= 0:
            return
        idx = self._fp_index
        for h in fingerprint:
            idx.pop(h, None)
            idx[h] = None               # re-insert = move to MRU end
        while len(idx) > self._fp_cap:
            idx.pop(next(iter(idx)))

    # ------------------------------------------------------ driver loop
    def _loop(self):
        eng = self.engine
        try:
            if self._warmup_fn is not None:
                self._warmup_fn(eng)
            if not eng.warmed:
                eng.finish_warmup()
            with self._lock:
                if self._state == "warming":
                    self._state = "ready"
            self._ready_evt.set()
            draining = False
            while True:
                item = self._next_item(block=not eng.has_work())
                while item is not None:
                    kind, payload = item
                    if kind == "sub":
                        self._start_sub(payload)
                    elif kind == "drain":
                        eng.drain(payload)
                        draining = True
                    elif kind == "stop":
                        draining = True
                        with self._lock:
                            self._stop_flag = True
                    item = self._next_item(block=False)
                with self._lock:
                    hard_stop = self._stop_flag
                if hard_stop:
                    break
                if eng.has_work():
                    self._advance()
                elif draining:
                    break               # engine.drained — hand off
        except Exception as exc:        # noqa: BLE001 — driver is a root
            self._die(exc)
            return
        # clean exit (drained or stopped): hand engine ownership back so
        # the router can tear it down from its own thread
        eng.contract.rebind()
        leftovers = self._collect_leftovers("stopped")
        self._ready_evt.set()
        self._stopped_evt.set()
        self._hand_off(leftovers, RuntimeError(
            f"replica {self.name} stopped"))

    def _next_item(self, block: bool):
        try:
            if block:
                # short poll so stop/drain commands land promptly even
                # on an idle replica
                return self._inbox.get(timeout=0.005)
            return self._inbox.get_nowait()
        except queue.Empty:
            return None

    def _start_sub(self, sub: Submission):
        try:
            rid = self.engine.add_request(
                sub.prompt, **{"arrival_s": sub.arrival_s, **sub.kwargs})
        except ValueError as exc:
            if self.engine.draining and self._on_reroute is not None:
                # drain raced an already-enqueued placement: not an
                # error, the request belongs on a surviving replica
                self._on_reroute([sub])
            else:
                sub.future.fail(exc)
            return
        self._live[rid] = sub

    def _advance(self):
        for rid, _tok, fin in self.engine.step():
            if not fin:
                continue
            sub = self._live.pop(rid, None)
            if sub is None:
                continue
            tokens = self.engine.completed.get(rid)
            sub.future.finish(
                np.asarray([] if tokens is None else tokens, np.int64),
                self.engine.finish_reasons.get(rid, ""), self.name)

    def _collect_leftovers(self, final_state: str):
        """Atomically flip to the terminal state and sweep everything
        that never finished: inbox submissions never admitted plus
        in-flight ones (driver thread, so ``_live`` is safe to read)."""
        with self._lock:
            self._state = final_state
            leftovers = []
            while True:
                try:
                    item = self._inbox.get_nowait()
                except queue.Empty:
                    break
                if item[0] == "sub":
                    leftovers.append(item[1])
        leftovers.extend(self._live.values())
        self._live = {}
        return leftovers

    def _hand_off(self, leftovers, fallback_exc):
        if not leftovers:
            return
        if self._on_reroute is not None:
            self._on_reroute(list(leftovers))
        else:
            for sub in leftovers:
                sub.future.fail(fallback_exc)

    def _die(self, exc: BaseException):
        self.error = exc
        leftovers = self._collect_leftovers("dead")
        self._ready_evt.set()
        self._stopped_evt.set()
        self._hand_off(leftovers, RuntimeError(
            f"replica {self.name} died: {exc!r}"))
