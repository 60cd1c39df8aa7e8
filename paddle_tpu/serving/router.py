"""Prefix-cache-aware router over N paged serving engines.

One ``submit(prompt, ...) -> RouterFuture`` front door over a fleet of
``ServingEngine`` replicas, each on its own driver thread (replica.py).
Placement is policy-driven (policy.py): the default ``prefix_affine``
fingerprints the prompt with the SAME chained content hashes
``PrefixCache`` keys blocks by — ``hash_blocks`` under the engines'
spec/block_size/cache-dtype namespace — and routes to the replica whose
bounded fingerprint index overlaps most, so shared-prefix traffic
concentrates where its KV blocks already live; ``least_loaded`` (queue
depth + free-block budget) is the fallback and ``round_robin`` the
baseline. Session affinity pins a session ID's follow-up turns to its
replica (multi-turn prompts hit decode-written blocks).

Rolling restarts never drop a request: ``drain(name, replacement=...)``
stops placement to the replica, starts the replacement warming
CONCURRENTLY, tells the engine to ``drain()`` (admission rejects with
reason "draining"; in-flight deadlines clamp to FLAGS_router_drain_ms
via the round-12 timeout path), waits for the driver to exit and
``rebind()`` the thread contract, and only admits the replacement once
it passed ``finish_warmup()`` AND the per-engine ``/healthz`` probe.
A replica that dies mid-flight fails over: its unfinished submissions
re-place on survivors and each future still completes exactly once.

Fleet metrics export through the round-16 shared ``/metrics`` endpoint
under an ``engine="routerN"`` label when ``FLAGS_obs_http_port`` is
set; D17 ``audit_fleet`` (analysis/serving.py) reads ``fleet_stats()``.
"""
from __future__ import annotations

import hashlib
import itertools
import time

import numpy as np

from ..core import lockdep
from ..core.flags import flag
from ..text.paged_cache import hash_blocks
from .policy import make_policy
from .replica import Replica, RouterFuture, Submission  # noqa: F401

#: process-unique names for the /metrics engine label (read-only next())
_ROUTER_IDS = itertools.count()

#: byte-identical-prompt tracking bound (the D17 independent repeat
#: fingerprint, same role as the engine's D7 repeat LRU)
_REPEAT_TRACK_CAP = 4096


class Router:
    """Owns N replicas behind one submit() API. All placement state is
    serialized by one lock; replicas do their own work on their driver
    threads. Lock order is Router._lock -> Replica._lock, never the
    reverse (driver threads call back into the router only lock-free)."""

    def __init__(self, engines, policy=None, warmup=None,
                 names=None):
        engines = list(engines)
        if not engines:
            raise ValueError("router needs at least one engine")
        ns = {e._prefix_namespace for e in engines}
        bs = {e.block_size for e in engines}
        if len(ns) != 1 or len(bs) != 1:
            raise ValueError(
                "heterogeneous fleet: replicas disagree on the prefix "
                "namespace (spec/block_size/cache dtype) — their KV "
                "blocks are not interchangeable, so prefix-affine "
                "routing would be meaningless")
        self._ns = ns.pop()
        self._block_size = bs.pop()
        self._fp_cap = int(flag("FLAGS_router_fingerprint_blocks"))
        self._policy = make_policy(
            policy if policy is not None else str(flag(
                "FLAGS_router_policy")))
        self._warmup = warmup
        self._lock = lockdep.make_lock("serving.Router._lock")
        self._replicas: dict = {}       # guarded-by: _lock
        self._sessions: dict = {}       # guarded-by: _lock (LRU)
        self._sessions_cap = int(flag("FLAGS_router_sessions_max"))
        # independent repeat fingerprint: sha256(prompt bytes) -> set of
        # replica names it was placed on (bounded LRU). Deliberately NOT
        # the hash_blocks chain, so a broken/drifting fingerprint can't
        # hide its own scattering from D17 (the D7 trick).
        self._seen: dict = {}           # guarded-by: _lock
        self._repeat_subs = 0           # guarded-by: _lock
        self._rids = itertools.count()
        self._rep_ids = itertools.count()
        self._closed = False            # guarded-by: _lock

        # ---- fleet telemetry: its own registry, exported through the
        # shared /metrics endpoint like any engine's
        from .. import obs

        self.registry = obs.Registry()
        reg = self.registry
        self._m_requests = reg.counter(
            "router_requests_total", "requests routed to a replica")
        self._m_affinity = reg.counter(
            "router_prefix_affinity_hits_total", "placements that landed "
            "on a replica whose fingerprint index already covered part "
            "of the prompt (its prefix cache can serve those blocks)")
        self._m_session = reg.counter(
            "router_session_affinity_hits_total", "placements pinned to "
            "their session's previous replica")
        self._m_rerouted = reg.counter(
            "router_rerouted_requests_total", "submissions re-placed on "
            "a survivor after their replica drained or died")
        self._m_dead_routes = reg.counter(
            "router_dead_replica_routes_total", "placements whose chosen "
            "replica was already dead/stopped at hand-off (rescued by "
            "fallback; D17 warns — a policy or pin is routing to a "
            "corpse)")
        self._m_drains = reg.counter(
            "router_drains_total", "drain/handoff cycles started "
            "(rolling restarts)")
        self._m_ready = reg.gauge(
            "router_ready_replicas", "replicas accepting placements")
        self._m_dead = reg.gauge(
            "router_dead_replicas", "replicas whose driver thread died")
        self._metrics_server = None
        self._router_name = None
        port = int(flag("FLAGS_obs_http_port"))
        if port > 0:
            try:
                self._router_name = f"router{next(_ROUTER_IDS)}"
                self._metrics_server = obs.shared_server(port)
                self._metrics_server.register_engine(
                    self._router_name, reg,
                    ready=lambda: self.ready_count > 0)
            except OSError:
                self._metrics_server = None

        names = list(names) if names is not None else []
        with self._lock:
            for eng in engines:
                name = (names.pop(0) if names
                        else f"r{next(self._rep_ids)}")
                rep = Replica(name, eng, warmup=warmup,
                              on_reroute=self._reroute)
                self._replicas[name] = rep
                rep.start()

    # ----------------------------------------------------------- status
    @property
    def replicas(self) -> list:
        with self._lock:
            return sorted(self._replicas)

    @property
    def ready_count(self) -> int:
        with self._lock:
            return sum(r.accepting for r in self._replicas.values())

    def replica(self, name: str) -> Replica:
        with self._lock:
            return self._replicas[name]

    def wait_ready(self, timeout=None) -> bool:
        """True once every current replica finished warmup."""
        with self._lock:
            reps = list(self._replicas.values())
        return all(r.wait_ready(timeout) for r in reps)

    # ------------------------------------------------------- submission
    def submit(self, prompt, session=None, **kwargs) -> RouterFuture:
        """Route one request; returns a future whose ``result()`` is
        the generated-token array (``finish_reason``/``replica`` ride
        along). ``kwargs`` pass through to ``engine.add_request``
        (max_new_tokens, do_sample, eos_token_id, max_time_ms, ...);
        ``session`` pins follow-up turns to this request's replica."""
        arrival_s = time.perf_counter()
        arr = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            np.int64).reshape(-1).astype(np.int32)
        sub = Submission(next(self._rids), arr, kwargs, session,
                         self._fingerprint(arr), arrival_s)
        self._place(sub)
        return sub.future

    def _fingerprint(self, prompt) -> tuple:
        """The prompt's chained prefix block hashes — the exact keys the
        replicas' PrefixCache uses (same namespace), so an index match
        predicts real cache hits."""
        if self._fp_cap <= 0:
            return ()
        return tuple(hash_blocks(prompt, self._block_size, self._ns))

    def _place(self, sub: Submission, exclude=frozenset()):
        sub.attempts += 1
        with self._lock:
            if self._closed:
                raise RuntimeError("router is closed")
            ready = [r for n, r in sorted(self._replicas.items())
                     if r.accepting and n not in exclude]
            if not ready:
                raise RuntimeError(
                    "no ready replicas (all draining, dead, or still "
                    "warming)")
            if sub.attempts > len(self._replicas) + 2:
                raise RuntimeError(
                    f"request {sub.rid} could not be placed after "
                    f"{sub.attempts} attempts")
            rep = None
            if sub.session is not None:
                pin = self._sessions.pop(sub.session, None)
                pinned = self._replicas.get(pin) if pin else None
                if pinned is not None and pinned.accepting \
                        and pin not in exclude:
                    rep = pinned
                    self._m_session.inc()
            if rep is None:
                chosen = self._policy.choose(ready, sub.fingerprint)
                if chosen is None or not chosen.accepting:
                    # a buggy policy (or a stale pin it holds) picked a
                    # replica that can't take work — rescue the request,
                    # and count the defect for D17
                    if chosen is not None \
                            and chosen.state in ("dead", "stopped"):
                        self._m_dead_routes.inc()
                    chosen = min(ready, key=lambda r: r.load())
                rep = chosen
            if sub.fingerprint \
                    and rep.fingerprint_score(sub.fingerprint) > 0:
                self._m_affinity.inc()
            rep.record_fingerprint(sub.fingerprint)
            if sub.session is not None:
                self._sessions[sub.session] = rep.name
                while len(self._sessions) > self._sessions_cap:
                    self._sessions.pop(next(iter(self._sessions)))
            digest = hashlib.sha256(sub.prompt.tobytes()).hexdigest()
            entry = self._seen.pop(digest, None)
            if entry is not None:
                self._repeat_subs += 1
            else:
                entry = set()
            entry.add(rep.name)
            self._seen[digest] = entry
            while len(self._seen) > _REPEAT_TRACK_CAP:
                self._seen.pop(next(iter(self._seen)))
            self._m_requests.inc()
            self._m_ready.set(sum(r.accepting
                                  for r in self._replicas.values()))
            self._m_dead.set(sum(r.state == "dead"
                                 for r in self._replicas.values()))
            target = rep
        try:
            target.submit(sub)
        except RuntimeError:
            # lost a race with the replica dying (a dead-replica route,
            # counted for D17) or starting to drain (a plain reroute)
            # between choose and hand-off — re-place on a survivor
            if target.state in ("dead", "stopped"):
                self._m_dead_routes.inc()
            else:
                self._m_rerouted.inc()
            sub.attempts -= 1           # the retry below re-increments
            self._place(sub, exclude=exclude | {target.name})

    def _reroute(self, subs):
        """Reroute callback (runs on a dying/draining replica's driver
        thread, lock-free on entry — Router._lock is taken inside
        ``_place``)."""
        for sub in subs:
            self._m_rerouted.inc()
            try:
                self._place(sub)
            except Exception as exc:    # noqa: BLE001 — fail the future
                sub.future.fail(exc)

    # -------------------------------------------------- drain / handoff
    def drain(self, name: str, replacement=None, deadline_ms=None,
              warmup=None, timeout_s=120.0):
        """Rolling restart of one replica: stop placements, let
        in-flight work finish (deadline-bounded by FLAGS_router_drain_ms
        through the per-request timeout path), tear the engine down
        after the driver ``rebind()``s its contract — and, when
        ``replacement`` (a fresh ServingEngine) is given, admit it only
        after it passes ``finish_warmup()`` + the per-engine ``/healthz``
        probe. Returns the replacement's replica name (or None)."""
        with self._lock:
            rep = self._replicas.get(name)
            if rep is None:
                raise KeyError(f"no replica {name!r}")
            new_name = f"r{next(self._rep_ids)}" \
                if replacement is not None else None
        self._m_drains.inc()
        new_rep = None
        if replacement is not None:
            # warm the replacement CONCURRENTLY with the drain — the
            # deploy's critical path is max(drain, warmup), not the sum
            new_rep = Replica(
                new_name, replacement,
                warmup=warmup if warmup is not None else self._warmup,
                on_reroute=self._reroute)
            new_rep.start()
        if deadline_ms is None:
            deadline_ms = float(flag("FLAGS_router_drain_ms"))
        rep.drain(deadline_ms if deadline_ms > 0 else None)
        budget = timeout_s
        if deadline_ms and deadline_ms > 0:
            budget = max(timeout_s, deadline_ms / 1e3 + 30.0)
        if not rep.wait_stopped(budget):
            raise RuntimeError(
                f"replica {name} did not drain within {budget:.0f}s")
        rep.engine.close()
        with self._lock:
            self._replicas.pop(name, None)
            for k in [k for k, v in self._sessions.items() if v == name]:
                self._sessions.pop(k)   # re-pin on the next turn
        if new_rep is not None:
            if not new_rep.wait_ready(timeout_s):
                raise RuntimeError(
                    f"replacement {new_name} failed warmup "
                    f"({new_rep.state}): {new_rep.error!r}")
            srv = getattr(new_rep.engine, "_metrics_server", None)
            ename = getattr(new_rep.engine, "_engine_name", None)
            if srv is not None and ename is not None:
                ok, msg = srv.health(engine=ename)
                if not ok:
                    raise RuntimeError(
                        "replacement failed /healthz readiness: "
                        + msg.strip())
            with self._lock:
                self._replicas[new_name] = new_rep
        with self._lock:
            self._m_ready.set(sum(r.accepting
                                  for r in self._replicas.values()))
        return new_name

    def close(self):
        """Tear the fleet down: hard-stop every driver (unfinished
        futures fail — use drain() for graceful handoff), close the
        engines, detach from the shared /metrics endpoint."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            reps = list(self._replicas.values())
            self._replicas = {}
        for rep in reps:
            rep.stop(reroute=False)
        for rep in reps:
            rep.wait_stopped(10.0)
            rep.engine.close()
        srv, self._metrics_server = self._metrics_server, None
        if srv is not None:
            srv.unregister_engine(self._router_name)

    # ------------------------------------------------------------- D17
    def fleet_stats(self) -> dict:
        """The D17 ``audit_fleet`` input (and the fleet dashboard): per-
        replica placement/load/prefix counters plus the router's own
        affinity and failure telemetry."""
        with self._lock:
            reps = dict(self._replicas)
            scattered = sum(1 for s in self._seen.values() if len(s) > 1)
            repeats = self._repeat_subs
        per = {}
        fleet_hits = fleet_misses = 0
        for name, rep in sorted(reps.items()):
            st = rep.engine.stats()
            per[name] = {
                "state": rep.state,
                "routed": rep.routed,
                "queue_depth": rep.queue_depth(),
                "kv_pool_free": int(st["kv_pool_free"]),
                "prefix_hits": int(st["prefix_blocks_hit"]),
                "drained_requests": int(st["drained_requests"]),
            }
            fleet_hits += int(st["prefix_blocks_hit"])
            fleet_misses += int(st["prefix_blocks_missed"])
        policy = getattr(self._policy, "name",
                         type(self._policy).__name__)
        return {
            "policy": policy,
            "replica_count": len(per),
            "ready": sum(1 for p in per.values()
                         if p["state"] == "ready"),
            "dead": sum(1 for p in per.values() if p["state"] == "dead"),
            "routed_total": int(self._m_requests.value),
            "affinity_hits": int(self._m_affinity.value),
            "session_hits": int(self._m_session.value),
            "rerouted": int(self._m_rerouted.value),
            "dead_replica_routes": int(self._m_dead_routes.value),
            "drains": int(self._m_drains.value),
            "repeat_submissions": repeats,
            "scattered_repeats": scattered,
            "fleet_prefix_hits": fleet_hits,
            "fleet_prefix_misses": fleet_misses,
            "replicas": per,
        }
