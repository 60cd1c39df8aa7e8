"""paddle_tpu.obs — runtime telemetry: metrics, spans, compile watchdog,
structured logging.

The observability substrate the ROADMAP's serving/partitioner items
report through (the role paddle.profiler + VisualDL play in the
reference stack, rebuilt serving-grade):

  * **metrics**  — Counter/Gauge/Histogram registry with labels
    (cardinality-capped), JSONL event log (``FLAGS_obs_log_path``) and
    Prometheus text exposition (``render_prometheus()`` +
    ``serve_metrics(port)`` stdlib endpoint). The serving engine owns a
    per-instance registry; the framework default (compile metrics) is
    ``default_registry()``.
  * **trace**    — ``span("name", **attrs)``: a
    ``jax.profiler.TraceAnnotation`` on every backend plus a record
    ``(name, start, end, parent, attrs)`` in the one process-wide span
    log (``span_events()``); ``capture_trace(dir)`` on-demand xplane
    capture.
  * **watchdog** — every compile/retrace (eager cache, to_static, the
    generation engine, serving buckets) becomes an event +
    ``compiles_total``/``compile_seconds``; ``audit_recompiles()`` turns
    storms and post-warmup compiles into ``analysis.Finding``s that fail
    ``tools/graft_lint.py`` (the ``obs`` smoke).
  * **logging**  — module-scoped VLOG driven by ``FLAGS_log_level`` with
    per-message rate limiting; the dy2static fallback + engine admission
    messages route through it.
  * **train_flight / goodput** (round 16) — the training twins of the
    request recorder + cost ledger: per-step span timelines (data wait,
    h2d, fwd/bwd/opt, lazy flushes, compiled dispatches, ckpt IO) with
    a dump-time wall-tiling assertion and anomaly postmortems
    (data starvation / step spike / ckpt stall), plus MFU
    (``train_mfu{program}``) and ML-goodput accounting
    (``train_goodput_seconds_total{category}``); ``audit_train_steps``
    (analysis D12) gates starvation streaks and MFU collapse in lint.

Overhead: metrics are OFF by default everywhere except the serving
engine (whose per-tick cost is a handful of attribute updates — measured
within 2% tok/s of uninstrumented steady-state decode, PERF.md round 11);
``FLAGS_obs_metrics=1`` opts the train loop in.
"""
from __future__ import annotations

from .costs import (ProgramCost, audit_cost_regressions, clear_ledger,
                    extract_cost, ledger, record_program,
                    reset_exec_stats, roofline_rows, write_baseline)
from .flight import FlightRecorder, RequestFlight, validate_trace
from .goodput import GoodputLedger, audit_train_steps
from .http import MetricsServer, serve_metrics, shared_server
from .logging import ObsLogger, get_logger
from .metrics import (DEFAULT_BUCKETS, OVERFLOW, Counter, Gauge, Histogram,
                      Registry, dump_registry, log_event)
from .peaks import DEVICE_PEAKS, device_peaks, peak_gbps, peak_tflops
from .trace import (SpanRecord, capture_trace, clear_spans, span,
                    span_events, span_log_start)
from .train_flight import (StepFlight, TrainFlightRecorder,
                           validate_train_trace)
from .watchdog import (CompileEvent, audit_ckpt_stalls, audit_recompiles,
                       ckpt_save_events, clear_events, compile_counts,
                       compile_events, jaxpr_size, post_warmup_compiles,
                       record_ckpt_save, record_compile)

#: process-default registry: compile watchdog counters, train-callback
#: metrics, anything not tied to one engine instance
_default = Registry()


def default_registry() -> Registry:
    return _default


def render_prometheus() -> str:
    """Prometheus text exposition of the default registry."""
    return _default.render_prometheus()


def metrics_enabled() -> bool:
    """Global opt-in for instrumentation OUTSIDE the serving engine
    (FLAGS_obs_metrics). The engine instruments unconditionally (its
    registry is the serving product); the watchdog records compiles
    unconditionally (compiles are rare events, not a hot path)."""
    from ..core.flags import flag

    return bool(flag("FLAGS_obs_metrics"))


__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "DEFAULT_BUCKETS",
    "OVERFLOW", "default_registry", "render_prometheus", "metrics_enabled",
    "dump_registry", "log_event",
    "span", "SpanRecord", "span_events", "span_log_start", "clear_spans",
    "capture_trace",
    "CompileEvent", "record_compile", "compile_events", "compile_counts",
    "post_warmup_compiles", "clear_events", "audit_recompiles",
    "jaxpr_size",
    "record_ckpt_save", "ckpt_save_events", "audit_ckpt_stalls",
    "get_logger", "ObsLogger",
    "serve_metrics", "MetricsServer", "shared_server",
    "FlightRecorder", "RequestFlight", "validate_trace",
    "TrainFlightRecorder", "StepFlight", "validate_train_trace",
    "GoodputLedger", "audit_train_steps",
    "DEVICE_PEAKS", "device_peaks", "peak_gbps", "peak_tflops",
    "ProgramCost", "record_program", "ledger", "clear_ledger",
    "reset_exec_stats", "roofline_rows", "extract_cost",
    "write_baseline", "audit_cost_regressions",
]
