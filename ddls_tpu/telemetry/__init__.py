"""Unified telemetry layer: spans, counters, gauges, latency histograms
(ISSUE 3) — one vocabulary for timing/attribution evidence across the
simulator, the train loops and the serve stack; ``benchmarks/`` reads it.

The process-global registry here is **disabled by default** and the
module-level API is a near-no-op while it stays disabled: one bool check,
a shared singleton span, no metric creation, no allocation. That is the
hot-path contract (CLAUDE.md): sim/env/train code may only touch
telemetry through these gated functions, so golden tests and the env
step loop are byte- and speed-identical with telemetry off
(tests/test_telemetry.py pins both).

Usage::

    from ddls_tpu import telemetry

    telemetry.enable(sink_path="run.jsonl")      # CLI entry points
    with telemetry.span("train.collect"):
        ...
    telemetry.inc("sim.lookahead_cache.hit")
    telemetry.record_event("serve_degraded", bucket_idx=1,
                           batch_fill=4)
    print(telemetry.snapshot())                  # JSON-friendly rollup

Every live span of the global registry is also a
``jax.profiler.TraceAnnotation`` named
``ddls.<name>``: a profile taken while telemetry is on (an operator's
``experiment.profile_jax=true``, the benchmark's traced window) shows
the program's spans on the device's clock. The device program itself is
named by ``jax.named_scope``s kept in ``telemetry/scopes.py``.

Subsystems that need isolated, always-on metrics (serve's per-server
stats) instantiate a private ``Registry(enabled=True)`` instead of the
global one — multiple servers must never share counters, and their stats
must keep working with global telemetry disabled. Start-up is the other
always-on registry (``telemetry.startup``): ``startup.*`` spans time the
once-per-process phases of building a run, where telemetry proper is
still off — nothing per step may ever use it.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

from ddls_tpu.telemetry import startup
from ddls_tpu.telemetry.metrics import (DEFAULT_LATENCY_BUCKETS_S,
                                        DEFAULT_WINDOW, NULL_SPAN,
                                        TRACE_ANNOTATION_PREFIX, Counter,
                                        Gauge, Histogram, NullSpan,
                                        Registry, Span, TransferSpan,
                                        aggregate_snapshots,
                                        overlap_summary,
                                        per_epoch_sums,
                                        percentile_from_bucket_counts,
                                        tree_nbytes)
from ddls_tpu.telemetry.sink import JsonlSink

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Span", "NullSpan",
    "NULL_SPAN", "TransferSpan", "JsonlSink", "DEFAULT_LATENCY_BUCKETS_S",
    "DEFAULT_WINDOW", "percentile_from_bucket_counts", "overlap_summary",
    "aggregate_snapshots", "per_epoch_sums", "tree_nbytes",
    "TRACE_ANNOTATION_PREFIX",
    "startup",
    "registry", "enabled", "enable", "disable", "span", "transfer", "inc",
    "observe", "set_gauge", "record_event", "snapshot", "span_summaries",
    "reset", "dump_snapshot", "clock_now", "record_span", "span_intervals",
]

_GLOBAL = Registry(enabled=False, annotate_spans=True)

# environment override for processes whose CLI has no telemetry flag
# (subprocess env workers): a path enables the global registry with a
# JSONL sink at import of the entry point that consults it
# (scripts/serve_policy.py)
SINK_ENV_VAR = "DDLS_TELEMETRY_JSONL"


def registry() -> Registry:
    """The process-global registry (for snapshot plumbing and tests —
    hot paths go through the gated module functions below)."""
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL.enabled


def enable(sink_path: Optional[str] = None,
           clock=None,
           record_intervals: Optional[bool] = None) -> Registry:
    """Turn the global registry on (idempotent; existing metrics are
    kept — call ``reset()`` first for a fresh measurement window).
    ``sink_path`` attaches a JSONL sink; ``record_intervals=True`` keeps
    per-span (start, end) pairs in a bounded ring for
    ``overlap_summary`` concurrency accounting."""
    if sink_path:
        _GLOBAL.sink = JsonlSink(sink_path)
    if clock is not None:
        _GLOBAL.clock = clock
    if record_intervals is not None:
        _GLOBAL.record_intervals = bool(record_intervals)
    _GLOBAL.enabled = True
    return _GLOBAL


def disable() -> None:
    """Flip telemetry off; recorded metrics survive until ``reset()``."""
    _GLOBAL.enabled = False


def env_sink_path() -> Optional[str]:
    return os.environ.get(SINK_ENV_VAR) or None


# ----------------------------------------------------------- gated hot API
def span(name: str):
    """A timed block; the shared no-op singleton when disabled (so a hot
    loop allocates nothing — identity-tested by the guard test)."""
    if not _GLOBAL.enabled:
        return NULL_SPAN
    return Span(_GLOBAL, name)


def transfer(name: str, direction: str):
    """A timed, byte-attributed block around an EXISTING explicit
    device_put/device_get/drain site (the transfer ledger, ISSUE 18):
    ``with telemetry.transfer("sebulba.params", "h2d") as tr: ...;
    tr.add(tree)``. The shared no-op singleton when disabled — zero
    allocation, and ``add`` never reads device data either way
    (``.nbytes`` metadata only), so transfer-guard pins stay valid."""
    if not _GLOBAL.enabled:
        return NULL_SPAN
    return TransferSpan(_GLOBAL, name, direction)


def inc(name: str, n: int = 1) -> None:
    if _GLOBAL.enabled:
        _GLOBAL.counter(name).inc(n)


def observe(name: str, value: float, **histogram_kwargs) -> None:
    if _GLOBAL.enabled:
        _GLOBAL.histogram(name, **histogram_kwargs).observe(value)


def set_gauge(name: str, value: float) -> None:
    if _GLOBAL.enabled:
        _GLOBAL.gauge(name).set(value)


def record_event(kind: str, **fields) -> None:
    if _GLOBAL.enabled:
        _GLOBAL.event(kind, **fields)


def clock_now() -> float:
    """The registry clock's current reading — the t0 source for
    ``record_span`` (injectable-clock discipline: never pair a raw
    wall-clock read with a registry-recorded end)."""
    return _GLOBAL.clock()


def record_span(name: str, t0: float, t1: Optional[float] = None) -> None:
    """Record an explicitly-timed span (see ``Registry.record_span``);
    no-op while disabled, like the context-manager form."""
    if _GLOBAL.enabled:
        _GLOBAL.record_span(name, t0, t1)


# --------------------------------------------------------------- readbacks
def snapshot() -> Dict[str, Any]:
    return _GLOBAL.snapshot()


def span_summaries() -> Dict[str, Dict[str, float]]:
    return _GLOBAL.span_summaries()


def span_intervals() -> list:
    return _GLOBAL.span_intervals()


def reset() -> None:
    _GLOBAL.reset()


def dump_snapshot(extra: Optional[Dict[str, Any]] = None) -> None:
    _GLOBAL.dump_snapshot(extra=extra)
