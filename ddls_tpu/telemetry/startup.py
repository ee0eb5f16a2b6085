"""Start-up spans: where the seconds before the first epoch go.

Set-up runs once per process, before a CLI or the benchmark turns
telemetry on, so its spans live in their own small ALWAYS-ON registry —
the one exemption, with serve's private ``ServeStats`` registry, from
the disabled-by-default rule. Nothing that runs per step, per request
or per epoch may ever use it.

``with startup.span("startup.env"): ...`` times one phase (interval
kept, so a reader can nest the phases under ``startup.build_run``).
While any start-up span is open, jax's own monitoring durations are
recorded beside them as ``startup.jax.trace`` / ``.lower`` /
``.compile`` spans (a phase ends when jax reports it, so its interval
is ``[now - seconds, now]``): what tracing, MLIR lowering and the
backend compile or persistent-cache load of the programs built during
set-up cost. That is one record per jitted function jax traces, inner
ones included — about 5,500 for a 320-lane fused run, a dozen for the
phases themselves. Inner jits report inside their outer program's
trace, so a reader takes the UNION of a name's intervals
(:func:`summary`), never their sum.

Sizes that set-up fixes once (``set_gauge``: the job graphs' op and
edge counts) are gauges of the same registry.

``report()`` is the registry's reader: one ``[startup] {...}`` line of
seconds per span name, then the gauges under their own names, printed
once when the first fused epoch ends.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from typing import Dict, Optional

from ddls_tpu.telemetry.metrics import Registry, overlap_summary

#: jax monitoring event -> the start-up span it is recorded as
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "startup.jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "startup.jax.lower",
    "/jax/core/compile/backend_compile_duration": "startup.jax.compile",
}


def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process: field 22 of
    ``/proc/self/stat`` against the boot clock. None where that cannot
    be read (not Linux)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class StartupRecorder:
    """The start-up registry and the jax listener that feeds it while a
    start-up span is open."""

    def __init__(self):
        self.registry = Registry(enabled=True)
        self.registry.record_intervals = True
        self._open = 0
        self._listening = False

    def _on_jax_duration(self, event: str, seconds: float, **_) -> None:
        name = JAX_PHASES.get(event)
        if name is not None:
            now = self.registry.clock()
            self.registry.record_span(name, now - seconds, now)

    def _listen(self, on: bool) -> None:
        # jax is imported lazily by the entry points: a span opened
        # before that has nothing to listen to yet
        jax = sys.modules.get("jax")
        if jax is None or on == self._listening:
            return
        if on:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_jax_duration)
        else:
            jax.monitoring.unregister_event_duration_listener(
                self._on_jax_duration)
        self._listening = on

    @contextlib.contextmanager
    def span(self, name: str):
        self._listen(True)
        self._open += 1
        try:
            with self.registry.span(name) as sp:
                yield sp
        finally:
            self._open -= 1
            if not self._open:
                self._listen(False)

    def span_since_process_start(self, name: str) -> None:
        age = process_age_s()
        if age is not None:
            now = self.registry.clock()
            self.registry.record_span(name, now - age, now)

    def summary(self) -> Dict[str, float]:
        by_name: Dict[str, list] = {}
        for interval in self.registry.span_intervals():
            by_name.setdefault(interval[0], []).append(interval)
        return {name: overlap_summary(ivs).get("covered_1_s", 0.0)
                for name, ivs in by_name.items()}


_RECORDER = StartupRecorder()


def registry() -> Registry:
    return _RECORDER.registry


def span(name: str):
    """A timed start-up phase (see the module docstring)."""
    return _RECORDER.span(name)


def span_since_process_start(name: str) -> None:
    """Record ``name`` as the span from this process's creation to now:
    what ran before the program's first own span (interpreter, imports,
    backend start, config composition)."""
    _RECORDER.span_since_process_start(name)


def set_gauge(name: str, value: float) -> None:
    """A size fixed during set-up, read beside the spans."""
    _RECORDER.registry.gauge(name).set(value)


def gauges() -> Dict[str, float]:
    return dict(_RECORDER.registry.snapshot().get("gauges", {}))


def summary() -> Dict[str, float]:
    """Seconds under each start-up span name, in order of first
    completion: the union of the name's intervals."""
    return _RECORDER.summary()


def report() -> str:
    """The one line an operator (and a benchmark run's log) gets."""
    return "[startup] " + json.dumps(
        {**{name.removeprefix("startup."): round(seconds, 3)
            for name, seconds in summary().items()}, **gauges()})
