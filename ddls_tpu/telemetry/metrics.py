"""Dependency-free metrics primitives: counters, gauges, fixed-bucket
latency histograms, span tracing, and a registry with snapshot/reset.

The substrate Podracer (arXiv 2104.06272) and MSRL (arXiv 2210.00882)
attribute their scaling wins to: per-stage instrumentation of the
actor/learner dataflow, here shared by the simulator, the train loops
and the serve stack, and read by ``benchmarks/``, so every perf claim
speaks one vocabulary.

Design rules (ISSUE 3):

* **Near-no-op when disabled.** The module-level API in
  ``ddls_tpu.telemetry`` early-outs on a single bool and returns one
  shared singleton span object, so a disabled hot loop performs no
  allocation and creates no metrics (guard-tested in
  tests/test_telemetry.py). Hot-path modules must only ever go through
  that gated API — never instantiate metrics per step.
* **Thread-safe aggregation.** Every mutation takes the metric's own
  lock (serve batches, background save threads, and the multi-host
  launcher all touch metrics off the main thread); registry
  create-or-get takes the registry lock.
* **Injectable clock.** ``Registry(clock=...)`` parameterises every
  span/duration measurement, so tests drive time deterministically —
  the same discipline as ``PolicyServer(clock=...)``.
* **Histograms carry fixed buckets AND a trailing sample window.** The
  bucket counts are exact over the metric's lifetime (what a JSONL sink
  or a cross-process aggregator can merge); the window gives exact
  ``np.percentile`` p50/p95/p99 over the last ``window`` samples — the
  same windowed-percentile semantics serve's stats always had, so
  histogram-derived latency figures agree bit-for-bit with them.
"""
from __future__ import annotations

import bisect
import sys
import threading
import time
from collections import deque
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np

# geometric ~1-2.5-5 ladder from 10 us to 30 s: spans range from a
# sub-ms host env step to a multi-second accelerator compile
DEFAULT_LATENCY_BUCKETS_S: Tuple[float, ...] = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0, 30.0)

# trailing-window size for exact percentiles: a long-lived process must
# not hold one float per observation ever made (matches serve's
# STATS_WINDOW; the bucket counts above the window stay exact forever)
DEFAULT_WINDOW = 8192


class Counter:
    """Monotonic event counter."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-value-wins instantaneous measurement."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value: Optional[float] = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = value

    @property
    def value(self) -> Optional[float]:
        return self._value


class Histogram:
    """Fixed-bucket histogram + trailing raw-sample window.

    ``buckets`` are ascending upper bounds (``le`` convention: a sample
    lands in the first bucket whose bound it does not exceed; one
    implicit overflow bucket catches the rest). Bucket counts, count,
    sum, min and max are exact over the histogram's lifetime; the
    percentiles are exact (``np.percentile``, linear interpolation) over
    the trailing ``window`` samples, falling back to bucket
    interpolation when the window is disabled (``window=0``).
    """

    __slots__ = ("name", "bounds", "_counts", "_count", "_sum", "_min",
                 "_max", "window", "_lock")

    def __init__(self, name: str,
                 buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_S,
                 window: int = DEFAULT_WINDOW):
        self.name = name
        self.bounds = tuple(sorted(float(b) for b in buckets))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.bounds) + 1)  # + overflow
        self._count = 0
        self._sum = 0.0
        self._min: Optional[float] = None
        self._max: Optional[float] = None
        self.window: Optional[deque] = (deque(maxlen=int(window))
                                        if window else None)
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self._counts[bisect.bisect_left(self.bounds, value)] += 1
            self._count += 1
            self._sum += value
            if self._min is None or value < self._min:
                self._min = value
            if self._max is None or value > self._max:
                self._max = value
            if self.window is not None:
                self.window.append(value)

    # ------------------------------------------------------------- readbacks
    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def min(self) -> Optional[float]:
        return self._min

    @property
    def max(self) -> Optional[float]:
        return self._max

    def window_values(self) -> list:
        """Copy of the trailing window taken under the lock — the only
        safe way to iterate it while another thread may be observing
        (a deque append during iteration raises RuntimeError)."""
        if self.window is None:
            return []
        with self._lock:
            return list(self.window)

    def percentile(self, q: float) -> Optional[float]:
        """Exact percentile over the trailing window (the semantics serve
        stats always used); bucket-interpolated when no window exists."""
        vals = self.window_values()
        if vals:
            return float(np.percentile(
                np.asarray(vals, dtype=np.float64), q))
        if self._count:
            return self.percentile_from_buckets(q)
        return None

    def percentile_from_buckets(self, q: float) -> Optional[float]:
        """Approximate percentile by linear interpolation inside the
        bucket holding the target rank (the only percentile available to
        an aggregator that sees bucket counts alone, e.g.
        scripts/telemetry_report.py over merged sink snapshots)."""
        return percentile_from_bucket_counts(
            self.bounds, self._counts, q, lo=self._min, hi=self._max)

    def bucket_counts(self) -> Dict[str, int]:
        """Nonzero buckets only, keyed by upper bound ('+inf' overflow)."""
        out = {}
        for bound, n in zip(self.bounds, self._counts):
            if n:
                out[repr(bound)] = n
        if self._counts[-1]:
            out["+inf"] = self._counts[-1]
        return out

    def summary(self) -> Dict[str, Any]:
        if not self._count:
            return {"count": 0}
        return {
            "count": self._count,
            "sum": self._sum,
            "mean": self._sum / self._count,
            "min": self._min,
            "max": self._max,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
            "buckets": self.bucket_counts(),
        }


def percentile_from_bucket_counts(bounds: Sequence[float],
                                  counts: Sequence[int], q: float,
                                  lo: Optional[float] = None,
                                  hi: Optional[float] = None
                                  ) -> Optional[float]:
    """Shared bucket-interpolation percentile (Histogram +
    telemetry_report.py): walk the cumulative counts to the bucket
    containing rank ``q/100 * count`` and interpolate linearly between
    its bounds, clamped to the observed [lo, hi] when known."""
    total = int(sum(counts))
    if not total:
        return None
    target = (q / 100.0) * total
    cum = 0
    for i, n in enumerate(counts):
        if not n:
            continue
        if cum + n >= target:
            b_lo = bounds[i - 1] if i > 0 else (lo if lo is not None
                                                else 0.0)
            b_hi = (bounds[i] if i < len(bounds)
                    else (hi if hi is not None else bounds[-1]))
            if lo is not None:
                b_lo = max(b_lo, lo) if i == 0 else b_lo
            if hi is not None:
                b_hi = min(b_hi, hi)
            frac = (target - cum) / n
            return float(b_lo + (b_hi - b_lo) * min(max(frac, 0.0), 1.0))
        cum += n
    return float(bounds[-1] if hi is None else hi)


class NullSpan:
    """The shared disabled-path span: a do-nothing context manager
    returned by ``telemetry.span`` (and ``telemetry.transfer``) when
    telemetry is off, so hot loops pay one bool check and zero
    allocations per call."""

    __slots__ = ()

    duration_s = 0.0
    bytes = 0

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def elapsed(self) -> float:
        return 0.0

    def add(self, tree) -> None:
        """No-op byte attribution (TransferSpan interface)."""


NULL_SPAN = NullSpan()


#: prefix of a span's name on the profiler's clock
#: (``jax.profiler.TraceAnnotation``); trace readers select the
#: program's spans by it
TRACE_ANNOTATION_PREFIX = "ddls."


class Span:
    """One timed block: ``with registry.span("collect"): ...`` records
    the duration into the registry's span histogram (and the JSONL sink
    when one is attached). ``duration_s`` is set on exit; ``elapsed()``
    reads the running clock mid-span.

    In a registry built with ``annotate_spans`` (the process-global
    one: its spans exist only while somebody measures) the block is
    also a ``jax.profiler.TraceAnnotation`` named ``ddls.<name>``, so a
    profile taken while the span runs (an operator's
    ``experiment.profile_jax``, the benchmark's traced window) shows the
    program's spans on the device's clock. The always-on private
    registries (serve's per-request spans, start-up) do not annotate;
    in a process that never imported jax (env workers) it is skipped."""

    __slots__ = ("_registry", "name", "_t0", "duration_s", "_annotation")

    def __init__(self, registry: "Registry", name: str):
        self._registry = registry
        self.name = name
        self._t0 = 0.0
        self.duration_s = 0.0
        self._annotation = None

    def __enter__(self) -> "Span":
        reg = self._registry
        jax = sys.modules.get("jax") if reg.annotate_spans else None
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(
                TRACE_ANNOTATION_PREFIX + self.name)
            self._annotation.__enter__()
        self._t0 = reg.clock()
        return self

    def __exit__(self, *exc) -> bool:
        reg = self._registry
        self.duration_s = reg.clock() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        reg._record_span(self.name, self.duration_s, t0=self._t0)
        return False

    def elapsed(self) -> float:
        return self._registry.clock() - self._t0


def tree_nbytes(tree) -> int:
    """Payload size of an array (py)tree from ``.nbytes`` METADATA only
    (shape x dtype — never a device read or sync, so a wrapped
    ``device_put`` stays legal under ``jax.transfer_guard``). Uses jax's
    tree flattener only if jax is already imported; leaves without
    ``.nbytes`` (scalars, None) count zero."""
    jax = sys.modules.get("jax")
    if jax is not None:
        leaves = jax.tree_util.tree_leaves(tree)
    else:  # minimal container walk so jax-less callers still attribute
        leaves, stack = [], [tree]
        while stack:
            node = stack.pop()
            if isinstance(node, dict):
                stack.extend(node.values())
            elif isinstance(node, (list, tuple)):
                stack.extend(node)
            else:
                leaves.append(node)
    total = 0
    for leaf in leaves:
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            try:
                total += int(nb)
            except TypeError:
                pass
    return total


class TransferSpan:
    """One explicit host<->device or mesh<->mesh hop (the transfer
    ledger, ISSUE 18): wraps an EXISTING explicit ``device_put`` /
    ``device_get`` / drain call site, timing it into the
    ``transfer.<name>`` span histogram and counting payload bytes the
    caller attributes via ``add(tree)``. Dispatch amortization falls
    straight out of ``transfer.<name>.calls`` vs ``.bytes`` per run."""

    __slots__ = ("_registry", "name", "direction", "bytes", "_t0",
                 "duration_s")

    def __init__(self, registry: "Registry", name: str, direction: str):
        self._registry = registry
        self.name = name
        self.direction = direction
        self.bytes = 0
        self._t0 = 0.0
        self.duration_s = 0.0

    def __enter__(self) -> "TransferSpan":
        self._t0 = self._registry.clock()
        return self

    def add(self, tree) -> None:
        """Attribute a payload (metadata-only byte count, see
        ``tree_nbytes``); call after the transfer dispatch with either
        the input or the output tree."""
        self.bytes += tree_nbytes(tree)

    def __exit__(self, *exc) -> bool:
        reg = self._registry
        self.duration_s = reg.clock() - self._t0
        reg.record_transfer(self.name, self.direction, self.bytes,
                            self.duration_s, t0=self._t0)
        return False


# bounded span-interval ring: overlap accounting needs (start, end) pairs,
# which the duration histograms deliberately do not keep; the ring caps the
# cost of leaving interval recording on for a long run
DEFAULT_INTERVAL_RING = 65536


class Registry:
    """A named collection of metrics + span tracer + optional sink.

    The process-global instance lives in ``ddls_tpu.telemetry`` (disabled
    by default; hot paths reach it only through the gated module API).
    Private instances are cheap and always-on — serve's per-server stats
    use one so concurrent servers never share counters and stats work
    with global telemetry disabled.
    """

    def __init__(self, enabled: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 sink=None, annotate_spans: bool = False):
        self.enabled = bool(enabled)
        self.clock = clock
        self.sink = sink
        # spans also enter a ``ddls.<name>`` profiler annotation
        # (Span): for the global registry only, never a per-request one
        self.annotate_spans = bool(annotate_spans)
        # opt-in (enable(record_intervals=True)): keep (name, t0, t1) for
        # every completed span so overlap/gap accounting can PROVE claimed
        # concurrency (e.g. train.update_device running under
        # train.collect) instead of asserting it
        self.record_intervals = False
        self._intervals: deque = deque(maxlen=DEFAULT_INTERVAL_RING)
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._spans: Dict[str, Histogram] = {}

    # ------------------------------------------------------------- metrics
    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_S,
                  window: int = DEFAULT_WINDOW) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(
                    name, buckets=buckets, window=window)
            return h

    def histogram_items(self):
        """Live (name, Histogram) pairs — read-side iteration for rollups
        (e.g. serve's per-bucket occupancy line)."""
        with self._lock:
            return list(self._histograms.items())

    def counter_items(self):
        """Live (name, value) counter pairs — a cheap read (dict copy
        under the lock) for callers that only need counters; a full
        ``snapshot()`` would also summarise every histogram."""
        with self._lock:
            return [(n, c.value) for n, c in self._counters.items()]

    # --------------------------------------------------------------- spans
    def span(self, name: str) -> Span:
        return Span(self, name)

    def _record_span(self, name: str, duration_s: float,
                     t0: Optional[float] = None) -> None:
        with self._lock:
            h = self._spans.get(name)
            if h is None:
                h = self._spans[name] = Histogram(name)
        h.observe(duration_s)
        if self.record_intervals and t0 is not None:
            # deque.append is itself thread-safe; bounded by maxlen
            self._intervals.append((name, t0, t0 + duration_s))
        sink = self.sink
        if sink is not None:
            sink.write({"type": "span", "name": name,
                        "dur_s": duration_s})

    def record_span(self, name: str, t0: float,
                    t1: Optional[float] = None) -> None:
        """Record an explicitly-timed span (same histogram/sink/interval
        plumbing as the context manager). For work whose start and end
        live on different threads — e.g. the pipelined train loop's
        device-update watcher, which captures t0 at dispatch on the main
        thread and closes the span from the thread that blocked on the
        device result."""
        if t1 is None:
            t1 = self.clock()
        self._record_span(name, t1 - t0, t0=t0)

    def record_transfer(self, name: str, direction: str, nbytes: int,
                        duration_s: float,
                        t0: Optional[float] = None) -> None:
        """Transfer-ledger record (see ``TransferSpan``): duration rides
        the span plumbing under ``transfer.<name>`` (histogram +
        interval ring + summaries), bytes/calls ride counters
        (``transfer.<name>.bytes`` / ``.calls`` plus the per-direction
        total ``transfer.<direction>.bytes``), and the sink gets one
        ``{"type": "transfer", ...}`` record the timeline renders as a
        flow arrow."""
        span_name = f"transfer.{name}"
        with self._lock:
            h = self._spans.get(span_name)
            if h is None:
                h = self._spans[span_name] = Histogram(span_name)
        h.observe(duration_s)
        if self.record_intervals and t0 is not None:
            self._intervals.append((span_name, t0, t0 + duration_s))
        self.counter(f"{span_name}.calls").inc()
        self.counter(f"{span_name}.bytes").inc(int(nbytes))
        self.counter(f"transfer.{direction}.bytes").inc(int(nbytes))
        sink = self.sink
        if sink is not None:
            sink.write({"type": "transfer", "name": name,
                        "direction": direction, "bytes": int(nbytes),
                        "dur_s": duration_s})

    def span_intervals(self) -> list:
        """Copy of the recorded (name, t0, t1) interval ring (empty unless
        ``record_intervals`` was set); feed to ``overlap_summary``."""
        return list(self._intervals)

    def span_summaries(self) -> Dict[str, Dict[str, float]]:
        """Per-span rollup in the units humans read spans in (ms), the
        shape both ``snapshot()['spans']`` and the W&B flatten emit."""
        out = {}
        with self._lock:
            spans = dict(self._spans)
        for name, h in spans.items():
            if not h.count:
                continue
            out[name] = {
                "count": h.count,
                "total_s": h.sum,
                "mean_ms": h.sum / h.count * 1e3,
                "p50_ms": h.percentile(50) * 1e3,
                "p95_ms": h.percentile(95) * 1e3,
                "p99_ms": h.percentile(99) * 1e3,
                "max_ms": (h.max or 0.0) * 1e3,
            }
        return out

    # -------------------------------------------------------------- events
    def event(self, kind: str, **fields) -> None:
        """A discrete occurrence (e.g. a degraded-mode transition): tallied as a
        counter (``event.<kind>``, plus ``event.<kind>.<phase>`` when a
        ``phase`` field is given) and written verbatim to the sink so the
        trail survives the process."""
        name = f"event.{kind}"
        self.counter(name).inc()
        phase = fields.get("phase")
        if phase is not None:
            self.counter(f"{name}.{phase}").inc()
        sink = self.sink
        if sink is not None:
            sink.write({"type": "event", "kind": kind, **fields})

    # ----------------------------------------------------- snapshot / reset
    def snapshot(self) -> Dict[str, Any]:
        """JSON-friendly dump of every live metric; empty sections are
        omitted (a registry that recorded nothing snapshots to ``{}``)."""
        with self._lock:
            counters = {n: c.value for n, c in self._counters.items()}
            gauges = {n: g.value for n, g in self._gauges.items()
                      if g.value is not None}
            hists = dict(self._histograms)
        out: Dict[str, Any] = {}
        if counters:
            out["counters"] = counters
        if gauges:
            out["gauges"] = gauges
        hist_section = {n: h.summary() for n, h in hists.items() if h.count}
        if hist_section:
            out["histograms"] = hist_section
        spans = self.span_summaries()
        if spans:
            out["spans"] = spans
        return out

    def reset(self) -> None:
        """Drop every metric and span (fresh dicts — outstanding handles
        keep counting into orphaned objects, which is the safe failure
        mode for a racing thread)."""
        with self._lock:
            self._counters = {}
            self._gauges = {}
            self._histograms = {}
            self._spans = {}
            self._intervals = deque(maxlen=DEFAULT_INTERVAL_RING)

    def dump_snapshot(self, extra: Optional[Dict[str, Any]] = None) -> None:
        """Write the current snapshot to the sink (no-op without one)."""
        sink = self.sink
        if sink is not None:
            data = self.snapshot()
            if extra:
                data = {**data, **extra}
            sink.write({"type": "snapshot", "data": data})


def aggregate_snapshots(snaps: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge several ``Registry.snapshot()`` dicts into one fleet-level
    rollup (ISSUE 8: N serving replicas each keep a PRIVATE always-on
    registry; the report surface needs the fleet total without the
    replicas ever sharing live metric objects).

    Exact merges only: counters and gauges sum, histogram ``count`` /
    ``sum`` / ``min`` / ``max`` and the fixed bucket counts add (the
    bucket counts are lifetime-exact by design — docs/telemetry.md), and
    the merged percentiles are reconstructed by bucket interpolation
    (``percentile_from_bucket_counts``) because trailing sample windows
    cannot be merged order-faithfully across registries. Span summaries
    merge count/total/mean/max the same way; their percentiles are
    dropped (window-only). Empty sections are omitted, mirroring
    ``snapshot()``.
    """
    counters: Dict[str, int] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, Dict[str, Any]] = {}
    spans: Dict[str, Dict[str, float]] = {}
    for snap in snaps:
        if not snap:
            continue
        for name, value in (snap.get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + int(value)
        for name, value in (snap.get("gauges") or {}).items():
            if value is not None:
                gauges[name] = gauges.get(name, 0.0) + float(value)
        for name, summ in (snap.get("histograms") or {}).items():
            if not summ.get("count"):
                continue
            agg = hists.setdefault(name, {
                "count": 0, "sum": 0.0, "min": None, "max": None,
                "buckets": {}})
            agg["count"] += int(summ["count"])
            agg["sum"] += float(summ.get("sum", 0.0))
            for bound, n in (summ.get("buckets") or {}).items():
                agg["buckets"][bound] = (agg["buckets"].get(bound, 0)
                                         + int(n))
            for key, pick in (("min", min), ("max", max)):
                v = summ.get(key)
                if v is not None:
                    agg[key] = (v if agg[key] is None
                                else pick(agg[key], v))
        for name, summ in (snap.get("spans") or {}).items():
            agg = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "max_ms": 0.0})
            agg["count"] += int(summ.get("count", 0))
            agg["total_s"] += float(summ.get("total_s", 0.0))
            agg["max_ms"] = max(agg["max_ms"],
                                float(summ.get("max_ms", 0.0)))
    for agg in hists.values():
        agg["mean"] = agg["sum"] / agg["count"]
        bounds = sorted(float(b) for b in agg["buckets"] if b != "+inf")
        cnts = [agg["buckets"].get(repr(b), agg["buckets"].get(str(b), 0))
                for b in bounds]
        cnts.append(agg["buckets"].get("+inf", 0))
        for q in (50, 95, 99):
            agg[f"p{q}"] = percentile_from_bucket_counts(
                bounds, cnts, q, lo=agg["min"], hi=agg["max"])
    for agg in spans.values():
        if agg["count"]:
            agg["mean_ms"] = agg["total_s"] / agg["count"] * 1e3
    out: Dict[str, Any] = {}
    if counters:
        out["counters"] = counters
    if gauges:
        out["gauges"] = gauges
    if hists:
        out["histograms"] = hists
    if spans:
        out["spans"] = spans
    return out


def overlap_summary(intervals: Sequence[Tuple[str, float, float]],
                    prefix: Optional[str] = None,
                    top_gaps: int = 3) -> Dict[str, Any]:
    """Concurrency accounting over span (name, t0, t1) intervals.

    The check Podracer-style pipelining claims need: over the window
    [min t0, max t1] of the (optionally ``prefix``-filtered) spans,
    report the wall-clock covered by >= 1 span (``covered_1_s``), by
    >= 2 concurrent spans (``covered_2_s`` — time when two instrumented
    phases genuinely ran at once), the uncovered gap total, and the
    ``top_gaps`` largest individual gaps. ``overlap_fraction`` =
    covered_2 / covered_1: 0 for a strictly sequential loop, > 0 only
    when phases actually overlap. Sources: a Registry's interval ring
    (``enable(record_intervals=True)``) or a JSONL sink's span records
    via ``(ts - dur_s, ts)`` (scripts/telemetry_report.py).
    """
    ivs = [(t0, t1) for name, t0, t1 in intervals
           if t1 > t0 and (prefix is None or name.startswith(prefix))]
    if not ivs:
        return {"n_spans": 0}
    events = []
    for t0, t1 in ivs:
        events.append((t0, 1))
        events.append((t1, -1))
    events.sort()
    window_t0, window_t1 = events[0][0], max(t1 for _, t1 in ivs)
    covered_1 = covered_2 = 0.0
    gaps = []  # (length, start, end) of zero-coverage stretches
    depth = 0
    prev_t = window_t0
    gap_start = None
    for t, delta in events:
        if t > prev_t:
            if depth >= 1:
                covered_1 += t - prev_t
            if depth >= 2:
                covered_2 += t - prev_t
        if depth == 0 and delta > 0 and gap_start is not None:
            if t > gap_start:
                gaps.append((t - gap_start, gap_start, t))
            gap_start = None
        prev_t = t
        depth += delta
        if depth == 0:
            gap_start = t
    gaps.sort(reverse=True)
    wall = window_t1 - window_t0
    return {
        "n_spans": len(ivs),
        "window_s": wall,
        "covered_1_s": covered_1,
        "covered_2_s": covered_2,
        "gap_s": max(wall - covered_1, 0.0),
        "overlap_fraction": (covered_2 / covered_1) if covered_1 else 0.0,
        "largest_gaps": [
            {"dur_s": g, "start": s, "end": e}
            for g, s, e in gaps[:max(top_gaps, 0)]],
    }


#: the span whose successive starts delimit a fused run's epochs
EPOCH_MARKER = "train.fused_epoch"


def per_epoch_sums(intervals: Sequence[Tuple[str, float, float]],
                   names) -> List[float]:
    """Per-epoch sums of a fused run's spans (docs/telemetry.md, "a
    fused epoch's five spans"): an epoch runs from one ``EPOCH_MARKER``
    span's start to the next one's, and a span belongs to the epoch it
    STARTS in. Returns the seconds of the ``names`` spans, one sum an
    epoch; ``[]`` where no marker span was recorded. Sources as for
    ``overlap_summary``; the benchmark's ``program_span_per_epoch``
    reader and ``scripts/telemetry_report.py`` both group through it."""
    starts = sorted(t0 for name, t0, _ in intervals
                    if name == EPOCH_MARKER)
    sums = [0.0] * len(starts)
    for name, t0, t1 in intervals:
        if name in names:
            epoch = bisect.bisect_right(starts, t0) - 1
            if epoch >= 0:
                sums[epoch] += t1 - t0
    return sums
