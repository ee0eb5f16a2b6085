"""Episode flight recorder: structured simulator event traces (ISSUE 6).

Telemetry (metrics.py) answers "how long / how many"; this module answers
"what did the simulated cluster DO, in what order". An enabled recorder
captures each episode as an ordered stream of typed events — job
arrivals, decisions (degree + action-mask context), partitions,
placements/mounts, lookahead results (with the backend that served
them), event-clock ticks, completions and blocks — emitted from the host
tick loop (sim/cluster.py, sim/actions.py, envs/partitioning_env.py).
Traces feed three consumers:

* ``scripts/trace_diff.py`` — run one scenario through two lookahead
  backends (host / C++, or the fully-jitted episode kernels at
  decision level) and report the FIRST divergent event, turning "parity
  test failed" into "event 412: lookahead jct 3.81 vs 3.84";
* ``scripts/trace_export.py`` — Chrome-trace/Perfetto JSON, so an
  episode timeline (per-worker rows, channel rows, decision markers)
  opens in the same viewer as the jax profiler captures telemetry hooks
  up (docs/telemetry.md "jax.profiler capture");
* ``scripts/telemetry_report.py`` — a trace summary section (events by
  kind, blocks by cause, per-job lifecycle table).

The Podracer/MSRL lesson (arXiv 2104.06272, 2210.00882) applied to the
simulator itself: per-stage structured records are what make behaviour
attributable; endpoint stats only say THAT backends disagree, never
where.

Gating contract (the telemetry invariant, CLAUDE.md): the recorder is
**disabled by default** and hot paths may only touch it as::

    from ddls_tpu.telemetry import flight as _flight
    ...
    if _flight.enabled():
        _flight.emit("job_arrived", t=clock, job_idx=idx, ...)

so a disabled env step performs ONE bool check and creates zero event
objects (guard-tested in tests/test_flight.py; emits in
``ddls_tpu/sim/``/``ddls_tpu/envs/`` are statically checked by
``scripts/check_flight_gated.py``). Detail events (per-op/flow
completions inside the host lookahead engine) additionally require
``enable(detail=True)`` — they exist only where the host engine serves
the lookahead, so cross-backend diffs exclude them by default.

Event schema: every event is a plain JSON-able dict with ``seq`` (per-
recorder emission index), ``kind``, ``t`` (simulated time), plus
kind-specific fields — see EVENT_KINDS and docs/telemetry.md "Flight
recorder & trace diffing" for the full table. Worker-process traces
(``rl/rollout.py`` subprocess envs) merge into the parent recorder on
the close ack, tagged with their ``env`` index — the same transport the
telemetry counters ride.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# the full vocabulary; emission sites are named per kind
EVENT_KINDS = (
    "job_arrived",      # cluster._get_next_job: job enters the system
    "action_decided",   # envs/partitioning_env.step: degree + mask +
                        # outcome (accepted / cause / lookahead jct)
    "partitioned",      # sim/actions.OpPartition: partitioned graph built
    "placed",           # cluster._place_ops: op -> worker commit
    "mounted",          # cluster._place_deps: dep -> channel commit
    "lookahead",        # cluster lookahead result + serving backend
    "tick",             # cluster.step event loop: clock advance
    "job_completed",    # cluster._register_completed_job
    "job_blocked",      # cluster._register_blocked_job (with cause)
    "op_completed",     # detail: host lookahead engine, per-op finish
    "flow_completed",   # detail: host lookahead engine, per-flow finish
    "worker_preempted", # cluster.step: scenario preemption window's t0
                        # crossed (t == window t0: pure (seed, spec) fn)
    "channel_degraded", # cluster.step: scenario straggler window's t0
                        # crossed (same determinism contract)
)

# kinds only the HOST lookahead engine can produce (the C++/jax engines
# return aggregates); excluded from cross-backend diffs by default
DETAIL_KINDS = ("op_completed", "flow_completed")

# payload fields that are context, not semantics: `seq` is emission
# order (differs when detail kinds are on), `backend` names which engine
# served a lookahead (host vs native IS the thing being diffed), `env`
# tags merged worker traces
DEFAULT_IGNORE_FIELDS = ("seq", "backend", "env")

# events above this count are dropped (with a tally) — a recorder left
# on across a long training run must not grow without bound
DEFAULT_MAX_EVENTS = 1_000_000


class FlightRecorder:
    """An ordered event log. The process-global instance is disabled by
    default; private instances (tests, trace scripts) are cheap."""

    __slots__ = ("enabled", "detail", "events", "max_events", "dropped",
                 "_seq")

    def __init__(self, enabled: bool = False, detail: bool = False,
                 max_events: int = DEFAULT_MAX_EVENTS):
        self.enabled = bool(enabled)
        self.detail = bool(detail)
        self.events: List[Dict[str, Any]] = []
        self.max_events = int(max_events)
        self.dropped = 0
        self._seq = 0

    def emit(self, kind: str, t: float, **fields) -> None:
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        event = {"seq": self._seq, "kind": kind, "t": float(t), **fields}
        self._seq += 1
        self.events.append(event)

    def extend(self, events: Iterable[Dict[str, Any]],
               env_index: Optional[int] = None) -> None:
        """Merge a foreign event list (a worker process's trace) —
        events keep their own ``seq``/``t`` and gain an ``env`` tag."""
        if not self.enabled:
            return
        for e in events:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                continue
            if env_index is not None:
                e = {**e, "env": int(env_index)}
            self.events.append(e)

    def drain(self) -> List[Dict[str, Any]]:
        out, self.events = self.events, []
        return out

    def reset(self) -> None:
        self.events = []
        self.dropped = 0
        self._seq = 0


_GLOBAL = FlightRecorder()


def recorder() -> FlightRecorder:
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL.enabled


def detail_enabled() -> bool:
    return _GLOBAL.enabled and _GLOBAL.detail


def enable(detail: bool = False,
           max_events: int = DEFAULT_MAX_EVENTS) -> FlightRecorder:
    _GLOBAL.detail = bool(detail)
    _GLOBAL.max_events = int(max_events)
    _GLOBAL.enabled = True
    return _GLOBAL


def disable() -> None:
    _GLOBAL.enabled = False


def emit(kind: str, t: float, **fields) -> None:
    """Gated append. Hot paths must still guard the CALL with
    ``if flight.enabled():`` so argument construction costs nothing when
    off (checked by scripts/check_flight_gated.py)."""
    _GLOBAL.emit(kind, t, **fields)


def extend(events: Iterable[Dict[str, Any]],
           env_index: Optional[int] = None) -> None:
    _GLOBAL.extend(events, env_index=env_index)


def events() -> List[Dict[str, Any]]:
    return list(_GLOBAL.events)


def drain() -> List[Dict[str, Any]]:
    return _GLOBAL.drain()


def reset() -> None:
    _GLOBAL.reset()


# ------------------------------------------------------------ persistence
def save_jsonl(path: str,
               evts: Optional[Sequence[Dict[str, Any]]] = None) -> int:
    """Write events as JSONL (``{"type": "flight", ...event}`` per line
    — the record shape scripts/telemetry_report.py summarises, so flight
    records can also ride inside a telemetry sink file). Returns the
    number of records written."""
    if evts is None:
        evts = _GLOBAL.events
    with open(path, "w") as f:
        for e in evts:
            f.write(json.dumps({"type": "flight", **e}) + "\n")
    return len(evts)


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    """Read flight events back from a JSONL file, tolerating interleaved
    non-flight telemetry records (span/event/snapshot lines are
    skipped)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("type") not in (None, "flight"):
                continue
            if "kind" not in rec or rec["kind"] not in EVENT_KINDS:
                continue
            rec.pop("type", None)
            out.append(rec)
    return out


# ---------------------------------------------------------------- diffing
def comparable_events(evts: Sequence[Dict[str, Any]],
                      kinds: Optional[Sequence[str]] = None,
                      include_detail: bool = False,
                      ignore_fields: Sequence[str] = DEFAULT_IGNORE_FIELDS
                      ) -> List[Dict[str, Any]]:
    """Canonicalise a trace for cross-backend comparison: filter to the
    requested kinds (default: everything non-detail) and strip the
    context-only fields."""
    drop = set(ignore_fields)
    keep_kinds = set(kinds) if kinds is not None else None
    out = []
    for e in evts:
        kind = e.get("kind")
        if keep_kinds is not None:
            if kind not in keep_kinds:
                continue
        elif not include_detail and kind in DETAIL_KINDS:
            continue
        out.append({k: v for k, v in e.items() if k not in drop})
    return out


def _values_equal(a: Any, b: Any, rtol: float) -> bool:
    if isinstance(a, (int, float)) and isinstance(b, (int, float)) \
            and not isinstance(a, bool) and not isinstance(b, bool):
        if a == b:
            return True
        if rtol <= 0.0:
            return False
        return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-300)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return (len(a) == len(b)
                and all(_values_equal(x, y, rtol) for x, y in zip(a, b)))
    return a == b


def first_divergence(a: Sequence[Dict[str, Any]],
                     b: Sequence[Dict[str, Any]],
                     rtol: float = 0.0) -> Optional[Dict[str, Any]]:
    """First index where two CANONICALISED traces disagree (run
    ``comparable_events`` first), or None when identical.

    ``rtol``: relative tolerance for float payload fields — 0.0 demands
    bit-exactness (host vs C++); the jitted-episode decision diff passes
    the parity tests' 1e-9 (tests/test_jax_episode.py)."""
    for i, (ea, eb) in enumerate(zip(a, b)):
        if ea.get("kind") != eb.get("kind"):
            return {"index": i, "reason": "kind", "a": ea, "b": eb,
                    "fields": []}
        keys_a, keys_b = set(ea), set(eb)
        diff_fields: List[Tuple[str, Any, Any]] = []
        for k in sorted(keys_a | keys_b):
            va, vb = ea.get(k), eb.get(k)
            if k not in ea or k not in eb or not _values_equal(va, vb,
                                                               rtol):
                diff_fields.append((k, va, vb))
        if diff_fields:
            return {"index": i, "reason": "field", "a": ea, "b": eb,
                    "fields": diff_fields}
    if len(a) != len(b):
        i = min(len(a), len(b))
        return {"index": i, "reason": "length",
                "a": a[i] if i < len(a) else None,
                "b": b[i] if i < len(b) else None, "fields": []}
    return None


def format_divergence(div: Optional[Dict[str, Any]],
                      label_a: str = "A", label_b: str = "B") -> str:
    """Human-readable one-stop report of a ``first_divergence`` result:
    the event index, kind + sim-time, and the payload diff with both
    sides' full context."""
    if div is None:
        return "traces identical"
    i = div["index"]
    if div["reason"] == "length":
        longer = label_a if div["a"] is not None else label_b
        extra = div["a"] if div["a"] is not None else div["b"]
        return (f"first divergence at event #{i}: {longer} has "
                f"{extra['kind']} @ t={extra['t']:.9g} where the other "
                f"trace ended\n  {longer}: {json.dumps(extra)}")
    ea, eb = div["a"], div["b"]
    if div["reason"] == "kind":
        return (f"first divergence at event #{i}: kind "
                f"{ea['kind']} @ t={ea['t']:.9g} ({label_a}) vs "
                f"{eb['kind']} @ t={eb['t']:.9g} ({label_b})\n"
                f"  {label_a}: {json.dumps(ea)}\n"
                f"  {label_b}: {json.dumps(eb)}")
    fields = ", ".join(f"{k}: {va!r} vs {vb!r}"
                       for k, va, vb in div["fields"])
    return (f"first divergence at event #{i}: {ea['kind']} @ "
            f"t={ea['t']:.9g} — {fields}\n"
            f"  {label_a}: {json.dumps(ea)}\n"
            f"  {label_b}: {json.dumps(eb)}")


# ---------------------------------------------------------------- summary
def _iter_labeled(evts: Sequence[Dict[str, Any]]):
    """(event, job_label) pairs. The label qualifies ``job_idx`` with the
    worker ``env`` tag (merged traces) and an episode generation — a
    ``job_arrived`` that re-sees an (env, job_idx) pair starts a new
    generation, because auto-reset episodes restart indices at 0 — so
    lifecycle accounting never conflates distinct jobs that happen to
    share an index. Single-episode single-env traces keep plain
    ``"<job_idx>"`` labels."""
    gen: Dict[Tuple[Any, int], int] = {}
    for e in evts:
        ji = e.get("job_idx")
        if ji is None:
            yield e, None
            continue
        key = (e.get("env"), int(ji))
        if e.get("kind") == "job_arrived":
            gen[key] = gen.get(key, -1) + 1
        label = str(ji) if key[0] is None else f"e{key[0]}:j{ji}"
        g = gen.get(key, 0)
        if g:
            label += f"#{g}"
        yield e, label


def summarize(evts: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Trace rollup for reports: events by kind, blocks by cause, and a
    per-job lifecycle table (arrival -> decision -> placement ->
    outcome) keyed by ``_iter_labeled`` job labels, in first-appearance
    order."""
    by_kind: Dict[str, int] = {}
    blocked_by_cause: Dict[str, int] = {}
    jobs: Dict[str, Dict[str, Any]] = {}

    t_max = 0.0
    for e, label in _iter_labeled(evts):
        kind = e.get("kind", "?")
        by_kind[kind] = by_kind.get(kind, 0) + 1
        t_max = max(t_max, float(e.get("t", 0.0)))
        if label is None:
            continue
        r = jobs.setdefault(label, {})
        if kind == "job_arrived":
            r["arrived"] = e["t"]
            r["model"] = e.get("model")
        elif kind == "action_decided":
            r["decided"] = e["t"]
            r["degree"] = e.get("degree")
        elif kind == "placed":
            r["placed"] = e["t"]
            r["n_workers"] = len(e.get("workers", ()))
        elif kind == "mounted":
            r["n_channels"] = len(e.get("channels", ()))
        elif kind == "lookahead":
            r["jct"] = e.get("jct")
            r["backend"] = e.get("backend")
        elif kind == "job_completed":
            r["completed"] = e["t"]
        elif kind == "job_blocked":
            r["blocked"] = e["t"]
            cause = str(e.get("cause", "?"))
            r["cause"] = cause
            blocked_by_cause[cause] = blocked_by_cause.get(cause, 0) + 1
    return {"n_events": len(evts), "t_end": t_max, "by_kind": by_kind,
            "blocked_by_cause": blocked_by_cause, "jobs": jobs}


# -------------------------------------------------------- Perfetto export
# simulated seconds -> Chrome-trace microseconds (sim time is the
# reference's abstract unit; the scale only sets zoom level)
_TRACE_US = 1e6

_PID_WORKERS = 1
_PID_CHANNELS = 2
_PID_EVENTS = 3


def to_perfetto(evts: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome-trace/Perfetto JSON for an episode trace: one row per
    worker (jobs as duration slices), one per channel (flow mounts),
    instant markers for arrivals/decisions/blocks, and a running-jobs
    counter track from the tick events. Open in ui.perfetto.dev or
    chrome://tracing — the same viewer as a jax profiler capture."""
    summary = summarize(evts)
    jobs = summary["jobs"]
    out: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": _PID_WORKERS,
         "args": {"name": "workers"}},
        {"name": "process_name", "ph": "M", "pid": _PID_CHANNELS,
         "args": {"name": "channels"}},
        {"name": "process_name", "ph": "M", "pid": _PID_EVENTS,
         "args": {"name": "episode events"}},
    ]

    worker_tid: Dict[Any, int] = {}
    channel_tid: Dict[Any, int] = {}

    def tid_for(table: Dict[Any, int], pid: int, key: Any) -> int:
        tid = table.get(key)
        if tid is None:
            tid = table[key] = len(table)
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": str(key)}})
        return tid

    # default end for jobs with no recorded outcome: the trace horizon
    horizon = summary["t_end"]

    for e, label in _iter_labeled(evts):
        kind = e.get("kind")
        ts = float(e.get("t", 0.0)) * _TRACE_US
        ji = e.get("job_idx")
        if kind == "placed":
            r = jobs.get(label, {})
            end = r.get("completed", r.get("blocked", horizon))
            dur = max(float(end) - float(e["t"]), 0.0) * _TRACE_US
            args = {"job": label, "degree": r.get("degree"),
                    "jct": r.get("jct"), "model": r.get("model")}
            for w in e.get("workers", ()):
                out.append({"name": f"job {label}", "cat": "job",
                            "ph": "X", "ts": ts, "dur": dur,
                            "pid": _PID_WORKERS,
                            "tid": tid_for(worker_tid, _PID_WORKERS, w),
                            "args": args})
        elif kind == "mounted":
            r = jobs.get(label, {})
            end = r.get("completed", r.get("blocked", horizon))
            dur = max(float(end) - float(e["t"]), 0.0) * _TRACE_US
            for c in e.get("channels", ()):
                out.append({"name": f"job {label} flows", "cat": "flow",
                            "ph": "X", "ts": ts, "dur": dur,
                            "pid": _PID_CHANNELS,
                            "tid": tid_for(channel_tid, _PID_CHANNELS,
                                           c),
                            "args": {"job": label}})
        elif kind == "action_decided":
            out.append({"name": f"decide {label} d={e.get('degree')}",
                        "cat": "decision", "ph": "i", "s": "g",
                        "ts": ts, "pid": _PID_EVENTS, "tid": 0,
                        "args": {k: e[k] for k in
                                 ("job_idx", "degree", "accepted",
                                  "cause", "jct") if k in e}})
        elif kind == "job_arrived":
            out.append({"name": f"arrive {label}", "cat": "arrival",
                        "ph": "i", "s": "g", "ts": ts,
                        "pid": _PID_EVENTS, "tid": 1,
                        "args": {"job_idx": ji,
                                 "model": e.get("model")}})
        elif kind == "job_blocked":
            out.append({"name": f"block {label}: {e.get('cause')}",
                        "cat": "block", "ph": "i", "s": "g", "ts": ts,
                        "pid": _PID_EVENTS, "tid": 2,
                        "args": {"job_idx": ji,
                                 "cause": e.get("cause")}})
        elif kind == "tick":
            out.append({"name": "jobs_running", "ph": "C", "ts": ts,
                        "pid": _PID_EVENTS,
                        "args": {"running": e.get("n_running", 0)}})
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": {"source": "ddls_tpu flight recorder",
                          "n_flight_events": len(evts)}}
