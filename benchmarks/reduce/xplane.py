"""From a jax profiler trace (``*.xplane.pb``) to busy/idle time,
per-program device time, the top device operations and the idle gaps by
what the host was doing.

What a v5e trace holds (read by hand, PERF.md section 3): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per execution of a jitted program, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (one event per HLO operation; a ``while`` is one event that
CONTAINS its body's events, so durations on this line must be unioned,
never summed), ``Async XLA Ops`` (copies in flight — intervals of
waiting, not of work), and ``Steps``. The plane ``/host:CPU`` has one
line per host thread; ``jax.profiler.TraceAnnotation`` spans land on
the thread that opened them, on the same clock as the device lines.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
HOST_PLANE_PREFIX = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: every span the benchmark opens itself carries this prefix
BENCH_SPAN_PREFIX = "bench."
WINDOW_SPAN = BENCH_SPAN_PREFIX + "trace_window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


# ------------------------------------------------------------ intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals (overlapping,
    nested and touching ones merge; empty ones vanish)."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(end - start for start, end in intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of ``a`` that no interval of ``b`` covers (both are
    unioned first)."""
    out: List[Interval] = []
    cover = union(b)
    for start, end in union(a):
        cursor = start
        for bs, be in cover:
            if be <= cursor:
                continue
            if bs >= end:
                break
            if bs > cursor:
                out.append((cursor, bs))
            cursor = max(cursor, be)
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given the busy ones."""
    return subtract([(lo, hi)], busy) if hi > lo else []


# ---------------------------------------------------------------- trace
@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        return next((l for l in self.lines if l.name == name), None)


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` the profiler wrote under
    ``trace_dir`` (``plugins/profile/<time>/<host>.xplane.pb``)."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load_planes(path: str) -> List[Plane]:
    """Planes, lines and events of an xplane file (``.gz`` allowed)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    return [Plane(plane.name,
                  [Line(line.name,
                        [Event(e.name, float(e.start_ns),
                               float(e.start_ns) + float(e.duration_ns))
                         for e in line.events])
                   for line in plane.lines])
            for plane in data.planes]


def short_op_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``: the trace
    names a device operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%").strip() or name


class Trace:
    """One traced window, reduced on demand. ``window`` is the traced
    interval on the profiler's clock; when not given it is the
    benchmark's ``bench.trace_window`` host span, else the extent of
    the device events."""

    def __init__(self, planes: Sequence[Plane],
                 window: Optional[Interval] = None):
        self.planes = list(planes)
        self.devices = sorted(
            (p for p in self.planes if DEVICE_PLANE.match(p.name)),
            key=lambda p: int(DEVICE_PLANE.match(p.name).group(2)))
        self.hosts = [p for p in self.planes
                      if p.name.startswith(HOST_PLANE_PREFIX)]
        self.window = window or self._find_window()

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        return cls(load_planes(path))

    # -------------------------------------------------------- the window
    def host_spans(self, prefix: str = BENCH_SPAN_PREFIX) -> List[Event]:
        return sorted((e for plane in self.hosts for line in plane.lines
                       for e in line.events if e.name.startswith(prefix)),
                      key=lambda e: e.start_ns)

    def _device_events(self, line_name: str) -> List[List[Event]]:
        out = []
        for plane in self.devices:
            line = plane.line(line_name)
            out.append(line.events if line is not None else [])
        return out

    def _find_window(self) -> Optional[Interval]:
        spans = [e for e in self.host_spans() if e.name == WINDOW_SPAN]
        if spans:
            return (spans[0].start_ns, spans[-1].end_ns)
        events = [e for per_dev in self._device_events(OPS_LINE)
                  + self._device_events(MODULES_LINE) for e in per_dev]
        if not events:
            return None
        return (min(e.start_ns for e in events),
                max(e.end_ns for e in events))

    @property
    def window_s(self) -> float:
        return total([self.window]) / 1e9 if self.window else 0.0

    # ---------------------------------------------------------- busy/idle
    def busy_intervals(self) -> List[List[Interval]]:
        """Per device: the union of its operations' intervals inside
        the window."""
        if not self.window:
            return []
        lo, hi = self.window
        return [clip(union((e.start_ns, e.end_ns) for e in events), lo, hi)
                for events in self._device_events(OPS_LINE)]

    def busy_s_per_device(self) -> List[float]:
        return [total(b) / 1e9 for b in self.busy_intervals()]

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over devices."""
        per_dev = self.busy_s_per_device()
        return sum(per_dev) / len(per_dev) if per_dev else None

    def idle_share_per_device(self) -> List[float]:
        if not self.window_s:
            return []
        return [1.0 - b / self.window_s for b in self.busy_s_per_device()]

    # ----------------------------------------------------------- programs
    def program_durations_s(self, match: str) -> List[List[float]]:
        """Per device: the duration of every execution of the jitted
        programs whose module name matches the regex."""
        rx = re.compile(match)
        return [[e.duration_ns / 1e9 for e in events if rx.search(e.name)]
                for events in self._device_events(MODULES_LINE)]

    def program_names(self) -> Dict[str, float]:
        """Module name (fingerprint stripped) -> device seconds, summed
        over executions, averaged over devices."""
        out: Dict[str, float] = {}
        per_dev = self._device_events(MODULES_LINE)
        for events in per_dev:
            for e in events:
                name = re.sub(r"\(\d+\)$", "", e.name)
                out[name] = out.get(name, 0.0) + e.duration_ns / 1e9
        return {k: v / max(len(per_dev), 1) for k, v in out.items()}

    # ---------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The operations with most device time on the first device:
        name, summed seconds. A ``while`` holds its body's operations,
        so the list shows containers beside what they contain — read it
        top down, not as shares of one total."""
        per_dev = self._device_events(OPS_LINE)
        if not per_dev:
            return []
        sums: Dict[str, float] = {}
        for e in per_dev[0]:
            name = short_op_name(e.name)
            sums[name] = sums.get(name, 0.0) + e.duration_ns / 1e9
        return sorted(sums.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 5) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device inside the window,
        each named by the innermost benchmark host span open at the
        middle of the gap (``unattributed`` when none was)."""
        busy = self.busy_intervals()
        if not busy:
            return []
        lo, hi = self.window
        spans = self.host_spans()
        out = []
        for start, end in sorted(gaps(busy[0], lo, hi),
                                 key=lambda g: g[0] - g[1])[:n]:
            mid = (start + end) / 2.0
            open_spans = [s for s in spans
                          if s.start_ns <= mid < s.end_ns
                          and s.name != WINDOW_SPAN]
            name = (min(open_spans, key=lambda s: s.duration_ns).name
                    if open_spans else "unattributed")
            out.append((name[len(BENCH_SPAN_PREFIX):]
                        if name.startswith(BENCH_SPAN_PREFIX) else name,
                        (end - start) / 1e9))
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """All idle time of the first device inside the window, summed
        by the innermost benchmark span open at each gap's middle."""
        out: Dict[str, float] = {}
        for name, seconds in self.idle_gaps(n=10**9):
            out[name] = out.get(name, 0.0) + seconds
        return out

    # --------------------------------------------------------- collectives
    def collective_exposed_s_per_device(self) -> List[float]:
        """Per device: seconds inside the window in which a collective
        operation ran and no other operation did. Containers (``while``,
        ``conditional``, ``call``) are not work of their own and are
        left out of the compute cover."""
        if not self.window:
            return []
        lo, hi = self.window
        out = []
        for events in self._device_events(OPS_LINE):
            coll, compute = [], []
            for e in events:
                op = short_op_name(e.name)
                if COLLECTIVE.search(op):
                    coll.append((e.start_ns, e.end_ns))
                elif not re.match(r"(while|conditional|call)\b", op):
                    compute.append((e.start_ns, e.end_ns))
            out.append(total(clip(subtract(coll, compute), lo, hi)) / 1e9)
        return out


# ---------------------------------------------------- writing (test data)
def to_text_proto(planes: Sequence[Plane]) -> str:
    """An XSpace text proto of the planes (names and times only), for
    ``ProfileData.text_proto_to_serialized_xspace``: how the recorded
    test trace was trimmed and how the unit tests build small ones."""
    chunks = []
    for plane in planes:
        ids: Dict[str, int] = {}
        body = [f"  name: {_quote(plane.name)}"]
        for line_id, line in enumerate(plane.lines, start=1):
            body.append(f"  lines {{ id: {line_id} "
                        f"name: {_quote(line.name)} timestamp_ns: 0")
            for e in line.events:
                mid = ids.setdefault(e.name, len(ids) + 1)
                body.append(
                    f"    events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round(e.start_ns * 1000))} "
                    f"duration_ps: {int(round(e.duration_ns * 1000))} }}")
            body.append("  }")
        for name, mid in ids.items():
            body.append(f"  event_metadata {{ key: {mid} value {{ "
                        f"id: {mid} name: {_quote(name)} }} }}")
        chunks.append("planes {\n" + "\n".join(body) + "\n}")
    return "\n".join(chunks) + "\n"


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def write_xplane(planes: Sequence[Plane], path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(
        to_text_proto(planes))
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wb") as fh:
        fh.write(data)
