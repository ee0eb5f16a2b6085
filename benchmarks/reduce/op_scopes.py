"""Which ``jax.named_scope`` each device operation of a trace ran in.

What a v5e trace carries (read by hand, PR 23): an event on a device
plane's ``XLA Ops`` line has three stats of its own (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``); the HLO ``op_name``
of its instruction — the scope path, e.g.
``jit(epoch)/while/body/closed_call/vmap(sim_lookahead)/while/body/gather:``
— is the ``tf_op`` stat of the event's METADATA, one per instruction.
``jax.profiler.ProfileData`` shows an event's own stats only, so this
module reads the xplane file's wire format itself (no dependency: the
four message kinds it needs are a few varints and length-delimited
fields). It is NOT a second general reader beside
``xplane.load_planes``: it parses only device planes' ``XLA Ops`` and
``XLA Modules`` lines, event names and times, and the one metadata stat
named above, and skips every other field. A ``while`` event carries no ``tf_op`` (it CONTAINS its body's
events, which do); data-formatting copies and a few fusions the
compiler builds itself carry none either — they count as unscoped.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks.reduce import xplane

OP_NAME_STAT = "tf_op"


# ------------------------------------------------------- wire format
def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, start: int, end: int
            ) -> Iterator[Tuple[int, int, int, int]]:
    """(field number, wire type, value or start, end) of each field of
    the message in ``buf[start:end]``; for a length-delimited field the
    payload is ``buf[value:end]``."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, wire, value, pos
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, wire, pos, pos + size
            pos += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield number, wire, pos, pos + size
            pos += size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf: bytes, start: int, end: int) -> str:
    return buf[start:end].decode("utf-8", "replace")


@dataclasses.dataclass
class OpEvent:
    """One device event with its instruction's scope path ('' if the
    instruction carries none)."""
    name: str
    op_name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class DeviceOps:
    """One device plane: its program executions and its operations."""
    plane: str
    modules: List[OpEvent]
    ops: List[OpEvent]


def _map_entry(buf, start, end):
    key, value = 0, (start, start)
    for number, wire, a, b in _fields(buf, start, end):
        if number == 1 and wire == 0:
            key = a
        elif number == 2 and wire == 2:
            value = (a, b)
    return key, value


def _stat(buf, start, end):
    """(metadata id, str value or None, ref value or None)."""
    meta, text, ref = 0, None, None
    for number, wire, a, b in _fields(buf, start, end):
        if number == 1 and wire == 0:
            meta = a
        elif number == 5 and wire == 2:
            text = _text(buf, a, b)
        elif number == 7 and wire == 0:
            ref = a
    return meta, text, ref


def _event_metadata(buf, start, end):
    """(name, [(stat metadata id, str, ref)]) of one XEventMetadata."""
    name, stats = "", []
    for number, wire, a, b in _fields(buf, start, end):
        if number == 2 and wire == 2:
            name = _text(buf, a, b)
        elif number == 5 and wire == 2:
            stats.append(_stat(buf, a, b))
    return name, stats


def _line(buf, start, end):
    """(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])."""
    name, timestamp_ns, events = "", 0, []
    for number, wire, a, b in _fields(buf, start, end):
        if number == 2 and wire == 2:
            name = _text(buf, a, b)
        elif number == 3 and wire == 0:
            timestamp_ns = a
        elif number == 4 and wire == 2:
            meta = offset = duration = 0
            for n2, w2, a2, _ in _fields(buf, a, b):
                if w2 != 0:
                    continue
                if n2 == 1:
                    meta = a2
                elif n2 == 2:
                    offset = a2
                elif n2 == 3:
                    duration = a2
            events.append((meta, offset, duration))
    return name, timestamp_ns, events


def _device_plane(buf, start, end) -> Optional[DeviceOps]:
    name, lines, metadata, stat_names = "", [], {}, {}
    for number, wire, a, b in _fields(buf, start, end):
        if wire != 2:
            continue
        if number == 2:
            name = _text(buf, a, b)
            if not xplane.DEVICE_PLANE.match(name):
                return None   # host planes are most of a file: skip
        elif number == 3:
            lines.append((a, b))
        elif number == 4:
            key, (va, vb) = _map_entry(buf, a, b)
            metadata[key] = _event_metadata(buf, va, vb)
        elif number == 5:
            key, (va, vb) = _map_entry(buf, a, b)
            stat_names[key] = next(
                (_text(buf, sa, sb)
                 for n, w, sa, sb in _fields(buf, va, vb)
                 if n == 2 and w == 2), "")
    if not xplane.DEVICE_PLANE.match(name):
        return None
    op_names = {}
    for key, (_, stats) in metadata.items():
        for meta, text, ref in stats:
            if stat_names.get(meta) == OP_NAME_STAT:
                op_names[key] = (text if text is not None
                                 else stat_names.get(ref, ""))
    out = DeviceOps(name, [], [])
    for a, b in lines:
        line_name, timestamp_ns, events = _line(buf, a, b)
        if line_name == xplane.OPS_LINE:
            target = out.ops
        elif line_name == xplane.MODULES_LINE:
            target = out.modules
        else:
            continue
        for meta, offset_ps, duration_ps in events:
            start_ns = timestamp_ns + offset_ps / 1e3
            target.append(OpEvent(
                metadata.get(meta, ("", ()))[0], op_names.get(meta, ""),
                start_ns, start_ns + duration_ps / 1e3))
    return out


def load_device_ops(path: str) -> List[DeviceOps]:
    """The device planes of an xplane file (``.gz`` allowed), ordered
    by device number."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        buf = fh.read()
    planes = []
    for number, wire, a, b in _fields(buf, 0, len(buf)):
        if number == 1 and wire == 2:
            plane = _device_plane(buf, a, b)
            if plane is not None:
                planes.append(plane)
    return sorted(planes, key=lambda p: int(
        xplane.DEVICE_PLANE.match(p.plane).group(2)))


# ------------------------------------------------------------ scopes
def scope_pattern(scopes: Sequence[str]) -> "re.Pattern[str]":
    """Matches an ``op_name`` that has one of the scopes as a path
    segment, bare or wrapped by transformations
    (``vmap(sim_lookahead)``, ``transpose(jvp(ppo_update))``); a merged
    instruction lists its sources' paths with ``;`` between them."""
    names = "|".join(re.escape(s) for s in scopes)
    return re.compile(rf"(?:^|[/;])(?:\w+\()*(?:{names})\)*(?=[/;:]|$)")


def scoped_seconds(device: DeviceOps, program: str,
                   scopes: Sequence[str]
                   ) -> List[Tuple[float, float]]:
    """Per execution of the programs whose module name matches
    ``program``: (seconds in which an operation of one of the scopes
    ran, seconds of the execution). A container holds its body's
    events, so the scoped time is the UNION of the events' intervals,
    never their sum."""
    wanted = scope_pattern(scopes)
    rx = re.compile(program)
    hits = [(e.start_ns, e.end_ns) for e in device.ops
            if e.op_name and wanted.search(e.op_name)]
    out = []
    for module in device.modules:
        if not rx.search(module.name):
            continue
        inside = xplane.clip(xplane.union(hits), module.start_ns,
                             module.end_ns)
        out.append((xplane.total(inside) / 1e9,
                    (module.end_ns - module.start_ns) / 1e9))
    return out
