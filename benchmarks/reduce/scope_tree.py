"""The device time of one jitted program as a TREE with self times.

    python3 -m benchmarks.reduce.scope_tree <xplane> [--program RX] [--top 5]

``reduce/op_scopes.py`` says which scope path each device operation
carries; ``scoped_seconds`` there is a flat union, which cannot read a
scope that ENCLOSES others. This module builds, per execution of the
program, the tree the ``XLA Ops`` line already is — events nest by time,
a ``while`` contains its body's events — and gives every node

* a PATH: its own ``tf_op``, or — a ``while``, a copy the compiler
  inserted — the longest common prefix, by path segment, of the named
  operations inside the smallest container that holds it (a container's
  own path is the common prefix of what IT holds; one that holds nothing
  named takes its container's). A top-level operation with no path keeps
  none. A path that does not start at the program (``jit(``) is a
  fragment the compiler kept of a longer one: it counts for the scopes it
  names, stands behind its container's prefix, and has no say in what
  others inherit;
* a SELF time: its interval less its children's.

The execution itself is the root node (no path; its self time is the
gaps between the top-level operations), so every picosecond of the
module is counted once: the self times sum to the module's duration.
An operation that starts inside its predecessor and ends after it (the
line does not nest there) is cut to the part after the predecessor's
end, counted (``overlaps``, ``overlap_ps``), never dropped.

The command prints the tree of ``ddls_tpu/telemetry/scopes.py:TREE``
for the execution of median duration: each scope's self seconds, own
and inherited apart, with its largest operations by HLO name.
"""
from __future__ import annotations

import argparse
import dataclasses
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.reduce import op_scopes, xplane

#: a path that starts here starts at the program; any other is a fragment
QUALIFIED = "jit("


@dataclasses.dataclass
class Execution:
    """One execution of the program. ``self_ps`` sums the nodes' self
    times by (path, inherited?, HLO instruction): the root node is
    ``("", False, "")``."""
    duration_ps: int
    events: int
    overlaps: int
    overlap_ps: int
    self_ps: Dict[Tuple[str, bool, str], int]

    def total_ps(self) -> int:
        return sum(self.self_ps.values())


def _ps(ns: float) -> int:
    return int(round(ns * 1000.0))


def _common(a: Optional[tuple], b: Optional[tuple]) -> Optional[tuple]:
    """Longest common prefix of two segment tuples; None = no vote."""
    if a is None or a is b:
        return b
    if b is None:
        return a
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return a[:n]


def _execution(start: int, end: int, ops: Sequence[tuple],
               segments: Dict[str, tuple]) -> Execution:
    """``ops``: (start_ps, end_ps, op_name, name) sorted by (start,
    -end), all starting inside [start, end)."""
    # node 0 is the execution; parents precede their children
    starts, ends, paths, names, parent = [start], [end], [""], [""], [0]
    overlaps = overlap_ps = 0
    stack = [0]
    for s, e, path, name in ops:
        e = min(e, end)
        while len(stack) > 1 and ends[stack[-1]] <= s:
            stack.pop()
        top = stack[-1]
        if e > ends[top]:
            # not nested: keep what lies behind the predecessor's end
            overlaps += 1
            overlap_ps += ends[top] - s
            s = ends[top]
            while len(stack) > 1 and ends[stack[-1]] <= s:
                stack.pop()
            e = min(e, ends[stack[-1]])
            top = stack[-1]
        if e <= s:
            continue
        starts.append(s)
        ends.append(e)
        paths.append(path)
        names.append(name)
        parent.append(top)
        stack.append(len(starts) - 1)
    n = len(starts)
    child_ps = [0] * n
    holds = [False] * n
    prefix: List[Optional[tuple]] = [None] * n
    for i in range(n - 1, 0, -1):       # children before their parents
        p = parent[i]
        child_ps[p] += ends[i] - starts[i]
        holds[p] = True
        path = paths[i]
        if path.startswith(QUALIFIED):
            own = segments.get(path)
            if own is None:
                own = segments[path] = tuple(path.split("/"))
            if not holds[i]:
                prefix[i] = own
        prefix[p] = _common(prefix[p], prefix[i])
    self_ps: Dict[Tuple[str, bool, str], int] = {}
    # what a container hands the pathless operations it holds: the
    # common prefix of its named ones, else its own path; the execution
    # hands none (a top-level operation has no container)
    handed = [""] * n
    for i in range(1, n):
        path, inherited, outer = paths[i], False, handed[parent[i]]
        within = "/".join(prefix[i]) if holds[i] and prefix[i] else ""
        if not path:
            path = within or outer
            inherited = bool(path)
        elif not path.startswith(QUALIFIED) and outer:
            path = outer + ";" + path
        if holds[i]:
            handed[i] = within or path
        own_ps = ends[i] - starts[i] - child_ps[i]
        if own_ps:
            key = (path, inherited, names[i])
            self_ps[key] = self_ps.get(key, 0) + own_ps
    if end - start - child_ps[0]:
        self_ps[("", False, "")] = end - start - child_ps[0]
    return Execution(end - start, n - 1, overlaps, overlap_ps, self_ps)


def executions(device: op_scopes.DeviceOps, program: str
               ) -> List[Execution]:
    """The tree of every execution of the programs whose module name
    matches ``program``, reduced to self times by path."""
    rx = re.compile(program)
    modules = sorted((_ps(m.start_ns), _ps(m.end_ns))
                     for m in device.modules if rx.search(m.name))
    if not modules:
        return []
    # a container before what it holds: by start, the longer first
    ops = sorted(((_ps(e.start_ns), _ps(e.end_ns), e.op_name, e.name)
                  for e in device.ops), key=lambda op: (op[0], -op[1]))
    out, segments, at = [], {}, 0
    for start, end in modules:
        while at < len(ops) and ops[at][0] < start:
            at += 1
        first = at
        while at < len(ops) and ops[at][0] < end:
            at += 1
        out.append(_execution(start, end, ops[first:at], segments))
    return out


class Holds:
    """Whether a path holds a scope as a segment (bare or wrapped by
    transformations, `op_scopes.scope_pattern`), remembered by path."""

    def __init__(self):
        self._patterns: Dict[tuple, "re.Pattern[str]"] = {}
        self._seen: Dict[tuple, bool] = {}

    def __call__(self, path: str, scopes: Sequence[str]) -> bool:
        scopes = tuple(scopes)
        if not scopes or not path:
            return False
        key = (path, scopes)
        if key not in self._seen:
            if scopes not in self._patterns:
                self._patterns[scopes] = op_scopes.scope_pattern(scopes)
            self._seen[key] = bool(self._patterns[scopes].search(path))
        return self._seen[key]


def _nodes(execution: Execution, scope: Optional[str],
           children: Sequence[str], holds: Holds):
    """((path, inherited, instruction), self ps) of the nodes whose
    path holds ``scope`` (None: the root, every path) and none of
    ``children``."""
    for key, ps in execution.self_ps.items():
        if ((scope is None or holds(key[0], (scope,)))
                and not holds(key[0], children)):
            yield key, ps


def self_seconds(execution: Execution, scope: Optional[str],
                 children: Sequence[str], inherited: str = "with",
                 pathless: bool = False, holds: Optional[Holds] = None
                 ) -> float:
    """Self time of the nodes whose path holds ``scope`` (None: the
    root, every path) and none of ``children``. ``inherited``: "with"
    counts the nodes that inherited their path too, "only" those alone.
    ``pathless``: only the nodes that ended under no path at all."""
    if inherited not in ("with", "only"):
        raise ValueError(f"inherited: {inherited!r}")
    return sum(
        ps for (path, inh, _), ps in _nodes(execution, scope, children,
                                            holds or Holds())
        if (inh or inherited == "with") and not (pathless and path)) / 1e12


def carried(execution: Execution, scopes: Sequence[str],
            holds: Optional[Holds] = None) -> List[bool]:
    """For each scope, whether some node's OWN path holds it."""
    holds = holds or Holds()
    own = {path for (path, inh, _) in execution.self_ps if not inh}
    return [any(holds(path, (scope,)) for path in own)
            for scope in scopes]


# ------------------------------------------------------------ command
def _report(execution: Execution, tree: Dict[Optional[str], tuple],
            top: int) -> List[str]:
    holds = Holds()
    lines = []

    def leaves(scope, children):
        rows: Dict[Tuple[str, bool], int] = {}
        for (_, inh, name), ps in _nodes(execution, scope, children,
                                         holds):
            key = (xplane.short_op_name(name) if name else "(gaps)", inh)
            rows[key] = rows.get(key, 0) + ps
        return sorted(rows.items(), key=lambda kv: -kv[1])[:top]

    def visit(scope, depth, seen):
        children = tree.get(scope, ())
        both = self_seconds(execution, scope, children, "with",
                            holds=holds)
        only = self_seconds(execution, scope, children, "only",
                            holds=holds)
        kind = "self" if scope in tree else "all"
        lines.append(f"{'  ' * depth}{scope or '(program)'}: {kind} "
                     f"{both:.6f} s = own {both - only:.6f} + inherited "
                     f"{only:.6f}")
        for (name, inh), ps in leaves(scope, children):
            lines.append(f"{'  ' * depth}    {ps / 1e12:.6f} s  {name}"
                         f"{'  (inherited)' if inh else ''}")
        for child in children:
            if (scope, child) not in seen:
                visit(child, depth + 1, seen | {(scope, child)})

    visit(None, 0, frozenset())
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("xplane")
    parser.add_argument("--program", default=r"^jit_epoch\(")
    parser.add_argument("--top", type=int, default=5)
    args = parser.parse_args(argv)
    from ddls_tpu.telemetry import scopes

    devices = op_scopes.load_device_ops(args.xplane)
    runs = executions(devices[0], args.program) if devices else []
    if not runs:
        print(f"no execution of {args.program} on a device plane")
        return 1
    run = sorted(runs, key=lambda r: r.duration_ps)[(len(runs) - 1) // 2]
    print(f"{len(runs)} execution(s) of {args.program}; the median one: "
          f"{run.duration_ps / 1e12:.6f} s, {run.events} events, "
          f"{run.overlaps} overlapping ({run.overlap_ps / 1e12:.6f} s "
          "cut)")
    print("\n".join(_report(run, scopes.TREE, args.top)))
    pathless = self_seconds(run, None, (), pathless=True)
    print(f"under no path (gaps included): {pathless:.6f} s = "
          f"{100 * pathless * 1e12 / run.duration_ps:.3f} % of the "
          "execution")
    print(f"sum of self times {run.total_ps() / 1e12:.6f} s; the "
          f"execution {run.duration_ps / 1e12:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
