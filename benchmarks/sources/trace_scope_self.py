"""{"kind": "trace_scope_self", "program": regex, "scope": name (absent
= the root: the program itself), "children": [names], "inherited":
"with" | "only" (optional, "with"), "pathless": true (optional),
"stat": "median", "share": "of_program" (optional)}: SELF device
seconds per execution of the matching jitted program of the scope —
the time of the tree's nodes (``reduce/scope_tree.py``: the ``XLA Ops``
line nested by time, a node's self time its interval less its
children's) whose path holds ``scope`` and none of ``children``,
reduced over the executions in the trace. What ``trace_scope_time``
cannot say: it is a flat union, so a scope that ENCLOSES others reads
as all of them.

A node with no path of its own (a ``while``, a copy the compiler
inserted) inherits one from what its container holds: ``"inherited":
"only"`` reads those nodes alone — the time under a scope that a union
of named operations does not see. ``"pathless": true`` keeps only the
nodes that ended under no path at all (the gaps between top-level
operations among them). With ``share`` the value is that time over the
execution's device time.

The trace is read once a run (``trace_scope_time.device_ops``), the
tree built once a program. None without a trace; None where no
operation of the program carries ``scope`` — and, for the root, where
one of ``children`` is carried by none: a program that was never given
the tree (a parent's, or a stale executable out of a compile cache that
keys on the program without its names), which is not the same as a
scope that took no time."""
from benchmarks.reduce import scope_tree
from benchmarks.sources import reduce_values, trace_scope_time


def trees(ctx, program):
    """(executions of ``program``, the run's one path matcher)."""
    cache = ctx.setdefault("scope_trees", {"holds": scope_tree.Holds()})
    if program not in cache:
        device = trace_scope_time.device_ops(ctx)
        cache[program] = ([] if device is None
                          else scope_tree.executions(device, program))
    return cache[program], cache["holds"]


def read(source, ctx):
    if ctx.get("trace") is None:
        return None
    runs, holds = trees(ctx, source["program"])
    if not runs:
        return None
    scope, children = source.get("scope"), source.get("children", ())
    must = (scope,) if scope is not None else tuple(children)
    values = []
    for run in runs:
        if not all(scope_tree.carried(run, must, holds)):
            continue
        seconds = scope_tree.self_seconds(
            run, scope, children, source.get("inherited", "with"),
            bool(source.get("pathless")), holds)
        if source.get("share") == "of_program":
            seconds = seconds * 1e12 / run.duration_ps
        elif source.get("share") is not None:
            raise ValueError(f"share: {source['share']!r}")
        values.append(seconds)
    return reduce_values(values, source.get("stat", "median"))
