"""multihost-deterministic-gates: branches guarding collectives must be
process-consistent.

Multi-host rule (CLAUDE.md, train/loops.py): any branch that decides
whether a jitted sharded call or cross-process collective runs must take
the SAME direction on every process — deterministic gates only (epoch
counters, config values, shared-stream rng). A gate that reads the wall
clock, the process-global ``random`` state, ``os.environ``, or the
filesystem can desync processes, and a desynced collective is a hang,
not an error (Podracer-style fused loops die on exactly this — PAPERS.md
arXiv 2104.06272).

Mechanics: in ``train/`` modules, an ``if``/``while`` condition that
lexically guards a call whose name ends with one of the guarded-call
names (``train_step``, ``update``, ``process_allgather``,
``materialize_group``, ``psum``/``pmean``/``all_gather``) — including
guarding by early return — may not read ``time.*``, ``random.*``,
``np.random.*``, ``os.environ``/``os.getenv``/``os.path``, or call
``open``/``Path``. ``jax.random.*`` stays legal: it is a pure function
of an explicitly-managed key.
"""
from __future__ import annotations

import ast
from typing import List, Sequence, Tuple

from ddls_tpu.lint.core import Context, Finding, Rule, SourceFile

DEFAULT_GUARDED_CALLS = (
    "train_step", "update", "process_allgather", "materialize_group",
    "psum", "pmean", "all_gather", "all_reduce", "broadcast_one_to_all",
    # the fused epoch IS the sharded update (rl/fused.py): a gate that
    # desyncs which process dispatches it is the same hang as a desynced
    # train_step
    "fused_epoch",
)

#: generic method names that only count as guarded calls when the
#: receiver's dotted name mentions one of the listed qualifiers —
#: ``self.learner.update(...)`` is the sharded call, ``cfg.update(...)``
#: is a dict method
RECEIVER_QUALIFIED = {"update": ("learner",)}

#: dotted-name prefixes whose read inside a gate condition is
#: process-inconsistent (jax.random is NOT here: key-driven, shared)
BANNED_PREFIXES = (
    "time.", "random.", "np.random.", "numpy.random.",
    "os.environ", "os.getenv", "os.path", "os.listdir", "os.stat",
    "datetime.",
)
BANNED_CALLS = ("open", "input", "Path", "perf_counter")


def _banned_reads(test: ast.AST) -> List[str]:
    out = []
    for node in ast.walk(test):
        name = None
        if isinstance(node, (ast.Attribute, ast.Name)):
            parts = []
            cur = node
            while isinstance(cur, ast.Attribute):
                parts.append(cur.attr)
                cur = cur.value
            if isinstance(cur, ast.Name):
                parts.append(cur.id)
                name = ".".join(reversed(parts))
        if name:
            if any(name == p.rstrip(".") or name.startswith(p)
                   for p in BANNED_PREFIXES):
                out.append(name)
        if isinstance(node, ast.Call):
            callee = node.func
            if isinstance(callee, ast.Name) and callee.id in BANNED_CALLS:
                out.append(f"{callee.id}()")
    return sorted(set(out))


def _is_early_exit(stmts: Sequence[ast.stmt]) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


class MultihostGatesRule(Rule):
    id = "multihost-deterministic-gates"
    pointer = ("gates guarding a jitted sharded call or collective must "
               "be process-consistent: epoch counters, config, or "
               "shared-stream jax.random draws only (CLAUDE.md "
               "multi-host rules) — never wall clock, `random`, "
               "os.environ, or filesystem state")
    # train/ loops plus the fused epoch driver: its fused_epoch dispatch
    # is a collective-shaped decision too
    scope_dirs = ("ddls_tpu/train/", "ddls_tpu/rl/fused.py")

    def _guarded_calls(self, ctx: Context) -> Tuple[str, ...]:
        extra = tuple(ctx.config.rule(self.id).get("guarded_calls", ()))
        return DEFAULT_GUARDED_CALLS + extra

    def check_file(self, sf: SourceFile, ctx: Context) -> List[Finding]:
        if sf.tree is None:
            return []
        guarded_names = self._guarded_calls(ctx)
        findings: List[Finding] = []

        def collective_calls(node) -> List[ast.Call]:
            out = []
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    callee = sub.func
                    last = (callee.attr if isinstance(callee, ast.Attribute)
                            else callee.id if isinstance(callee, ast.Name)
                            else None)
                    if last not in guarded_names:
                        continue
                    qualifiers = RECEIVER_QUALIFIED.get(last)
                    if qualifiers is not None:
                        receiver = (ast.unparse(callee.value)
                                    if isinstance(callee, ast.Attribute)
                                    else "")
                        if not any(q in receiver for q in qualifiers):
                            continue
                    out.append(sub)
            return out

        def report(test: ast.AST, calls: List[ast.Call]) -> None:
            reads = _banned_reads(test)
            if not reads:
                return
            for call in calls:
                callee = call.func
                last = (callee.attr if isinstance(callee, ast.Attribute)
                        else getattr(callee, "id", "?"))
                findings.append(Finding(
                    self.id, sf.rel, call.lineno,
                    f"collective/sharded call {last}(...) is gated by a "
                    f"process-inconsistent condition (line {test.lineno} "
                    f"reads {', '.join(reads)}) — multi-host gates must "
                    "be deterministic"))

        def visit_block(stmts: Sequence[ast.stmt]) -> None:
            for i, stmt in enumerate(stmts):
                if isinstance(stmt, (ast.If, ast.While)):
                    body_calls = []
                    for s in stmt.body:
                        body_calls.extend(collective_calls(s))
                    orelse_calls = []
                    for s in getattr(stmt, "orelse", []):
                        orelse_calls.extend(collective_calls(s))
                    report(stmt.test, body_calls + orelse_calls)
                    # an early-exit `if` guards the REST of this block
                    # (the `if not ...: return` sync-gate idiom)
                    if (isinstance(stmt, ast.If)
                            and _is_early_exit(stmt.body)
                            and not stmt.orelse):
                        rest_calls = []
                        for s in stmts[i + 1:]:
                            rest_calls.extend(collective_calls(s))
                        report(stmt.test, rest_calls)
                    visit_block(stmt.body)
                    visit_block(getattr(stmt, "orelse", []))
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef,
                                       ast.ClassDef)):
                    visit_block(stmt.body)
                elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.With,
                                       ast.AsyncWith)):
                    visit_block(stmt.body)
                    visit_block(getattr(stmt, "orelse", []))
                elif isinstance(stmt, ast.Try):
                    visit_block(stmt.body)
                    for h in stmt.handlers:
                        visit_block(h.body)
                    visit_block(stmt.orelse)
                    visit_block(stmt.finalbody)
                elif isinstance(stmt, ast.Match):
                    for case in stmt.cases:
                        visit_block(case.body)

        visit_block(sf.tree.body)
        findings.sort(key=lambda f: f.line)
        return findings
