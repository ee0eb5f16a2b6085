"""hot-path-transfer: pin the metrics-are-futures contract on the
collect->update path.

The pipelined epoch loop (train/loops.py, docs/perf_round6.md) keeps
learner metrics on device as ``LazyMetrics`` futures and drains them in
ONE batched fetch per sync boundary; one innocent ``float()``/``.item()``
/``np.asarray`` on the hot path re-pays a blocking device round
trip EVERY update (the CPU-actor transfer tax of arXiv 2012.04210).
This rule flags the *implicit* coercions — ``float(...)``, ``.item()``,
``np.asarray(...)`` — in the collect->update modules; explicit staging
(``jax.device_put``/``jax.device_get``) stays legal because explicitness
is exactly what the contract asks for, and ``train/metrics.py`` is the
one sanctioned home for scalar coercion (``as_float``/``LazyMetrics``).

Boundary functions (eval, W&B flatten, setup, the sequential-mode
contract) are allowlisted per function in
``[tool.ddls_lint.hot-path-transfer.allow]`` as ``"path::qualname" =
"why"`` — the written reason is mandatory and stale entries are lint
errors.
"""
from __future__ import annotations

import ast
import os
from typing import List

from ddls_tpu.lint.core import (Context, Finding, Rule, SourceFile,
                                dotted_name)

#: the collect->update path: the epoch loops, the rollout collectors,
#: and the fused epoch driver (whose in-program epoch makes an implicit
#: coercion doubly expensive: it would re-serialise the ONE dispatch per
#: epoch the fusion exists to amortise)
DEFAULT_MODULES = (
    "ddls_tpu/train/loops.py",
    "ddls_tpu/rl/rollout.py",
    "ddls_tpu/rl/ppo_device.py",
    "ddls_tpu/rl/shm.py",
    "ddls_tpu/rl/ring.py",
    "ddls_tpu/rl/fused.py",
    # the in-kernel lookahead memo rides the carried device state of
    # every collect; an implicit coercion here would fetch the table (or
    # its counters) EVERY decision step
    "ddls_tpu/sim/jax_memo.py",
)

_IMPLICIT_COERCIONS = {"np.asarray", "numpy.asarray"}


class HotPathTransferRule(Rule):
    id = "hot-path-transfer"
    pointer = ("metrics are FUTURES on the collect->update path: route "
               "scalar coercions through ddls_tpu/train/metrics.py "
               "(as_float / LazyMetrics) or make the transfer explicit "
               "(jax.device_get at a sync boundary); genuine boundary "
               "functions go in [tool.ddls_lint.hot-path-transfer.allow] "
               "as \"path::qualname\" = \"why\"")

    def _modules(self, ctx: Context):
        return tuple(ctx.config.rule(self.id).get("modules",
                                                  DEFAULT_MODULES))

    def in_scope(self, rel: str) -> bool:
        # scoping is a module LIST from config, which needs the Context —
        # check_file does the real filter
        return True

    def check_file(self, sf: SourceFile, ctx: Context) -> List[Finding]:
        if sf.tree is None:
            return []
        modules = self._modules(ctx)
        if sf.rel.startswith("ddls_tpu/") and sf.rel not in modules:
            return []
        allow = ctx.config.rule(self.id).get("allow", {})
        findings = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            label = None
            if isinstance(node.func, ast.Name) and node.func.id == "float":
                label = "float(...)"
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item" and not node.args):
                label = ".item()"
            elif dotted_name(node.func) in _IMPLICIT_COERCIONS:
                label = f"{dotted_name(node.func)}(...)"
            if label is None:
                continue
            qual = sf.enclosing_qualname(node.lineno)
            if qual is not None and f"{sf.rel}::{qual}" in allow:
                continue
            findings.append(Finding(
                self.id, sf.rel, node.lineno,
                f"implicit device->host coercion {label} on the "
                f"collect->update path"
                + (f" (in {qual})" if qual else " (module level)")))
        findings.sort(key=lambda f: f.line)
        return findings

    def check_tree(self, ctx: Context) -> List[Finding]:
        findings = self.validate_allow_keys(
            ctx, ctx.config.rule(self.id).get("allow", {}),
            want_qualname=True)
        for rel in ctx.config.rule(self.id).get("modules", ()):
            if not os.path.exists(os.path.join(ctx.repo_root, rel)):
                findings.append(Finding(
                    self.id, "pyproject.toml", 1,
                    f"stale [tool.ddls_lint.{self.id}] modules entry: "
                    f"{rel!r} does not exist"))
        return findings
