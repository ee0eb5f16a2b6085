"""Whole-tree invariant lint: every rule, one AST pass per file.

The single entry point for the contracts that used to live in CLAUDE.md
prose and three standalone checker scripts (ddls_tpu/lint/, docs/
lint.md): hot-path transfer discipline, multi-host deterministic gates,
telemetry/flight gating, the flow-mask predicate ban, frozen checkpoint
param-tree names, host<->jitted backend surface parity, bare timers and
shm unlink pairing.

Run: ``python scripts/lint.py`` (rc 0 clean, 1 flagged; tier-1 via
tests/test_lint.py). ``--json`` emits machine-readable findings (rule
id, file, line, message, suppression state) for report tooling;
``--rules a,b`` restricts the run; ``--paths`` scans alternate roots
(the self-tests use synthetic trees).

Allowlists live in ``[tool.ddls_lint]`` in pyproject.toml; inline
suppressions use the ``ddls-lint: allow(rule-id) -- <why>`` comment
syntax (the reason is mandatory — the example here omits the leading
hash so the engine's own scan of scripts/ does not parse it as a real
suppression). The legacy ``check_no_bare_timers.py`` /
``check_flight_gated.py`` / ``check_shm_unlink.py`` scripts are thin
shims over single rules of this engine.
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ddls_tpu.lint.engine import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(repo_root=REPO))
