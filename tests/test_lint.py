"""Invariant lint engine self-tests (ISSUE 9, ddls_tpu/lint, docs/lint.md).

Per-rule fixture trees — one clean, one violating, one
suppressed-with-reason each — prove every rule fires on its target
pattern and every suppression path works; engine-level tests pin the
mandatory-reason contract, the stale-allowance guard (an unknown-file
allowance entry is itself a lint error), the parse-each-file-exactly-once
budget, and the tier-1 real-tree clean run that replaces the three
separate guard-script invocations with ONE engine call
(``python scripts/lint.py --json``). The legacy shim CLIs stay covered by
their original homes (tests/test_telemetry.py, test_flight.py,
test_shm.py)."""
import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ddls_tpu.lint import (ALL_RULES, Config, get_rules,  # noqa: E402
                           run_lint)

RULE_IDS = [r.id for r in ALL_RULES]


def lint_tree(tmp_path, files, rule, config=None):
    """Run ONE rule over a synthetic tree rooted (and repo-rooted) at
    ``tmp_path`` — rels in findings/config keys are then bare names."""
    for name, text in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return run_lint(roots=[str(tmp_path)], repo_root=str(tmp_path),
                    rules=get_rules([rule]),
                    config=Config(config or {}))


def errors_of(result, rule):
    return [f for f in result.errors if f.rule == rule]


# ------------------------------------------------------------ registry
def test_registry_has_all_ten_rules():
    assert RULE_IDS == [
        "bare-timers", "flight-gated", "shm-unlink", "socket-lifecycle",
        "hot-path-transfer", "multihost-deterministic-gates",
        "telemetry-gated", "flow-mask", "frozen-param-tree",
        "backend-surface-parity"]


def test_get_rules_rejects_unknown_id():
    with pytest.raises(ValueError, match="unknown lint rule"):
        get_rules(["bare-timers", "no-such-rule"])


# ---------------------------------------------------------- bare-timers
TIMER_BAD = ("import time\n"
             "t0 = time.perf_counter()\n"
             "dt = time.perf_counter() - t0\n")


def test_bare_timers_fires(tmp_path):
    # one finding PER occurrence beyond the allowance, each on its line
    res = lint_tree(tmp_path, {"hot.py": TIMER_BAD}, "bare-timers")
    found = errors_of(res, "bare-timers")
    assert [(f.rel, f.line) for f in found] == [("hot.py", 2),
                                               ("hot.py", 3)]
    assert "allowance 0" in found[0].message


def test_bare_timers_clean(tmp_path):
    res = lint_tree(tmp_path, {"ok.py": "import time\nx = time.time()\n"},
                    "bare-timers")
    assert res.errors == []


def test_bare_timers_suppressed_with_reason(tmp_path):
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(bare-timers) "
           "-- injected default clock, never reported\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers")
    assert res.errors == []
    (f,) = [f for f in res.findings if f.suppressed]
    assert f.suppress_reason == "injected default clock, never reported"


def test_bare_timers_config_allowance(tmp_path):
    res = lint_tree(tmp_path, {"hot.py": TIMER_BAD}, "bare-timers",
                    {"bare-timers": {"allow": {"hot.py": 2}}})
    assert res.errors == []


def test_bare_timers_inline_suppression_covers_only_its_line(tmp_path):
    # a suppressed occurrence must not green-light future bare timers
    # elsewhere in the file
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(bare-timers) "
           "-- injectable clock default\n"
           "t1 = time.perf_counter()\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers")
    (f,) = errors_of(res, "bare-timers")
    assert f.line == 3
    assert any(x.suppressed and x.line == 2 for x in res.findings)


def test_bare_timers_over_allowance_flags_every_line(tmp_path):
    # a count allowance has no line identity: when a NEW timer lands
    # BEFORE the audited occurrence, flagging a positional subset would
    # point at the audited line — every unsuppressed line is flagged
    src = ("import time\n"
           "t_new = time.perf_counter()\n"
           "t_audited = time.perf_counter()\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers",
                    {"bare-timers": {"allow": {"hot.py": 1}}})
    assert [f.line for f in errors_of(res, "bare-timers")] == [2, 3]


def test_bare_timers_config_and_inline_mix_is_error(tmp_path):
    # combined, an inline suppression could mask which occurrence is
    # new — the mechanisms are exclusive per file
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(bare-timers) "
           "-- injectable clock\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers",
                    {"bare-timers": {"allow": {"hot.py": 1}}})
    assert any("mixes" in f.message and "inline suppressions" in f.message
               for f in errors_of(res, "bare-timers"))


def test_bare_timers_non_int_allowance_is_config_error_not_crash(tmp_path):
    # a maintainer copying the hot-path-transfer "path" = "why" shape
    # must get a config finding, not a ValueError traceback
    res = lint_tree(tmp_path, {"hot.py": TIMER_BAD}, "bare-timers",
                    {"bare-timers": {"allow": {"hot.py": "clock param"}}})
    msgs = [f.message for f in errors_of(res, "bare-timers")]
    assert any("must be an integer occurrence count" in m for m in msgs)
    # and the malformed value grants nothing: the occurrences still fire
    assert any("bare perf_counter" in m for m in msgs)


def test_bare_timers_overgranted_allowance_is_stale(tmp_path):
    # an allowance above the file's actual count is green headroom for
    # NEW bare timers — flagged as stale, like a deleted-file entry
    res = lint_tree(tmp_path, {"hot.py": TIMER_BAD}, "bare-timers",
                    {"bare-timers": {"allow": {"hot.py": 5}}})
    (f,) = errors_of(res, "bare-timers")
    assert f.rel == "pyproject.toml"
    assert "stale" in f.message and "grants 5" in f.message


# --------------------------------------------------------- flight-gated
FLIGHT_BAD = ("from ddls_tpu.telemetry import flight as _flight\n"
              "def step(t):\n"
              "    _flight.emit('tick', t=t)\n"
              "    if _flight.enabled():\n"
              "        _flight.emit('ok', t=t)\n"
              "    _flight.enable()\n")


def test_flight_gated_fires(tmp_path):
    res = lint_tree(tmp_path, {"hot.py": FLIGHT_BAD}, "flight-gated")
    lines = [f.line for f in errors_of(res, "flight-gated")]
    assert lines == [3, 6]  # ungated emit + switch; gated emit clean


def test_flight_gated_clean(tmp_path):
    src = ("from ddls_tpu.telemetry import flight as _flight\n"
           "def step(t):\n"
           "    if _flight.enabled():\n"
           "        _flight.emit('tick', t=t)\n")
    res = lint_tree(tmp_path, {"ok.py": src}, "flight-gated")
    assert res.errors == []


def test_flight_gated_inverted_gate_is_not_a_guard(tmp_path):
    # `if not _flight.enabled():` runs its BODY when the recorder is
    # OFF — an emit there is exactly the violation; the ELSE branch is
    # the guarded side
    src = ("from ddls_tpu.telemetry import flight as _flight\n"
           "def step(t):\n"
           "    if not _flight.enabled():\n"
           "        _flight.emit('oops', t=t)\n"
           "    else:\n"
           "        _flight.emit('ok', t=t)\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "flight-gated")
    assert [f.line for f in errors_of(res, "flight-gated")] == [4]


def test_flight_gated_suppressed(tmp_path):
    src = ("from ddls_tpu.telemetry import flight as _flight\n"
           "_flight.emit('boot')  # ddls-lint: allow(flight-gated) "
           "-- module-import one-shot, not a hot path\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "flight-gated")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# ----------------------------------------------------------- shm-unlink
SHM_BAD = ("from multiprocessing import shared_memory\n"
           "seg = shared_memory.SharedMemory(create=True, size=64)\n")
SHM_GOOD = ("import weakref\n"
            "from multiprocessing import shared_memory\n"
            "seg = shared_memory.SharedMemory(create=True, size=64)\n"
            "weakref.finalize(seg, seg.unlink)\n"
            "seg.unlink()\n")


def test_shm_unlink_fires(tmp_path):
    res = lint_tree(tmp_path, {"leaky.py": SHM_BAD}, "shm-unlink")
    (f,) = errors_of(res, "shm-unlink")
    assert f.line == 2
    assert "unlink" in f.message and "finalizer" in f.message


def test_shm_unlink_clean(tmp_path):
    res = lint_tree(tmp_path, {"ok.py": SHM_GOOD}, "shm-unlink")
    assert res.errors == []


def test_shm_unlink_inline_suppression_covers_only_its_create(tmp_path):
    src = ("from multiprocessing import shared_memory\n"
           "a = shared_memory.SharedMemory(create=True, size=64)  "
           "# ddls-lint: allow(shm-unlink) -- tracker-owned scratch\n"
           "b = shared_memory.SharedMemory(create=True, size=64)\n")
    res = lint_tree(tmp_path, {"leaky.py": src}, "shm-unlink")
    (f,) = errors_of(res, "shm-unlink")
    assert f.line == 3


def test_shm_unlink_overgranted_allowance_is_stale(tmp_path):
    # allowance 2 covers the single create (no violation finding) but
    # the unused grant is itself stale; an exact grant stays clean
    res = lint_tree(tmp_path, {"leaky.py": SHM_BAD}, "shm-unlink",
                    {"shm-unlink": {"allow": {"leaky.py": 2}}})
    (f,) = errors_of(res, "shm-unlink")
    assert f.rel == "pyproject.toml"
    assert "stale" in f.message and "grants 2" in f.message
    res = lint_tree(tmp_path, {"leaky.py": SHM_BAD}, "shm-unlink",
                    {"shm-unlink": {"allow": {"leaky.py": 1}}})
    assert res.errors == []


def test_shm_unlink_multi_segment_triple(tmp_path):
    """ISSUE 15 fixture: a trajectory-ring-shaped file creating THREE
    segments must flag every create line when the pairing is missing,
    and go clean once the unlink + finalizer pair appears (one pairing
    covers all segments of a ring, as SlabSet does per segment)."""
    triple = ("from multiprocessing import shared_memory\n"
              "ring = [shared_memory.SharedMemory(create=True, size=64),\n"
              "        shared_memory.SharedMemory(create=True, size=64),\n"
              "        shared_memory.SharedMemory(create=True, size=64)]\n")
    res = lint_tree(tmp_path, {"ring.py": triple}, "shm-unlink")
    flagged = errors_of(res, "shm-unlink")
    assert [f.line for f in flagged] == [2, 3, 4]
    assert all("3 create(s)" in f.message for f in flagged)

    paired = (triple
              + "import weakref\n"
              + "for seg in ring:\n"
              + "    weakref.finalize(seg, seg.unlink)\n"
              + "    seg.unlink()\n")
    res = lint_tree(tmp_path, {"ring.py": paired}, "shm-unlink")
    assert res.errors == []


def test_shm_unlink_suppressed(tmp_path):
    src = ("from multiprocessing import shared_memory\n"
           "seg = shared_memory.SharedMemory(create=True, size=64)  "
           "# ddls-lint: allow(shm-unlink) -- tracker-owned scratch "
           "segment, unlinked by the resource tracker\n")
    res = lint_tree(tmp_path, {"scratch.py": src}, "shm-unlink")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# ------------------------------------------------------ socket-lifecycle
SOCK_BAD = ("import socket\n"
            "lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)\n"
            "conn, _ = lst.accept()\n")
SOCK_GOOD = ("import socket\n"
             "import weakref\n"
             "lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)\n"
             "conn, _ = lst.accept()\n"
             "weakref.finalize(lst, lst.close)\n"
             "conn.close()\n")


def test_socket_lifecycle_fires(tmp_path):
    # one finding per create site (socket() AND accept()), each on its
    # line, naming what is missing
    res = lint_tree(tmp_path, {"leaky.py": SOCK_BAD}, "socket-lifecycle")
    found = errors_of(res, "socket-lifecycle")
    assert [(f.rel, f.line) for f in found] == [("leaky.py", 2),
                                               ("leaky.py", 3)]
    assert all("close" in f.message and "finalizer" in f.message
               for f in found)


def test_socket_lifecycle_clean(tmp_path):
    res = lint_tree(tmp_path, {"ok.py": SOCK_GOOD}, "socket-lifecycle")
    assert res.errors == []


def test_socket_lifecycle_import_only_not_flagged(tmp_path):
    # `import socket` for gethostname() creates nothing (runlog.py)
    src = "import socket\nhost = socket.gethostname()\n"
    res = lint_tree(tmp_path, {"host.py": src}, "socket-lifecycle")
    assert res.findings == []


def test_socket_lifecycle_inline_suppression_covers_only_its_create(
        tmp_path):
    src = ("import socket\n"
           "a = socket.socket()  "
           "# ddls-lint: allow(socket-lifecycle) -- caller-owned fd\n"
           "b = socket.socket()\n")
    res = lint_tree(tmp_path, {"leaky.py": src}, "socket-lifecycle")
    (f,) = errors_of(res, "socket-lifecycle")
    assert f.line == 3
    assert any(x.suppressed and x.line == 2 for x in res.findings)


def test_socket_lifecycle_overgranted_allowance_is_stale(tmp_path):
    src = "import socket\ns = socket.socket()\n"
    res = lint_tree(tmp_path, {"leaky.py": src}, "socket-lifecycle",
                    {"socket-lifecycle": {"allow": {"leaky.py": 2}}})
    (f,) = errors_of(res, "socket-lifecycle")
    assert f.rel == "pyproject.toml"
    assert "stale" in f.message and "grants 2" in f.message
    res = lint_tree(tmp_path, {"leaky.py": src}, "socket-lifecycle",
                    {"socket-lifecycle": {"allow": {"leaky.py": 1}}})
    assert res.errors == []


# ---------------------------------------------------- hot-path-transfer
HOT_BAD = ("def drain(metrics):\n"
           "    return {k: float(v) for k, v in metrics.items()}\n"
           "def fetch(arr):\n"
           "    return arr.item()\n")


def test_hot_path_transfer_fires(tmp_path):
    res = lint_tree(tmp_path, {"loops.py": HOT_BAD}, "hot-path-transfer")
    msgs = [f.message for f in errors_of(res, "hot-path-transfer")]
    assert len(msgs) == 2
    assert any("float(...)" in m and "(in drain)" in m for m in msgs)
    assert any(".item()" in m and "(in fetch)" in m for m in msgs)


def test_hot_path_transfer_clean(tmp_path):
    src = ("import jax\n"
           "def drain(metrics):\n"
           "    return jax.device_get(metrics)\n")
    res = lint_tree(tmp_path, {"loops.py": src}, "hot-path-transfer")
    assert res.errors == []


def test_hot_path_transfer_suppressed(tmp_path):
    src = ("def drain(metrics):\n"
           "    return float(metrics)  # ddls-lint: "
           "allow(hot-path-transfer) -- eval boundary, one per epoch\n")
    res = lint_tree(tmp_path, {"loops.py": src}, "hot-path-transfer")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


def test_hot_path_transfer_qualname_allowance(tmp_path):
    cfg = {"hot-path-transfer": {
        "allow": {"loops.py::drain": "sanctioned sync boundary"}}}
    res = lint_tree(tmp_path, {"loops.py": HOT_BAD}, "hot-path-transfer",
                    cfg)
    # drain is allowlisted, fetch still fires
    msgs = [f.message for f in errors_of(res, "hot-path-transfer")]
    assert len(msgs) == 1 and "(in fetch)" in msgs[0]


def test_hot_path_transfer_stale_qualname_allowance_is_error(tmp_path):
    cfg = {"hot-path-transfer": {
        "allow": {"loops.py::gone": "was removed"}}}
    res = lint_tree(tmp_path, {"loops.py": HOT_BAD}, "hot-path-transfer",
                    cfg)
    assert any("no function 'gone'" in f.message
               for f in errors_of(res, "hot-path-transfer"))


def test_hot_path_transfer_fused_driver_shape(tmp_path):
    """PR 8: the fused driver's epoch body must stay coercion-free
    while its drain-boundary harvest (already-fetched numpy) is
    config-allowlisted — the exact pyproject shape rl/fused.py ships
    with."""
    src = ("import numpy as np\n"
           "class FusedEpochDriver:\n"
           "    def fused_epoch(self, state, rngs):\n"
           "        return float(state.step)\n"
           "    def harvest_episodes(self, ep):\n"
           "        return [int(x) for x in np.asarray(ep['done'])]\n")
    cfg = {"hot-path-transfer": {
        "allow": {"fused.py::FusedEpochDriver.harvest_episodes":
                  "records from the already-fetched host trace"}}}
    res = lint_tree(tmp_path, {"fused.py": src}, "hot-path-transfer",
                    cfg)
    msgs = [f.message for f in errors_of(res, "hot-path-transfer")]
    assert len(msgs) == 1
    assert "float(...)" in msgs[0] and "fused_epoch" in msgs[0]


# ------------------------------------------- multihost-deterministic-gates
GATE_BAD = ("import time\n"
            "def run(self, learner, x):\n"
            "    if time.time() % 2 > 1:\n"
            "        learner.train_step(x)\n")
GATE_EARLY_RETURN = ("import os\n"
                     "def run(self, learner, x):\n"
                     "    if os.environ.get('SKIP'):\n"
                     "        return\n"
                     "    learner.train_step(x)\n")


def test_multihost_gates_fires(tmp_path):
    res = lint_tree(tmp_path, {"loop.py": GATE_BAD},
                    "multihost-deterministic-gates")
    (f,) = errors_of(res, "multihost-deterministic-gates")
    assert f.line == 4 and "train_step" in f.message
    assert "time.time" in f.message


def test_multihost_gates_early_return_guard_fires(tmp_path):
    res = lint_tree(tmp_path, {"loop.py": GATE_EARLY_RETURN},
                    "multihost-deterministic-gates")
    (f,) = errors_of(res, "multihost-deterministic-gates")
    assert "os.environ" in f.message


def test_multihost_gates_clean_deterministic(tmp_path):
    src = ("import jax\n"
           "def run(self, learner, x, epoch, rng):\n"
           "    if epoch % self.sync_interval == 0:\n"
           "        learner.train_step(x)\n"
           "    if float(jax.random.uniform(rng)) < 0.5:\n"
           "        learner.update(x)\n")
    res = lint_tree(tmp_path, {"loop.py": src},
                    "multihost-deterministic-gates")
    assert res.errors == []


def test_multihost_gates_sees_inside_match_statements(tmp_path):
    src = ("import time\n"
           "def run(self, learner, x, mode):\n"
           "    match mode:\n"
           "        case 'fast':\n"
           "            if time.time() > self.deadline:\n"
           "                learner.train_step(x)\n")
    res = lint_tree(tmp_path, {"loop.py": src},
                    "multihost-deterministic-gates")
    (f,) = errors_of(res, "multihost-deterministic-gates")
    assert f.line == 6 and "train_step" in f.message


def test_multihost_gates_dict_update_is_not_a_collective(tmp_path):
    # `update` is receiver-qualified: cfg.update(...) is a dict method,
    # learner.update(...) is the sharded call
    src = ("import os\n"
           "def merge(self, cfg, overrides, learner, x):\n"
           "    if os.environ.get('WANDB_MODE'):\n"
           "        cfg.update(overrides)\n"
           "    if os.environ.get('FAST'):\n"
           "        self.learner.update(x)\n")
    res = lint_tree(tmp_path, {"loop.py": src},
                    "multihost-deterministic-gates")
    (f,) = errors_of(res, "multihost-deterministic-gates")
    assert f.line == 6 and "update" in f.message


def test_multihost_gates_covers_fused_epoch_calls(tmp_path):
    """PR 8 coverage: the fused epoch dispatch (rl/fused.py) is a
    guarded call — a nondeterministic gate around it is the same
    desynced-collective hang as one around train_step."""
    src = ("import time\n"
           "def run(self, state, rngs):\n"
           "    if time.time() > self.deadline:\n"
           "        self.fused.fused_epoch(state, rngs)\n")
    res = lint_tree(tmp_path, {"fused.py": src},
                    "multihost-deterministic-gates")
    (f,) = errors_of(res, "multihost-deterministic-gates")
    assert f.line == 4 and "fused_epoch" in f.message


def test_multihost_gates_fused_cached_config_gate_clean(tmp_path):
    # a gate that is a pure function of the loop's CONFIG (+ epoch
    # counters) is process-consistent — that shape must lint clean
    src = ("def run(self, state, rngs):\n"
           "    if self.fused_config.get('lanes') != 1:\n"
           "        self.fused.fused_epoch(state, rngs)\n"
           "    if self.epoch_counter % self.sync_interval == 0:\n"
           "        self.fused.fused_epoch(state, rngs)\n")
    res = lint_tree(tmp_path, {"fused.py": src},
                    "multihost-deterministic-gates")
    assert res.errors == []


def test_multihost_gates_fused_epoch_suppressed(tmp_path):
    src = ("import os\n"
           "def run(self, state, rngs):\n"
           "    if os.environ.get('FORCE_FUSED'):\n"
           "        self.fused.fused_epoch(state, rngs)  # ddls-lint: "
           "allow(multihost-deterministic-gates) -- single-process "
           "debug hook, fused rejects multi-host at build\n")
    res = lint_tree(tmp_path, {"fused.py": src},
                    "multihost-deterministic-gates")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


def test_rules_scope_covers_fused_driver():
    """The PR 8 scope extension itself: rl/fused.py is on the
    hot-path-transfer module list AND inside the multihost rule's
    scan scope (train/ alone no longer bounds the collective surface)."""
    from ddls_tpu.lint.rules.hot_path_transfer import DEFAULT_MODULES
    from ddls_tpu.lint.rules.multihost_gates import (
        DEFAULT_GUARDED_CALLS, MultihostGatesRule)

    assert "ddls_tpu/rl/fused.py" in DEFAULT_MODULES
    assert "fused_epoch" in DEFAULT_GUARDED_CALLS
    rule = MultihostGatesRule()
    assert rule.in_scope("ddls_tpu/rl/fused.py")
    assert rule.in_scope("ddls_tpu/train/loops.py")
    assert not rule.in_scope("ddls_tpu/rl/ppo.py")


def test_multihost_gates_suppressed(tmp_path):
    src = ("import time\n"
           "def run(self, learner, x):\n"
           "    if time.time() > self.deadline:\n"
           "        learner.train_step(x)  # ddls-lint: "
           "allow(multihost-deterministic-gates) -- single-process "
           "tool, never launched multi-host\n")
    res = lint_tree(tmp_path, {"loop.py": src},
                    "multihost-deterministic-gates")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# ------------------------------------------------------- telemetry-gated
TEL_BAD = ("from ddls_tpu import telemetry\n"
           "def step(sizes):\n"
           "    telemetry.inc('sim.bytes', sum(sizes))\n"
           "    telemetry.enable()\n")


def test_telemetry_gated_fires(tmp_path):
    res = lint_tree(tmp_path, {"hot.py": TEL_BAD}, "telemetry-gated")
    lines = [f.line for f in errors_of(res, "telemetry-gated")]
    assert lines == [3, 4]  # computed-arg inc + switch


def test_telemetry_gated_clean(tmp_path):
    src = ("from ddls_tpu import telemetry\n"
           "def step(n, sizes):\n"
           "    telemetry.inc('sim.steps', n)\n"  # trivial args: legal
           "    if telemetry.enabled():\n"
           "        telemetry.inc('sim.bytes', sum(sizes))\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "telemetry-gated")
    assert res.errors == []


def test_telemetry_gated_suppressed(tmp_path):
    src = ("from ddls_tpu import telemetry\n"
           "def close(self):\n"
           "    telemetry.inc('sim.final', self.a + self.b)  "
           "# ddls-lint: allow(telemetry-gated) -- close() runs once, "
           "not a hot path\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "telemetry-gated")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


def test_telemetry_gated_relative_import_fires(tmp_path):
    # `from .. import telemetry` (the natural in-package refactor of the
    # absolute import) must not silently disable gating enforcement
    src = ("from .. import telemetry\n"
           "def step(sizes):\n"
           "    telemetry.inc('sim.bytes', sum(sizes))\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "telemetry-gated")
    (f,) = errors_of(res, "telemetry-gated")
    assert f.line == 3


def test_telemetry_gated_dotted_import_fires(tmp_path):
    # unaliased `import ddls_tpu.telemetry` reaches the API through the
    # full dotted path — the call target is an Attribute chain, not a
    # bare Name, and must still be resolved
    src = ("import ddls_tpu.telemetry\n"
           "def step(x):\n"
           "    ddls_tpu.telemetry.inc('sim.' + str(x), 1)\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "telemetry-gated")
    (f,) = errors_of(res, "telemetry-gated")
    assert f.line == 3


# ------------------------------------------------------------ flow-mask
FLOW_BAD = ("def pack(size, src, dst):\n"
            "    is_flow = size > 0 and src != dst\n"
            "    return is_flow\n")


def test_flow_mask_fires(tmp_path):
    res = lint_tree(tmp_path, {"packer.py": FLOW_BAD}, "flow-mask")
    (f,) = errors_of(res, "flow-mask")
    assert f.line == 2 and "flow_mask_from_codes" in f.message


def test_flow_mask_fires_on_bitwise_chain(tmp_path):
    src = ("def pack(dep_size, sc_src, sc_dst, valid):\n"
           "    return valid & (dep_size > 0) & (sc_src != sc_dst)\n")
    res = lint_tree(tmp_path, {"packer.py": src}, "flow-mask")
    assert len(errors_of(res, "flow-mask")) == 1


def test_flow_mask_clean_in_defining_module_and_elsewhere(tmp_path):
    # the canonical helper's own body is exempt (defining_module) and a
    # non-flow `and` chain elsewhere does not match the fingerprint
    cfg = {"flow-mask": {"defining_module": "op_graph.py"}}
    res = lint_tree(tmp_path, {
        "op_graph.py": ("def flow_mask_from_codes(size, a, b):\n"
                        "    return (size > 0) & (a != b)\n"),
        "other.py": ("def ready(n, state):\n"
                     "    return n > 0 and state is None\n"),
    }, "flow-mask", cfg)
    assert res.errors == []


def test_flow_mask_suppressed(tmp_path):
    src = ("def traced(dep_size, sc_src, sc_dst):\n"
           "    return (dep_size > 0) & (sc_src != sc_dst)  "
           "# ddls-lint: allow(flow-mask) -- traced mirror, numpy "
           "helper cannot run under jit\n")
    res = lint_tree(tmp_path, {"kernel.py": src}, "flow-mask")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# ----------------------------------------------------- frozen-param-tree
NET_SRC = ("class Net:\n"
           "    def setup(self):\n"
           "        self.gnn = 1\n"
           "        self.logit_head = 2\n")


def test_frozen_param_tree_unregistered_class_fires(tmp_path):
    res = lint_tree(tmp_path, {"net.py": NET_SRC}, "frozen-param-tree")
    (f,) = errors_of(res, "frozen-param-tree")
    assert "no frozen-param-tree entry" in f.message


def test_frozen_param_tree_drift_fires(tmp_path):
    cfg = {"frozen-param-tree": {"classes": {
        "net.py::Net": ["gnn", "value_head"]}}}
    res = lint_tree(tmp_path, {"net.py": NET_SRC}, "frozen-param-tree",
                    cfg)
    (f,) = errors_of(res, "frozen-param-tree")
    assert "unexpected ['logit_head']" in f.message
    assert "missing ['value_head']" in f.message


def test_frozen_param_tree_clean(tmp_path):
    cfg = {"frozen-param-tree": {"classes": {
        "net.py::Net": ["gnn", "logit_head"]}}}
    res = lint_tree(tmp_path, {"net.py": NET_SRC}, "frozen-param-tree",
                    cfg)
    assert res.errors == []


def test_frozen_param_tree_stale_class_entry_is_error(tmp_path):
    cfg = {"frozen-param-tree": {"classes": {
        "net.py::Gone": ["gnn"]}}}
    res = lint_tree(tmp_path, {"net.py": NET_SRC}, "frozen-param-tree",
                    cfg)
    assert any("no class 'Gone'" in f.message
               for f in errors_of(res, "frozen-param-tree"))


def test_frozen_param_tree_suppressed(tmp_path):
    src = ("class Probe:\n"
           "    def setup(self):  # ddls-lint: allow(frozen-param-tree) "
           "-- test-only module, no shipped checkpoint\n"
           "        self.head = 1\n")
    res = lint_tree(tmp_path, {"probe.py": src}, "frozen-param-tree")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# partition-rule table cross-validation (same rule id, ISSUE 19): the
# fixture module is only PARSED — P/PartitionSpec need not resolve.
def _part_src(fsdp_rules):
    return (
        'FSDP_AXIS = "dp"\n'
        "CANONICAL_PARAM_PATHS = (\n"
        '    "gnn/Dense_0/kernel",\n'
        '    "gnn/Dense_0/bias",\n'
        '    "logit_head/Dense_0/kernel",\n'
        ")\n"
        'LARGE_KERNEL_PATHS = ("logit_head/Dense_0/kernel",)\n'
        "PARTITION_RULES = {\n"
        '    "replicated": ((r".*", P()),),\n'
        '    "fsdp": (\n'
        + fsdp_rules +
        "    ),\n"
        "}\n")


def test_partition_table_clean(tmp_path):
    src = _part_src(
        '        (r"Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n'
        '        (r"Dense_\\d+/bias$", P()),\n')
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    assert res.errors == []


def test_partition_table_stale_rule_fires(tmp_path):
    src = _part_src(
        '        (r"decoder/Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n'
        '        (r"Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n'
        '        (r"Dense_\\d+/bias$", P()),\n')
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    (f,) = errors_of(res, "frozen-param-tree")
    assert "matches no CANONICAL_PARAM_PATHS entry" in f.message
    assert "decoder" in f.message


def test_partition_table_uncovered_path_fires(tmp_path):
    # no bias rule: gnn/Dense_0/bias would raise in match_partition_rules
    src = _part_src(
        '        (r"Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n')
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    (f,) = errors_of(res, "frozen-param-tree")
    assert "covers no rule for canonical path 'gnn/Dense_0/bias'" \
        in f.message


def test_partition_table_unsharded_large_leaf_fires(tmp_path):
    # a replicate catch-all shadows the sharding rule for the big kernel
    src = _part_src(
        '        (r"kernel$", P()),\n'
        '        (r"Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n'
        '        (r"Dense_\\d+/bias$", P()),\n')
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    msgs = [f.message for f in errors_of(res, "frozen-param-tree")]
    assert any("first-matches the replicate rule" in m for m in msgs)


def test_partition_table_missing_canonical_paths_fires(tmp_path):
    src = 'PARTITION_RULES = {"replicated": ((r".*", P()),)}\n'
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    (f,) = errors_of(res, "frozen-param-tree")
    assert "cannot be cross-validated" in f.message


def test_partition_table_suppressed(tmp_path):
    src = _part_src(
        '        (r"decoder/.*", P(FSDP_AXIS, None)),  '
        "# ddls-lint: allow(frozen-param-tree) -- fixture stale rule\n"
        '        (r"Dense_\\d+/kernel$", P(FSDP_AXIS, None)),\n'
        '        (r"Dense_\\d+/bias$", P()),\n')
    res = lint_tree(tmp_path, {"partition.py": src}, "frozen-param-tree")
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


def test_partition_table_real_tree_clean():
    """The shipped rule table in ddls_tpu/parallel/partition.py passes
    its own cross-validation (and the canonical-path literal there stays
    in sync with the runtime tree — tests/test_partition.py pins that
    side)."""
    from ddls_tpu.lint import run_lint as _run
    res = _run(rules=get_rules(["frozen-param-tree"]))
    assert [f for f in res.errors
            if "partition" in f.rel.lower()] == []


# ------------------------------------------------ backend-surface-parity
def parity_files(jax_env_extra="", host_strings=("'queue_full'",
                                                 "'mounted'"),
                 ppo_extra="", harvest_keys=("'env_index'", "'ret'"),
                 host_key_fns=("lookahead_key_for",
                               "_assemble_lookahead_key"),
                 memo_surface=("'lookahead_key_for'",
                               "'_assemble_lookahead_key'"),
                 memo_trace_keys=("'memo_hits'",),
                 memo_extra="",
                 wide_probe=("'jax_lookahead'", "'skip'"),
                 lookahead_src=("def jax_lookahead(x, *, skip=None):\n"
                                "    pass\n"),
                 forward_call=("def run_lookahead(skip=None):\n"
                               "    jax_lookahead(1, skip=skip)\n"),
                 failure_map=("FAILURE_PREEMPT: 'worker_preempted', "
                              "FAILURE_STRAGGLE: 'channel_degraded'"),
                 flight_kinds=("'worker_preempted'",
                               "'channel_degraded'"),
                 host_emits=("'worker_preempted'",
                             "'channel_degraded'")):
    jax_env = (
        "CAUSE_QUEUE_FULL = 0\n"
        "CAUSE_MOUNTED = 1\n"
        "CAUSE_CODE_TO_STR = {CAUSE_QUEUE_FULL: 'queue_full', "
        "CAUSE_MOUNTED: 'mounted'}\n"
        + jax_env_extra +
        "def make_segment_fn():\n"
        "    trace = {'ep_ret': 0, 'action': 1, 'memo_hits': 2}\n"
        + forward_call)
    host = ("HOST_CAUSES = (" + ", ".join(host_strings) + ")\n"
            "HOST_EMITS = (" + ", ".join(host_emits) + ",)\n"
            + "".join(f"def {fn}():\n    pass\n" for fn in host_key_fns))
    ppo = ("def collect(trace):\n"
           "    r = trace['ep_ret']\n"
           + ppo_extra +
           "def _harvest_episodes(trace):\n"
           "    return [{" + ": 1, ".join(harvest_keys) + ": 2}]\n")
    rollout = ("def harvest_episode_record(env):\n"
               "    return {'env_index': 0, 'ret': 1.0}\n")
    memo = ("HOST_KEY_SURFACE = (" + ", ".join(memo_surface) + ",)\n"
            "MEMO_TRACE_KEYS = (" + ", ".join(memo_trace_keys) + ",)\n"
            "WIDE_PROBE_SURFACE = (" + ", ".join(wide_probe) + ",)\n"
            + memo_extra)
    failures = ("FAILURE_PREEMPT = 0\n"
                "FAILURE_STRAGGLE = 1\n"
                "FAILURE_KIND_TO_EVENT = {" + failure_map + "}\n")
    flight = "EVENT_KINDS = (" + ", ".join(flight_kinds) + ",)\n"
    return {"jax_env.py": jax_env, "cluster.py": host, "ppo.py": ppo,
            "rollout.py": rollout, "jax_memo.py": memo,
            "jax_lookahead.py": lookahead_src,
            "failures.py": failures, "flight.py": flight}


PARITY_CFG = {"backend-surface-parity": {
    "jax_env": "jax_env.py", "ppo_device": "ppo.py",
    "rollout": "rollout.py", "jax_memo": "jax_memo.py",
    "jax_lookahead": "jax_lookahead.py",
    "failures": "failures.py", "flight": "flight.py",
    "host_cause_files": ["cluster.py"],
    "jitted_only_causes": []}}


def test_backend_parity_clean(tmp_path):
    res = lint_tree(tmp_path, parity_files(), "backend-surface-parity",
                    PARITY_CFG)
    assert res.errors == []


def test_backend_parity_nonbijective_table_fires(tmp_path):
    files = parity_files(jax_env_extra="CAUSE_NEW = 2\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("not a bijection" in f.message and "CAUSE_NEW" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_missing_host_cause_fires(tmp_path):
    files = parity_files(host_strings=("'queue_full'",))  # no 'mounted'
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'mounted'" in f.message and "drifted" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_untraced_counter_fires(tmp_path):
    files = parity_files(ppo_extra="    b = trace['ep_blocked']\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'ep_blocked'" in f.message
               and "make_segment_fn does not trace" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_unknown_episode_key_fires(tmp_path):
    files = parity_files(harvest_keys=("'env_index'", "'novel_key'"))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'novel_key'" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_docstring_mention_does_not_mask_drift(tmp_path):
    # the host vocabulary is CODE strings only: a cause word surviving
    # in a docstring must not keep the drift check green
    files = parity_files(host_strings=("'queue_full'",))
    files["cluster.py"] = ('"""The mounted state is documented here."""\n'
                          + files["cluster.py"])
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'mounted'" in f.message and "drifted" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_missing_host_file_is_flagged(tmp_path):
    # a typo'd host_cause_files path must fail loudly, not silently
    # shrink the host vocabulary the causes are checked against
    files = parity_files()
    del files["cluster.py"]
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("cannot read 'cluster.py'" in f.message
               for f in errors_of(res, "backend-surface-parity"))
    # and the half-vocabulary drift compare is skipped (no noise)
    assert not any("drifted" in f.message
                   for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_memo_missing_host_key_builder_fires(tmp_path):
    # the memo mirrors the host memo-key builders (ISSUE 13): renaming
    # one in cluster.py without updating the in-kernel mirror must fail
    # at lint time, not at the first stale-memo debugging session
    files = parity_files(host_key_fns=("lookahead_key_for",))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'_assemble_lookahead_key'" in f.message
               and "host memo-key builders moved" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_memo_untraced_counter_key_fires(tmp_path):
    files = parity_files(memo_trace_keys=("'memo_hits'",
                                          "'memo_evictions'"))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'memo_evictions'" in f.message
               and "would not drain" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_memo_counter_via_emitter_is_clean(tmp_path):
    # the real tree's shape: make_segment_fn emits the counters through
    # jax_memo.memo_trace_counters (one naming home) — keys literal in
    # that helper count as traced
    files = parity_files(
        memo_trace_keys=("'memo_hits'", "'memo_misses'"),
        memo_extra=("def memo_trace_counters(memo):\n"
                    "    return {'memo_misses': memo}\n"))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert res.errors == []


def test_backend_parity_memo_surface_moved_fires(tmp_path):
    files = parity_files()
    files["jax_memo.py"] = "def memo_init():\n    pass\n"
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    msgs = [f.message for f in errors_of(res, "backend-surface-parity")]
    assert any("HOST_KEY_SURFACE" in m and "moved" in m for m in msgs)
    assert any("MEMO_TRACE_KEYS" in m and "moved" in m for m in msgs)


def test_backend_parity_wide_probe_missing_entry_fn_fires(tmp_path):
    # the batched probe's masking surface (ISSUE 17): renaming the
    # lookahead entry point without the memo mirror must fail at lint —
    # an unmasked probe is correct but inert, so no parity test catches
    # the drift
    files = parity_files(
        lookahead_src="def jax_lookahead_v2(x, *, skip=None):\n    pass\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'jax_lookahead'" in f.message
               and "entry point moved" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_wide_probe_missing_keyword_fires(tmp_path):
    files = parity_files(
        lookahead_src="def jax_lookahead(x):\n    pass\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'skip'" in f.message and "nothing to bind" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_wide_probe_not_forwarded_fires(tmp_path):
    files = parity_files(
        forward_call="def run_lookahead():\n    jax_lookahead(1)\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("forwards skip=" in f.message and "inert" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_wide_probe_surface_moved_fires(tmp_path):
    files = parity_files()
    files["jax_memo.py"] = (
        "HOST_KEY_SURFACE = ('lookahead_key_for', "
        "'_assemble_lookahead_key',)\n"
        "MEMO_TRACE_KEYS = ('memo_hits',)\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("WIDE_PROBE_SURFACE" in f.message and "moved" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_failure_map_nonbijective_fires(tmp_path):
    # a FAILURE_* kind code with no event mapping (ISSUE 16): adding a
    # failure kind without naming its flight event must fail at lint
    files = parity_files(
        failure_map="FAILURE_PREEMPT: 'worker_preempted'")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("FAILURE_KIND_TO_EVENT is not a bijection" in f.message
               and "FAILURE_STRAGGLE" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_failure_event_not_in_flight_kinds_fires(tmp_path):
    files = parity_files(flight_kinds=("'worker_preempted'",))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'channel_degraded'" in f.message
               and "EVENT_KINDS" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_failure_event_no_host_emission_fires(tmp_path):
    files = parity_files(host_emits=("'worker_preempted'",))
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("'channel_degraded'" in f.message
               and "no host emission site" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_missing_memo_file_is_flagged(tmp_path):
    files = parity_files()
    del files["jax_memo.py"]
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert any("cannot read 'jax_memo.py'" in f.message
               for f in errors_of(res, "backend-surface-parity"))


def test_backend_parity_suppressed(tmp_path):
    files = parity_files(host_strings=("'queue_full'",))
    files["jax_env.py"] = files["jax_env.py"].replace(
        "CAUSE_MOUNTED: 'mounted'}\n",
        "CAUSE_MOUNTED: 'mounted'}  # ddls-lint: "
        "allow(backend-surface-parity) -- fixture: host side pending\n")
    res = lint_tree(tmp_path, files, "backend-surface-parity",
                    PARITY_CFG)
    assert res.errors == []
    assert any(f.suppressed for f in res.findings)


# ----------------------------------------------- suppression / allowance
def test_suppression_without_reason_is_error(tmp_path):
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(bare-timers)\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers")
    rules = {f.rule for f in res.errors}
    # the bare allow() is rejected AND does not suppress the finding
    assert "lint-suppression" in rules and "bare-timers" in rules


def test_suppression_for_wrong_rule_does_not_suppress(tmp_path):
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(flow-mask) "
           "-- wrong rule id on purpose\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers")
    assert errors_of(res, "bare-timers")


def test_unknown_file_allowance_is_lint_error(tmp_path):
    cfg = {"bare-timers": {"allow": {"no/such/file.py": 1}}}
    res = lint_tree(tmp_path, {"ok.py": "x = 1\n"}, "bare-timers", cfg)
    (f,) = errors_of(res, "bare-timers")
    assert f.rel == "pyproject.toml"
    assert "stale" in f.message and "no/such/file.py" in f.message


def test_parse_error_is_reported(tmp_path):
    res = lint_tree(tmp_path, {"broken.py": "def f(:\n"}, "bare-timers")
    assert any(f.rule == "parse-error" for f in res.errors)


def test_unknown_suppression_rule_id_is_error_in_every_run(tmp_path):
    """A typo'd rule id suppresses nothing — flagged even by restricted
    (shim) runs, mirroring get_rules raising on unknown --rules ids."""
    src = ("import time\n"
           "t0 = time.perf_counter()  # ddls-lint: allow(baretimers) "
           "-- typo'd rule id\n")
    res = lint_tree(tmp_path, {"hot.py": src}, "shm-unlink")
    (f,) = res.errors
    assert f.rule == "lint-suppression"
    assert "unknown rule id 'baretimers'" in f.message
    # and the typo'd comment does not suppress the real finding
    res = lint_tree(tmp_path, {"hot.py": src}, "bare-timers")
    assert {f.rule for f in res.errors} == {"lint-suppression",
                                            "bare-timers"}


def test_restricted_run_skips_other_rules_bad_suppressions(tmp_path):
    """Shim parity: a single-rule run (the legacy-shim surface) must not
    fail on another rule's reasonless suppression — that finding belongs
    to the rule the comment names. A suppression naming NO rule is
    engine-level garbage and fails every run."""
    src = ("x = 1  # ddls-lint: allow(flow-mask)\n")
    res = lint_tree(tmp_path, {"mod.py": src}, "shm-unlink")
    assert res.errors == []
    res = lint_tree(tmp_path, {"mod.py": src}, "flow-mask")
    assert [f.rule for f in res.errors] == ["lint-suppression"]
    res = lint_tree(tmp_path, {"mod.py": "x = 1  # ddls-lint: allow()\n"},
                    "shm-unlink")
    assert [f.rule for f in res.errors] == ["lint-suppression"]


# ------------------------------------------------- whole-tree / tier-1
def expected_tree_files():
    # the default run's scan surface: ddls_tpu/ plus the bare-timers
    # rule's extra_roots ("scripts" — every other rule is gated off
    # those files, but they are parsed once like any other)
    out = []
    for root in ("ddls_tpu", "scripts"):
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(REPO, root)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
                rel = rel.replace(os.sep, "/")
                if fn.endswith(".py") and not rel.startswith(
                        "ddls_tpu/lint/"):
                    out.append(rel)
    return out


def test_real_tree_clean_one_engine_call_and_parse_once(monkeypatch):
    """THE tier-1 guard: one engine call covers what the three legacy
    script invocations covered (plus the six new rules), the tree is
    clean, every suppression carries a reason, and every file is parsed
    exactly ONCE for the full 9-rule run."""
    from ddls_tpu.lint import core

    parse_calls = []
    real_parse = ast.parse

    def counting_parse(source, *args, **kwargs):
        parse_calls.append(1)
        return real_parse(source, *args, **kwargs)

    monkeypatch.setattr(core.ast, "parse", counting_parse)
    result = run_lint(repo_root=REPO)
    assert result.errors == [], "\n".join(str(f) for f in result.errors)
    assert all(f.suppress_reason for f in result.findings if f.suppressed)
    # one ast.parse per tree file; the backend-parity cross-file reads
    # reuse the same cache (its targets all live under ddls_tpu/)
    assert len(parse_calls) == len(expected_tree_files())


def test_cli_json_real_tree():
    """`scripts/lint.py --json` over the real tree: rc 0, machine-
    readable findings with rule id, file, line, message, suppression
    state (the bench/report-tooling surface)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--json"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["returncode"] == 0
    assert payload["counts"]["errors"] == 0
    for f in payload["findings"]:
        assert {"rule", "file", "line", "message",
                "suppressed"} <= set(f)
        assert f["suppressed"] and f["suppress_reason"]


def test_cli_unknown_rule_id_fails_clean(tmp_path):
    """A typo'd --rules id fails loud but clean: rc 2, no traceback,
    and --json keeps its machine-readable contract."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--rules", "nosuchrule", "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert "unknown lint rule" in out.stdout
    assert "Traceback" not in out.stderr
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--rules", "nosuchrule", "--json", "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    payload = json.loads(out.stdout)
    assert payload["returncode"] == 2 and "unknown" in payload["error"]


def test_cli_rules_restriction(tmp_path):
    """--rules runs only the named rules (the shim surface): a tree that
    violates bare-timers passes a flow-mask-only run."""
    bad = tmp_path / "hot.py"
    bad.write_text(TIMER_BAD)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--rules", "flow-mask", "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--rules", "bare-timers", "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 1
    assert "hot.py" in out.stdout and "bare-timers" in out.stdout
