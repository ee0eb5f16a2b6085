"""Run ledger (ISSUE 18): a train/conformance/serve run can leave
one fingerprinted, diffable directory.

A :class:`RunLedger` owns a run directory holding:

* ``manifest.json`` — who/what/where: argv, the resolved config, scenario
  fingerprint, device/mesh topology (recorded ONLY if a jax backend is
  already initialized — the ledger must never force backend init in a
  host-only process), process index/count, git sha,
  and a ``clock`` block (paired ``unix``/``perf`` readings) that lets
  ``telemetry.timeline`` correlate multi-process runs by clock offset.
* ``telemetry.jsonl`` — the JSONL sink for the run's window: spans,
  events, transfer-ledger records, snapshots (see telemetry/sink.py).
* ``result.json`` — every result payload the run emitted (the train
  loop's final results, conformance's report doc).
* ``snapshot.json`` — the final registry snapshot plus named counter
  blocks (ring ledger stats, memo counters, fleet rollups).

The ledger is OPT-IN and composes with the existing telemetry window
discipline: ``open()`` saves the global registry's (enabled, sink) pair,
points the sink at the run directory, and ``finalize()`` restores both —
so a caller's own enable/restore window wraps it cleanly. Metrics are
NOT reset here; the caller owns the measurement window.

Hot-path contract: nothing here is ever called per step — ``open`` /
``record_result`` / ``add_block`` / ``finalize`` run at run boundaries
only.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Sequence

from ddls_tpu import telemetry
from ddls_tpu.telemetry.sink import JsonlSink

MANIFEST_NAME = "manifest.json"
SINK_NAME = "telemetry.jsonl"
RESULT_NAME = "result.json"
SNAPSHOT_NAME = "snapshot.json"


def _git_sha(repo_dir: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Best-effort git identity; never raises (a run outside a checkout
    still gets a manifest)."""
    try:
        cwd = repo_dir or os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=cwd, capture_output=True,
            text=True, timeout=10)
        if sha.returncode != 0:
            return None
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=cwd,
            capture_output=True, text=True, timeout=10)
        return {"sha": sha.stdout.strip(),
                "dirty": bool(dirty.stdout.strip())
                if dirty.returncode == 0 else None}
    except Exception:
        return None


def _device_summary() -> Optional[Dict[str, Any]]:
    """Topology of an ALREADY-initialized jax backend; None otherwise.
    Never triggers backend init: a manifest is also written by
    host-only processes (conformance), and
    ``jax.devices()`` on a cold process would open the default backend
    — an accelerator that may belong to another process."""
    from ddls_tpu.utils.runtime import jax_process_state

    if not jax_process_state()["backends"]:
        return None
    jax = sys.modules["jax"]
    try:
        devs = jax.devices()
        return {
            "count": len(devs),
            "local_count": jax.local_device_count(),
            "platform": devs[0].platform if devs else None,
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "kinds": sorted({getattr(d, "device_kind", "?")
                             for d in devs}),
        }
    except Exception:
        return None


def _jsonable(obj):
    if hasattr(obj, "tolist"):
        return obj.tolist()
    try:
        return float(obj)
    except (TypeError, ValueError):
        return repr(obj)


def _write_json(path: str, doc: Any) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=_jsonable)
        f.write("\n")
    os.replace(tmp, path)


class RunLedger:
    """One run's correlated artifact directory (module docstring has the
    file layout). Lifecycle: construct → ``open()`` (mkdir + manifest +
    telemetry sink swap) → work → ``record_result``/``add_block`` →
    ``finalize()`` (snapshot + restore). ``open``/``finalize`` are
    idempotent; a ledger that is never opened is inert."""

    def __init__(self, run_dir: str, kind: str,
                 argv: Optional[Sequence[str]] = None,
                 config: Optional[Dict[str, Any]] = None,
                 scenario_fingerprint: Optional[str] = None,
                 process_index: int = 0, process_count: int = 1,
                 extra: Optional[Dict[str, Any]] = None,
                 enable_telemetry: bool = True):
        self.run_dir = str(run_dir)
        self.kind = str(kind)
        self.argv = list(argv if argv is not None else sys.argv)
        self.config = dict(config or {})
        self.scenario_fingerprint = scenario_fingerprint
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.extra = dict(extra or {})
        self.enable_telemetry = bool(enable_telemetry)
        self._opened = False
        self._finalized = False
        self._results: list = []
        self._blocks: Dict[str, Any] = {}
        self._own_sink: Optional[JsonlSink] = None
        self._prior: Optional[tuple] = None  # (enabled, sink)

    # ------------------------------------------------------------- paths
    def path(self, name: str) -> str:
        return os.path.join(self.run_dir, name)

    @property
    def manifest_path(self) -> str:
        return self.path(MANIFEST_NAME)

    @property
    def sink_path(self) -> str:
        return self.path(SINK_NAME)

    # --------------------------------------------------------- lifecycle
    def open(self) -> "RunLedger":
        if self._opened:
            return self
        os.makedirs(self.run_dir, exist_ok=True)
        manifest = {
            "kind": self.kind,
            "argv": self.argv,
            "config": self.config,
            "scenario_fingerprint": self.scenario_fingerprint,
            "process": {"index": self.process_index,
                        "count": self.process_count},
            # paired clock readings: sink ``ts`` stamps are unix
            # wall-clock; registry spans/intervals use the perf clock —
            # the offset (unix - perf) aligns both per process, and
            # unix itself aligns processes on one host
            "clock": {"unix": time.time(),
                      "perf": time.perf_counter()},
            "host": {"hostname": socket.gethostname(),
                     "pid": os.getpid(),
                     "platform": sys.platform,
                     "python": sys.version.split()[0]},
            "git": _git_sha(),
            "devices": _device_summary(),
        }
        if self.extra:
            manifest["extra"] = self.extra
        _write_json(self.manifest_path, manifest)
        if self.enable_telemetry:
            reg = telemetry.registry()
            self._prior = (reg.enabled, reg.sink)
            self._own_sink = JsonlSink(self.sink_path)
            reg.sink = self._own_sink
            telemetry.enable(record_intervals=True)
        self._opened = True
        return self

    def update_config(self, fields: Dict[str, Any]) -> None:
        """Merge resolved-config fields in; if the manifest is already
        on disk (the caller opened early to capture the whole telemetry
        window) it is rewritten with the merged config."""
        self.config.update(fields)
        if self._opened and os.path.exists(self.manifest_path):
            try:
                with open(self.manifest_path) as f:
                    manifest = json.load(f)
            except Exception:
                return
            manifest["config"] = self.config
            _write_json(self.manifest_path, manifest)

    def record_result(self, payload: Dict[str, Any]) -> None:
        """Append one result payload and rewrite ``result.json`` —
        called at reporting boundaries only."""
        if not self._opened:
            return
        self._results.append(payload)
        _write_json(self.path(RESULT_NAME), {"results": self._results})

    def add_block(self, name: str, data: Any) -> None:
        """Attach a named counter block (ring ``stats()``, memo
        counters, fleet rollup) for ``snapshot.json``."""
        if data is not None:
            self._blocks[str(name)] = data

    def finalize(self, blocks: Optional[Dict[str, Any]] = None) -> None:
        """Write ``snapshot.json`` (final registry snapshot + blocks),
        close the run's sink, and restore the prior telemetry state."""
        if not self._opened or self._finalized:
            return
        self._finalized = True
        for k, v in (blocks or {}).items():
            self.add_block(k, v)
        reg = telemetry.registry()
        doc = {"snapshot": reg.snapshot()}
        if self._blocks:
            doc["blocks"] = self._blocks
        intervals = reg.span_intervals()
        if intervals:
            # perf-clock intervals; timeline aligns them via the
            # manifest clock offset (sink records are already unix)
            doc["span_intervals"] = [
                [n, t0, t1] for n, t0, t1 in intervals]
        _write_json(self.path(SNAPSHOT_NAME), doc)
        if self.enable_telemetry and self._prior is not None:
            prior_enabled, prior_sink = self._prior
            reg.sink = prior_sink
            reg.enabled = prior_enabled
            self._prior = None
        if self._own_sink is not None:
            self._own_sink.close()
            self._own_sink = None


def load_run_dir(run_dir: str) -> Dict[str, Any]:
    """Read a ledger directory back: manifest + sink records + snapshot
    + results (missing pieces → absent keys; a half-written run must
    still load for the timeline/report tools)."""
    out: Dict[str, Any] = {"run_dir": str(run_dir)}
    man = os.path.join(run_dir, MANIFEST_NAME)
    if os.path.exists(man):
        with open(man) as f:
            out["manifest"] = json.load(f)
    snap = os.path.join(run_dir, SNAPSHOT_NAME)
    if os.path.exists(snap):
        with open(snap) as f:
            out["snapshot"] = json.load(f)
    res = os.path.join(run_dir, RESULT_NAME)
    if os.path.exists(res):
        with open(res) as f:
            out["results"] = json.load(f).get("results", [])
    sink = os.path.join(run_dir, SINK_NAME)
    records = []
    if os.path.exists(sink):
        with open(sink) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn final line of a crashed run
    out["records"] = records
    return out
