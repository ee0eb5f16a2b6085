"""JSONL event sink: one JSON object per line, append-only.

Three record types land here (all stamped with a wall-clock ``ts``):

* ``{"type": "span", "name": ..., "dur_s": ...}`` — one per completed
  span (written by ``Registry._record_span``);
* ``{"type": "event", "kind": ..., ...fields}`` — discrete occurrences
  (degraded-mode transitions, ring segment lifecycles);
* ``{"type": "snapshot", "data": {...}}`` — a full registry dump
  (``Registry.dump_snapshot``), the record ``scripts/telemetry_report.py``
  reads counters/histograms from.

Writes are line-buffered and lock-guarded so spans recorded off the main
thread (serve batches, background savers) interleave whole lines, and a
crash loses at most the current line.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict


class JsonlSink:
    def __init__(self, path: str):
        self.path = str(path)
        self._lock = threading.Lock()
        self._f = open(self.path, "a", buffering=1)

    def write(self, record: Dict[str, Any]) -> None:
        line = json.dumps({"ts": time.time(), **record},
                          default=_jsonable)
        with self._lock:
            self._f.write(line + "\n")

    def close(self) -> None:
        with self._lock:
            self._f.close()


def _jsonable(obj):
    """Last-resort coercion: telemetry must never crash the code it
    observes over an exotic field type (numpy scalars etc.)."""
    try:
        return float(obj)
    except (TypeError, ValueError):
        return repr(obj)
