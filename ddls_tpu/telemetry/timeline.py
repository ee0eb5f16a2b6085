"""Unified run timeline (ISSUE 18): merge one or more RunLedger
directories into ONE Perfetto/Chrome trace.

What lands on the timeline (``python -m ddls_tpu.telemetry.timeline
<run_dir> [<run_dir> ...] -o trace.json``, or ``scripts/
telemetry_report.py --timeline``):

* **Span tracks** — every sink ``span`` record becomes a duration slice
  on a per-name thread track; sink ``ts`` stamps are unix wall-clock at
  span END, so the slice is ``(ts - dur_s, ts)`` and multiple processes
  on one host align with no extra bookkeeping (each run dir gets its
  own pid; the manifest ``clock`` block carries the unix/perf offset
  for any perf-clock data).
* **Ring segment lifecycles** — the ring ledger's gated
  ``ring_segment`` events render as async lease→release slices per
  segment (publish as an instant inside, stalls as flagged instants on
  the stall track): the lease→publish→release ownership story from
  docs/perf_round10.md, now visible per run.
* **Cross-mesh hops** — transfer-ledger records (``sebulba.params``,
  ``sebulba.rngs``, ``stage.traj``, drain fetches) become slices with
  byte sizes in args plus Perfetto flow arrows from the hop's dispatch
  track to its destination track, so dispatch amortization is visible
  as arrow density.
* **Counter tracks** — memo hit-rate (``memo_counters`` drain events)
  and ``params_age_updates`` (ring consume events) as ph "C" counters.
* **Optional device trace** — any ``jax.profiler`` capture under the
  run dir (``plugins/profile/*/*.trace.json.gz``) is folded in with a
  remapped pid, tying XLA device timelines to the same wall of spans.

This supersedes the sim-only ``scripts/trace_export.py`` view (flight
events remain exportable there; a flight JSONL passed as a run dir file
is out of scope here).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
from typing import Any, Dict, List, Optional, Sequence

from ddls_tpu.telemetry.runlog import load_run_dir

_US = 1e6  # chrome trace timestamps are microseconds

# direction → destination track label for the flow-arrow endpoint
_DIRECTION_DEST = {
    "h2d": "device",
    "d2h": "host",
    "l2a": "actor mesh",
    "a2l": "learner mesh",
    "d2d": "device",
    # fragment frames between the learner and its actor-host processes
    # (rl/fragments.py): host memory on both ends, the wire in between
    "h2h": "remote host",
}


def _meta(pid: int, name: str, tid: Optional[int] = None) -> Dict[str, Any]:
    ev: Dict[str, Any] = {
        "name": "process_name" if tid is None else "thread_name",
        "ph": "M", "pid": pid,
        "args": {"name": name},
    }
    if tid is not None:
        ev["tid"] = tid
    return ev


class _Tids:
    """Stable per-process thread-track ids, metadata emitted once."""

    def __init__(self, pid: int, events: List[Dict[str, Any]]):
        self.pid = pid
        self.events = events
        self._ids: Dict[str, int] = {}

    def __call__(self, name: str) -> int:
        tid = self._ids.get(name)
        if tid is None:
            tid = self._ids[name] = len(self._ids) + 1
            self.events.append(_meta(self.pid, name, tid))
        return tid


def build_trace(runs: Sequence[Dict[str, Any]],
                include_device_trace: bool = True) -> Dict[str, Any]:
    """``runs`` are ``load_run_dir`` dicts; returns the Chrome trace
    document (``traceEvents`` + ``otherData``)."""
    events: List[Dict[str, Any]] = []
    # global unix origin so multi-run traces share one axis
    t0 = None
    for run in runs:
        for rec in run.get("records", ()):
            ts = rec.get("ts")
            if ts is not None:
                start = ts - float(rec.get("dur_s") or 0.0)
                t0 = start if t0 is None else min(t0, start)
        man_clock = (run.get("manifest") or {}).get("clock") or {}
        if man_clock.get("unix") is not None:
            t0 = (man_clock["unix"] if t0 is None
                  else min(t0, man_clock["unix"]))
    if t0 is None:
        t0 = 0.0

    def us(ts_unix: float) -> float:
        return max(0.0, (ts_unix - t0) * _US)

    flow_id = 0
    other: Dict[str, Any] = {"runs": []}
    for pid, run in enumerate(runs, start=1):
        man = run.get("manifest") or {}
        kind = man.get("kind", "run")
        # train ledgers carry loop_mode only in config — fold it into the
        # track label so two train runs stay distinguishable when merged
        mode = (man.get("config") or {}).get("loop_mode")
        if mode and kind.startswith("train") and mode not in kind:
            kind = "{}:{}".format(kind, mode)
        label = "{}:{}".format(
            kind,
            os.path.basename(os.path.normpath(run.get("run_dir", "?"))))
        proc = man.get("process") or {}
        if proc.get("count", 1) > 1:
            label += " (p{}/{})".format(proc.get("index", 0),
                                        proc.get("count"))
        events.append(_meta(pid, label))
        tids = _Tids(pid, events)
        other["runs"].append({
            "pid": pid, "run_dir": run.get("run_dir"),
            "kind": man.get("kind"),
            "scenario_fingerprint": man.get("scenario_fingerprint"),
            "git": man.get("git"), "devices": man.get("devices"),
        })

        ring_open: Dict[Any, float] = {}  # (segment, generation) → ts
        memo_last: Optional[Dict[str, Any]] = None
        for rec in run.get("records", ()):
            ts = rec.get("ts")
            if ts is None:
                continue
            rtype = rec.get("type")
            if rtype == "span":
                dur = float(rec.get("dur_s") or 0.0)
                events.append({
                    "name": rec.get("name", "?"), "ph": "X",
                    "pid": pid, "tid": tids(rec.get("name", "?")),
                    "ts": us(ts - dur), "dur": dur * _US,
                })
            elif rtype == "transfer":
                dur = float(rec.get("dur_s") or 0.0)
                name = rec.get("name", "?")
                direction = rec.get("direction", "?")
                tid = tids("transfer:{}".format(name))
                start = us(ts - dur)
                events.append({
                    "name": name, "ph": "X", "pid": pid, "tid": tid,
                    "ts": start, "dur": max(dur * _US, 1.0),
                    "args": {"bytes": rec.get("bytes"),
                             "direction": direction},
                })
                # flow arrow: dispatch slice → a 1 us arrival slice on
                # the direction's destination track
                flow_id += 1
                dest = _DIRECTION_DEST.get(direction, direction)
                dest_tid = tids("arrivals:{}".format(dest))
                end = us(ts)
                events.append({
                    "name": "{} → {}".format(name, dest), "ph": "s",
                    "cat": "transfer", "id": flow_id, "pid": pid,
                    "tid": tid, "ts": start + max(dur * _US, 1.0) / 2})
                events.append({
                    "name": "{} arrive".format(name), "ph": "X",
                    "pid": pid, "tid": dest_tid, "ts": end, "dur": 1.0,
                    "args": {"bytes": rec.get("bytes")},
                })
                events.append({
                    "name": "{} → {}".format(name, dest), "ph": "f",
                    "bp": "e", "cat": "transfer", "id": flow_id,
                    "pid": pid, "tid": dest_tid, "ts": end + 0.5})
            elif rtype == "event":
                kind = rec.get("kind")
                if kind == "ring_segment":
                    phase = rec.get("phase")
                    seg = rec.get("segment")
                    gen = rec.get("generation")
                    key = (seg, gen)
                    track = tids("ring seg{}".format(seg))
                    if phase == "lease":
                        ring_open[key] = ts
                        events.append({
                            "name": "seg{} g{}".format(seg, gen),
                            "ph": "b", "cat": "ring",
                            "id": "ring:{}:{}".format(seg, gen),
                            "pid": pid, "tid": track, "ts": us(ts)})
                    elif phase == "release":
                        events.append({
                            "name": "seg{} g{}".format(seg, gen),
                            "ph": "e", "cat": "ring",
                            "id": "ring:{}:{}".format(seg, gen),
                            "pid": pid, "tid": track, "ts": us(ts)})
                        ring_open.pop(key, None)
                    elif phase == "publish":
                        events.append({
                            "name": "publish seg{}".format(seg),
                            "ph": "i", "s": "t", "pid": pid,
                            "tid": track, "ts": us(ts)})
                    elif phase == "stall":
                        events.append({
                            "name": "RING STALL", "ph": "i", "s": "p",
                            "pid": pid, "tid": tids("ring stalls"),
                            "ts": us(ts),
                            "args": {"segment": seg}})
                elif kind == "memo_counters":
                    hits = rec.get("hits") or 0
                    misses = rec.get("misses") or 0
                    total = hits + misses
                    rate = (hits / total) if total else 0.0
                    memo_last = rec
                    events.append({
                        "name": "memo hit rate", "ph": "C", "pid": pid,
                        "ts": us(ts),
                        "args": {"hit_rate": round(rate, 4)}})
                elif kind == "params_age":
                    events.append({
                        "name": "params_age_updates", "ph": "C",
                        "pid": pid, "ts": us(ts),
                        "args": {"updates": rec.get("value", 0)}})
                else:
                    events.append({
                        "name": "event:{}".format(kind), "ph": "i",
                        "s": "t", "pid": pid, "tid": tids("events"),
                        "ts": us(ts),
                        "args": {k: v for k, v in rec.items()
                                 if k not in ("ts", "type", "kind")}})
        if memo_last is not None:
            other["runs"][-1]["memo_counters"] = {
                k: v for k, v in memo_last.items()
                if k not in ("ts", "type", "kind")}

        if include_device_trace:
            events.extend(_fold_device_trace(run, base_pid=1000 * pid))

    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


def _fold_device_trace(run: Dict[str, Any],
                       base_pid: int) -> List[Dict[str, Any]]:
    """Fold any jax.profiler capture under the run dir in, with pids
    offset so device tracks sit beside (not inside) the host tracks.
    Device-trace timestamps are profiler-relative, not unix — Perfetto
    shows them as their own process group; correlation is by span
    structure (the one-shot capture is owned by a named span)."""
    out: List[Dict[str, Any]] = []
    run_dir = run.get("run_dir")
    if not run_dir:
        return out
    pattern = os.path.join(
        run_dir, "**", "plugins", "profile", "*", "*.trace.json.gz")
    for path in sorted(glob.glob(pattern, recursive=True))[:1]:
        try:
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
        except Exception:
            continue
        for ev in doc.get("traceEvents", []):
            if "pid" in ev:
                ev = dict(ev)
                ev["pid"] = base_pid + int(ev["pid"])
            out.append(ev)
    return out


def write_timeline(run_dirs: Sequence[str], out_path: str,
                   include_device_trace: bool = True) -> Dict[str, Any]:
    runs = [load_run_dir(d) for d in run_dirs]
    doc = build_trace(runs, include_device_trace=include_device_trace)
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return doc


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Merge RunLedger directories into one Perfetto "
                    "trace (open in ui.perfetto.dev or "
                    "chrome://tracing).")
    p.add_argument("run_dirs", nargs="+", help="RunLedger directories")
    p.add_argument("-o", "--out", default="timeline.json")
    p.add_argument("--no-device-trace", action="store_true",
                   help="skip folding in jax.profiler captures")
    args = p.parse_args(argv)
    for d in args.run_dirs:
        if not os.path.isdir(d):
            p.error("not a directory: {}".format(d))
    doc = write_timeline(args.run_dirs, args.out,
                         include_device_trace=not args.no_device_trace)
    n_ev = len(doc["traceEvents"])
    print("wrote {} ({} events from {} run dir{})".format(
        args.out, n_ev, len(args.run_dirs),
        "s" if len(args.run_dirs) != 1 else ""))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
