"""Summarize a telemetry JSONL sink file into per-span / per-metric tables.

Usage::

    python scripts/telemetry_report.py run.jsonl

Reads the three record types ``ddls_tpu.telemetry`` writes
(docs/telemetry.md "Sink format"):

* ``span`` records are aggregated per name into count / total / mean /
  p50 / p95 / p99 / max (exact percentiles — every duration is on disk);
* ``event`` records are tallied per (kind, phase) with the last
  occurrence's fields shown (e.g. the last ``tpu_probe`` outcome);
* the LAST ``snapshot`` record supplies the counters / gauges /
  histograms tables (histogram percentiles fall back to fixed-bucket
  interpolation via ``percentile_from_bucket_counts`` when the snapshot
  carries buckets but no window percentiles);
* ``flight`` records (episode flight-recorder traces,
  ``ddls_tpu.telemetry.flight`` — also the whole-file format
  ``flight.save_jsonl`` writes) get a trace summary: events by kind,
  blocks by cause, and a per-job lifecycle table;
* ``transfer`` records (the gated transfer ledger,
  ``telemetry.transfer(...)``) get a per-hop table (count / bytes /
  duration / effective bandwidth) plus a sebulba cross-mesh section
  when the run carried ``l2a``/``a2l`` hops (docs/telemetry.md "Run
  ledger & unified timeline").

``--timeline RUN_DIR [RUN_DIR ...]`` delegates to
``ddls_tpu.telemetry.timeline`` instead: merge RunLedger directories
into one Perfetto trace (``-o`` names the output, default
timeline.json).

Exit codes: 0 on success (even for an empty file — it says so), 2 when
the file is missing/unreadable.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import OrderedDict, defaultdict
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _fmt_ms(seconds: float) -> str:
    return f"{seconds * 1e3:10.3f}"


def _span_table(durations: Dict[str, List[float]]) -> List[str]:
    lines = [f"{'span':<28}{'count':>7}{'total_ms':>12}{'mean_ms':>11}"
             f"{'p50_ms':>11}{'p95_ms':>11}{'p99_ms':>11}{'max_ms':>11}"]
    for name in sorted(durations):
        d = np.asarray(durations[name], dtype=np.float64)
        lines.append(
            f"{name:<28}{d.size:>7}{_fmt_ms(d.sum()):>12}"
            f"{_fmt_ms(d.mean()):>11}"
            f"{_fmt_ms(float(np.percentile(d, 50))):>11}"
            f"{_fmt_ms(float(np.percentile(d, 95))):>11}"
            f"{_fmt_ms(float(np.percentile(d, 99))):>11}"
            f"{_fmt_ms(d.max()):>11}")
    return lines


def _walk_snapshot(data: Dict[str, Any], prefix: str = ""
                   ) -> Dict[str, Dict[str, Any]]:
    """Flatten nested snapshot sections ('serve' subtrees etc.) into
    {counters, gauges, histograms, spans} with prefixed metric names."""
    out: Dict[str, Dict[str, Any]] = defaultdict(OrderedDict)
    for key, val in (data or {}).items():
        if key in ("counters", "gauges", "histograms", "spans"):
            for name, payload in val.items():
                out[key][prefix + name] = payload
        elif isinstance(val, dict):
            for section, items in _walk_snapshot(
                    val, prefix=f"{prefix}{key}.").items():
                out[section].update(items)
    return out


def _histogram_percentiles(summ: Dict[str, Any]) -> Dict[str, Any]:
    """Prefer the snapshot's window-exact percentiles; reconstruct from
    bucket counts when only those survived (merged/foreign snapshots)."""
    if summ.get("p50") is not None:
        return summ
    buckets = summ.get("buckets") or {}
    bounds, counts = [], []
    overflow = 0
    for bound, n in buckets.items():
        if bound == "+inf":
            overflow = int(n)
        else:
            bounds.append(float(bound))
            counts.append(int(n))
    order = np.argsort(bounds)
    bounds = [bounds[i] for i in order]
    counts = [counts[i] for i in order] + [overflow]
    from ddls_tpu.telemetry import percentile_from_bucket_counts

    out = dict(summ)
    for q in (50, 95, 99):
        out[f"p{q}"] = percentile_from_bucket_counts(
            bounds, counts, q, lo=summ.get("min"), hi=summ.get("max"))
    return out


def _overlap_section(intervals: List[tuple]) -> List[str]:
    """Concurrency accounting over the sink's ``train.*`` spans (each
    record's interval is ``(ts - dur_s, ts)`` — the sink stamps ``ts``
    at span exit). Makes pipelining claims checkable from any run's
    JSONL: wall covered by >= 1 span, by >= 2 CONCURRENT spans (real
    overlap, e.g. train.update_device under train.collect), and the
    largest uncovered gaps (loop time no phase span accounts for).

    Fused epochs (``train.fused_epoch``, rl/fused.py) are ONE span per
    epoch whose collect/update rounds overlap INSIDE the compiled
    program, invisible to span accounting — counting them with the
    collect/update pairs would read a fused run as 0% overlap. They are
    split out and labelled; the overlap math runs over the remaining
    host-visible phase spans."""
    from ddls_tpu.telemetry import overlap_summary

    train = [iv for iv in intervals if iv[0].startswith("train.")]
    fused = [iv for iv in train if iv[0] == "train.fused_epoch"]
    train = [iv for iv in train if iv[0] != "train.fused_epoch"]
    fused_lines = []
    if fused:
        fused_total = sum(t1 - t0 for _, t0, t1 in fused)
        fused_lines = [
            "== fused epochs (train.fused_epoch: collect+update rounds "
            "overlap IN-PROGRAM; excluded from span-overlap accounting) "
            "==",
            f"{'fused_epochs':<28}{len(fused):>10}",
            f"{'fused_epoch_total_s':<28}{fused_total:>10.3f}", ""]
    ov = overlap_summary(train)
    if not ov.get("n_spans"):
        return fused_lines
    window_t0 = min(t0 for _, t0, _ in train)
    lines = fused_lines + [
             "== overlap (train.* spans, intervals from ts - dur_s) ==",
             f"{'spans':<28}{ov['n_spans']:>10}",
             f"{'window_s':<28}{ov['window_s']:>10.3f}",
             f"{'covered_by_>=1_span_s':<28}{ov['covered_1_s']:>10.3f}",
             f"{'covered_by_>=2_spans_s':<28}{ov['covered_2_s']:>10.3f}",
             f"{'overlap_fraction':<28}{ov['overlap_fraction']:>10.3f}",
             f"{'uncovered_gap_s':<28}{ov['gap_s']:>10.3f}"]
    for i, gap in enumerate(ov["largest_gaps"], 1):
        lines.append(f"{'gap_' + str(i) + '_s':<28}{gap['dur_s']:>10.3f}"
                     f"  (at +{gap['start'] - window_t0:.3f}s into the "
                     f"window)")
    return lines + [""]


#: a fused epoch's spans by what they are (docs/telemetry.md, "a fused
#: epoch's five spans"): the wait for the device, the host time an epoch
#: pays with telemetry off too, and what the instrument itself adds —
#: the sums the benchmark's ``epoch_*_p50_*`` metrics read
EPOCH_PARTS = (
    ("device wait", ("train.device_wait",)),
    ("host (telemetry off too)",
     ("train.fused_epoch", "train.host_sync", "train.harvest")),
    ("observer (telemetry only)", ("train.telemetry_reduce",)),
)


def _epoch_anatomy_section(intervals: List[tuple]) -> List[str]:
    """A fused run's epochs from inside the program: per epoch
    (``telemetry.per_epoch_sums``: delimited by successive
    ``train.fused_epoch`` starts), each of ``EPOCH_PARTS`` — median and
    max over the epochs."""
    from ddls_tpu.telemetry import per_epoch_sums

    rows = [(label, np.asarray(per_epoch_sums(intervals, names)))
            for label, names in EPOCH_PARTS]
    if not rows[0][1].size:
        return []
    lines = [f"== epoch anatomy ({rows[0][1].size} fused epochs; "
             "per-epoch sums of the program's spans) ==",
             f"{'part':<28}{'p50_ms':>12}{'max_ms':>12}"]
    lines += [f"{label:<28}{np.median(values) * 1e3:>12.3f}"
              f"{values.max() * 1e3:>12.3f}" for label, values in rows]
    return lines + [""]


def _flight_section(flight_events: List[dict]) -> List[str]:
    """Trace summary: events by kind, blocks by cause, per-job
    lifecycle (arrival -> decision -> placement -> outcome)."""
    from ddls_tpu.telemetry import flight

    summ = flight.summarize(flight_events)
    lines = [f"== flight trace ({summ['n_events']} events, sim horizon "
             f"t={summ['t_end']:.6g}) ==",
             f"{'kind':<24}{'count':>8}"]
    for kind, n in sorted(summ["by_kind"].items()):
        lines.append(f"{kind:<24}{n:>8}")
    if summ["blocked_by_cause"]:
        lines += ["", f"{'blocked by cause':<44}{'count':>8}"]
        for cause, n in sorted(summ["blocked_by_cause"].items()):
            lines.append(f"{cause:<44}{n:>8}")
    # scenario failure windows (ddls_tpu/scenarios): per-resource tally
    # of the deterministic preemption/straggler crossings in the trace
    fails: Dict[str, int] = {}
    for e in flight_events:
        if e.get("kind") == "worker_preempted":
            key = f"worker_preempted (server {e.get('server', '?')})"
        elif e.get("kind") == "channel_degraded":
            key = f"channel_degraded (channel {e.get('channel', '?')})"
        else:
            continue
        fails[key] = fails.get(key, 0) + 1
    if fails:
        lines += ["", f"{'scenario failure window':<44}{'count':>8}"]
        for key, n in sorted(fails.items()):
            lines.append(f"{key:<44}{n:>8}")
    jobs = summ["jobs"]
    if jobs:
        lines += ["", f"{'job':>9} {'arrived':>12} {'deg':>4} "
                      f"{'placed':>12} {'jct':>12} {'outcome':<42}"]
        max_rows = 50

        def cell(v, fmt="{:.6g}"):
            return "-" if v is None else fmt.format(v)

        # insertion order == first-appearance (arrival) order; labels are
        # env/generation-qualified strings (flight._iter_labeled)
        for ji in list(jobs)[:max_rows]:
            r = jobs[ji]
            if "completed" in r:
                outcome = f"completed @ {r['completed']:.6g}"
            elif "blocked" in r:
                outcome = (f"blocked @ {r['blocked']:.6g} "
                           f"({r.get('cause', '?')})")
            else:
                outcome = "running at trace end"
            lines.append(
                f"{ji:>9} {cell(r.get('arrived')):>12} "
                f"{cell(r.get('degree'), '{}'): >4} "
                f"{cell(r.get('placed')):>12} "
                f"{cell(r.get('jct')):>12} {outcome:<42}")
        if len(jobs) > max_rows:
            lines.append(f"... ({len(jobs) - max_rows} more jobs)")
    return lines + [""]


def _transfer_section(transfers: List[dict]) -> List[str]:
    """Transfer-ledger rollup (``telemetry.transfer``): one row per hop
    name with count / total bytes / duration percentiles / effective
    bandwidth, so the dispatch amortisation is readable from
    any run's JSONL (bytes ride record metadata — no device sync was
    paid to collect them)."""
    by_name: Dict[str, List[dict]] = defaultdict(list)
    for rec in transfers:
        by_name[rec.get("name", "?")].append(rec)
    # layout-tagged hop names (sebulba.params[gather-from-fsdp],
    # rl/sebulba.py) overflow a fixed column — size it to the names
    w = max(24, max(len(n) for n in by_name) + 2)
    lines = ["== transfers (gated ledger; bytes from aval metadata) ==",
             f"{'hop':<{w}}{'dir':<6}{'count':>7}{'total_MB':>10}"
             f"{'mean_ms':>10}{'p95_ms':>10}{'MB/s':>10}"]
    for name in sorted(by_name):
        recs = by_name[name]
        durs = np.asarray([float(r.get("dur_s", 0.0)) for r in recs])
        total_b = sum(int(r.get("bytes", 0)) for r in recs)
        total_s = float(durs.sum())
        bw = (total_b / 1e6 / total_s) if total_s > 0 else 0.0
        lines.append(
            f"{name:<{w}}{recs[-1].get('direction', '?'):<6}"
            f"{len(recs):>7}{total_b / 1e6:>10.3f}"
            f"{durs.mean() * 1e3:>10.3f}"
            f"{float(np.percentile(durs, 95)) * 1e3:>10.3f}"
            f"{bw:>10.1f}")
    return lines + [""]


def _sebulba_section(transfers: List[dict],
                     span_durations: Dict[str, List[float]]) -> List[str]:
    """Actor/learner split accounting (rl/sebulba.py, loop_mode=
    "sebulba"): only renders when the run carried cross-mesh hops
    (``l2a`` params broadcasts or ``a2l`` trajectory stagings). Reports
    each hop's count/bytes/mean alongside the per-sub-mesh busy time
    (actor = train.collect, learner = train.update_device) — on one
    socket of virtual devices the two CANNOT overlap, so the busy-time
    ratio is the honest number, not a speedup claim
    (docs/perf_round12.md)."""
    hops = [r for r in transfers
            if r.get("direction") in ("l2a", "a2l")]
    if not hops:
        return []
    by_name: Dict[str, List[dict]] = defaultdict(list)
    for rec in hops:
        by_name[rec.get("name", "?")].append(rec)
    w = max(24, max(len(n) for n in by_name) + 2)
    lines = ["== sebulba cross-mesh hops (explicit device_put only) ==",
             f"{'hop':<{w}}{'dir':<6}{'count':>7}{'total_MB':>10}"
             f"{'mean_ms':>10}"]
    for name in sorted(by_name):
        recs = by_name[name]
        durs = np.asarray([float(r.get("dur_s", 0.0)) for r in recs])
        total_b = sum(int(r.get("bytes", 0)) for r in recs)
        lines.append(f"{name:<{w}}{recs[-1].get('direction', '?'):<6}"
                     f"{len(recs):>7}{total_b / 1e6:>10.3f}"
                     f"{durs.mean() * 1e3:>10.3f}")
    # the params hop carries its resolved partition layout in the name
    # (rl/sebulba.py "sebulba.params[gather-from-<layout>]"; plain
    # "sebulba.params" = replicated) — say it outright so a sharded
    # learner's gather cost is attributable without decoding the tag
    layouts = set()
    for n in by_name:
        if n.startswith("sebulba.params"):
            m = re.search(r"\[gather-from-([^\]]+)\]", n)
            layouts.add(m.group(1) if m else "replicated")
    if layouts:
        lines.append(f"{'params_hop_layout':<{w}}"
                     f"{', '.join(sorted(layouts))}")
    actor_s = sum(span_durations.get("train.collect", []))
    learner_s = sum(span_durations.get("train.update_device", []))
    if actor_s or learner_s:
        lines += ["",
                  f"{'actor_mesh_busy_s':<28}{actor_s:>10.3f}"
                  "  (train.collect)",
                  f"{'learner_mesh_busy_s':<28}{learner_s:>10.3f}"
                  "  (train.update_device)"]
        if learner_s > 0:
            lines.append(f"{'actor/learner_ratio':<28}"
                         f"{actor_s / learner_s:>10.3f}")
    return lines + [""]


def _fragments_section(transfers: List[dict],
                       sections: Dict[str, Dict[str, Any]]) -> List[str]:
    """Cross-host fragment accounting (rl/fragments.py,
    collect_transport='socket'): only renders when the run carried
    ``h2h`` frames (params broadcasts out, trajectory segments in).
    Reports each frame kind's count/bytes/mean duration plus a
    per-actor-host table — segments published, acks returned, mean/max
    segment transit (wire + framing lag net of the actor's own collect
    wall), and the learner ring's stall count (an acked-but-stalled
    ring means the UPDATE gated collection, not the wire)."""
    hops = [r for r in transfers if r.get("direction") == "h2h"]
    if not hops:
        return []
    by_name: Dict[str, List[dict]] = defaultdict(list)
    for rec in hops:
        by_name[rec.get("name", "?")].append(rec)
    w = max(24, max(len(n) for n in by_name) + 2)
    lines = ["== cross-host fragments (h2h frames) ==",
             f"{'frame':<{w}}{'count':>7}{'total_MB':>10}{'mean_ms':>10}"]
    for name in sorted(by_name):
        recs = by_name[name]
        durs = np.asarray([float(r.get("dur_s", 0.0)) for r in recs])
        total_b = sum(int(r.get("bytes", 0)) for r in recs)
        lines.append(f"{name:<{w}}{len(recs):>7}"
                     f"{total_b / 1e6:>10.3f}{durs.mean() * 1e3:>10.3f}")
    counters = sections.get("counters") or {}
    hists = sections.get("histograms") or {}
    hosts = sorted({k.split(".")[1] for k in counters
                    if k.startswith("fragments.h")})
    if hosts:
        lines += ["", f"{'actor host':<12}{'segments':>10}{'acks':>8}"
                      f"{'transit_mean_ms':>17}{'transit_max_ms':>16}"]
        for h in hosts:
            segs = counters.get(f"fragments.{h}.segments", 0)
            acks = counters.get(f"fragments.{h}.acks", 0)
            transit = hists.get(f"fragments.{h}.transit_s") or {}
            mean = transit.get("mean")
            mx = transit.get("max")
            lines.append(
                f"{h:<12}{segs:>10}{acks:>8}"
                f"{(mean * 1e3 if mean is not None else 0.0):>17.3f}"
                f"{(mx * 1e3 if mx is not None else 0.0):>16.3f}")
    stalls = counters.get("rollout.ring.stall")
    if stalls is not None:
        lines.append(f"{'learner_ring_stalls':<28}{stalls:>10}")
    return lines + [""]


def _ring_section(sections: Dict[str, Dict[str, Any]]) -> List[str]:
    """Trajectory-ring ledger rollup (rl/ring.py, ISSUE 15): lease/
    stall/publish/release counters, the lease-time occupancy histogram
    (how full the ring ran — a saturated ring means the learner gated
    collection), and the mean params age in updates (the staleness
    V-trace absorbed). All from the last snapshot's gated
    ``rollout.ring.*`` metrics."""
    counters = sections.get("counters") or {}
    hists = sections.get("histograms") or {}
    ring_counters = {k: v for k, v in counters.items()
                     if k.startswith("rollout.ring.")}
    occ = hists.get("rollout.ring.occupancy")
    age = hists.get("rollout.ring.params_age_updates")
    if not ring_counters and not occ and not age:
        return []
    lines = ["== trajectory ring (rollout.ring.*) =="]
    for name in ("lease", "stall", "publish", "release"):
        key = f"rollout.ring.{name}"
        if key in ring_counters:
            lines.append(f"{name + 's':<28}{ring_counters[key]:>10}")
    if occ and occ.get("count"):
        lines.append("")
        lines.append(f"{'occupancy at lease':<28}{'count':>10}")
        buckets = occ.get("buckets") or {}
        for bound, n in sorted(
                ((b, c) for b, c in buckets.items() if b != "+inf"),
                key=lambda kv: float(kv[0])):
            if int(n):
                lines.append(f"{'<= ' + f'{float(bound):g}':<28}"
                             f"{int(n):>10}")
        overflow = int(buckets.get("+inf", 0))
        if overflow:
            lines.append(f"{'> max bucket':<28}{overflow:>10}")
        if occ.get("mean") is not None:
            lines.append(f"{'mean_occupancy':<28}{occ['mean']:>10.3f}")
    if age and age.get("count"):
        lines.append("")
        lines.append(f"{'params_age_updates count':<28}"
                     f"{age['count']:>10}")
        if age.get("mean") is not None:
            lines.append(f"{'mean_params_age':<28}{age['mean']:>10.3f}")
        if age.get("max") is not None:
            lines.append(f"{'max_params_age':<28}{age['max']:>10.3f}")
    return lines + [""]


def _fleet_section(serve: Dict[str, Any]) -> List[str]:
    """Per-replica comparison when the snapshot's ``serve`` subtree
    carries a fleet dump (``r<id>`` replica registries + the
    ``aggregate`` multi-registry merge — serve/fleet.py
    ``registry_snapshots``): one row per replica plus the exact
    aggregate row, so replica imbalance is readable at a glance."""
    replicas = {k: v for k, v in serve.items()
                if k.startswith("r") and k[1:].isdigit()
                and isinstance(v, dict)}
    if len(replicas) < 2:
        return []

    def row(name, snap):
        counters = snap.get("counters") or {}
        hists = snap.get("histograms") or {}
        lat = _histogram_percentiles(hists.get("serve.latency_s", {})) \
            if hists.get("serve.latency_s") else {}
        occ = hists.get("serve.batch_occupancy", {})

        def cell(v, scale=1.0):
            return "n/a" if v is None else f"{v * scale:.3f}"

        return (f"{name:<12}{counters.get('serve.requests', 0):>10}"
                f"{counters.get('serve.policy', 0):>10}"
                f"{counters.get('serve.fallback', 0):>10}"
                f"{cell(lat.get('p50'), 1e3):>12}"
                f"{cell(lat.get('p99'), 1e3):>12}"
                f"{cell(occ.get('mean') if occ.get('count') else None):>12}")

    lines = ["== serving fleet (per-replica registries) ==",
             f"{'replica':<12}{'requests':>10}{'policy':>10}"
             f"{'fallback':>10}{'p50_ms':>12}{'p99_ms':>12}"
             f"{'occupancy':>12}"]
    for name in sorted(replicas, key=lambda r: int(r[1:])):
        lines.append(row(name, replicas[name]))
    agg = serve.get("aggregate")
    if isinstance(agg, dict):
        lines.append(row("aggregate", agg))
    return lines + [""]


def render_report(path: str) -> List[str]:
    span_durations: Dict[str, List[float]] = defaultdict(list)
    span_intervals: List[tuple] = []
    event_counts: Dict[tuple, int] = defaultdict(int)
    event_last: Dict[tuple, dict] = {}
    flight_events: List[dict] = []
    transfers: List[dict] = []
    last_snapshot: Dict[str, Any] = {}
    n_lines = n_bad = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            n_lines += 1
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                n_bad += 1
                continue
            kind = rec.get("type")
            if kind == "span":
                dur = float(rec.get("dur_s", 0.0))
                span_durations[rec.get("name", "?")].append(dur)
                if rec.get("ts") is not None:
                    ts = float(rec["ts"])
                    span_intervals.append(
                        (rec.get("name", "?"), ts - dur, ts))
            elif kind == "event":
                key = (rec.get("kind", "?"), rec.get("phase"))
                event_counts[key] += 1
                event_last[key] = rec
            elif kind == "snapshot":
                last_snapshot = rec.get("data") or {}
            elif kind == "flight":
                flight_events.append(rec)
            elif kind == "transfer":
                transfers.append(rec)

    lines = [f"telemetry report: {path} ({n_lines} records"
             + (f", {n_bad} unparseable" if n_bad else "") + ")", ""]
    if span_durations:
        lines += ["== spans (from per-span records; exact percentiles) =="]
        lines += _span_table(span_durations)
        lines += [""]
    if span_intervals:
        lines += _overlap_section(span_intervals)
        lines += _epoch_anatomy_section(span_intervals)
    snapshot_sections = (_walk_snapshot(last_snapshot)
                         if last_snapshot else {})
    if transfers:
        lines += _transfer_section(transfers)
        lines += _sebulba_section(transfers, span_durations)
        lines += _fragments_section(transfers, snapshot_sections)
    if flight_events:
        lines += _flight_section(flight_events)
    if event_counts:
        lines += ["== events ==",
                  f"{'kind':<24}{'phase':<18}{'count':>7}  last"]
        for (kind, phase), count in sorted(event_counts.items()):
            last = {k: v for k, v in event_last[(kind, phase)].items()
                    if k not in ("type", "kind", "phase", "ts")}
            lines.append(f"{kind:<24}{str(phase):<18}{count:>7}  "
                         f"{json.dumps(last)}")
        lines += [""]
    if isinstance(last_snapshot.get("serve"), dict):
        lines += _fleet_section(last_snapshot["serve"])
    if last_snapshot:
        sections = snapshot_sections
        lines += _ring_section(sections)
        if sections.get("counters"):
            lines += ["== counters (last snapshot) =="]
            for name, value in sorted(sections["counters"].items()):
                lines.append(f"{name:<52}{value:>12}")
            lines += [""]
        if sections.get("gauges"):
            lines += ["== gauges (last snapshot) =="]
            for name, value in sorted(sections["gauges"].items()):
                lines.append(f"{name:<52}{value:>12}")
            lines += [""]
        if sections.get("histograms"):
            lines += ["== histograms (last snapshot) ==",
                      f"{'metric':<40}{'count':>8}{'mean':>12}{'p50':>12}"
                      f"{'p95':>12}{'p99':>12}"]
            for name, summ in sorted(sections["histograms"].items()):
                if not summ.get("count"):
                    continue
                summ = _histogram_percentiles(summ)

                def cell(v):
                    return "n/a" if v is None else f"{v:.6g}"

                lines.append(
                    f"{name:<40}{summ['count']:>8}"
                    f"{cell(summ.get('mean')):>12}"
                    f"{cell(summ.get('p50')):>12}"
                    f"{cell(summ.get('p95')):>12}"
                    f"{cell(summ.get('p99')):>12}")
            lines += [""]
        if sections.get("spans") and not span_durations:
            lines += ["== spans (last snapshot; windowed percentiles) ==",
                      f"{'span':<28}{'count':>7}{'total_s':>10}"
                      f"{'mean_ms':>11}{'p50_ms':>11}{'p99_ms':>11}"]
            for name, summ in sorted(sections["spans"].items()):
                lines.append(
                    f"{name:<28}{summ['count']:>7}"
                    f"{summ['total_s']:>10.3f}{summ['mean_ms']:>11.3f}"
                    f"{summ['p50_ms']:>11.3f}{summ['p99_ms']:>11.3f}")
            lines += [""]
    if len(lines) == 2:
        lines.append("(no telemetry records found)")
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Summarize a telemetry JSONL sink file")
    parser.add_argument("path", nargs="?", default=None,
                        help="JSONL file written via --telemetry-jsonl / "
                             "DDLS_TELEMETRY_JSONL")
    parser.add_argument("--timeline", nargs="+", metavar="RUN_DIR",
                        default=None,
                        help="instead of a report: merge RunLedger run "
                             "directories into one Perfetto trace "
                             "(telemetry/timeline.py)")
    parser.add_argument("-o", "--out", default="timeline.json",
                        help="output path for --timeline")
    args = parser.parse_args(argv)
    if args.timeline:
        from ddls_tpu.telemetry.timeline import write_timeline

        doc = write_timeline(args.timeline, args.out)
        print(f"wrote {args.out} ({len(doc['traceEvents'])} events from "
              f"{len(args.timeline)} run dir(s))")
        return 0
    if not args.path:
        parser.error("path is required unless --timeline is given")
    if not os.path.exists(args.path):
        print(f"error: no such file: {args.path}", file=sys.stderr)
        return 2
    print("\n".join(render_report(args.path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
