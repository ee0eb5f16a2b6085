"""Multi-segment shared-memory trajectory ring: decoupled actor→learner
dataflow within one host (ROADMAP item 4; the MSRL "dataflow fragments"
shape of arXiv 2210.00882 at single-host scale).

The PR 4 slab (rl/shm.py) gave the deferred-fetch collector a zero-copy
trajectory — worker obs writes land directly in the ``[T+1, B, ...]``
slab rows — but ONE slab rewritten in place forces a bulk defensive copy
of every segment before the asynchronously-executing update may read it:
jax's CPU client zero-copy aliases page-aligned host buffers (shm mmaps
are) into ``device_put`` results whenever no layout change is needed, so
slab views staged into the update would be silently rewritten by the
next segment's worker writes (docs/perf_round7.md). This module replaces
the single slab with a ring of K independently-owned segments so the
copy becomes unnecessary: a segment is not rewritten until it is
RELEASED, and release happens only after whatever staged from it has
been consumed.

Ownership ledger (extending the CLAUDE.md slab contract one level up —
workers still own only their ``[row, env_index]`` slice between a step
command and its pipe reply):

* ``free``      — nobody reads or writes; the only state a lease may
  take a segment from;
* ``leased``    — the COLLECTOR owns it: worker step writes target its
  rows, the collector reads them back as trajectory views;
* ``published`` — the LEARNER owns it: the collector is done, the rows
  are (or are about to be) staged into the update; nobody writes.

``release`` — the transition back to ``free`` — is driven by a
*release token*: any object with jax's ``is_ready()`` protocol (a
staged device array, an update-output metric). The token is chosen per
segment by the ALIAS VERDICT, probed once per segment at its first
staging (``staged_aliases``: does the device-put result share the
segment's host memory?):

* no alias (host→device copy, or the strided shards of a multi-device
  mesh): the staged buffers are independent the moment the copy
  completes — the phase-1 token is the staged tree itself;
* alias (e.g. any 1-device CPU mesh): the update reads the segment's
  own bytes — only an output of the consuming update can mark them
  consumed (donation never bites here: donation is disabled on CPU,
  the only backend where host aliasing exists — rl/ppo.py
  traj_donate_argnums).

Phase 2 (``note_update``) attaches an update-output token
UNCONDITIONALLY after the update dispatch: on donating backends the
update deletes a phase-1 staging token's buffers at dispatch — before
the queued host→device transfer necessarily finished reading the
segment — so a deleted token reads not-ready and waits for this
replacement rather than releasing early.

``lease()`` sweeps ready tokens non-blockingly; when every segment is
unreleased it counts a STALL and polls token readiness under a hard
``timeout_s`` deadline (never ``block_until_ready`` — a wedged update
must surface as the timeout error, not an unbounded hang). All
counters ride the gated telemetry API (one bool check when disabled —
CLAUDE.md hot-path contract).

Segment lifecycle/unlink safety is delegated to ``SlabSet`` (each
segment carries its own ``weakref.finalize`` crash fallback), so an
interrupted run leaves no ``/dev/shm`` litter; the lint engine's
``shm-unlink`` rule covers the creates in rl/shm.py.

DEVICE MODE (round 12, rl/sebulba.py): ``TrajRing(fields=None, ...)``
builds SLAB-LESS segments — no shm, ``views`` empty — for the Sebulba
actor→learner device queue, where a "segment" is one in-flight
device-resident batch rather than host memory. The ledger, the lease
backpressure, and the two-phase token protocol carry over UNCHANGED:
with no host views the alias probe trivially verdicts "copied"
(``staged_aliases`` over zero address ranges), so the phase-1 token is
the tree ``device_put`` onto the learner sub-mesh — ready exactly when
the device-to-device transfer completes — and phase 2's unconditional
update-output token still covers donating backends deleting the staged
buffers at dispatch. Worker-attach surfaces (``specs``,
``segment_names``) reject loudly in this mode.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.rl.shm import SlabSet

#: occupancy histogram bucket bounds (occupied segment count at lease
#: time); rings beyond 8 segments land in the overflow bucket
OCCUPANCY_BUCKETS = tuple(range(9))


def _token_ready(token: Any) -> bool:
    """Non-blocking readiness of a release token (a pytree of jax arrays
    or anything exposing ``is_ready``). A DELETED leaf (a staged buffer
    donated into the update) counts as NOT ready: donation deletes at
    dispatch, not at consumption — the queued host→device transfer may
    still be reading the segment's bytes — so a deleted staging token
    must wait to be REPLACED by the update-output token
    (``note_update``), which is ready only after the consumer ran."""
    import jax

    for leaf in jax.tree_util.tree_leaves(token):
        ready = getattr(leaf, "is_ready", None)
        if ready is None:
            continue
        try:
            if not ready():
                return False
        except RuntimeError:
            return False  # deleted: unusable as a marker — see docstring
    return True


def staged_aliases(staged, views: Dict[str, np.ndarray]) -> bool:
    """Whether any leaf of the staged (device) tree shares memory with
    the segment's host slab views — the per-segment alias verdict.

    A shard on an accelerator lives in device memory: staging it was a
    host→device copy and it cannot alias (its pointer is a device
    address, meaningless against host ranges). For CPU shards the
    primary probe is each addressable shard's ``unsafe_buffer_pointer``
    against the views' host address ranges (no transfer, works under
    ``jax.transfer_guard``). Fallback: ``np.shares_memory`` on the
    shard's host export. Any probe failure returns True — the
    conservative verdict only delays release until the update's token,
    it can never corrupt data."""
    import jax

    ranges: List[Tuple[int, int]] = []
    for v in views.values():
        base = v.__array_interface__["data"][0]
        ranges.append((base, base + v.nbytes))

    def hits(ptr: int) -> bool:
        return any(lo <= ptr < hi for lo, hi in ranges)

    for leaf in jax.tree_util.tree_leaves(staged):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            return True
        for shard in shards:
            if shard.device.platform != "cpu":
                continue
            try:
                if hits(shard.data.unsafe_buffer_pointer()):
                    return True
            except Exception:
                try:
                    host = np.asarray(shard.data)
                except Exception:
                    return True
                if any(np.shares_memory(host, v) for v in views.values()):
                    return True
    return False


class RingSegment:
    """One ``[rows, B, ...]`` slab plus its ledger entry. ``slabs=None``
    is a DEVICE-MODE segment (see module docstring): pure ledger entry
    for one in-flight device batch, no host memory, empty ``views``."""

    __slots__ = ("index", "slabs", "state", "release_token", "aliased",
                 "generation")

    def __init__(self, index: int, slabs: Optional[SlabSet]):
        self.index = index
        self.slabs = slabs
        self.state = "free"
        self.release_token: Any = None
        # alias verdict: None until the first staging probes it
        self.aliased: Optional[bool] = None
        # lease counter: token calls carry the generation they belong
        # to, so a SLOW consumer's late token can never release a
        # segment that was already recycled for a newer batch
        self.generation = 0

    @property
    def views(self) -> Dict[str, np.ndarray]:
        return self.slabs.views if self.slabs is not None else {}


class TrajRing:
    """K independently-owned trajectory segments with the ledger above.

    Thread contract: ``lease``/``publish`` run on the collecting thread
    (the main thread at ``pipeline_depth=0``, the background collection
    thread otherwise); ``set_release_token`` may run on either (staging
    tokens on the collector thread, update tokens on the main thread).
    One condition variable serialises the ledger.
    """

    def __init__(self,
                 fields: Optional[Dict[str, Tuple[Tuple[int, ...],
                                                  np.dtype]]],
                 rows: int, num_envs: int, segments: int):
        if segments < 2:
            raise ValueError(
                f"a trajectory ring needs >= 2 segments, got {segments}")
        self.rows = int(rows)
        self.num_envs = int(num_envs)
        # fields=None: device mode — slab-less ledger-only segments
        self.fields = dict(fields) if fields is not None else None
        self.segments: List[RingSegment] = []
        try:
            for i in range(segments):
                self.segments.append(RingSegment(
                    i, None if fields is None
                    else SlabSet(fields, rows=rows, num_envs=num_envs)))
        except Exception:
            self.close()
            raise
        self._cond = threading.Condition()
        self._next = 0  # round-robin lease cursor
        # ledger counters (host ints; fetched once at reporting
        # boundaries — loops.ring_stats(), telemetry_report's section)
        self.leases = 0
        self.stalls = 0
        self.publishes = 0
        self.releases = 0
        # exact occupancy histogram: occupied-segment count at each
        # lease, index = occupancy (the report artifact)
        self.occupancy_counts = [0] * (segments + 1)
        self._params_age_sum = 0
        self._params_age_n = 0

    # ------------------------------------------------------------- ledger
    def _sweep_locked(self) -> None:
        for seg in self.segments:
            if seg.state == "published" and seg.release_token is not None:
                if _token_ready(seg.release_token):
                    self._release_locked(seg)

    def _release_locked(self, seg: RingSegment) -> None:
        seg.state = "free"
        seg.release_token = None
        self.releases += 1
        if telemetry.enabled():
            telemetry.inc("rollout.ring.release")
            telemetry.record_event("ring_segment", phase="release",
                                   segment=seg.index,
                                   generation=seg.generation)
        self._cond.notify_all()

    def _next_free_locked(self) -> Optional[RingSegment]:
        K = len(self.segments)
        for off in range(K):
            seg = self.segments[(self._next + off) % K]
            if seg.state == "free":
                self._next = (seg.index + 1) % K
                return seg
        return None

    def lease(self, timeout_s: float = 300.0) -> RingSegment:
        """Claim the next free segment for collection, waiting (and
        counting a stall) while every segment is leased/published —
        token readiness is POLLED under the hard ``timeout_s``
        deadline, so a lost or never-ready release token turns into an
        error instead of a silent hang (same discipline as the vec
        env's ``step_timeout_s``)."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            self._sweep_locked()
            occupied = sum(1 for s in self.segments if s.state != "free")
            self.occupancy_counts[occupied] += 1
            if telemetry.enabled():
                telemetry.observe("rollout.ring.occupancy", occupied,
                                  buckets=OCCUPANCY_BUCKETS)
            seg = self._next_free_locked()
            if seg is None:
                self.stalls += 1
                if telemetry.enabled():
                    telemetry.inc("rollout.ring.stall")
                    telemetry.record_event("ring_segment", phase="stall",
                                           segment=None,
                                           occupied=occupied)
            while seg is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    states = [(s.index, s.state,
                               s.release_token is not None)
                              for s in self.segments]
                    raise RuntimeError(
                        f"trajectory ring lease timed out after "
                        f"{timeout_s:.0f}s — no segment released "
                        f"(ledger: {states}); a published segment's "
                        "release token was never set or never became "
                        "ready")
                # bounded poll: wait for a release/token notification
                # (or the next readiness check) and re-sweep. Polling —
                # not jax.block_until_ready — keeps the deadline REAL:
                # an update that never completes surfaces as the timeout
                # error above instead of an unbounded silent hang.
                self._cond.wait(timeout=min(remaining, 0.05))
                self._sweep_locked()
                seg = self._next_free_locked()
            seg.state = "leased"
            seg.release_token = None
            seg.generation += 1
            self.leases += 1
            if telemetry.enabled():
                telemetry.inc("rollout.ring.lease")
                telemetry.record_event("ring_segment", phase="lease",
                                       segment=seg.index,
                                       generation=seg.generation)
            return seg

    def publish(self, seg: RingSegment) -> None:
        """Collection done: ownership passes to the learner. The segment
        stays unwritable until its release token reports ready."""
        with self._cond:
            if seg.state != "leased":
                raise RuntimeError(
                    f"publish on segment {seg.index} in state "
                    f"{seg.state!r} (must be leased)")
            seg.state = "published"
            self.publishes += 1
            if telemetry.enabled():
                telemetry.inc("rollout.ring.publish")
                telemetry.record_event("ring_segment", phase="publish",
                                       segment=seg.index,
                                       generation=seg.generation)
            self._cond.notify_all()

    def set_release_token(self, seg: RingSegment, token: Any,
                          generation: Optional[int] = None) -> None:
        """Attach the consumption marker that turns this published
        segment free once ready (staged tree when staging copied, an
        update output when staging aliased the segment). ``generation``
        — when the caller knows which lease its batch came from — makes
        a LATE token harmless: it no-ops if the segment was already
        released and re-leased for a newer batch."""
        with self._cond:
            if seg.state != "published":
                return  # already released (or re-leased): nothing to do
            if generation is not None and seg.generation != generation:
                return  # stale consumer: this token's batch is long gone
            seg.release_token = token
            self._cond.notify_all()

    def sweep(self) -> None:
        """Release every published segment whose token is ready (the
        same pass a lease performs) — for callers that need the ledger
        current without leasing (e.g. the vec env's reset guard)."""
        with self._cond:
            self._sweep_locked()

    def release(self, seg: RingSegment) -> None:
        """Immediate explicit release (teardown/tests); the normal path
        is token-driven via the lease-time sweep."""
        with self._cond:
            if seg.state == "free":
                return
            self._release_locked(seg)

    # ------------------------------------------- consumer token protocol
    # The ONE authoritative implementation of the two-phase handoff
    # (every consumer calls these — the verdict/token choice must never
    # fork between consumers; train/loops.py is the model).
    def note_staged(self, seg: RingSegment, staged_tree,
                    generation: Optional[int] = None) -> None:
        """Phase 1, at staging time: probe the alias verdict ONCE per
        segment (cached — the steady state stays probe-free), and when
        staging COPIED the segment's bytes, attach the staged tree as
        the release token (free the moment the copies land). Pass the
        batch's ``ring_generation`` so a slow consumer can never token
        a recycled segment."""
        if seg.aliased is None:
            seg.aliased = staged_aliases(staged_tree, seg.views)
        if not seg.aliased:
            self.set_release_token(seg, staged_tree,
                                   generation=generation)

    def note_update(self, seg: RingSegment, update_output,
                    generation: Optional[int] = None) -> None:
        """Phase 2, after the update dispatch — UNCONDITIONAL: for an
        alias-verdict segment the update output is the earliest safe
        release marker; for a copy-verdict segment it REPLACES a phase-1
        staging token whose buffers the update may have donated-and-
        deleted (a deleted token reads not-ready forever — see
        ``_token_ready``). A segment the phase-1 token already released
        — or one re-leased past this batch's ``generation`` — is a
        no-op."""
        self.set_release_token(seg, update_output, generation=generation)

    # ------------------------------------------------------------ metrics
    def observe_params_age(self, age: int) -> None:
        """Record one consumed batch's params age (updates between its
        collection params snapshot and its consumption) — the V-trace
        staleness the ring asks IMPALA to absorb."""
        self._params_age_sum += int(age)
        self._params_age_n += 1
        if telemetry.enabled():
            telemetry.observe("rollout.ring.params_age_updates", int(age),
                              buckets=OCCUPANCY_BUCKETS)
            telemetry.record_event("params_age", value=int(age))

    def stats(self) -> Dict[str, Any]:
        """Ledger counters as one host-side dict (no device fetch):
        the run ledger's `ring` block / report section payload."""
        with self._cond:
            return {
                "segments": len(self.segments),
                "rows": self.rows,
                "leases": self.leases,
                "stalls": self.stalls,
                "publishes": self.publishes,
                "releases": self.releases,
                "occupancy_counts": list(self.occupancy_counts),
                "mean_params_age": (
                    self._params_age_sum / self._params_age_n
                    if self._params_age_n else None),
                "aliased_segments": [bool(s.aliased) for s in self.segments
                                     if s.aliased is not None],
            }

    # ---------------------------------------------------------- lifecycle
    def specs(self) -> List[list]:
        """Per-segment slab specs for the workers' ring attach."""
        if self.fields is None:
            raise RuntimeError(
                "device-mode trajectory ring has no slabs: worker "
                "attach (specs) is a shm-ring surface only")
        return [seg.slabs.spec() for seg in self.segments]

    def segment_names(self) -> List[str]:
        if self.fields is None:
            raise RuntimeError(
                "device-mode trajectory ring has no slabs: worker "
                "attach (segment_names) is a shm-ring surface only")
        return [name for seg in self.segments
                for name in seg.slabs.segment_names()]

    def close(self) -> None:
        """Unlink every segment (idempotent); each SlabSet's own
        ``weakref.finalize`` covers paths that never reach here."""
        for seg in self.segments:
            if seg.slabs is not None:
                seg.slabs.close()
