"""Telemetry-driven autoscaling for the serving fleet (ISSUE 8).

The control loop closes over the SAME counters a load driver reports: the
Router's ``autoscale_snapshot()`` reads each replica's private
``ServeStats`` registry (rolling windowed p99, batch-occupancy window)
plus the live microbatch queue depths, and :class:`Autoscaler.decide`
maps that snapshot to a target replica count. Nothing else feeds the
decision — if a driver's result line says the fleet was slow, the
autoscaler saw the same numbers.

Design rules:

* **Deterministic.** ``decide`` is a pure function of (config, the
  decision counter state, the snapshot) — no wall clock, no randomness.
  A recorded snapshot sequence replays to the identical decision
  sequence (pinned in tests/test_fleet.py), which is what makes a
  production scaling incident reconstructable from a telemetry dump.
* **Hysteresis.** Scale-up triggers on breach (p99 over target OR mean
  queue depth over the high watermark); scale-down needs ALL of: queue
  below the low watermark, p99 under half the target, occupancy under
  the low watermark — and every change arms a cooldown of ``cooldown``
  decide() calls so the fleet never flaps on one noisy window.
* **The autoscaler only picks targets.** Applying them —
  ``Router.scale_to`` — drains retiring replicas (no dropped answers)
  and builds fresh ones through the replica factory; the controller
  records every applied decision in the router's private registry
  (``fleet.autoscale.{up,down}`` + the ``fleet.replicas`` gauge), so
  scaling history rides the same snapshot surface.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional


@dataclass
class AutoscaleConfig:
    """Watermarks are in the serve stats' own units: ``target_p99_ms`` wall
    milliseconds (the SLO-adjacent latency budget), queue depths in
    requests per replica, occupancy as batch-fill fraction."""
    min_replicas: int = 1
    max_replicas: int = 4
    target_p99_ms: float = 50.0
    queue_high: float = 8.0    # mean queued/replica that forces growth
    queue_low: float = 1.0     # mean queued/replica idle enough to shrink
    occupancy_low: float = 0.5  # batches this empty mean spare capacity
    cooldown: int = 3          # decide() calls held after any change

    def __post_init__(self):
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{self.min_replicas}..{self.max_replicas}")
        if self.queue_low > self.queue_high:
            raise ValueError("queue_low must not exceed queue_high")


class AutoscaleDecision(NamedTuple):
    target: int
    reason: str


class Autoscaler:
    """Snapshot -> target replica count, with cooldown hysteresis.

    ``decide`` mutates only the internal cooldown counter; feed it the
    same snapshot sequence from the same initial state and the decision
    sequence is identical.
    """

    def __init__(self, config: Optional[AutoscaleConfig] = None):
        self.config = config or AutoscaleConfig()
        self._cooldown = 0

    def decide(self, snapshot: Dict[str, Any]) -> AutoscaleDecision:
        cfg = self.config
        n = int(snapshot["replicas"])
        clamped = min(max(n, cfg.min_replicas), cfg.max_replicas)
        if clamped != n:
            # out-of-band fleet size (manual scale, config change):
            # snap back inside the configured range first
            return AutoscaleDecision(clamped, "clamp")
        if self._cooldown > 0:
            self._cooldown -= 1
            return AutoscaleDecision(n, "cooldown")
        p99 = snapshot.get("p99_latency_ms")
        occ = snapshot.get("batch_occupancy")
        queue_mean = snapshot["queued_total"] / max(n, 1)
        if n < cfg.max_replicas and (
                (p99 is not None and p99 > cfg.target_p99_ms)
                or queue_mean > cfg.queue_high):
            self._cooldown = cfg.cooldown
            why = ("p99" if (p99 is not None and p99 > cfg.target_p99_ms)
                   else "queue")
            return AutoscaleDecision(n + 1, f"up:{why}")
        if (n > cfg.min_replicas
                and queue_mean <= cfg.queue_low
                and (p99 is None or p99 < 0.5 * cfg.target_p99_ms)
                and (occ is None or occ < cfg.occupancy_low)):
            self._cooldown = cfg.cooldown
            return AutoscaleDecision(n - 1, "down:idle")
        return AutoscaleDecision(n, "hold")


class AutoscaleController:
    """Wires an :class:`Autoscaler` to a fleet ``Router``: each
    ``step()`` snapshots the fleet, decides, applies the change through
    ``Router.scale_to`` (drain-then-retire on the way down), and records
    the decision. ``decisions`` keeps the full (snapshot, decision,
    resolved) history, so a scaling trajectory can ride a measurement
    artifact."""

    def __init__(self, router, autoscaler: Optional[Autoscaler] = None):
        self.router = router
        self.autoscaler = autoscaler or Autoscaler()
        self.decisions: List[Dict[str, Any]] = []

    def step(self, now: Optional[float] = None) -> AutoscaleDecision:
        snapshot = self.router.autoscale_snapshot()
        decision = self.autoscaler.decide(snapshot)
        resolved = snapshot["replicas"]
        if decision.target != snapshot["replicas"]:
            resolved = self.router.scale_to(decision.target, now=now)
        self.decisions.append({"snapshot": snapshot,
                               "target": decision.target,
                               "reason": decision.reason,
                               "resolved": resolved})
        return decision
