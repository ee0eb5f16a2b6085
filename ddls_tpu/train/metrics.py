"""Lazy training-metric materialisation: device scalars as futures.

The pipelined epoch loop (train/loops.py, docs/perf_round6.md) never
blocks the hot collect→update path on a device→host transfer: learner
metrics stay on device as jax arrays, wrapped in a ``LazyMetrics``
mapping that rides the epoch's results dict unchanged. They are
materialised — ONE batched ``jax.device_get`` for everything pending —
only at a logging/eval boundary (``metrics_sync_interval`` epochs, a
W&B flatten, a Logger disk flush, or first item access), so the per-
update blocking device round trip the sequential loop paid under
``train.host_sync`` disappears from steady state (CLAUDE.md invariant:
metrics are futures until a sync boundary).

``LazyMetrics`` is a ``Mapping``: ``results["learner"]["total_loss"]``
still works everywhere (first scalar access materialises the whole
dict), ``"k" in m`` / ``len(m)`` / iteration never touch the device,
and a materialised instance is indistinguishable from the plain float
dict the sequential loop returns — the bit-exactness pin in
tests/test_train_pipeline.py compares them directly.
"""
from __future__ import annotations

import threading
from collections.abc import Mapping
from typing import Any, Dict, Iterable, List, Optional


def as_float(value) -> float:
    """Scalar coercion for metric values that may live on device. Use at
    sync boundaries only — on a device array this blocks on the
    transfer, which is exactly what the hot loop must never do."""
    import numpy as np

    return float(np.asarray(value))


class LazyMetrics(Mapping):
    """Mapping over scalar training metrics with deferred device→host.

    ``device_metrics`` is either one dict of device (or host) scalars,
    or — with ``reduce="mean"`` — a LIST of such dicts (the DQN epoch
    shape: many updates per epoch, logged as their per-key mean) or one
    dict of ``[U]``-STACKED device arrays (the fused epoch shape,
    rl/fused.py: a ``lax.scan`` stacks each update's metrics, and the
    whole epoch's dict is fetched in one transfer then averaged).
    ``extras`` are host-side scalars (counters the loop already owns)
    merged in at materialisation and readable/writable without any
    device traffic.
    """

    __slots__ = ("_device", "_host", "_extras", "_reduce", "_lock")

    def __init__(self, device_metrics=None,
                 extras: Optional[Dict[str, Any]] = None,
                 reduce: Optional[str] = None):
        if reduce not in (None, "mean"):
            raise ValueError(f"unknown reduce {reduce!r}")
        if reduce is None and isinstance(device_metrics, list):
            raise ValueError("a list of metric dicts needs reduce='mean'")
        self._device = device_metrics
        self._host: Optional[Dict[str, float]] = None
        self._extras: Dict[str, Any] = dict(extras or {})
        self._reduce = reduce
        self._lock = threading.Lock()
        if device_metrics is None or (isinstance(device_metrics, list)
                                      and not device_metrics):
            self._host = {}
            self._device = None

    # ------------------------------------------------------------ futures
    @property
    def pending(self) -> bool:
        return self._host is None

    def device_values(self):
        """The unfetched device tree (None once materialised) — what a
        group sync hands to one batched ``jax.device_get``."""
        return self._device if self._host is None else None

    def _finish(self, fetched) -> Dict[str, float]:
        """Install the host values for a tree fetched elsewhere (the
        group-sync path); idempotent under the instance lock."""
        with self._lock:
            if self._host is None:
                self._host = self._reduce_host(fetched)
                self._device = None
            return self._host

    def _reduce_host(self, fetched) -> Dict[str, float]:
        import numpy as np

        if self._reduce == "mean":
            if isinstance(fetched, dict):
                # fused-epoch shape: one dict of [U]-stacked arrays;
                # accumulate in f64 exactly like the list path below
                # (float(v) per update, then a python-float mean)
                return {k: float(np.mean(np.asarray(v, np.float64)))
                        for k, v in fetched.items()}
            dicts = [{k: float(v) for k, v in d.items()} for d in fetched]
            return {k: float(np.mean([d[k] for d in dicts]))
                    for k in (dicts[0] if dicts else {})}
        return {k: float(v) for k, v in fetched.items()}

    def materialize(self) -> Dict[str, float]:
        """Host dict of floats (device + extras); fetches at most once.
        This is the ONLY place a LazyMetrics touches the device."""
        if self._host is None:
            import jax

            with self._lock:
                if self._host is None:
                    self._host = self._reduce_host(
                        jax.device_get(self._device))
                    self._device = None
        return {**self._host, **{k: as_float(v)
                                 for k, v in self._extras.items()}}

    @staticmethod
    def materialize_group(group: Iterable["LazyMetrics"]) -> None:
        """Materialise every pending instance with ONE ``device_get``
        over all their trees — the metrics-ring sync boundary."""
        import jax

        pending = [lm for lm in group if lm.pending]
        if not pending:
            return
        fetched = jax.device_get([lm._device for lm in pending])
        for lm, host in zip(pending, fetched):
            lm._finish(host)

    # ------------------------------------------------------------ mapping
    def _keys(self) -> List[str]:
        if self._host is not None:
            base = list(self._host)
        elif self._reduce == "mean" and not isinstance(self._device,
                                                       dict):
            base = list(self._device[0]) if self._device else []
        else:
            base = list(self._device or {})
        return base + [k for k in self._extras if k not in base]

    def __getitem__(self, key: str):
        if key in self._extras:
            return self._extras[key]
        return self.materialize()[key]

    def __setitem__(self, key: str, value) -> None:
        """Host-side extras only (e.g. ES's eval_fitness_mean, DQN's
        replay_size) — never a fresh device future."""
        self._extras[key] = value

    def __contains__(self, key) -> bool:
        return key in self._keys()

    def __iter__(self):
        return iter(self._keys())

    def __len__(self) -> int:
        return len(self._keys())

    def __repr__(self) -> str:
        state = "pending" if self.pending else "materialized"
        return f"LazyMetrics({state}, keys={self._keys()})"

    def __eq__(self, other) -> bool:
        if isinstance(other, (LazyMetrics, dict)):
            return dict(self.materialize()) == dict(
                other.materialize() if isinstance(other, LazyMetrics)
                else other)
        return NotImplemented

    def __ne__(self, other) -> bool:
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq


def materialize_results(node):
    """Deep-copy a results tree with every ``LazyMetrics`` replaced by
    its materialised float dict (and shared containers copied), so the
    result is plain-picklable. Called by persistence boundaries
    (train/logger.py's background save thread, the W&B flatten) — i.e.
    the sync happens off the epoch critical path."""
    if isinstance(node, LazyMetrics):
        return node.materialize()
    if isinstance(node, dict):
        return {k: materialize_results(v) for k, v in node.items()}
    if isinstance(node, list):
        return [materialize_results(v) for v in node]
    if isinstance(node, tuple):
        return tuple(materialize_results(v) for v in node)
    return node
