"""In-process online policy server: bucket -> microbatch -> one jitted
flat-batched forward -> partition-degree decision.

The inference half of the stack (ISSUE 1): turns a shipped checkpoint into
an online "partition this arriving job" service. Three design rules carried
over from the training-side measurements:

* **Fixed compile shapes.** Every bucket runs ONE XLA program: the
  flat-rows batched forward (``GNNPolicy.flat_batched`` — never a vmapped
  apply, round-5 invariant) at a fixed batch size ``max_batch``. Partial
  flushes are padded by replicating the first request's rows; at a fixed
  program a request's output rows are bit-identical whatever rides in the
  other slots (XLA CPU tiles by shape, not by data — pinned in
  tests/test_serve.py), so batching can never change an answer, and each
  bucket compiles exactly once.
* **Deadline microbatching.** Requests queue per bucket and flush on fill
  or when the oldest has waited ``deadline_s`` (serve/microbatch.py) — the
  per-dispatch cost is amortised across the batch instead of paid per
  request.
* **Heuristic degraded mode.** When the queue saturates, a request fits no
  bucket, or the device forward fails, the answer
  comes from the rule-extracted ``FixedDegreePacking`` heuristic
  (envs/baselines.py) — the decision rule the shipped checkpoints
  themselves implement (docs/results_round5/rule_extraction.md), so
  degraded-mode answers agree with the policy at the extracted degree. The
  server never blocks on the device and never drops a request.

The server is single-threaded and clock-parameterised: ``submit``/``poll``
take an optional ``now`` so tests and load drivers drive time
deterministically; production callers just let it default to
``time.perf_counter``.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.envs.baselines import FixedDegreePacking
from ddls_tpu.envs.obs import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
from ddls_tpu.serve.bucketing import (BucketOverflowError, BucketSpec,
                                      ObsBucketer, default_buckets)
from ddls_tpu.serve.microbatch import MicrobatchEngine, PendingRequest

# the canonical 32-server extraction (rule_extraction.md): what the shipped
# ppo_device_trained / ppo_price_mixed policies implement
DEFAULT_FALLBACK_DEGREE = 8

# every encoded-obs key the batched forward stacks (envs/obs.py contract)
# PLUS action_set, which every heuristic-fallback path reads
# (envs/baselines.py _valid_actions); validated at submit so one malformed
# request errors to ITS caller instead of poisoning a batch (or latching
# degraded mode)
_REQUIRED_OBS_KEYS = ("node_features", "edge_features", "graph_features",
                      "edges_src", "edges_dst", "node_split", "edge_split",
                      "action_set", "action_mask")


def _validate_obs(obs: Dict[str, Any], widths: Dict[str, int]) -> None:
    """Reject a malformed obs at submit, before it can reach a batch: the
    fixed per-row feature widths come from the ``envs/obs.py`` encode
    contract; the config-dependent ``graph_features``/``action_mask``
    widths come from the server's model/config where known
    (``PolicyServer`` seeds them) and are otherwise pinned to the first
    accepted request — pins commit only after the WHOLE obs passes, so a
    rejected request can never poison the contract. Without the width
    checks a single bad request passes submit and fails at the device
    call — downgrading innocent co-batched requests to the heuristic (or
    wrongly latching degraded mode for a healthy backend)."""
    missing = [k for k in _REQUIRED_OBS_KEYS if k not in obs]
    if missing:
        raise ValueError(f"request obs missing keys {missing}")
    for key, dim in (("node_features", NODE_FEATURE_DIM),
                     ("edge_features", EDGE_FEATURE_DIM)):
        arr = np.asarray(obs[key])
        if arr.ndim != 2 or arr.shape[1] != dim:
            raise ValueError(f"obs[{key!r}] must be 2-D [rows, {dim}], "
                             f"got shape {arr.shape}")
    # split counts must be consistent with the rows actually present: an
    # inflated split would make pad_obs_to zero-fill phantom "real" rows
    # (served as a garbage policy decision), a negative one silently
    # truncates real rows — both are data errors owed to the caller
    for split_key, rows_key, row_count in (
            ("node_split", "node_features",
             int(np.asarray(obs["node_features"]).shape[0])),
            ("edge_split", "edge_features",
             int(np.asarray(obs["edge_features"]).shape[0]))):
        split = np.asarray(obs[split_key]).reshape(-1)
        if split.size != 1:
            raise ValueError(f"obs[{split_key!r}] must hold one count, "
                             f"got {split.size} values")
        count = int(split[0])
        if not 0 <= count <= row_count:
            raise ValueError(f"obs[{split_key!r}]={count} out of range "
                             f"for {row_count} {rows_key} rows")
    m = int(np.asarray(obs["edge_split"]).reshape(-1)[0])
    n = int(np.asarray(obs["node_split"]).reshape(-1)[0])
    for key in ("edges_src", "edges_dst"):
        arr = np.asarray(obs[key])
        if arr.ndim != 1 or arr.shape[0] < m:
            raise ValueError(f"obs[{key!r}] must be 1-D with >= "
                             f"edge_split={m} entries, got shape "
                             f"{arr.shape}")
        # REAL edges must point at REAL nodes of THIS graph: in the
        # flat-batched mega-graph (the aggregation's index form,
        # ops/segment.py) an out-of-range endpoint escapes its slot
        # (dst + k*N lands in a neighbour's node rows) and the
        # scatter silently changes a CO-BATCHED client's embedding —
        # the one way a request could break "batching never changes an
        # answer". Padded edges beyond edge_split are masked; no
        # constraint on them.
        real = arr[:m]
        if m and (int(real.min()) < 0 or int(real.max()) >= n):
            raise ValueError(
                f"obs[{key!r}] endpoints must lie in [0, "
                f"node_split={n}) for the first edge_split={m} edges; "
                f"got range [{int(real.min())}, {int(real.max())}]")
    pins: Dict[str, int] = {}
    for key in ("graph_features", "action_mask"):
        arr = np.asarray(obs[key])
        if arr.ndim != 1:
            raise ValueError(f"obs[{key!r}] must be 1-D, "
                             f"got shape {arr.shape}")
        expected = widths.get(key)
        if expected is None:
            pins[key] = int(arr.shape[0])
        elif int(arr.shape[0]) != expected:
            raise ValueError(f"obs[{key!r}] width {arr.shape[0]} != "
                             f"{expected} (this server's model)")
    n_mask = int(np.asarray(obs["action_mask"]).shape[0])
    if np.asarray(obs["action_set"]).shape != (n_mask,):
        raise ValueError(
            f"obs['action_set'] shape "
            f"{np.asarray(obs['action_set']).shape} != action_mask's "
            f"({n_mask},)")
    widths.update(pins)


@dataclass
class ServeResponse:
    request_id: int
    action: int
    source: str           # "policy" | "fallback"
    reason: str           # "batched" | "saturated" | "overflow"
                          # | "invalid" | "degraded"
    bucket_idx: Optional[int]
    latency_s: float
    batch_fill: Optional[int] = None   # real requests in the flushed batch


# trailing-window size for the percentile/occupancy samples: a long-lived
# server must not hold one float per request ever served (the counters
# above the window stay exact forever)
STATS_WINDOW = 8192

# batch-fill fractions land in (0, 1]: an eighth-ladder matches the
# default max_batch=8 (one bucket per possible fill count)
_OCCUPANCY_BUCKETS = tuple((i + 1) / 8 for i in range(8))


class ServeStats:
    """Serving accounting on the shared telemetry primitives (ISSUE 3):
    counters + fixed-bucket latency/occupancy histograms in a PRIVATE
    always-on ``telemetry.Registry`` — per-server isolation (concurrent
    servers must never share counters) and independence from the global
    telemetry enable switch (serve's counters are part of its contract,
    pinned bit-equal by tests/test_serve.py). ``summary()`` keeps its
    JSON shape; percentiles/occupancy read the histograms' trailing
    ``STATS_WINDOW`` windows — the exact semantics the hand-rolled deques
    had. ``registry.snapshot()`` is the report surface.
    """

    def __init__(self, registry: Optional[telemetry.Registry] = None):
        self.registry = (registry if registry is not None
                         else telemetry.Registry(enabled=True))
        r = self.registry
        self._requests = r.counter("serve.requests")
        self._policy = r.counter("serve.policy")
        self._fallback = r.counter("serve.fallback")
        self._flushes = r.counter("serve.flushes")
        self._degraded = r.counter("serve.degraded_transitions")
        self._compiles = r.gauge("serve.compiles")
        self._latency = r.histogram("serve.latency_s",
                                    window=STATS_WINDOW)
        self._occupancy = r.histogram("serve.batch_occupancy",
                                      buckets=_OCCUPANCY_BUCKETS,
                                      window=STATS_WINDOW)

    # --------------------------------------------------------------- intake
    def record_request(self) -> None:
        self._requests.inc()

    def record_bucket_hit(self, bucket_idx: int) -> None:
        self.registry.counter(f"serve.bucket_hits.{bucket_idx}").inc()

    def record_response(self, resp: ServeResponse) -> None:
        self._latency.observe(resp.latency_s)
        if resp.source == "policy":
            self._policy.inc()
        else:
            self._fallback.inc()
            self.registry.counter(
                f"serve.fallback_reason.{resp.reason}").inc()

    def record_flush(self, fill: int, capacity: int,
                     bucket_idx: Optional[int] = None,
                     cause: Optional[str] = None) -> None:
        self._flushes.inc()
        occ = fill / capacity
        self._occupancy.observe(occ)
        if bucket_idx is not None:
            self.registry.histogram(
                f"serve.batch_occupancy.bucket{bucket_idx}",
                buckets=_OCCUPANCY_BUCKETS,
                window=STATS_WINDOW).observe(occ)
        if cause is not None:
            self.registry.counter(f"serve.flush_cause.{cause}").inc()

    def record_degraded_transition(self) -> None:
        self._degraded.inc()

    # ------------------------------------------------------------ readbacks
    def _prefixed_counts(self, prefix: str) -> Dict[str, int]:
        return {name[len(prefix):]: value
                for name, value in self.registry.counter_items()
                if name.startswith(prefix)}

    @property
    def n_requests(self) -> int:
        return self._requests.value

    @property
    def n_policy(self) -> int:
        return self._policy.value

    @property
    def n_fallback(self) -> int:
        return self._fallback.value

    @property
    def n_flushes(self) -> int:
        return self._flushes.value

    @property
    def degraded_transitions(self) -> int:
        return self._degraded.value

    @property
    def n_compiles(self) -> int:
        return int(self._compiles.value or 0)

    @n_compiles.setter
    def n_compiles(self, value: int) -> None:
        self._compiles.set(int(value))

    @property
    def fallback_reasons(self) -> Dict[str, int]:
        return self._prefixed_counts("serve.fallback_reason.")

    @property
    def flush_causes(self) -> Dict[str, int]:
        return self._prefixed_counts("serve.flush_cause.")

    @property
    def bucket_hits(self) -> Dict[int, int]:
        return {int(k): v
                for k, v in self._prefixed_counts(
                    "serve.bucket_hits.").items()}

    @property
    def latencies_s(self):
        return self._latency.window

    @property
    def occupancies(self):
        return self._occupancy.window

    def per_bucket_occupancy(self) -> Dict[int, float]:
        """Mean batch-fill fraction per bucket ladder rung (over the
        trailing window) — the --stats-interval line's occupancy field."""
        out = {}
        for name, h in self.registry.histogram_items():
            if name.startswith("serve.batch_occupancy.bucket"):
                vals = h.window_values()
                if vals:
                    idx = int(name[len("serve.batch_occupancy.bucket"):])
                    out[idx] = float(np.mean(
                        np.asarray(vals, dtype=np.float64)))
        return out

    def summary(self) -> Dict[str, Any]:
        n_requests = self.n_requests
        n_fallback = self.n_fallback
        lat = self._latency
        return {
            "n_requests": n_requests,
            "n_policy": self.n_policy,
            "n_fallback": n_fallback,
            "fallback_rate": (n_fallback / n_requests
                              if n_requests else 0.0),
            "fallback_reasons": self.fallback_reasons,
            "bucket_hits": {str(k): v
                            for k, v in sorted(self.bucket_hits.items())},
            "n_flushes": self.n_flushes,
            "n_compiles": self.n_compiles,
            "p50_latency_ms": (lat.percentile(50) * 1e3
                               if lat.count else None),
            "p99_latency_ms": (lat.percentile(99) * 1e3
                               if lat.count else None),
            "batch_occupancy": (float(np.mean(np.asarray(
                self._occupancy.window_values(), dtype=np.float64)))
                                if self._occupancy.count else None),
            "flush_causes": self.flush_causes,
            "degraded_transitions": self.degraded_transitions,
        }


class BucketForward:
    """The fixed-shape batched forward for one bucket ladder.

    ``forward(obs_list)`` stacks up to ``max_batch`` same-bucket
    observations (padding free slots with replicas of the first — masked
    rows and replica rows change no real output bits at a fixed program
    shape) and runs ``GNNPolicy.flat_batched`` through one jitted call,
    returning per-request (logits, values) as numpy. One XLA program per
    bucket, compiled on that bucket's first flush.
    """

    def __init__(self, model, params, max_batch: int,
                 apply_fn: Optional[Callable] = None):
        import jax

        from ddls_tpu.models.policy import batched_policy_apply

        self.model = model
        self.params = params
        self.max_batch = int(max_batch)
        raw = apply_fn or (lambda p, o: batched_policy_apply(model, p, o))
        self._jit = jax.jit(raw)
        self._compiled_shapes: set = set()
        self._stack_bufs: Dict[tuple, Dict[str, np.ndarray]] = {}

    @property
    def n_compiles(self) -> int:
        return len(self._compiled_shapes)

    def stack(self, obs_list: Sequence[Dict[str, np.ndarray]]
              ) -> Tuple[Dict[str, np.ndarray], int]:
        """Host-side batch assembly, separated from the device call so the
        server can tell malformed request DATA (stack fails here) apart
        from a dead device BACKEND (run fails below). The stacked batch
        is assembled into a per-shape REUSED buffer, so steady-state
        flushes allocate nothing. Reuse is safe because ``run`` DRAINS
        the forward (``jax.device_get``) before returning, and the next
        ``stack`` cannot happen until then — NOT because jax copies the
        input: its CPU client zero-copy ALIASES page-aligned host
        buffers (rl/rollout.py round-7 discovery), so making ``run``
        async would require a fresh buffer per flush."""
        if not obs_list:
            raise ValueError("empty batch")
        if len(obs_list) > self.max_batch:
            raise ValueError(f"batch of {len(obs_list)} exceeds max_batch "
                             f"{self.max_batch}")
        n_real = len(obs_list)
        filled = list(obs_list) + [obs_list[0]] * (self.max_batch - n_real)
        arrays = {k: [np.asarray(o[k]) for o in filled]
                  for k in ("node_features", "edge_features",
                            "graph_features", "edges_src", "edges_dst",
                            "node_split", "edge_split", "action_mask")}
        shape_key = tuple(sorted((k, v[0].shape, str(v[0].dtype))
                                 for k, v in arrays.items()))
        stacked = self._stack_bufs.get(shape_key)
        if stacked is None:
            stacked = {k: np.empty((self.max_batch,) + v[0].shape,
                                   v[0].dtype) for k, v in arrays.items()}
            self._stack_bufs[shape_key] = stacked
        for k, v in arrays.items():
            np.stack(v, out=stacked[k])
        return stacked, n_real

    def run(self, stacked: Dict[str, np.ndarray], n_real: int
            ) -> Tuple[np.ndarray, np.ndarray]:
        import jax

        self._compiled_shapes.add(
            tuple(sorted((k, v.shape) for k, v in stacked.items())))
        logits, values = jax.device_get(self._jit(self.params, stacked))
        return np.asarray(logits)[:n_real], np.asarray(values)[:n_real]

    def forward(self, obs_list: Sequence[Dict[str, np.ndarray]]
                ) -> Tuple[np.ndarray, np.ndarray]:
        stacked, n_real = self.stack(obs_list)
        return self.run(stacked, n_real)


class PolicyServer:
    """Batched online partition-degree serving from a policy's params.

    Parameters
    ----------
    model, params : the ``GNNPolicy`` and its (restored) variables.
    buckets : (max_nodes, max_edges) ladder; defaults to a 3-step halving
        ladder under ``max_nodes``/``max_edges``.
    max_batch : microbatch size = the fixed compile batch per bucket.
    deadline_s : latency budget before a partial batch flushes.
    max_queue : total queued requests before saturation fallback.
    fallback : heuristic actor for degraded mode (default
        ``FixedDegreePacking(8)``, the checkpoint-extracted rule).
    graph_feature_dim : the obs encoder's graph-vector width under the
        model's training config (``build_model_from_config`` returns it).
        When given, a request from a client built against a DIFFERENT env
        config (e.g. without candidate-price features) is rejected at
        submit instead of failing inside the device call — which would
        wrongly latch degraded mode on a healthy backend. When omitted,
        the width is pinned to the first accepted request.
    apply_fn : test hook — replaces the batched forward (e.g. with one
        that raises, to simulate a dead device backend).
    clock : test hook — the time source for deadlines/latency.
    """

    def __init__(self, model, params,
                 buckets: Optional[Sequence[BucketSpec]] = None,
                 max_nodes: int = 32, max_edges: Optional[int] = None,
                 max_batch: int = 8, deadline_s: float = 0.01,
                 max_queue: int = 64,
                 fallback=None,
                 graph_feature_dim: Optional[int] = None,
                 apply_fn: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter):
        # arena reuse: bucketed obs land in recycled per-bucket arrays
        # (pad_obs_to out=); leases are released at the end of each
        # flush in _run_batch, after the batch (or its fallback) is
        # fully resolved — the pool bound tracks the queue budget
        self.bucketer = ObsBucketer(
            buckets if buckets is not None
            else default_buckets(max_nodes, max_edges),
            reuse_arenas=True, max_pool_per_bucket=max(int(max_queue), 1))
        self.engine = MicrobatchEngine(len(self.bucketer.buckets),
                                       max_batch=max_batch,
                                       deadline_s=deadline_s,
                                       max_queue=max_queue)
        self._forward = BucketForward(model, params, max_batch,
                                      apply_fn=apply_fn)
        self.fallback = (fallback if fallback is not None
                         else FixedDegreePacking(
                             degree=DEFAULT_FALLBACK_DEGREE))
        self.clock = clock
        self.stats = ServeStats()
        self.degraded = False
        # fleet lifecycle flags (ISSUE 8): ``draining`` tells a Router to
        # stop routing here while queued work finishes normally (policy
        # answers, never a mid-swap degraded latch); ``closed`` rejects
        # new submits after close()
        self.draining = False
        self.closed = False
        self._next_id = 0
        self._ready: List[ServeResponse] = []
        self._submit_time: Dict[int, float] = {}
        # config-dependent obs widths (see _validate_obs): action width
        # always comes from the model itself; graph width from the
        # training config when the caller knows it, else pinned at the
        # first accepted request
        self._obs_widths: Dict[str, int] = {}
        n_actions = getattr(model, "n_actions", None)
        if n_actions is not None:
            self._obs_widths["action_mask"] = int(n_actions)
        if graph_feature_dim is not None:
            self._obs_widths["graph_features"] = int(graph_feature_dim)

    # ---------------------------------------------------------------- intake
    def submit(self, obs: Dict[str, np.ndarray],
               now: Optional[float] = None,
               meta: Optional[dict] = None) -> int:
        """Accept one request; returns its request_id. The decision arrives
        via ``poll``/``drain`` — immediately (fallback paths) or once its
        microbatch flushes. Raises ``ValueError`` (before any state
        changes) for an obs missing required keys or mis-shaped — data
        errors belong to the submitting caller, never to the batch."""
        if self.closed:
            raise RuntimeError("PolicyServer is closed")
        _validate_obs(obs, self._obs_widths)
        now = self.clock() if now is None else now
        rid = self._next_id
        self._next_id += 1
        self.stats.record_request()
        self._submit_time[rid] = now

        # fallback answers complete at the clock's now, not the (possibly
        # backdated) arrival instant `now` — a caller submitting arrivals
        # late (a real-time driving loop reaching a request after a
        # blocking forward) must still see that wait in latency
        if self.degraded:
            self._resolve_fallback(rid, obs, self.clock(), reason="degraded")
            return rid
        if self.engine.would_saturate():
            # answer NOW from the heuristic rather than queue beyond the
            # latency budget — saturation must degrade quality, not
            # availability
            self._resolve_fallback(rid, obs, self.clock(),
                                   reason="saturated")
            return rid
        try:
            idx, bucketed = self.bucketer.bucket_obs(obs)
        except BucketOverflowError:
            self._resolve_fallback(rid, obs, self.clock(), reason="overflow")
            return rid
        self.stats.record_bucket_hit(idx)
        self.engine.submit(PendingRequest(
            request_id=rid, bucket_idx=idx, obs=bucketed,
            enqueue_time=now, meta=meta))
        return rid

    # ---------------------------------------------------------------- serving
    def poll(self, now: Optional[float] = None,
             force: bool = False) -> List[ServeResponse]:
        """Flush every due microbatch and return all completed responses
        (including fallback answers resolved at submit time)."""
        real_time = now is None
        now = self.clock() if real_time else now
        for idx, reqs in self.engine.due_batches(now, force=force):
            self._run_batch(idx, reqs, now, reread_clock=real_time,
                            force=force)
        out, self._ready = self._ready, []
        return out

    def drain(self, now: Optional[float] = None) -> List[ServeResponse]:
        """Force-flush everything still queued (shutdown / end of input)."""
        return self.poll(now=now, force=True)

    def serve_one(self, obs: Dict[str, np.ndarray]) -> ServeResponse:
        """Synchronous single-request convenience: submit + immediate
        drain, matched by request id — responses the forced drain resolves
        for OTHER queued requests stay pending for the caller's next
        ``poll``. Runs the same fixed-shape program as full batches, so
        the answer is bit-identical to the batched path."""
        rid = self.submit(obs)
        resolved = self.drain()
        mine = next(r for r in resolved if r.request_id == rid)
        self._ready.extend(r for r in resolved if r.request_id != rid)
        return mine

    def next_deadline(self) -> Optional[float]:
        return self.engine.next_deadline()

    def queued(self) -> int:
        return self.engine.queued()

    # ------------------------------------------------------- fleet lifecycle
    def begin_drain(self) -> None:
        """Stop being a routing target (the fleet Router consults
        ``draining``); queued work keeps flushing normally via ``poll``.
        Already-admitted requests MUST still be answered on the normal
        path — a draining replica never latches degraded and never
        drops (ISSUE 8 satellite)."""
        self.draining = True

    def end_drain(self) -> None:
        self.draining = False

    def swap_params(self, params, now: Optional[float] = None) -> None:
        """Checkpoint hot-swap, drain-then-swap: everything already
        admitted is force-flushed and answered by the OLD params (policy
        answers — a swap must never produce dropped or degraded-mode
        decisions), the answers stay queued for the caller's next
        ``poll``, then the forward's params are replaced in place. The
        compiled bucket programs are shape-keyed, so the swap costs no
        recompile."""
        # drain FIRST, then re-park: ``poll`` rebinds ``_ready`` to a
        # fresh list, so extending the pre-drain binding would strand
        # the answers in an orphaned object
        pending = self.drain(now=now)
        self._ready.extend(pending)
        self._forward.params = params

    def reconfigure_buckets(self, buckets: Sequence[BucketSpec],
                            now: Optional[float] = None) -> None:
        """Bucket-ladder re-fit: drain (old ladder answers everything
        already admitted), then rebuild the bucketer + microbatch queues
        on the new ladder. New buckets compile on their first flush;
        stats/degraded state carry over untouched."""
        pending = self.drain(now=now)  # see swap_params: drain rebinds
        self._ready.extend(pending)
        eng = self.engine
        self.bucketer = ObsBucketer(
            buckets, reuse_arenas=True,
            max_pool_per_bucket=max(int(eng.max_queue), 1))
        self.engine = MicrobatchEngine(len(self.bucketer.buckets),
                                       max_batch=eng.max_batch,
                                       deadline_s=eng.deadline_s,
                                       max_queue=eng.max_queue)

    def close(self, now: Optional[float] = None) -> List[ServeResponse]:
        """Drain-aware, idempotent shutdown: the first call answers every
        already-admitted request (forced flush — policy answers, plus
        anything already resolved and unfetched) and returns those
        responses; later calls return ``[]`` and change nothing. New
        submits raise after close. Safe under the fleet's concurrent
        lifecycle (autoscaler retire racing a router close: whichever
        runs first does the drain, the other is a no-op)."""
        if self.closed:
            return []
        self.draining = True
        responses = self.drain(now=now)
        self.closed = True
        return responses

    # --------------------------------------------------------------- internal
    def _run_batch(self, bucket_idx: int, reqs: List[PendingRequest],
                   now: float, reread_clock: bool = True,
                   force: bool = False) -> None:
        try:
            self._run_batch_inner(bucket_idx, reqs, now, reread_clock,
                                  force)
        finally:
            # every path below is done with the bucketed obs (policy
            # answers read only logits; fallback answers resolve
            # synchronously inside), so the arenas recycle here
            for r in reqs:
                self.bucketer.release(bucket_idx, r.obs)

    def _run_batch_inner(self, bucket_idx: int, reqs: List[PendingRequest],
                         now: float, reread_clock: bool = True,
                         force: bool = False) -> None:
        # flush-cause attribution: a full batch always means fill (the
        # engine pops full batches before deadline/force partials)
        cause = ("fill" if len(reqs) >= self.engine.max_batch
                 else ("drain" if force else "deadline"))
        self.stats.record_flush(len(reqs), self.engine.max_batch,
                                bucket_idx=bucket_idx, cause=cause)
        try:
            stacked, n_real = self._forward.stack([r.obs for r in reqs])
        except Exception:
            # host-side batch assembly failed: malformed request DATA
            # (wrong dtype/feature width slipping past submit validation),
            # not a device failure — answer this batch from the heuristic
            # but do NOT latch degraded, the backend is healthy
            done = self.clock() if reread_clock else now
            for r in reqs:
                self._resolve_fallback(r.request_id, r.obs, done,
                                       reason="invalid")
            return
        try:
            logits, _values = self._forward.run(stacked, n_real)
            self.stats.n_compiles = self._forward.n_compiles
        except Exception as exc:
            # device backend died mid-flight: answer this batch from the
            # heuristic and stop offering the device path to later
            # requests — LOUDLY, because the heuristic answers the same
            # action the shipped policy does and nothing else would
            # show the difference. Real-time mode re-reads the
            # clock so the (possibly seconds-long) failed forward is
            # charged to these requests' latency, same as the policy path.
            if not self.degraded:
                warnings.warn(
                    f"serve: batched forward failed ({exc!r}); latching "
                    "degraded mode — the heuristic fallback answers "
                    "from here on")
                self.stats.record_degraded_transition()
                telemetry.record_event("serve_degraded",
                                       bucket_idx=bucket_idx,
                                       batch_fill=len(reqs))
            self.degraded = True
            done = self.clock() if reread_clock else now
            for r in reqs:
                self._resolve_fallback(r.request_id, r.obs, done,
                                       reason="degraded")
            return
        # real-time mode charges the forward itself to latency; explicit
        # ``now`` (tests, virtual clocks) stays deterministic
        done = self.clock() if reread_clock else now
        for r, lg in zip(reqs, logits):
            # logits are already log(0)-masked by the model; argmax can
            # never pick an invalid action
            action = int(np.argmax(lg))
            self._emit(ServeResponse(
                request_id=r.request_id, action=action, source="policy",
                reason="batched", bucket_idx=bucket_idx,
                latency_s=done - self._submit_time.pop(r.request_id),
                batch_fill=len(reqs)))

    def _resolve_fallback(self, rid: int, obs, done: float,
                          reason: str) -> None:
        """``done`` is the completion timestamp (the fallback answers
        synchronously, so completion = when the caller reached us, not
        the request's arrival instant)."""
        action = int(self.fallback.compute_action(obs))
        self._emit(ServeResponse(
            request_id=rid, action=action, source="fallback", reason=reason,
            bucket_idx=None,
            latency_s=done - self._submit_time.pop(rid)))

    def _emit(self, resp: ServeResponse) -> None:
        self.stats.record_response(resp)
        self._ready.append(resp)


def build_model_from_config(config_path: str, config_name: str,
                            overrides: Sequence[str] = ()) -> Tuple:
    """(model, n_actions, graph_feature_dim) from the training config tree
    — same model merge as train_from_config.build_epoch_loop_kwargs (model
    group + algo-level model overrides), so a checkpoint restores onto the
    exact architecture it was trained with (the shipped PPO checkpoints
    override fcnet_hiddens at the algo level; a default-architecture
    ``GNNPolicy`` cannot load them). ``graph_feature_dim`` is the obs
    encoder's graph-vector width under this config (envs/obs.py: base
    features + action mask + candidate prices when
    ``obs_include_candidate_prices``) — what a template obs for param init
    must use."""
    import copy

    from ddls_tpu.config import load_config
    from ddls_tpu.envs.obs import graph_feature_width
    from ddls_tpu.train.loops import build_policy_from_model_config
    from ddls_tpu.utils.common import recursive_update

    cfg = load_config(config_path, config_name, list(overrides or []))
    model_cfg = copy.deepcopy(cfg.get("model") or {})
    algo_model = (cfg.get("algo") or {}).get("model")
    if algo_model:
        model_cfg = recursive_update(model_cfg, copy.deepcopy(algo_model))
    env_cfg = cfg["env_config"]
    n_actions = int(env_cfg["max_partitions_per_op"]) + 1
    graph_feature_dim = graph_feature_width(
        n_actions, bool(env_cfg.get("obs_include_candidate_prices")))
    return (build_policy_from_model_config(n_actions, model_cfg),
            n_actions, graph_feature_dim)


def checkpoint_graph_feature_dim(params) -> Optional[int]:
    """The graph-vector input width a restored checkpoint's params were
    trained with — ``graph_module/Dense_0/kernel``'s input dimension (the
    attribute names are frozen by the shipped checkpoints, CLAUDE.md).
    Lets a caller reject a checkpoint/config pairing at startup (e.g.
    the plain-obs 34-wide ``ppo_device_trained`` under a price-features
    51-wide config) instead of crashing inside the first forward — which
    the server would misread as a dead device backend and latch degraded
    mode. Returns None for an unrecognised param-tree shape."""
    try:
        kernel = params["params"]["graph_module"]["Dense_0"]["kernel"]
        return int(kernel.shape[0])
    except (KeyError, TypeError, IndexError, AttributeError):
        return None


def load_checkpoint_params(checkpoint_path: str):
    """Restore a shipped checkpoint's policy variables without building a
    training loop: raw (target-free) restore of the saved TrainState,
    returning its ``params`` subtree (the flax variables dict
    ``{"params": ...}`` that ``model.apply`` takes)."""
    from ddls_tpu.train.checkpointer import restore_train_state

    raw = restore_train_state(checkpoint_path)
    if not isinstance(raw, dict) or "params" not in raw:
        raise ValueError(
            f"checkpoint at {checkpoint_path} has no 'params' subtree "
            f"(keys: {list(raw) if isinstance(raw, dict) else type(raw)})")
    return raw["params"]
