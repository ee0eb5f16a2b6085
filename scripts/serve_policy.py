"""Online policy-serving front end: JSON requests on stdin, decisions on
stdout.

Each input line is one request::

    {"id": "job-17", "obs": {"node_features": [[...]], "edge_features":
     [[...]], "graph_features": [...], "edges_src": [...], "edges_dst":
     [...], "node_split": [n], "edge_split": [m], "action_set": [...],
     "action_mask": [...]}}

``obs`` is the encoded observation dict ``envs/obs.py`` produces (any pad
bound — the server re-pads onto its bucket ladder). Each answered request
emits one line::

    {"id": "job-17", "action": 8, "source": "policy", "reason": "batched",
     "bucket": 1, "latency_ms": 3.2}

Requests route through the fleet ``Router`` (``ddls_tpu.serve.fleet``)
into ``--replicas N`` PolicyServers — one by default, so the protocol
and answer bits match the single-server stack exactly — each
microbatching per bucket (flush on fill or deadline; heuristic
``FixedDegreePacking`` fallback when the queue saturates, a graph fits
no bucket, or the device backend fails). An optional ``tenant`` request
field feeds consistent-hash affinity routing and, with ``--quota-rps``,
per-tenant token-bucket admission (quota sheds answer ``action: null``,
``source: "shed"``). A summary JSON line with the fleet counters lands
on stderr at EOF.

The production path serves on the backend JAX gives it and exits
non-zero when that is the CPU without ``JAX_PLATFORMS=cpu`` having been
set on purpose; it never switches platform after a failure.

``--selftest`` runs the whole pipeline end-to-end on a synthetic dataset
(CPU-pinned): real env observations through the bucketed
batched forward, plus a forced-saturation pass through the fallback, then
prints one ``{"selftest": "ok", ...}`` line and exits 0.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_OBS_INT_KEYS = ("edges_src", "edges_dst", "node_split", "edge_split",
                 "action_set", "action_mask")


class LineAssembler:
    """Splits raw fd chunks into complete lines. The serving loop selects
    on the stdin fd, and select() reports readable once per CHUNK, not
    once per line — so every complete line in a chunk must be handled
    before returning to select. A buffered ``sys.stdin.readline()`` there
    would return line 1, drain the fd into Python's buffer, and leave
    lines 2..N stranded while select blocks on the now-unreadable fd: a
    long-lived client that writes a burst and waits for answers deadlocks
    (EOF-terminated pipes mask this — a closed pipe keeps the fd
    readable)."""

    def __init__(self):
        self._buf = b""

    def feed(self, chunk: bytes) -> list:
        self._buf += chunk
        *lines, self._buf = self._buf.split(b"\n")
        return [ln.decode("utf-8", "replace") for ln in lines]

    def flush(self) -> list:
        """The final unterminated line at EOF, if any."""
        buf, self._buf = self._buf, b""
        return [buf.decode("utf-8", "replace")] if buf.strip() else []


def obs_from_json(obj: dict) -> dict:
    obs = {}
    for key, val in obj.items():
        dtype = np.int32 if key in _OBS_INT_KEYS else np.float32
        obs[key] = np.asarray(val, dtype=dtype)
    for key in ("node_split", "edge_split"):
        obs[key] = np.atleast_1d(obs[key])
    return obs


def build_model_from_config(config_path, config_name, overrides):
    """(model, n_actions, graph_feature_dim) — checkpoint-faithful model
    construction lives with the serve subsystem."""
    from ddls_tpu.serve import build_model_from_config as _build

    return _build(config_path, config_name, overrides)


def make_fleet(args, model, params, graph_feature_dim=None):
    """The stdin front end serves through the fleet Router (ISSUE 8) —
    one replica by default, so the stdout protocol and answer bits are
    exactly the single-server path's; ``--replicas N`` scales out with
    each replica compiling its own bucket ladder. Quota shedding only
    arms when ``--quota-rps`` is set (a shed answers ``action: null``
    with ``source: "shed"`` — clients opting into quotas opt into
    refusals)."""
    from ddls_tpu.envs.baselines import FixedDegreePacking
    from ddls_tpu.serve import build_fleet

    buckets = None
    if args.buckets:
        buckets = [tuple(int(x) for x in b.split("x"))
                   for b in args.buckets.split(",")]
    return build_fleet(
        model, params, n_replicas=args.replicas, routing=args.routing,
        shed_enabled=bool(args.quota_rps),
        quota_rps=args.quota_rps or None,
        quota_burst=args.quota_burst or None,
        buckets=buckets,
        max_nodes=args.max_nodes, max_batch=args.max_batch,
        deadline_s=args.deadline_ms / 1e3, max_queue=args.max_queue,
        graph_feature_dim=graph_feature_dim,
        fallback=FixedDegreePacking(degree=args.degree))


def template_obs(max_nodes: int, max_edges: int, n_actions: int,
                 graph_feature_dim: int) -> dict:
    """A zero observation at a bucket shape — enough to init params.
    Feature widths come from the encode contract (envs/obs.py), not
    hardcoded: a width drift would init params the real requests can't
    run through."""
    from ddls_tpu.envs.obs import EDGE_FEATURE_DIM, NODE_FEATURE_DIM

    return {
        "action_set": np.arange(n_actions, dtype=np.int32),
        "action_mask": np.ones(n_actions, np.int32),
        "node_features": np.zeros((max_nodes, NODE_FEATURE_DIM),
                                  np.float32),
        "edge_features": np.zeros((max_edges, EDGE_FEATURE_DIM),
                                  np.float32),
        "graph_features": np.zeros(graph_feature_dim, np.float32),
        "edges_src": np.zeros(max_edges, np.int32),
        "edges_dst": np.zeros(max_edges, np.int32),
        "node_split": np.array([1], np.int32),
        "edge_split": np.array([0], np.int32),
    }


def dataset_pad_bounds(dataset_dir: str) -> dict:
    """Max op/dep counts over a dataset's graph files: the tight
    observation pad and the top of the serving bucket ladder (padded
    rows are fully masked, so a tighter pad changes no output bit)."""
    from ddls_tpu.demands.jobs_generator import discover_profile_files
    from ddls_tpu.graphs.readers import read_graph_file

    paths = discover_profile_files(dataset_dir)
    if not paths:
        # max_nodes=0 would read as "padding disabled" downstream and
        # break obs stacking with a far-away shape error
        raise FileNotFoundError(f"no graph profiles in {dataset_dir}")
    graphs = [read_graph_file(path) for path in paths]
    return {"max_nodes": max(g.n_ops for g in graphs),
            "max_edges": max(g.n_deps for g in graphs)}


def selftest_obs_pool(dataset_dir: str, bounds: dict, n_obs: int) -> list:
    """Real encoded observations: step one canonical-scenario env over
    the dataset with random valid actions and snapshot each decision's
    obs (the arriving population a deployed server would see)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.scenarios import ScenarioSpec, env_kwargs

    spec = ScenarioSpec(name="serve_selftest", pad_obs=dict(bounds))
    env = RampJobPartitioningEnvironment(
        **env_kwargs(spec, dataset_dir=dataset_dir))
    obs = env.reset(seed=0)
    rng = np.random.RandomState(0)
    pool = []
    while len(pool) < n_obs:
        pool.append({k: np.copy(v) for k, v in obs.items()})
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            obs = env.reset(seed=len(pool))
    return pool


def run_selftest(args) -> int:
    """End-to-end smoke on CPU: real env obs -> bucketed batched serving,
    then a forced-saturation fallback pass. One JSON line, rc 0 on ok."""
    import tempfile

    import jax

    from ddls_tpu.envs.baselines import FixedDegreePacking
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
    from ddls_tpu.models.policy import GNNPolicy
    from ddls_tpu.serve import PolicyServer, default_buckets

    with tempfile.TemporaryDirectory(prefix="serve_selftest_") as dataset_dir:
        generate_pipedream_txt_files(dataset_dir, n_cnn=3, n_translation=2,
                                     seed=0, min_ops=8, max_ops=16)
        bounds = dataset_pad_bounds(dataset_dir)
        pool = selftest_obs_pool(dataset_dir, bounds,
                                 args.selftest_requests)
    n_actions = int(np.asarray(pool[0]["action_mask"]).shape[0])
    buckets = default_buckets(bounds["max_nodes"], bounds["max_edges"])
    model = GNNPolicy(n_actions=n_actions)
    params = model.init(jax.random.PRNGKey(0),
                        jax.tree_util.tree_map(np.asarray, pool[0]))

    server = PolicyServer(model, params, buckets=buckets,
                          max_batch=args.max_batch,
                          deadline_s=args.deadline_ms / 1e3,
                          fallback=FixedDegreePacking(degree=args.degree))
    ids = [server.submit(o) for o in pool]
    responses = server.drain()
    ok = (sorted(r.request_id for r in responses) == sorted(ids)
          and all(np.asarray(pool[r.request_id]["action_mask"])[r.action]
                  for r in responses))

    # saturation pass: a 2-deep queue answers the overflow from the
    # heuristic without dropping anything
    sat = PolicyServer(model, params, buckets=buckets,
                       max_batch=args.max_batch, deadline_s=10.0,
                       max_queue=2,
                       fallback=FixedDegreePacking(degree=args.degree))
    rule = FixedDegreePacking(degree=args.degree)
    for o in pool:
        sat.submit(o)
    sat_responses = sat.poll() + sat.drain()
    fb = [r for r in sat_responses if r.source == "fallback"]
    ok = (ok and len(sat_responses) == len(pool) and len(fb) > 0
          and all(r.action == rule.compute_action(pool[r.request_id])
                  for r in fb))

    print(json.dumps({"selftest": "ok" if ok else "FAILED",
                      "n_requests": len(pool),
                      "n_fallback_saturated": len(fb),
                      **{f"serve_{k}": v
                         for k, v in server.stats.summary().items()
                         if not isinstance(v, dict)}}), flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Serve partition-degree decisions over stdin/stdout")
    parser.add_argument("--checkpoint", default=None,
                        help="orbax checkpoint dir (omit for random-init "
                             "params — selftest/smoke only)")
    parser.add_argument("--config-path",
                        default=os.path.join(os.path.dirname(__file__),
                                             "ramp_job_partitioning_configs"))
    parser.add_argument("--config-name", default="rllib_config")
    parser.add_argument("--override", action="append", default=[],
                        help="config override, e.g. env_config=env_load32")
    parser.add_argument("--buckets", default=None,
                        help="explicit ladder, e.g. '16x32,32x96'")
    parser.add_argument("--max-nodes", type=int, default=32,
                        help="top bucket bound when --buckets is omitted")
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--deadline-ms", type=float, default=10.0)
    parser.add_argument("--max-queue", type=int, default=64)
    parser.add_argument("--replicas", type=int, default=1,
                        help="PolicyServer replicas behind the fleet "
                             "Router (each compiles its own bucket "
                             "ladder; stdout protocol unchanged)")
    parser.add_argument("--routing",
                        choices=("affinity", "least_loaded",
                                 "round_robin", "hash"),
                        default="affinity",
                        help="fleet routing policy (affinity = "
                             "consistent-hash on the request's "
                             "'tenant' field, least-loaded otherwise)")
    parser.add_argument("--quota-rps", type=float, default=0.0,
                        help="per-tenant token-bucket admission rate; "
                             "0 disables quotas (quota sheds answer "
                             "action null, source 'shed')")
    parser.add_argument("--quota-burst", type=float, default=0.0,
                        help="quota burst size (default: --quota-rps)")
    parser.add_argument("--degree", type=int, default=8,
                        help="FixedDegreePacking fallback degree (8 = the "
                             "canonical 32-server extraction)")
    parser.add_argument("--selftest", action="store_true",
                        help="CPU end-to-end smoke; no stdin")
    parser.add_argument("--selftest-requests", type=int, default=24)
    parser.add_argument("--stats-interval", type=float, default=None,
                        help="print a one-line telemetry snapshot "
                             "(decisions/s, p99, fallback rate, per-bucket"
                             " occupancy) to STDERR every N seconds; the "
                             "stdout JSON protocol is untouched")
    parser.add_argument("--telemetry-jsonl", default=None,
                        help="append telemetry span/event/snapshot records"
                             " to this JSONL sink (summarize with "
                             "scripts/telemetry_report.py; env fallback: "
                             "DDLS_TELEMETRY_JSONL)")
    parser.add_argument("--run-dir", default=None,
                        help="write a RunLedger directory (manifest + "
                             "telemetry sink + fleet snapshot — "
                             "telemetry/runlog.py)")
    args = parser.parse_args(argv)

    from ddls_tpu.utils.runtime import (configure_compile_cache,
                                        pin_cpu_platform,
                                        require_accelerator)

    configure_compile_cache()
    if args.selftest:
        # tier-1 contract: the selftest never touches an accelerator
        pin_cpu_platform()
        return run_selftest(args)
    try:
        device = require_accelerator("serve_policy.py")
    except RuntimeError as err:
        print(f"error: {err}", file=sys.stderr)
        return 2
    print(f"serving on {device}", file=sys.stderr)

    # telemetry on whenever the caller asked for stats or a sink;
    # otherwise the global registry stays disabled
    from ddls_tpu import telemetry

    sink_path = args.telemetry_jsonl or telemetry.env_sink_path()
    if args.stats_interval or sink_path:
        telemetry.enable(sink_path=sink_path)
    ledger = None
    if args.run_dir:
        from ddls_tpu.telemetry.runlog import RunLedger

        # the ledger's sink takes over for the run window (its open
        # enables telemetry); the fleet rollup lands as a snapshot block
        # in finalize() below
        ledger = RunLedger(args.run_dir, kind="serve",
                           config={"config_name": args.config_name,
                                   "checkpoint": args.checkpoint,
                                   "replicas": args.replicas}).open()

    model, n_actions, graph_dim = build_model_from_config(
        args.config_path, args.config_name, args.override)
    if args.checkpoint:
        from ddls_tpu.serve import (checkpoint_graph_feature_dim,
                                    load_checkpoint_params)

        params = load_checkpoint_params(args.checkpoint)
        # reject a checkpoint/config mismatch at startup with its actual
        # cause: restore is target-free, so mis-paired params would load
        # fine and then fail the first forward — which the server would
        # misread as a dead backend and latch degraded mode
        ckpt_dim = checkpoint_graph_feature_dim(params)
        if ckpt_dim is not None and ckpt_dim != graph_dim:
            print(f"error: checkpoint {args.checkpoint} was trained at "
                  f"graph width {ckpt_dim} but the config builds "
                  f"{graph_dim}; pass the checkpoint's training config "
                  f"(--config-name/--override)", file=sys.stderr)
            return 2
    else:
        import jax

        print("warning: no --checkpoint; serving RANDOM-INIT params",
              file=sys.stderr)
        params = model.init(
            jax.random.PRNGKey(0),
            {k: np.asarray(v) for k, v in template_obs(
                args.max_nodes, args.max_nodes * 2, n_actions,
                graph_dim).items()})

    server = make_fleet(args, model, params, graph_feature_dim=graph_dim)
    rid_to_client: dict = {}

    def emit_responses(responses) -> None:
        for r in responses:
            print(json.dumps({
                "id": rid_to_client.pop(r.request_id, r.request_id),
                "action": r.action, "source": r.source,
                "reason": r.reason, "bucket": r.bucket_idx,
                "latency_ms": round(r.latency_s * 1e3, 3)}), flush=True)

    def handle_line(line: str) -> None:
        if not line.strip():
            return
        # one malformed line errors to ITS client and never kills
        # the serving loop (or the batches already queued)
        client_id = None
        try:
            obj = json.loads(line)
            tenant = None
            if isinstance(obj, dict):
                client_id = obj.get("id")
                tenant = obj.get("tenant")
            rid = server.submit(obs_from_json(obj["obs"]), tenant=tenant)
            rid_to_client[rid] = (client_id if client_id is not None
                                  else rid)
        except Exception as exc:
            print(json.dumps({
                "id": client_id,
                "error": f"{type(exc).__name__}: {exc}"}),
                flush=True)

    # select-with-timeout pump: deadline flushes must fire while BLOCKED
    # on input, or an interactive client (one request, waits for the
    # answer before sending the next) deadlocks against its own partial
    # batch until EOF. Reads go through os.read on the raw fd +
    # LineAssembler, NOT buffered readline — see LineAssembler.
    import select
    import time

    # --stats-interval bookkeeping: the periodic line goes to STDERR (the
    # stdout JSON protocol carries only decisions), decisions/s is over
    # the interval window, everything else reads the live fleet stats —
    # fleet-level p99/fallback plus one column per replica (queue depth,
    # batch occupancy, degraded flag)
    def stats_line(window_done: int, window_s: float) -> str:
        snap = server.autoscale_snapshot()
        p99 = snap["p99_latency_ms"]
        p99_txt = "n/a" if p99 is None else f"{p99:.2f} ms"
        n_req = n_fb = 0
        for rep in server.replica_set.replicas:
            n_req += rep.server.stats.n_requests
            n_fb += rep.server.stats.n_fallback
        summ = server.summary()
        cols = []
        for rid, s in sorted(summ["per_replica"].items()):
            occ = s["batch_occupancy"]
            cols.append(
                f"{rid} q={s['queued']}"
                f" occ={'-' if occ is None else format(occ, '.2f')}"
                + (" degraded" if s["degraded"] else ""))
        return (f"[serve] {window_done / max(window_s, 1e-9):.1f} dec/s"
                f" | p99 {p99_txt}"
                f" | fallback {(n_fb / n_req if n_req else 0) * 100:.1f}%"
                f" | shed {summ['shed_rate'] * 100:.1f}%"
                f" | queued {server.queued()}"
                f" | " + " | ".join(cols))

    def decisions_done() -> int:
        return sum(rep.server.stats.n_policy + rep.server.stats.n_fallback
                   for rep in server.replica_set.replicas)

    fd = sys.stdin.fileno()
    lines_in = LineAssembler()
    stdin_open = True
    last_stats_t = time.perf_counter()
    last_stats_done = 0
    while stdin_open:
        now = time.perf_counter()
        deadline = server.next_deadline()
        timeouts = []
        if deadline is not None:
            timeouts.append(max(0.0, deadline - now))
        if args.stats_interval:
            timeouts.append(max(0.0,
                                last_stats_t + args.stats_interval - now))
        ready, _, _ = select.select([fd], [], [],
                                    min(timeouts) if timeouts else None)
        if ready:
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                stdin_open = False
                for line in lines_in.flush():
                    handle_line(line)
            else:
                for line in lines_in.feed(chunk):
                    handle_line(line)
        emit_responses(server.poll())
        now = time.perf_counter()
        if (args.stats_interval
                and now - last_stats_t >= args.stats_interval):
            done = decisions_done()
            print(stats_line(done - last_stats_done, now - last_stats_t),
                  file=sys.stderr, flush=True)
            last_stats_t = now
            last_stats_done = done
    emit_responses(server.drain())
    print(json.dumps({"serve_stats": server.summary()}),
          file=sys.stderr, flush=True)
    if telemetry.enabled():
        # sink gets the final global + per-replica registries plus the
        # fleet aggregate (the record scripts/telemetry_report.py reads
        # counters/histograms from)
        telemetry.dump_snapshot(
            extra={"serve": server.registry_snapshots()})
    if ledger is not None:
        ledger.record_result({"serve_stats": server.summary()})
        ledger.finalize(blocks={"serve": server.registry_snapshots()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
