"""The serving path: the configuration's checkpoint behind one
``PolicyServer``, driven open loop by a seeded arrival trace.

Set-up restores the checkpoint, builds a pool of real observations by
stepping one host env under seeded random valid actions, builds the
server as shipped and warms every bucket the pool touches (and no
other). The window submits each request at its scheduled arrival from
one pump thread and polls; latency is taken on the benchmark's own
clock, from the SCHEDULED arrival to the instant the response is
handed back. After the last arrival the pump drains for
``drain_timeout_s`` and force-flushes once; what is still unanswered
has failed.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

from benchmarks import harness, loadgen, reference

OBS_KEYS = ("node_split", "edge_split")
#: how a request ended; index 0 = never answered. Only "policy" counts.
SOURCES = ("unanswered", "policy", "shed", "fallback:saturated",
           "fallback:overflow", "fallback:invalid", "fallback:degraded",
           "fallback:other")


def source_code(resp) -> int:
    name = (resp.source if resp.source != "fallback"
            else f"fallback:{resp.reason}")
    return SOURCES.index(name) if name in SOURCES else len(SOURCES) - 1


def build_pool(env_config: dict, n_obs: int, seed: int) -> List[dict]:
    """Real encoded observations of the configuration's environment,
    taken at every decision of seeded random valid actions (episodes
    restart with a new seed), then sorted by graph size so the trace's
    size ranks map onto them."""
    import numpy as np

    from ddls_tpu.envs import RampJobPartitioningEnvironment

    env = RampJobPartitioningEnvironment(**env_config)
    rng = np.random.RandomState(seed)
    obs = env.reset(seed=seed)
    pool: List[dict] = []
    while len(pool) < n_obs:
        pool.append({k: np.copy(v) for k, v in obs.items()})
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            obs = env.reset(seed=seed + len(pool))
    return sorted(pool, key=lambda o: tuple(
        int(np.asarray(o[k]).reshape(-1)[0]) for k in OBS_KEYS))


def build_server(cell: harness.Cell):
    """(server, model, params, env_config): the checkpoint behind a
    ``PolicyServer`` with the traffic file's settings on the
    configuration's pads."""
    from ddls_tpu.config import load_config
    from ddls_tpu.serve import (PolicyServer, build_model_from_config,
                                checkpoint_graph_feature_dim,
                                default_buckets, load_checkpoint_params)

    composed = cell.config["composed_from"]
    config_path = os.path.join(harness.REPO, composed["config_path"])
    cfg = load_config(config_path, composed["config_name"],
                      list(composed["overrides"]))
    harness.check_expectations(cfg, cell.config["expect"])
    params = load_checkpoint_params(
        os.path.join(harness.REPO, cell.config["checkpoint"]))
    model, _, graph_dim = build_model_from_config(
        config_path, composed["config_name"], list(composed["overrides"]))
    if checkpoint_graph_feature_dim(params) != graph_dim:
        raise SystemExit("checkpoint and configuration disagree on the "
                         "graph feature width")
    pads = cfg["env_config"]["pad_obs_kwargs"]
    settings = cell.traffic["server"]
    server = PolicyServer(
        model, params,
        buckets=default_buckets(pads["max_nodes"], pads["max_edges"]),
        max_batch=settings["max_batch"],
        deadline_s=settings["deadline_s"],
        max_queue=settings["max_queue"], graph_feature_dim=graph_dim)
    return server, model, params, cfg["env_config"]


def bucket_of(server, obs) -> int:
    import numpy as np

    return server.bucketer.bucket_index(
        *(int(np.asarray(obs[k]).reshape(-1)[0]) for k in OBS_KEYS))


def warm(server, pool: List[dict]) -> List[int]:
    """One full batch through every bucket the pool touches: each
    bucket's program compiles (or loads) once, in set-up."""
    touched = sorted({bucket_of(server, o) for o in pool})
    for idx in touched:
        obs = next(o for o in pool if bucket_of(server, o) == idx)
        for _ in range(server.engine.max_batch):
            server.submit(obs)
        server.drain()
    server.stats = type(server.stats)()  # warm-up never counts
    return touched


def pump(server, pool: List[dict], trace: dict, rec: harness.Recorder,
         drain_timeout_s: float, traced: bool = False) -> Dict[str, Any]:
    """Drive the trace in real time from this one thread. Returns per
    request: the response (or None) and the client-side latency. A
    traced window is traced whole (stopping the profiler stalls the
    pump for seconds, so it never happens inside a window)."""
    import numpy as np

    arrivals = np.asarray(trace["arrival_s"], dtype=np.float64)
    n = len(arrivals)
    sized = [pool[min(int(f * len(pool)), len(pool) - 1)]
             for f in trace["size_frac"]]
    # per-request results live in arrays, not in kept response objects:
    # tens of thousands of long-lived python objects would make the
    # interpreter's full garbage collections frequent, and each one
    # stalls pump AND server for ~0.15 s (my chip runs, PR 22)
    index_of = np.full(n + 1024, -1, dtype=np.int64)
    source = np.zeros(n, dtype=np.int8)          # SOURCES index, 0 = none
    action = np.full(n, -1, dtype=np.int64)
    latency = np.full(n, np.nan, dtype=np.float64)
    late = np.zeros(n, dtype=np.float64)
    answered = 0
    duplicates = 0
    first_id: Optional[int] = None

    def take(batch) -> None:
        nonlocal answered, duplicates
        if not batch:
            return
        now = time.perf_counter()
        for resp in batch:
            slot = resp.request_id - first_id
            i = index_of[slot] if 0 <= slot < len(index_of) else -1
            if i < 0 or source[i]:
                duplicates += 1
                continue
            source[i] = source_code(resp)
            action[i] = resp.action
            latency[i] = now - (start + arrivals[i])
            answered += 1

    if traced:
        rec.start_trace()
    start = time.perf_counter()
    i = 0
    end_of_input: Optional[float] = None
    while answered < n:
        now = time.perf_counter()
        if i < n and now - start >= arrivals[i]:
            with rec.span("serve.submit"):
                while i < n and now - start >= arrivals[i]:
                    # the scheduled arrival is the request's clock, not
                    # the instant the pump got to it
                    rid = server.submit(sized[i],
                                        now=start + arrivals[i])
                    if first_id is None:
                        first_id = rid
                    index_of[rid - first_id] = i
                    late[i] = now - (start + arrivals[i])
                    i += 1
                    now = time.perf_counter()
        with rec.span("serve.poll"):
            take(server.poll())
        if i >= n:
            if end_of_input is None:
                end_of_input = time.perf_counter()
            if time.perf_counter() - end_of_input >= drain_timeout_s:
                break
        events = [start + arrivals[i]] if i < n else []
        deadline = server.next_deadline()
        if deadline is not None:
            events.append(deadline)
        if events:
            pause = min(events) - time.perf_counter()
            if pause > 0:
                with rec.span("serve.sleep"):
                    time.sleep(min(pause, 0.005))
        elif i >= n:
            take(server.drain())
    take(server.drain())
    elapsed = time.perf_counter() - start
    rec.stop_trace()
    rec.spans["generator_late"] = late.tolist()
    return {"source": source, "action": action, "latency": latency,
            "arrival_s": arrivals, "duplicates": duplicates,
            "elapsed_s": elapsed,
            "backlog_at_end": n - answered, "sized": sized}


def summarise(out: Dict[str, Any], seconds: float, percentile: float,
              subwindows: int = 1, rate_rps: Optional[float] = None
              ) -> Dict[str, Any]:
    """A request counts only if the POLICY answered it: shed, fallback,
    invalid and unanswered requests have failed, and take the largest
    latency of their (sub-)window in the percentile.

    The two end-to-end readings are MEDIANS over ``subwindows`` equal
    slices of the window (by scheduled arrival): the slice's percentile,
    and the slice's share of policy answers times the offered rate. The
    chip machine shares its host: about once in 20-30 s this process is
    stalled for ~0.15 s, and a single percentile over the whole window
    then flips between 9 ms and 150 ms on whether one or two stalls
    fell into it (my chip runs, PR 22). ``failed`` and the whole-window
    numbers stay whole."""
    import numpy as np

    policy = out["source"] == SOURCES.index("policy")
    sources = {SOURCES[int(code)]: int(count) for code, count in zip(
        *np.unique(out["source"], return_counts=True))}
    masked_ok = all(
        bool(np.asarray(obs["action_mask"])[int(a)])
        for obs, a in zip(out["sized"], out["action"]) if a >= 0)
    good = np.where(policy, out["latency"], np.nan)

    def charged(values) -> Dict[str, Any]:
        return loadgen.latency_summary(values, q=percentile)

    summary = charged(good)
    n_policy = summary["attempted"] - summary["failed"]
    edges = np.linspace(0.0, float(seconds), int(subwindows) + 1)
    slices = [(out["arrival_s"] >= lo) & (out["arrival_s"] < hi)
              for lo, hi in zip(edges[:-1], edges[1:])]
    slices = [m for m in slices if m.any()]
    slice_pq = [charged(good[m])["pq_ms"] for m in slices]
    slice_share = [float(policy[m].mean()) for m in slices]
    offered = (rate_rps if rate_rps is not None
               else summary["attempted"] / float(seconds))
    return {**summary, "sources": sources, "actions_in_mask": masked_ok,
            "decisions_per_s_whole_window": n_policy / float(seconds),
            "slice_pq_ms": slice_pq, "slice_policy_share": slice_share,
            "pq_ms_median_of_slices": (
                float(np.median([v for v in slice_pq if v is not None]))
                if any(v is not None for v in slice_pq) else None),
            "decisions_per_s": float(np.median(slice_share)) * offered,
            "answered_once": out["duplicates"] == 0}


def pump_stalls(rec: harness.Recorder) -> Dict[str, Any]:
    """The longest single call of each pump span: where a stall of the
    process fell (inside the sleep: the host; inside the poll: the
    forward or its fetch)."""
    return {name: {"n": len(rec.spans[name]),
                   "max_ms": max(rec.spans[name]) * 1e3}
            for name in ("serve.submit", "serve.poll", "serve.sleep")
            if rec.spans.get(name)}


def reference_sample(server, pool: List[dict], n: int, seed: int
                     ) -> List[dict]:
    """A seeded sample of pool observations through the server's own
    bucket programs (already warm): the action it answers and the
    logits behind it."""
    import numpy as np

    rng = np.random.RandomState(seed)
    picks = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
    served = []
    for k in picks:
        obs = pool[int(k)]
        resp = server.serve_one(obs)
        idx, bucketed = server.bucketer.bucket_obs(obs)
        try:
            logits, _ = server._forward.forward([bucketed])
        finally:
            server.bucketer.release(idx, bucketed)
        served.append({"obs": obs, "action": resp.action,
                       "source": resp.source, "logits": logits[0]})
    return served


def setup(cell: harness.Cell, seed: int):
    t0 = time.perf_counter()
    server, model, params, env_config = build_server(cell)
    t1 = time.perf_counter()
    pool = build_pool(env_config, cell.traffic["pool"]["observations"],
                      seed)
    t2 = time.perf_counter()
    touched = warm(server, pool)
    harness.note("setup_parts_s", {
        "checkpoint_model_server": t1 - t0, "observation_pool": t2 - t1,
        "warm_buckets": time.perf_counter() - t2})
    return server, model, params, pool, touched


def run(cell: harness.Cell, args, rec: harness.Recorder,
        meter: harness.CompileMeter, t_start: float) -> Dict[str, Any]:
    import jax

    traffic = cell.traffic
    if not traffic.get("rate_rps"):
        raise SystemExit(f"traffic mix {cell.traffic_name} has no "
                         "rate_rps yet (measure the knee first)")
    harness.note("setup_parts_s", {
        "imports_and_backend": time.perf_counter() - t_start})
    server, model, params, pool, touched = setup(cell, args.seed)
    # a traced run measures a short window of its own: the trace of a
    # whole window would be too large to reduce
    seconds = (min(args.seconds, traffic["trace_seconds"]) if args.trace
               else args.seconds)
    trace = loadgen.fixed_span_trace(seconds, traffic["rate_rps"],
                                     args.seed, **traffic["arrivals"])
    harness.note("setup", {
        "buckets_touched": touched,
        "buckets": [list(b) for b in server.bucketer.buckets],
        "trace_fingerprint": loadgen.trace_fingerprint(trace),
        "requests": len(trace["arrival_s"]),
        "span_scale": trace["span_scale"], **meter.totals()})

    rec.reset()
    compile_setup = meter.totals()
    setup_s = time.perf_counter() - t_start
    with harness.GcWatch() as gc_watch:
        out = pump(server, pool, trace, rec, traffic["drain_timeout_s"],
                   traced=bool(args.trace))
    compile_window = harness.CompileMeter.delta(meter.totals(),
                                                compile_setup)
    stats = server.stats.summary()
    summary = summarise(out, seconds, traffic["percentile"],
                        traffic["subwindows"], traffic["rate_rps"])

    served = reference_sample(server, pool, traffic["reference"]["sample"],
                              args.seed)
    ref = reference.serve_reference_check(
        model, params, served, traffic["reference"]["logit_atol"])
    checks = {
        "answered_once": summary["answered_once"],
        "actions_in_mask": summary["actions_in_mask"],
        "reference": ref["ok"],
        "sample_from_policy": all(s["source"] == "policy"
                                  for s in served),
        "no_compile_in_window": compile_window["compiles"] == 0,
        "not_degraded": not server.degraded,
    }
    harness.note("reference", ref)
    harness.note("checks", checks)
    harness.note("window", {
        k: summary[k] for k in (
            "attempted", "failed", "p50_ms", "pq_ms", "max_ms",
            "beyond_pq", "sources", "decisions_per_s_whole_window",
            "slice_pq_ms", "slice_policy_share")}
        | {"elapsed_s": out["elapsed_s"], "gc": gc_watch.summary(),
           "pump_stalls": pump_stalls(rec),
           "backlog_at_end": out["backlog_at_end"],
           "server": {k: stats[k] for k in (
               "n_requests", "n_policy", "n_fallback", "n_flushes",
               "batch_occupancy", "flush_causes", "bucket_hits",
               "n_compiles", "p50_latency_ms", "p99_latency_ms")}})
    memory_stats = [d.memory_stats() or {} for d in jax.devices()]
    server.close()

    counters = {"serve.batch_occupancy": stats["batch_occupancy"],
                "serve.fallback_rate": stats["fallback_rate"],
                "serve.flushes": stats["n_flushes"]}
    return {
        "correct": all(checks.values()),
        "attempted": summary["attempted"],
        "failed": summary["failed"],
        "end_to_end": {"serve_decisions_per_s": summary["decisions_per_s"],
                       "serve_p99_ms": summary["pq_ms_median_of_slices"],
                       "setup_s": setup_s},
        "summary": summary,
        "ctx": {"spans": {"bench": rec.spans, "program": {}},
                "counters": {k: v for k, v in counters.items()
                             if v is not None},
                "compile": {"setup": compile_setup,
                            "window": compile_window},
                "memory_stats": memory_stats},
    }
