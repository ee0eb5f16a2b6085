"""The training path: whole collect->update epochs through
``scripts/train_from_config.py``'s ``build_run``, as a user runs them.

Set-up composes the configuration's YAML tree with the configuration's
and the traffic mix's overrides, builds the run, and warms up the
mix's number of epochs (the first one compiles or loads the programs).
The window then drives ``loop.run`` directly, each epoch closed by
``block_until_ready(loop.state)``, for as long as an epoch STARTS
inside ``--seconds``; the last one may overrun. The per-epoch
checkpoint save of ``Launcher`` is outside the metric (save stall is
its own later metric, PERF.md).

The host-oracle fidelity replay runs outside the window, after it.
"""
from __future__ import annotations

import functools
import os
import statistics
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmarks import harness, reference

STEPS_METRIC = "train_env_steps_per_s"


def _train_from_config():
    scripts = os.path.join(harness.REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import train_from_config

    return train_from_config


def compose(cell: harness.Cell, seed: int, save_root: str) -> dict:
    """The configuration's YAML tree under the configuration's and the
    mix's overrides, pinned to the cell's chips."""
    from ddls_tpu.config import load_config
    from ddls_tpu.train.compat import apply_reference_compat

    composed = cell.config["composed_from"]
    cfg = load_config(
        os.path.join(harness.REPO, composed["config_path"]),
        composed["config_name"],
        [*composed["overrides"], *cell.traffic["overrides"],
         f"epoch_loop.n_devices={cell.chips}",
         f"experiment.train_seed={cell.traffic.get('train_seed', seed)}",
         f"experiment.path_to_save={save_root}",
         f"experiment.name={cell.name}"])
    apply_reference_compat(cfg)
    harness.check_expectations(cfg, cell.config["expect"])
    return cfg


def _wrap(rec: harness.Recorder, owner, attr: str, span: str) -> None:
    """Time ``owner.attr`` under a benchmark span, from outside. A
    program that no longer has the attribute just loses the span."""
    inner = getattr(owner, attr, None)
    if inner is None:
        return

    @functools.wraps(inner)
    def timed(*args, **kwargs):
        with rec.span(span):
            return inner(*args, **kwargs)

    setattr(owner, attr, timed)


def instrument(loop, rec: harness.Recorder) -> None:
    """The benchmark's wrapper spans around the calls into each layer:
    collect (host collection), update_dispatch (the async dispatch of
    the update), host_sync (the metric drain that waits for the
    device)."""
    _wrap(rec, getattr(loop, "collector", None), "collect", "collect")
    _wrap(rec, getattr(loop, "learner", None), "train_step",
          "update_dispatch")
    _wrap(rec, loop, "_maybe_sync_metrics", "host_sync")


def run_epoch(loop, rec: harness.Recorder) -> Dict[str, Any]:
    import jax

    t0, cpu0 = time.perf_counter(), time.process_time()
    with rec.span("epoch"):
        results = loop.run()
        jax.block_until_ready(loop.state)
    return {"start": t0, "seconds": time.perf_counter() - t0,
            "cpu_s": time.process_time() - cpu0,
            "env_steps": int(results["env_steps_this_iter"]),
            "loss": float(results["learner"]["total_loss"])}


def memo_counts(loop) -> Dict[str, float]:
    fused = getattr(loop, "fused", None)
    counters = fused.memo_counters() if fused is not None else None
    if not counters:
        return {}
    return {f"memo.{k}": float(counters[k])
            for k in ("hits", "misses", "evicts")}


def program_scratch_bytes(loop) -> int:
    """The scratch (XLA temp allocation) the fused epoch program holds
    on each chip while it runs, from the compiled program's own memory
    analysis. The TPU runtime's ``peak_bytes_in_use`` counts buffers
    and leaves an executable's scratch out (at 320 lanes it read
    2.57 GB after an epoch whose program had 2.26 GB of arguments and
    2.22 GB of scratch: my chip run, PR 22), so the chip's peak is the
    sum. Lowered on the arguments the next epoch would be called with:
    that is the program the window ran, and jit serves it from its own
    cache (nothing is traced or compiled again). 0 where the path has
    no single epoch program (host collection)."""
    fused = getattr(loop, "fused", None)
    if fused is None:
        return 0
    try:
        lowered = fused._jit_epoch.lower(
            loop.state, fused._state, loop._collect_rng, loop._rng)
    except AttributeError:   # the driver's own probe: compiles again
        lowered = fused.lower(loop.state)
    analysis = lowered.compile().memory_analysis()
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def steps_per_s(epochs: List[dict], statistic: str,
                window: Optional[Tuple[float, float]] = None) -> float:
    """``ratio``: env steps of the epochs over their wall time.
    ``median_epoch_rate``: the median of the per-epoch rates, which a
    few slow or fast epochs cannot move.
    ``window_share``: env steps inside ``window`` = (start, seconds)
    over its seconds, the epoch that straddles the end credited by the
    share of it that lies inside. Where epochs differ (a warming memo)
    the plain ratio jumps by a whole epoch when the last boundary
    crosses the window's end; this is continuous in it."""
    if statistic == "ratio":
        return (sum(e["env_steps"] for e in epochs)
                / sum(e["seconds"] for e in epochs))
    if statistic == "window_share":
        start, seconds = window
        steps = 0.0
        for e in epochs:
            inside = min(e["start"] + e["seconds"], start + seconds) \
                - max(e["start"], start)
            steps += e["env_steps"] * max(inside, 0.0) / e["seconds"]
        return steps / seconds
    if statistic == "median_epoch_rate":
        return statistics.median(e["env_steps"] / e["seconds"]
                                 for e in epochs)
    raise ValueError(f"unknown statistic {statistic!r}")


#: an epoch loop that keeps raising is broken, not slow
MAX_RAISED_EPOCHS = 3

#: the window goes on past ``--seconds`` for a measured set of epochs
#: that is not complete yet, to at most this many times ``--seconds``
SET_OVERRUN = 1.5

#: an epoch of the window is LONG when it took more than this many
#: times the window's median epoch and more than this many seconds
LONG_TIMES_MEDIAN = 3.0
LONG_MIN_S = 1.0


def measured_set(epochs: List[dict], measure_epochs) -> Optional[List[dict]]:
    """The epochs ``[k0, k1)`` of the window, by index from its first;
    None while the window has not run them all."""
    k0, k1 = (int(k) for k in measure_epochs)
    if not 0 <= k0 < k1:
        raise ValueError(f"measure_epochs {measure_epochs!r}: want "
                         f"0 <= k0 < k1")
    return epochs[k0:k1] if len(epochs) >= k1 else None


def long_epochs(epochs: List[dict], window: Tuple[float, float]
                ) -> List[int]:
    """Indices of the epochs that start inside ``window`` = (start,
    seconds) and took over ``LONG_TIMES_MEDIAN`` x the median of those
    epochs AND over ``LONG_MIN_S`` seconds. With the training seed
    pinned the program's own long epochs (a cold memo: most lanes run
    their lookahead) stand at the same indices in every run of a tree;
    one at another index is the host's: it held the loop while the
    device had nothing new to do."""
    start, seconds = window
    inside = [i for i, e in enumerate(epochs)
              if e["start"] < start + seconds]
    if not inside:
        return []
    median = statistics.median(epochs[i]["seconds"] for i in inside)
    return [i for i in inside
            if epochs[i]["seconds"] > max(LONG_TIMES_MEDIAN * median,
                                          LONG_MIN_S)]


def measure_window(loop, rec: harness.Recorder, seconds: float,
                   trace_epochs: int, measure_epochs=None):
    """Run epochs for as long as one STARTS inside ``seconds``; the
    profiler (when on) covers the first ``trace_epochs`` of them. Where
    the mix names a set of ``measure_epochs`` that is not complete by
    then, epochs go on until it is, to at most ``SET_OVERRUN`` x
    ``seconds``: what is read over the set is read over the SAME epochs
    in every run, or not at all.
    Returns (epochs, epochs that raised, start of the window)."""
    epochs: List[dict] = []
    raised = 0
    need = int(measure_epochs[1]) if measure_epochs else 0
    t_window = time.perf_counter()

    def goes_on() -> bool:
        elapsed = time.perf_counter() - t_window
        return elapsed < seconds or (len(epochs) < need
                                     and elapsed < SET_OVERRUN * seconds)

    while goes_on() and raised < MAX_RAISED_EPOCHS:
        try:
            epochs.append(run_epoch(loop, rec))
        except Exception as exc:  # an epoch that raised has failed
            harness.note("epoch_failed", repr(exc))
            raised += 1
        if len(epochs) == trace_epochs:
            rec.stop_trace()
    rec.stop_trace()
    return epochs, raised, t_window


def window_facts(epochs: List[dict], t_window: float, seconds: float,
                 measure_epochs) -> Dict[str, Any]:
    """What the window held, for the ``[bench] epochs`` note and the
    per-layer readers: the long epochs (an epoch with no CPU time
    waited, for the device or for the core) and what the measured set
    of epochs read, began and ended at."""
    window = (t_window, seconds)
    facts: Dict[str, Any] = {
        "in_window": sum(e["start"] < t_window + seconds for e in epochs),
        "window_share": steps_per_s(epochs, "window_share", window),
        "ratio_steps_per_s": steps_per_s(epochs, "ratio"),
        "median_epoch_rate": steps_per_s(epochs, "median_epoch_rate"),
        "long_epochs": [
            {"index": i, "seconds": epochs[i]["seconds"],
             "cpu_s": epochs[i]["cpu_s"],
             "began_s": epochs[i]["start"] - t_window}
            for i in long_epochs(epochs, window)]}
    if measure_epochs:
        chosen = measured_set(epochs, measure_epochs)
        facts["measure_epochs"] = [int(k) for k in measure_epochs]
        facts["set"] = None if chosen is None else {
            "median_epoch_rate": steps_per_s(chosen, "median_epoch_rate"),
            "ratio_steps_per_s": steps_per_s(chosen, "ratio"),
            "wall_s": sum(e["seconds"] for e in chosen),
            "began_s": chosen[0]["start"] - t_window,
            "ended_s": (chosen[-1]["start"] + chosen[-1]["seconds"]
                        - t_window)}
    return facts


def output_checks(cell: harness.Cell, cfg: dict, loop, before,
                  epochs: List[dict]) -> Dict[str, bool]:
    """What must hold of the training that ran (``epochs`` = warm-up
    and window) for the timing to mean anything."""
    import jax
    import numpy as np

    after = jax.device_get(loop.state.params)
    platforms = {d.platform
                 for leaf in jax.tree_util.tree_leaves(loop.state)
                 for d in leaf.devices()}
    checks = {
        "env_steps_each": all(
            e["env_steps"] == cell.traffic["epoch"]["env_steps"]
            for e in epochs),
        "losses_finite": all(np.isfinite(e["loss"]) for e in epochs),
        "params_moved": any(
            float(np.abs(np.asarray(a) - np.asarray(b)).max()) > 0
            for a, b in zip(jax.tree_util.tree_leaves(before),
                            jax.tree_util.tree_leaves(after))),
        "state_on_accelerator": platforms == {jax.devices()[0].platform},
        "mesh": dict(loop.mesh.shape) == {"dp": cell.chips},
        "loop_mode": loop.loop_mode == cfg["epoch_loop"]["loop_mode"],
    }
    if loop.loop_mode == "fused":
        checks["fused_shape"] = (
            (loop.fused.num_lanes, loop.fused.segment_len)
            == (cell.traffic["epoch"]["lanes"],
                cell.traffic["epoch"]["steps"]))
    return checks


def workers_cpu_pinned(loop, expected: int) -> bool:
    """Spawned env workers report on their close ack: every one of them
    CPU-pinned, none with another backend open."""
    workers = getattr(loop.vec_env, "worker_states", None)
    return (workers is not None and len(workers) == expected
            and all(w is not None and w["jax_platforms"] == "cpu"
                    and set(w["backends"]) <= {"cpu"} for w in workers))


def run(cell: harness.Cell, args, rec: harness.Recorder,
        meter: harness.CompileMeter, t_start: float) -> Dict[str, Any]:
    import jax
    import numpy as np

    from ddls_tpu import telemetry

    traffic = cell.traffic
    with tempfile.TemporaryDirectory(prefix="ddls_bench_") as save_root:
        cfg = compose(cell, args.seed, save_root)
        loop = _train_from_config().build_run(cfg).epoch_loop
        try:
            instrument(loop, rec)
            before = jax.device_get(loop.state.params)
            build_s = time.perf_counter() - t_start
            warm = [run_epoch(loop, rec)
                    for _ in range(int(traffic["warmup_epochs"]))]
            harness.note("warmup", {
                "build_s": build_s,
                "epoch_s": [e["seconds"] for e in warm],
                "memo": memo_counts(loop), **meter.totals()})

            if args.trace:
                telemetry.enable(record_intervals=True)
                telemetry.reset()
                rec.start_trace()
            rec.reset()
            compile_setup = meter.totals()
            memo_before = memo_counts(loop)
            setup_s = time.perf_counter() - t_start
            epochs, raised, t_window = measure_window(
                loop, rec, args.seconds, int(traffic["trace_epochs"]),
                traffic.get("measure_epochs"))
            window_s = time.perf_counter() - t_window
            compile_window = harness.CompileMeter.delta(meter.totals(),
                                                        compile_setup)
            memo_after = memo_counts(loop)
            program_spans: Dict[str, List[float]] = {}
            if args.trace:
                for name, t0, t1 in telemetry.span_intervals():
                    program_spans.setdefault(name, []).append(t1 - t0)
                telemetry.disable()

            checks = output_checks(cell, cfg, loop, before, warm + epochs)
            checks["epochs_ran"] = bool(epochs)
            checks["no_compile_in_window"] = compile_window["compiles"] == 0
            # read before the fidelity replay, which may run a program
            # of its own: the peak is the measured path's
            memory_stats = [d.memory_stats() or {} for d in jax.devices()]
            t_scratch = time.perf_counter()
            scratch_bytes = program_scratch_bytes(loop)
            harness.note("memory", {
                "allocator_peak_bytes": [s.get("peak_bytes_in_use")
                                         for s in memory_stats],
                "program_scratch_bytes": scratch_bytes,
                "read_s": time.perf_counter() - t_scratch,
                **harness.CompileMeter.delta(meter.totals(),
                                             compile_setup)})
            fidelity = reference.train_fidelity(
                cfg["env_config"], traffic["fidelity"], args.seed,
                cell.name)
            checks["fidelity"] = fidelity["ok"]
            harness.note("fidelity", fidelity)
        finally:
            loop.close()
    if "expect_workers" in traffic:
        checks["workers_cpu_pinned"] = workers_cpu_pinned(
            loop, traffic["expect_workers"])
    harness.note("checks", checks)
    if not epochs:
        raise SystemExit("no epoch finished inside the window")
    facts = window_facts(epochs, t_window, args.seconds,
                         traffic.get("measure_epochs"))
    harness.note("epochs", {
        "seconds": [e["seconds"] for e in epochs],
        "cpu_s": [e["cpu_s"] for e in epochs],
        "loss": [e["loss"] for e in epochs],
        "window_s": window_s, "overrun_s": window_s - args.seconds,
        "memo_before": memo_before, "memo_after": memo_after,
        **facts})
    if args.trace and facts.get("measure_epochs") and facts["set"] is None:
        # the traced run is where the set is read: no reading over
        # fewer or other epochs takes its place
        raise SystemExit(
            f"measure_epochs {facts['measure_epochs']}: the window ran "
            f"{len(epochs)} epochs in {window_s:.1f} s (it goes on to "
            f"{SET_OVERRUN} x --seconds for the set), fewer than "
            f"k1 = {facts['measure_epochs'][1]}: nothing to read")

    return {
        "correct": all(checks.values()),
        "attempted": len(epochs) + raised,
        "failed": raised + sum(not np.isfinite(e["loss"]) for e in epochs),
        "compared": harness.compared_checks(checks),
        "end_to_end": {
            STEPS_METRIC: steps_per_s(epochs, traffic["statistic"],
                                      (t_window, args.seconds)),
            "setup_s": setup_s},
        "ctx": {"spans": {"bench": rec.spans, "program": program_spans},
                "window": facts,
                "counters": {
                    "program.scratch_bytes": float(scratch_bytes),
                    **{k: memo_after[k] - memo_before.get(k, 0.0)
                       for k in memo_after}},
                "compile": {"setup": compile_setup,
                            "window": compile_window},
                "memory_stats": memory_stats,
                "scratch_bytes": scratch_bytes},
    }
