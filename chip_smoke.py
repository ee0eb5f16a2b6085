"""Chip smoke: does the system still start, compile and step on the TPU?

Drives the repo's main path once through the entry points a user calls,
at the full width of the shipped config (``env_dev`` RAMP 4x4x2,
degree-16 pads, ``model=gnn``, ``algo=ppo``, 8 envs x 32 steps):

* leg (a) — host-collected PPO, the default ``loop_mode: pipelined``
  with spawned env workers: 3 epochs through ``train_from_config``'s
  ``build_run`` + ``Launcher``/``Logger``/``Checkpointer``, then the last
  checkpoint restored bit-equal onto the live state's shardings;
* leg (b) — device-collected PPO, ``loop_mode=fused`` pinned at
  8 lanes x 32 steps: 2 epochs, the second under
  ``jax.transfer_guard("disallow")``, memo counters reported;
* serve — the shipped ``ppo_device_trained`` checkpoint behind a
  ``PolicyServer`` answering 16 ``env_load32`` observations from the
  policy (never the fallback, which answers the same action).

Every leg asserts; any failure exits non-zero. The process exits
non-zero BEFORE running anything when jax's backend is not ``tpu`` —
jax itself quietly picks the CPU when it finds no chip. One process
holds the chip: everything is imported and called, nothing shells out,
and the env workers are CPU-pinned (checked). The same file is the
1-chip and the 4-chip smoke: the device count comes from jax.

Timings printed here are smoke timings (cold = compile included), not
benchmark metrics. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SHIPPED_CHECKPOINT = os.path.join(REPO, "checkpoints", "ppo_device_trained")

# both training legs: evaluation off, metrics drained every epoch
COMMON_OVERRIDES = ("eval_config.evaluation_interval=null",
                    "epoch_loop.metrics_sync_interval=1")
LEG_A_OVERRIDES = ("epoch_loop.loop_mode=pipelined",
                   "epoch_loop.use_parallel_envs=auto")
# 8 lanes x 32 steps: what num_envs x rollout_length of the composed
# config already say, pinned so that the leg's shape reads here
LEG_B_OVERRIDES = ("epoch_loop.loop_mode=fused",
                   "epoch_loop.updates_per_epoch=1",
                   "epoch_loop.fused_config={lanes: 8, segment_len: 32}")
SERVE_OVERRIDES = ("env_config=env_load32",)
SERVE_REQUESTS = 16
SERVE_ACTION = 8  # the shipped policy IS FixedDegreePacking(8)


def print_header() -> dict:
    """Start-up facts, first lines of output; returns the device dict of
    the final JSON line."""
    from ddls_tpu.utils.runtime import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    from ddls_tpu.native import build_key, native_available

    dev = jax.devices()[0]
    print(f"jax {jax.__version__} backend={jax.default_backend()} "
          f"device_kind={dev.device_kind!r} "
          f"device_count={jax.device_count()}")
    print(f"compile cache dir: {cache_dir}")
    native = native_available()
    print(f"native_available={native} build_key={build_key()}")
    if not native:
        raise SystemExit("chip_smoke: the native (C++) lookahead engine "
                         "did not build — env workers would run the "
                         "~50x slower Python engine")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


class CompileMeter:
    """Per-leg compile seconds and persistent-cache hits/writes, from
    jax's own monitoring events — a warm second run must show hits and
    far fewer compile seconds."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self._mark = (0.0, 0, 0)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def since_last(self) -> dict:
        """Totals since the previous call (one call per leg)."""
        now = (self.compile_s, self.cache_hits, self.cache_writes)
        then, self._mark = self._mark, now
        return {"compile_s": round(now[0] - then[0], 1),
                "cache_hits": now[1] - then[1],
                "cache_writes": now[2] - then[2]}


class EpochProbe:
    """Wraps ONE loop instance's ``run`` (and its learner's
    ``shard_traj``) for the duration of a leg: times every epoch to
    ``block_until_ready``, keeps each epoch's results, runs the listed
    epochs under ``jax.transfer_guard("disallow")``, and records where
    each staged trajectory landed. The Launcher keeps driving the loop
    exactly as in ``train_from_config.main``."""

    def __init__(self, loop, meter: CompileMeter, guarded_epochs=()):
        self.loop = loop
        self.meter = meter
        self.guarded_epochs = set(guarded_epochs)
        self.seconds = []
        self.compile_seconds = []  # of which compiling, per epoch
        self.results = []
        self.memo = []
        self.staged = []  # [(device, lanes)] per staged trajectory
        self._run = loop.run
        loop.run = self.run
        if loop.fused is None:  # host collection stages through here
            self._shard_traj = loop.learner.shard_traj
            loop.learner.shard_traj = self.shard_traj

    def run(self):
        import jax

        epoch = self.loop.epoch_counter + 1
        guard = (jax.transfer_guard("disallow")
                 if epoch in self.guarded_epochs
                 else contextlib.nullcontext())
        t0, c0 = time.perf_counter(), self.meter.compile_s
        with guard:
            results = self._run()
        jax.block_until_ready(self.loop.state)
        self.seconds.append(time.perf_counter() - t0)
        self.compile_seconds.append(self.meter.compile_s - c0)
        self.results.append(results)
        if self.loop.fused is not None:
            self.memo.append(self.loop.fused.memo_counters())
        return results

    def shard_traj(self, traj, last_values):
        straj, slv = self._shard_traj(traj, last_values)
        # metadata only — the update donates these buffers
        self.staged.append([(s.device, s.data.shape[1])
                            for s in straj["actions"].addressable_shards])
        return straj, slv


def _assert_on_platform(tree, platform: str, what: str) -> int:
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    assert leaves, f"{what}: empty tree"
    for leaf in leaves:
        assert isinstance(leaf, jax.Array), (what, type(leaf))
        wrong = [d for d in leaf.devices() if d.platform != platform]
        assert not wrong, f"{what}: leaf on {wrong}, expected {platform}"
    return len(leaves)


def _train_from_config():
    """scripts/train_from_config.py as a module (its own functions
    drive both training legs and name the config tree)."""
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import train_from_config

    return train_from_config


def _assert_lanes_spread(shards, lanes: int, what: str) -> None:
    """One shard per device, every device distinct, lanes/n each."""
    import jax

    n = jax.device_count()
    devices = {d for d, _ in shards}
    assert len(shards) == n and len(devices) == n, (what, shards)
    assert all(k == lanes // n for _, k in shards), (what, shards)


def train_leg(name: str, overrides, num_epochs: int, platform: str,
              save_root: str, meter: CompileMeter, guarded_epochs=(),
              env_steps: int = 256, fused_shape=(8, 32)) -> dict:
    """One training leg through train_from_config's own functions.
    ``env_steps``/``fused_shape`` are the shipped config's; only the
    CPU test of this file (tests/test_chip_smoke.py) passes smaller
    ones."""
    import jax
    import numpy as np

    from ddls_tpu.config import load_config
    from ddls_tpu.train.checkpointer import restore_train_state
    from ddls_tpu.train.compat import apply_reference_compat

    tfc = _train_from_config()
    t_leg = time.perf_counter()
    cfg = load_config(tfc.DEFAULT_CONFIG_PATH, "rllib_config", [
        *COMMON_OVERRIDES, *overrides,
        f"launcher.num_epochs={num_epochs}",
        f"experiment.path_to_save={save_root}",
        f"experiment.name={name}"])
    apply_reference_compat(cfg)
    asked_mode = cfg["epoch_loop"]["loop_mode"]
    run = tfc.build_run(cfg)
    loop = run.epoch_loop
    try:
        n_dev = jax.device_count()
        assert dict(loop.mesh.shape) == {"dp": n_dev}, loop.mesh.shape
        before = jax.device_get(loop.state.params)
        probe = EpochProbe(loop, meter, guarded_epochs)
        build_s = time.perf_counter() - t_leg
        summary = run.launcher.run(logger=run.logger,
                                   checkpointer=run.checkpointer)
        cold_s = build_s + probe.seconds[0]

        # ---- what came out
        assert summary["epochs_run"] == num_epochs, summary["epochs_run"]
        for r in probe.results:
            assert r["env_steps_this_iter"] == env_steps, r
            loss = float(r["learner"]["total_loss"])
            assert np.isfinite(loss), loss
        after = jax.device_get(loop.state.params)
        moved = [float(np.abs(np.asarray(a) - np.asarray(b)).max()) > 0
                 for a, b in zip(jax.tree_util.tree_leaves(before),
                                 jax.tree_util.tree_leaves(after))]
        assert any(moved), "no parameter moved"
        assert loop.loop_mode == asked_mode, (loop.loop_mode, asked_mode)
        n_leaves = _assert_on_platform(loop.state, platform,
                                       f"{name} loop.state")
        if platform != "cpu":  # the CPU client reports no memory stats
            for dev in jax.devices():
                stats = dev.memory_stats()
                assert stats and stats["bytes_in_use"] > 0, (dev, stats)

        out = {"leg": name, "loop_mode": loop.loop_mode,
               "epochs": num_epochs, "build_s": round(build_s, 1),
               "cold_first_epoch_s": round(cold_s, 1),
               "epoch_s": [round(s, 2) for s in probe.seconds],
               "epoch_compile_s": [round(s, 2)
                                   for s in probe.compile_seconds],
               "last_epoch_s": round(probe.seconds[-1], 2),
               "total_loss": [float(r["learner"]["total_loss"])
                              for r in probe.results],
               "params_moved": f"{sum(moved)}/{len(moved)} leaves",
               "state_leaves_on_" + platform: n_leaves,
               **meter.since_last()}

        if asked_mode == "fused":
            assert loop.fused is not None
            shape = (loop.fused.num_lanes, loop.fused.segment_len)
            assert shape == tuple(fused_shape), shape
            bank = jax.tree_util.tree_leaves(loop.fused._banks)[0]
            _assert_lanes_spread(
                [(s.device, s.data.shape[0])
                 for s in bank.addressable_shards], shape[0],
                "fused banks")
            _assert_on_platform(loop.fused._state, platform,
                                "fused sim state")
            assert all(m is not None for m in probe.memo), probe.memo
            out["memo_per_epoch"] = [
                {k: int(m[k]) for k in ("hits", "misses", "evicts")}
                for m in probe.memo]
            out["memo_hit_rate"] = round(probe.memo[-1]["hit_rate"], 4)
            out["native_lookahead"] = bool(
                loop.vec_env.envs[0].cluster.use_native_lookahead)
        else:
            # the last per-epoch checkpoint IS the live state: restore
            # it onto the live shardings, bit-equal
            ckpts = sorted(os.listdir(run.checkpointer.checkpoints_dir))
            assert ckpts == [f"checkpoint_{i:06d}"
                             for i in range(num_epochs + 1)], ckpts
            restored = restore_train_state(
                os.path.join(run.checkpointer.checkpoints_dir, ckpts[-1]),
                target=loop.state)
            for got, live in zip(jax.tree_util.tree_leaves(restored),
                                 jax.tree_util.tree_leaves(loop.state)):
                assert got.sharding == live.sharding, (got.sharding,
                                                       live.sharding)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(live))
            assert len(probe.staged) == num_epochs, len(probe.staged)
            for shards in probe.staged:
                _assert_lanes_spread(shards, loop.num_envs,
                                     "staged trajectory")
            out["vec_env"] = getattr(loop.vec_env, "backend", "inproc")
            out["checkpoints"] = len(ckpts)
    finally:
        loop.close()

    workers = getattr(loop.vec_env, "worker_states", None)
    if workers is not None:
        # spawned env workers: every one reported, CPU-pinned, and none
        # opened another backend than the CPU's
        assert all(w is not None for w in workers), workers
        for w in workers:
            assert w["jax_platforms"] == "cpu", w
            assert set(w["backends"]) <= {"cpu"}, w
        out["workers"] = len(workers)
        out["workers_native_lookahead"] = all(
            w["native_lookahead"] for w in workers)
    print(f"[smoke timing, not a metric] {json.dumps(out)}", flush=True)
    return out


def serve_leg(platform: str, meter: CompileMeter) -> dict:
    """The shipped checkpoint behind a PolicyServer answers 16
    ``env_load32`` observations from the POLICY."""
    import jax
    import numpy as np

    from ddls_tpu.config import load_config
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.envs.baselines import FixedDegreePacking
    from ddls_tpu.serve import (PolicyServer, build_model_from_config,
                                checkpoint_graph_feature_dim,
                                load_checkpoint_params)

    tfc = _train_from_config()
    t0 = time.perf_counter()
    params = load_checkpoint_params(SHIPPED_CHECKPOINT)
    _assert_on_platform(params, platform, "restored checkpoint params")
    model, n_actions, graph_dim = build_model_from_config(
        tfc.DEFAULT_CONFIG_PATH, "rllib_config", list(SERVE_OVERRIDES))
    assert checkpoint_graph_feature_dim(params) == graph_dim

    cfg = load_config(tfc.DEFAULT_CONFIG_PATH, "rllib_config",
                      list(SERVE_OVERRIDES))
    env = RampJobPartitioningEnvironment(**cfg["env_config"])
    # first decision of 16 episodes: an empty cluster, where the rule
    # the policy implements answers degree 8
    pool = [env.reset(seed=1000 + i) for i in range(SERVE_REQUESTS)]
    rule = FixedDegreePacking(degree=SERVE_ACTION)
    assert all(rule.compute_action(o) == SERVE_ACTION for o in pool)

    pads = cfg["env_config"]["pad_obs_kwargs"]
    server = PolicyServer(model, params, max_nodes=pads["max_nodes"],
                          max_edges=pads["max_edges"], max_batch=8,
                          deadline_s=0.005, graph_feature_dim=graph_dim,
                          fallback=rule)
    ids = [server.submit(o) for o in pool]
    first = server.drain()
    first_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    ids += [server.submit(o) for o in pool]
    responses = first + server.drain()
    warm_s = time.perf_counter() - t1

    assert sorted(r.request_id for r in responses) == sorted(ids)
    for r in responses:
        assert r.source == "policy", r
        assert r.action == SERVE_ACTION, r
    summary = server.stats.summary()
    assert summary["fallback_rate"] == 0, summary
    assert not server.degraded
    assert summary["degraded_transitions"] == 0, summary
    out = {"leg": "serve", "requests": len(responses),
           "n_actions": n_actions,
           "cold_first_drain_s": round(first_s, 1),
           "warm_drain_s": round(warm_s, 3),
           "n_compiles": server.stats.n_compiles,
           "fallback_rate": summary["fallback_rate"],
           **meter.since_last()}
    print(f"[smoke timing, not a metric] {json.dumps(out)}", flush=True)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.parse_args(argv)

    t0 = time.perf_counter()
    device = print_header()
    if device["platform"] != "tpu":
        print(f"chip_smoke: backend is {device['platform']!r}, not 'tpu' "
              "— refusing to smoke-test another device",
              file=sys.stderr)
        return 2

    meter = CompileMeter()
    with tempfile.TemporaryDirectory(prefix="ddls_chip_smoke_") as scratch:
        train_leg("leg_a_pipelined", LEG_A_OVERRIDES, num_epochs=3,
                  platform="tpu", save_root=scratch, meter=meter)
        train_leg("leg_b_fused", LEG_B_OVERRIDES, num_epochs=2,
                  platform="tpu", save_root=scratch, meter=meter,
                  guarded_epochs=(2,))
        serve_leg("tpu", meter)
    print(f"chip_smoke: all legs passed in "
          f"{time.perf_counter() - t0:.0f}s (smoke timing)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # leg (a) spawns env workers, and spawn re-imports __main__: the
    # body must stay under this guard, in a real file
    sys.exit(main())
