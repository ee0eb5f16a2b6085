"""Small shared utilities.

TPU-native counterpart of the reference's grab-bag ``ddls/utils.py``
(reference: ddls/utils.py:20-104,485-558). Seeding covers numpy/random and
returns a JAX PRNG key instead of touching torch/CUDA state.
"""
from __future__ import annotations

import glob
import importlib
import pathlib
import pickle
import random
import sqlite3
from typing import Any, Mapping

import numpy as np


class SqliteDict:
    """Minimal persistent dict over stdlib sqlite3 (sqlitedict stand-in used
    by the reference's Logger/cluster save paths)."""

    def __init__(self, path: str):
        self.path = str(path)
        self._conn = sqlite3.connect(self.path)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS kv (key TEXT PRIMARY KEY, val BLOB)")
        self._conn.commit()

    def __setitem__(self, key: str, value: Any) -> None:
        self._conn.execute(
            "REPLACE INTO kv (key, val) VALUES (?, ?)",
            (key, pickle.dumps(value)))

    def __getitem__(self, key: str) -> Any:
        row = self._conn.execute(
            "SELECT val FROM kv WHERE key = ?", (key,)).fetchone()
        if row is None:
            raise KeyError(key)
        return pickle.loads(row[0])

    def get(self, key: str, default: Any = None) -> Any:
        try:
            return self[key]
        except KeyError:
            return default

    def keys(self):
        return [r[0] for r in
                self._conn.execute("SELECT key FROM kv").fetchall()]

    def commit(self) -> None:
        self._conn.commit()

    def close(self) -> None:
        self._conn.commit()
        self._conn.close()


def save_logs_to_dir(out_dir, logs: Mapping[str, Mapping[str, Any]],
                     use_sqlite: bool) -> None:
    """Write each named log dict into ``out_dir`` as either a gzip pickle
    or a SqliteDict database. Callers must pass a SNAPSHOT (not live,
    still-mutating dicts) when invoking this from a background thread."""
    out_dir = pathlib.Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    for log_name, log in logs.items():
        if use_sqlite:
            db = SqliteDict(str(out_dir / f"{log_name}.sqlite"))
            try:
                for key, val in dict(log).items():
                    db[key] = val
                db.commit()
            finally:
                db.close()
        else:
            import gzip

            with gzip.open(out_dir / f"{log_name}.pkl", "wb") as f:
                pickle.dump(dict(log), f)


def snapshot_logs(logs: Mapping[str, Mapping[str, Any]]
                  ) -> dict:
    """Shallow-copy each log's dict and list values on the calling thread
    so a background writer never races the simulator's mutations."""
    return {name: {k: (list(v) if isinstance(v, list) else v)
                   for k, v in log.items()}
            for name, log in logs.items()}


def merge_logs(old: Any, new: Any) -> Any:
    """Extend-by-key merge for incremental log flushes: dicts merge
    recursively, lists extend, scalars overwrite."""
    if isinstance(old, dict) and isinstance(new, dict):
        out = dict(old)
        for k, v in new.items():
            out[k] = merge_logs(out.get(k), v) if k in out else v
        return out
    if isinstance(old, list) and isinstance(new, list):
        return old + new
    return new


class Stopwatch:
    """Simulated wall clock (reference: ddls/utils.py:485)."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self._time = 0.0

    def tick(self, amount: float = 1.0) -> None:
        self._time += amount

    def time(self) -> float:
        return self._time


def available_cores() -> int:
    """CPU cores this process may use (affinity-aware where supported)."""
    import os

    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


def seed_everything(seed: int) -> None:
    """Seed numpy + stdlib random.

    The reference seeds numpy/random/torch-CUDA globally
    (ddls/utils.py:20-47). JAX randomness is functional; use
    :func:`prng_key` in RL code to thread a key through instead of mutating
    backend state. Deliberately does NOT import jax: the simulator is pure
    host code and must not force accelerator-backend initialisation.
    """
    np.random.seed(seed)
    random.seed(seed)


def prng_key(seed: int):
    """A JAX PRNG key for the learner/rollout code paths."""
    import jax

    return jax.random.PRNGKey(seed)


def flatten_lists(nested) -> list:
    return [item for sub in nested for item in sub]


def get_class_from_path(path: str):
    """Import ``pkg.module.ClassName`` from its dotted path.

    Also accepts reference-repo class paths (``ddls.devices...``) and maps them
    onto their ddls_tpu equivalents so the reference Hydra config trees run
    unchanged (reference: ddls/utils.py:513).
    """
    path = _REFERENCE_CLASS_ALIASES.get(path, path)
    module_path, _, name = path.rpartition(".")
    module = importlib.import_module(module_path)
    return getattr(module, name)


# Class paths appearing in the reference's config trees, mapped to ours.
_REFERENCE_CLASS_ALIASES = {
    "ddls.devices.processors.gpus.A100.A100": "ddls_tpu.hardware.devices.A100",
    "ddls.distributions.fixed.Fixed": "ddls_tpu.demands.distributions.Fixed",
    "ddls.distributions.uniform.Uniform": "ddls_tpu.demands.distributions.Uniform",
    "ddls.distributions.probability_mass_function.ProbabilityMassFunction":
        "ddls_tpu.demands.distributions.ProbabilityMassFunction",
    "ddls.distributions.custom_skew_norm.CustomSkewNorm":
        "ddls_tpu.demands.distributions.CustomSkewNorm",
    "ddls.distributions.list_of_distributions.ListOfDistributions":
        "ddls_tpu.demands.distributions.ListOfDistributions",
    "ddls.environments.ramp_job_partitioning.ramp_job_partitioning_environment.RampJobPartitioningEnvironment":
        "ddls_tpu.envs.partitioning_env.RampJobPartitioningEnvironment",
    "ddls.environments.ramp_job_placement_shaping.ramp_job_placement_shaping_environment.RampJobPlacementShapingEnvironment":
        "ddls_tpu.envs.placement_shaping_env.RampJobPlacementShapingEnvironment",
    "ddls.loops.eval_loop.EvalLoop": "ddls_tpu.train.loops.EvalLoop",
    "ddls.environments.ramp_job_partitioning.agents.random.Random":
        "ddls_tpu.envs.baselines.RandomActor",
    "ddls.environments.ramp_job_partitioning.agents.no_parallelism.NoParallelism":
        "ddls_tpu.envs.baselines.NoParallelism",
    "ddls.environments.ramp_job_partitioning.agents.min_parallelism.MinParallelism":
        "ddls_tpu.envs.baselines.MinParallelism",
    "ddls.environments.ramp_job_partitioning.agents.max_parallelism.MaxParallelism":
        "ddls_tpu.envs.baselines.MaxParallelism",
    "ddls.environments.ramp_job_partitioning.agents.sip_ml.SiPML":
        "ddls_tpu.envs.baselines.SiPML",
    "ddls.environments.ramp_job_partitioning.agents.acceptable_jct.AcceptableJCT":
        "ddls_tpu.envs.baselines.AcceptableJCT",
    "ddls.environments.ramp_job_placement_shaping.agents.first_fit.FirstFit":
        "ddls_tpu.envs.baselines.FirstFitShaper",
    "ddls.environments.ramp_job_placement_shaping.agents.last_fit.LastFit":
        "ddls_tpu.envs.baselines.LastFitShaper",
    "ddls.environments.ramp_job_placement_shaping.agents.random.Random":
        "ddls_tpu.envs.baselines.RandomShaper",
    # legacy simulator path
    "ddls.environments.cluster.cluster_environment.ClusterEnvironment":
        "ddls_tpu.sim.legacy_cluster.ClusterEnvironment",
    "ddls.environments.job_placing.job_placing_all_nodes_environment.JobPlacingAllNodesEnvironment":
        "ddls_tpu.envs.job_placing_env.JobPlacingAllNodesEnvironment",
    "ddls.managers.placers.random_job_placer.RandomJobPlacer":
        "ddls_tpu.agents.managers.RandomJobPlacer",
    "ddls.managers.schedulers.fifo_job_scheduler.FIFOJobScheduler":
        "ddls_tpu.agents.managers.FIFOJobScheduler",
    "ddls.managers.schedulers.srpt_job_scheduler.SRPTJobScheduler":
        "ddls_tpu.agents.managers.SRPTJobScheduler",
    "ddls.managers.schedulers.random_job_scheduler.RandomJobScheduler":
        "ddls_tpu.agents.managers.RandomJobScheduler",
}


def unique_experiment_dir(base: str, name: str) -> str:
    """Create ``base/name/name_<i>/`` with the next free integer suffix
    (reference: ddls/utils.py:530)."""
    root = pathlib.Path(base) / name
    root.mkdir(parents=True, exist_ok=True)
    taken = []
    for item in glob.glob(str(root / f"{name}_*")):
        tail = item.rsplit("_", 1)[-1]
        if tail.isdigit():
            taken.append(int(tail))
    idx = max(taken) + 1 if taken else 0
    out = root / f"{name}_{idx}"
    out.mkdir(parents=True, exist_ok=False)
    return str(out)


def recursive_update(base: dict, overrides: Mapping[str, Any]) -> dict:
    """Deep-merge ``overrides`` into ``base`` (reference: ddls/utils.py:577)."""
    for key, val in overrides.items():
        if key in base and isinstance(base[key], dict) and isinstance(val, Mapping):
            recursive_update(base[key], val)
        else:
            base[key] = val
    return base
