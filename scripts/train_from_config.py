"""Train a PAC-ML policy from a composed YAML config.

TPU-native equivalent of the reference's scripts/train_rllib_from_config.py
(SURVEY.md §3.1): composes the config-group tree, seeds globally, builds the
epoch loop (merging algo/model/env_config/eval_config groups into its
kwargs exactly as the reference merges them into the RLlib config), then
runs Launcher + Logger + Checkpointer. Instead of CUDA device picking and
Ray worker spawning, device discovery is ``jax.devices()`` on the pod
slice/chip this process owns.

Usage:
    python scripts/train_from_config.py \
        [--config-path scripts/ramp_job_partitioning_configs] \
        [--config-name rllib_config] \
        [launcher.num_epochs=3 algo=ppo env_config=env_dev ...]
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddls_tpu.config import load_config, save_config
from ddls_tpu.telemetry import startup
from ddls_tpu.train.compat import apply_reference_compat
from ddls_tpu.train import Checkpointer, Launcher, Logger, make_epoch_loop
from ddls_tpu.utils.common import seed_everything, unique_experiment_dir
from ddls_tpu.utils.runtime import configure_compile_cache

DEFAULT_CONFIG_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "ramp_job_partitioning_configs")


def build_epoch_loop_kwargs(cfg: dict) -> dict:
    """Merge config groups into epoch-loop kwargs (the reference merges the
    same groups into cfg.epoch_loop.rllib_config:
    train_rllib_from_config.py:46-64)."""
    kwargs = {k: v for k, v in cfg.get("epoch_loop", {}).items()
              if k != "_target_"}
    if "env_config" in cfg:
        kwargs["env_config"] = cfg["env_config"]
    if "model" in cfg:
        import copy

        model = copy.deepcopy(cfg["model"])  # don't alias/mutate cfg
        algo_model = (cfg.get("algo") or {}).get("model")
        if algo_model:
            from ddls_tpu.utils.common import recursive_update
            model = recursive_update(model, copy.deepcopy(algo_model))
        kwargs["model"] = model
    if "algo" in cfg:
        kwargs["algo_config"] = cfg["algo"].get("algo_config", {})
    if "eval_config" in cfg:
        for key in ("evaluation_interval", "evaluation_duration",
                    "evaluation_config"):
            if key in cfg["eval_config"]:
                kwargs[key] = cfg["eval_config"][key]
    experiment = cfg.get("experiment", {})
    if "train_seed" in experiment:
        kwargs["seed"] = experiment["train_seed"]
    if "test_seed" in experiment:
        kwargs["test_seed"] = experiment["test_seed"]
    return kwargs


@dataclasses.dataclass
class TrainRun:
    """Everything one training run owns, built from a composed config."""
    epoch_loop: object
    launcher: Launcher
    logger: Optional[Logger]
    checkpointer: Optional[Checkpointer]
    save_dir: Optional[str]
    primary: bool


def _prepare_experiment(cfg: dict):
    """What comes before the epoch loop (``startup.config``): the
    distributed runtime, the seeds, the save dir with the composed
    config, wandb. Returns (primary, save_dir, wandb)."""
    experiment = cfg.get("experiment", {})

    # XLA dump must be requested before the first backend init (SURVEY
    # §5.1; jax is only imported lazily below, so this is early enough)
    if experiment.get("xla_dump_to"):
        from ddls_tpu.utils.profiling import enable_xla_dump

        enable_xla_dump(experiment["xla_dump_to"])

    # opt-in multi-host: join the global JAX runtime before any backend
    # init so the mesh spans every host's devices (SURVEY.md §5.8; replaces
    # the reference's Ray worker topology)
    distributed_cfg = dict(cfg.get("distributed") or {})
    primary = True
    if distributed_cfg.pop("enabled", False):
        from ddls_tpu.parallel import initialize_distributed, is_primary

        info = initialize_distributed(**distributed_cfg)
        primary = is_primary()
        print(f"Joined distributed runtime: process "
              f"{info['process_index']}/{info['process_count']}, "
              f"{info['num_local_devices']} local / "
              f"{info['num_global_devices']} global devices")

    seed_everything(int(experiment.get("train_seed", 0)))

    # only the primary process owns disk artifacts and external logging
    save_dir = None
    if primary:
        save_dir = unique_experiment_dir(
            experiment.get("path_to_save", "/tmp/ddls_tpu/sims"),
            experiment.get("name", "experiment"))
        cfg.setdefault("experiment", {})["save_dir"] = save_dir
        save_config(cfg, os.path.join(save_dir, "config.yaml"))
        print(f"Experiment save dir: {save_dir}")

    wandb = None
    if primary and cfg.get("wandb"):
        try:
            import wandb as wandb_module

            wandb_module.init(config=cfg, **cfg["wandb"].get("init", {}))
            wandb = wandb_module
        except ImportError:
            print("wandb requested but not installed; continuing without it")
    return primary, save_dir, wandb


def build_run(cfg: dict) -> TrainRun:
    """Seed, create the save dir, and build epoch loop + Launcher +
    Logger + Checkpointer from a composed (and compat-applied) config.
    The caller owns ``run.epoch_loop.close()``. Timed as
    ``startup.build_run``, its phases nested inside it
    (docs/telemetry.md lists them); what the process did before it is
    ``startup.before_build``."""
    startup.span_since_process_start("startup.before_build")
    with startup.span("startup.build_run"):
        with startup.span("startup.config"):
            primary, save_dir, wandb = _prepare_experiment(cfg)
        algo_name = (cfg.get("algo") or {}).get("algo_name", "ppo")
        epoch_loop = make_epoch_loop(algo_name, wandb=wandb,
                                     **build_epoch_loop_kwargs(cfg))
    print(f"Initialised {type(epoch_loop).__name__} ({algo_name}): "
          f"{epoch_loop.num_envs} envs x "
          f"{epoch_loop.rollout_length} steps on mesh "
          f"{dict(epoch_loop.mesh.shape)}")

    return TrainRun(
        epoch_loop=epoch_loop,
        launcher=Launcher(epoch_loop=epoch_loop,
                          **cfg.get("launcher", {})),
        logger=(Logger(path_to_save=save_dir, **cfg.get("logger", {}))
                if primary else None),
        checkpointer=(Checkpointer(path_to_save=save_dir,
                                   **cfg.get("checkpointer", {}))
                      if primary else None),
        save_dir=save_dir, primary=primary)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config-path", default=DEFAULT_CONFIG_PATH)
    parser.add_argument("--config-name", default="rllib_config")
    parser.add_argument("overrides", nargs="*",
                        help="dotted-path overrides, e.g. launcher.num_epochs=3")
    args = parser.parse_args(argv)

    configure_compile_cache()
    cfg = load_config(args.config_path, args.config_name, args.overrides)
    apply_reference_compat(cfg)
    run = build_run(cfg)

    from ddls_tpu.utils.profiling import jax_profiler_trace

    jax_trace_dir = (os.path.join(run.save_dir, "jax_trace")
                     if (run.primary and (cfg.get("experiment")
                                          or {}).get("profile_jax"))
                     else None)
    with jax_profiler_trace(jax_trace_dir):
        summary = run.launcher.run(logger=run.logger,
                                   checkpointer=run.checkpointer)
    if jax_trace_dir:
        print(f"Saved jax profiler trace under {jax_trace_dir}")
    if run.primary:
        print(f"Best checkpoint: {summary['best_checkpoint']} "
              f"({run.epoch_loop.metric}={summary['best_metric_value']})")
    run.epoch_loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
