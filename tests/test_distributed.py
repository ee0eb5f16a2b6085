"""Multi-host mesh path: 2 CPU processes x 2 virtual devices each join one
global mesh; shard_batch assembles per-process rollout shards and the jitted
update all-reduces gradients across hosts (SURVEY.md §5.8 TPU-native
equivalent of the reference's Ray worker topology)."""
import glob
import os
import socket
import subprocess
import sys
from typing import List

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_distributed_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


#: output fingerprints of the coordinator/gloo CONNECT race (the
#: documented position-44 tier-1 flake, ISSUE 13): the port picked by
#: ``_free_port`` can be re-bound by another process between selection
#: and the coordinator's bind (TOCTOU), and gloo's connectFullMesh can
#: time out when one worker's jax init outruns the other's. Both are
#: environment races, not code failures — retried once with a FRESH
#: port; anything else still fails immediately.
_CONNECT_RACE_PATTERNS = (
    "Address already in use",
    "Connection refused",
    "Connection reset",
    "connectFullMesh",
    "DEADLINE_EXCEEDED",
    "Timed out waiting",
    "failed to connect",
)


def _looks_like_connect_race(outputs: List[str]) -> bool:
    return any(p in out for out in outputs if out
               for p in _CONNECT_RACE_PATTERNS)


def _run_lockstep(make_argvs, timeout: float, attempts: int = 2):
    """Launch one process per argv in lockstep; returns (procs, outputs).

    ``make_argvs`` is a zero-arg factory returning the argv list — it is
    re-invoked on retry so each attempt picks a FRESH coordinator port
    (the deflake: a recycled port is exactly the race being retried).
    Retries are bounded and only fire for the connect race (a timeout,
    or a nonzero exit whose output carries a connect-race fingerprint);
    deterministic failures surface on the first attempt. On timeout
    every child is killed AND reaped before retrying/failing, so no
    zombies or stale coordinator sockets leak into later tests."""
    env = _worker_env()
    for attempt in range(attempts):
        last = attempt == attempts - 1
        procs = [subprocess.Popen(argv, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True,
                                  env=env)
                 for argv in make_argvs()]
        outputs = []
        timed_out = False
        for proc in procs:
            try:
                out, _ = proc.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                for p in procs:
                    p.wait()
                timed_out = True
                break
            outputs.append(out)
        if timed_out:
            if last:
                pytest.fail("distributed processes timed out "
                            f"({attempts} attempts, fresh port each)")
            continue
        failed = any(p.returncode != 0 for p in procs)
        if failed and not last and _looks_like_connect_race(outputs):
            continue
        return procs, outputs
    raise AssertionError("unreachable")  # pragma: no cover


def test_two_process_global_mesh():
    def argvs():
        coordinator = f"localhost:{_free_port()}"
        return [[sys.executable, WORKER, coordinator, "2", str(i), REPO]
                for i in range(2)]

    procs, outputs = _run_lockstep(argvs, timeout=180)
    for i, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"worker {i} failed:\n{out}"
        assert "global_devices=4" in out, out
        assert f"UPDATE process={i} w=1.300000" in out, out


def test_two_process_training_cli(tmp_path):
    """The full multi-host path through the real CLI: 2 CPU processes x 2
    virtual devices train PPO for 1 epoch over one global mesh; only the
    primary writes artifacts."""
    script = os.path.join(REPO, "scripts", "train_from_config.py")

    def argvs():
        overrides = [
            "launcher.num_epochs=1", "epoch_loop.num_envs=2",
            "epoch_loop.rollout_length=4",
            "epoch_loop.use_parallel_envs=false",
            "eval_config.evaluation_interval=null",
            "env_config.jobs_config.replication_factor=2",
            "env_config.jobs_config.job_sampling_mode=remove",
            "env_config.jobs_config.synthetic.n_cnn=1",
            "env_config.jobs_config.synthetic.n_translation=1",
            "env_config.pad_obs_kwargs.max_nodes=32",
            "env_config.pad_obs_kwargs.max_edges=64",
            "algo.algo_config.num_sgd_iter=2",
            f"experiment.path_to_save={tmp_path}",
            "distributed.enabled=true",
            f"distributed.coordinator_address=localhost:{_free_port()}",
            "distributed.num_processes=2", "distributed.platform=cpu",
        ]
        return [[sys.executable, script] + overrides
                + [f"distributed.process_id={i}"] for i in range(2)]

    procs, outputs = _run_lockstep(argvs, timeout=420)
    for i, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"process {i} failed:\n{out[-3000:]}"
        assert f"process {i}/2" in out
        assert "Run complete: 1 epochs" in out
    # primary-only artifacts
    assert "Experiment save dir" in outputs[0]
    assert "Experiment save dir" not in outputs[1]
    assert glob.glob(str(tmp_path / "**" / "results.*"), recursive=True)


def test_four_process_real_epoch_bit_identical_params():
    """VERDICT r3 next #7: one real collect+update epoch (x2) of the
    actual partitioning env across 4 gloo processes in a blocking-heavy
    regime. Each process's envs diverge (different blocking patterns —
    the deterministic-gate hazard class), yet the replicated parameters
    must end BIT-identical on every process."""
    worker = os.path.join(REPO, "tests", "_distributed_epoch_worker.py")

    def argvs():
        coordinator = f"localhost:{_free_port()}"
        return [[sys.executable, worker, coordinator, "4", str(i), REPO]
                for i in range(4)]

    procs, outputs = _run_lockstep(argvs, timeout=600)
    digests, blocked = [], []
    for i, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith(f"PARAMS process={i} "):
                digests.append(line.split("digest=")[1].strip())
            if line.startswith(f"DIVERGE process={i} "):
                # strip the process id so the set compares only histories
                blocked.append(line.split(" ", 2)[2])
    assert len(digests) == 4, outputs
    assert len(set(digests)) == 1, f"params diverged across hosts: {digests}"
    # the hazard actually exercised: processes saw different env histories
    assert len(set(blocked)) >= 2, f"env histories identical: {blocked}"


def test_two_process_device_collector_bit_identical_params():
    """VERDICT r4 item 6: multi-host x device_collector. Each of 2 gloo
    processes collects fixed-length segments in the jitted env on its
    OWN per-process job banks (banks must differ — asserted), runs the
    sharded update over the global mesh, and the replicated parameters
    must end BIT-identical (in-kernel resets/done gates are the new
    deterministic-gate hazard class)."""
    worker = os.path.join(REPO, "tests", "_distributed_device_worker.py")

    def argvs():
        coordinator = f"localhost:{_free_port()}"
        return [[sys.executable, worker, coordinator, "2", str(i), REPO]
                for i in range(2)]

    procs, outputs = _run_lockstep(argvs, timeout=600)
    params, banks = [], []
    for i, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith(f"PARAMS process={i} "):
                params.append(line.split("digest=")[1].strip())
            if line.startswith(f"BANKS process={i} "):
                banks.append(line.split("digest=")[1].strip())
    assert len(params) == 2, outputs
    assert len(set(params)) == 1, f"params diverged across hosts: {params}"
    assert len(set(banks)) == 2, "per-process banks were identical"
