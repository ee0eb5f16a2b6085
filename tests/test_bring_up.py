"""Start-up policy pins (ISSUE 21): where the compile cache goes, which
backend an entry point accepts, what a child process may touch, and
which native library gets loaded. All CPU, all cheap — the chip-side
proof is ``chip_smoke.py`` itself."""
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "scripts")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from ddls_tpu.utils import runtime


# ------------------------------------------------------- compile cache
class _ConfigSpy:
    """Records jax.config.update calls without applying them."""

    def __init__(self, monkeypatch):
        import jax

        self.updates = {}
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, value: self.updates.__setitem__(name, value))


def test_cache_dir_from_environment_wins_and_is_never_set_in_code(
        monkeypatch, tmp_path):
    spy = _ConfigSpy(monkeypatch)
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(runtime.CACHE_DIR_ENV, placed)
    assert runtime.configure_compile_cache() == placed
    assert os.environ[runtime.CACHE_DIR_ENV] == placed
    assert "jax_compilation_cache_dir" not in spy.updates


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    spy = _ConfigSpy(monkeypatch)
    monkeypatch.delenv(runtime.CACHE_DIR_ENV)
    first = runtime.configure_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert spy.updates["jax_compilation_cache_dir"] == first
    # exported, so spawned children inherit it — and the same answer on
    # a second call and in a second process (nothing from tempfile, a
    # pid or the clock in the path)
    assert os.environ[runtime.CACHE_DIR_ENV] == first
    assert runtime.configure_compile_cache() == first
    env = {k: v for k, v in os.environ.items()
           if k != runtime.CACHE_DIR_ENV}
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); "
         "from ddls_tpu.utils.runtime import configure_compile_cache; "
         "print(configure_compile_cache()); "
         "assert 'jax' not in sys.modules", REPO],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == first


def test_cache_thresholds_respect_the_callers_environment(monkeypatch):
    spy = _ConfigSpy(monkeypatch)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "7")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                       raising=False)
    runtime.configure_compile_cache()
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "7"
    assert "jax_persistent_cache_min_compile_time_secs" not in spy.updates
    assert spy.updates["jax_persistent_cache_min_entry_size_bytes"] == 0


# ------------------------------------------------------ backend policy
def test_require_accelerator_rejects_an_unrequested_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="needs an accelerator"):
        runtime.require_accelerator("serve_policy.py")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    summary = runtime.require_accelerator("serve_policy.py")
    assert summary["platform"] == "cpu"
    assert set(summary) == {"platform", "device_kind", "device_count"}


def test_serve_policy_fails_on_unrequested_cpu(monkeypatch, capsys):
    """JAX quietly picks the CPU when it finds no chip; an accelerator
    entry point must not: rc != 0, an error line — and no platform
    switch afterwards."""
    import jax

    import serve_policy

    monkeypatch.delenv("JAX_PLATFORMS")
    assert serve_policy.main([]) != 0
    assert "needs an accelerator" in capsys.readouterr().err
    assert "JAX_PLATFORMS" not in os.environ
    assert jax.config.jax_platforms == "cpu"  # the conftest's, untouched


# ----------------------------------------------------- native artefact
def test_native_artefact_is_keyed_by_source_content(monkeypatch, tmp_path):
    from ddls_tpu import native

    key, path = native.build_key(), native.lib_path()
    assert key in os.path.basename(path)
    edited = tmp_path / "engine.cpp"
    shutil.copy(native._SRC, edited)
    with open(edited, "a") as f:
        f.write("// one more byte\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    edited_key = native.build_key()
    assert edited_key != key
    assert native.lib_path() != path
    monkeypatch.setattr(native, "_CXX_FLAGS", native._CXX_FLAGS + ("-g",))
    assert native.build_key() not in (key, edited_key)


def test_wrong_hash_library_in_build_dir_is_never_loaded(monkeypatch,
                                                         tmp_path):
    """A ``_build/`` that travelled with a copied tree may hold a
    library built from another commit's engine.cpp; only the file named
    by THIS source's hash is ever opened."""
    from ddls_tpu import native

    if not native.native_available():
        pytest.skip("no C++ toolchain")
    build = tmp_path / "_build"
    build.mkdir()
    for stale in ("libddls_native.so", "libddls_native.0123456789abcdef.so"):
        (build / stale).write_bytes(b"not a shared object")
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    lib = native.get_lib()
    assert lib is not None
    assert lib._name == os.path.join(
        str(build), f"libddls_native.{native.build_key()}.so")


# ------------------------------------------------- one process per chip
def test_spawned_env_worker_is_cpu_pinned(dataset_dir, monkeypatch):
    """The parent may hold an accelerator and export a platform list
    that prefers it: the worker pins itself to the CPU before building
    its env and reports what it opened on the close ack."""
    from test_fused import _env_config

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.rl.rollout import ParallelVectorEnv

    env_kwargs = _env_config(dataset_dir)
    # what the chip tool's machine exports; the child inherits it
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    vec = ParallelVectorEnv(RampJobPartitioningEnvironment, env_kwargs,
                            num_envs=1, backend="pipe")
    try:
        vec.reset()
        vec.step(np.zeros(1, dtype=np.int64))
    finally:
        vec.close()
    (state,) = vec.worker_states
    assert state["jax_platforms"] == "cpu"
    assert set(state["backends"]) <= {"cpu"}
    assert state["native_lookahead"] in (True, False)


def test_staged_aliases_ignores_accelerator_shards():
    """A device pointer is not a host address: a shard in accelerator
    memory can never alias the ring's host slab, whatever its pointer
    value happens to be."""
    from ddls_tpu.rl.ring import staged_aliases

    view = np.zeros(64, np.float32)
    inside = view.__array_interface__["data"][0] + 8

    def leaf(platform):
        data = types.SimpleNamespace(unsafe_buffer_pointer=lambda: inside)
        shard = types.SimpleNamespace(
            device=types.SimpleNamespace(platform=platform), data=data)
        return types.SimpleNamespace(addressable_shards=[shard])

    assert staged_aliases(leaf("cpu"), {"obs": view})
    assert not staged_aliases(leaf("tpu"), {"obs": view})


# ----------------------------------------------------------- the smoke
def _run_smoke(*args, **env_overrides):
    env = {**os.environ, **env_overrides}
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)


def test_chip_smoke_import_and_help_are_side_effect_free():
    env = {k: v for k, v in os.environ.items()
           if k != runtime.CACHE_DIR_ENV}
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, sys; sys.path.insert(0, sys.argv[1]); "
         "before = dict(os.environ); import chip_smoke; "
         "assert dict(os.environ) == before; "
         "assert 'jax' not in sys.modules; "
         "assert 'ddls_tpu' not in sys.modules", REPO],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    helped = _run_smoke("--help")
    assert helped.returncode == 0
    assert "usage" in helped.stdout.lower()


def test_chip_smoke_refuses_a_backend_that_is_not_tpu():
    out = _run_smoke(JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "backend=cpu" in out.stdout  # the start-up facts still print
    assert "not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout  # and no result line


@pytest.mark.slow
def test_chip_smoke_legs_pass_on_cpu_at_tiny_size(tmp_path):
    """The legs' own assertions, driven at a size the CPU finishes: the
    place to debug ``chip_smoke.py`` before spending chip time on it.
    Runs in a subprocess from a real file — leg (a) spawns env workers,
    and spawn re-imports ``__main__``."""
    driver = tmp_path / "smoke_tiny.py"
    driver.write_text('''
import sys, tempfile
sys.path.insert(0, sys.argv[1])
import chip_smoke as cs

TINY = ("env_config=env_small",
        "epoch_loop.num_envs=8", "epoch_loop.rollout_length=2",
        "algo.algo_config.num_sgd_iter=2",
        "algo.algo_config.sgd_minibatch_size=8",
        "algo.algo_config.train_batch_size=16",
        "model.custom_model_config.out_features_msg=4",
        "model.custom_model_config.out_features_hidden=8",
        "model.custom_model_config.out_features_node=4",
        "model.custom_model_config.out_features_graph=4")

if __name__ == "__main__":
    cs.print_header()
    meter = cs.CompileMeter()
    with tempfile.TemporaryDirectory() as d:
        cs.train_leg("leg_a", cs.LEG_A_OVERRIDES + TINY, 3, "cpu", d,
                     meter, env_steps=16)
        cs.train_leg("leg_b", ("epoch_loop.loop_mode=fused",
                               "epoch_loop.updates_per_epoch=1",
                               "epoch_loop.fused_config="
                               "{lanes: 8, segment_len: 2}") + TINY,
                     2, "cpu", d, meter, guarded_epochs=(2,),
                     env_steps=16, fused_shape=(8, 2))
        cs.serve_leg("cpu", meter)
    print("TINY_SMOKE_OK")
''')
    out = subprocess.run([sys.executable, str(driver), REPO],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    assert "TINY_SMOKE_OK" in out.stdout
