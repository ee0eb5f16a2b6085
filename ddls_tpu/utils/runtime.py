"""Process start-up policy: compile-cache placement and backend selection.

Every entry point (train/serve scripts, ``benchmarks/run.py``,
``chip_smoke.py``, ``__graft_entry__.py``, tests/conftest.py) calls
:func:`configure_compile_cache` before its first jax op, so one
persistent XLA compilation cache is shared by every process of a run.
The program uses the backend JAX gives it and never switches platform
after a failure: measurement paths call :func:`require_accelerator`,
host-only children call :func:`pin_cpu_platform`.

This module is jax-free at import so callers can run it before jax
reads its environment.
"""
from __future__ import annotations

import os
import sys
from typing import Dict

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the in-checkout default (git-ignored). A FIXED path on purpose: a
#: directory that moves between processes (tempfile, pid, time) never
#: hits, and a spawned child must find its parent's entries.
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

# cache every compile that costs real time, whatever its size (the
# suite and the chip smoke re-compile the same kernels across processes)
_CACHE_SETTINGS = {
    "jax_persistent_cache_min_compile_time_secs": 0.5,
    "jax_persistent_cache_min_entry_size_bytes": 0,
    # jax keys an entry on the module with its debug info STRIPPED, and
    # a ``jax.named_scope`` is debug info: two builds that differ only
    # in their scopes share a key, and the second is handed the first's
    # executable, whose ``op_name`` paths (what a profile shows, and
    # what every scope metric reads) are the FIRST build's. With the
    # metadata in the key a profile's names are the build's own
    "jax_compilation_cache_include_metadata_in_key": True,
}


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in
    force.

    ``JAX_COMPILATION_CACHE_DIR`` set by the caller wins and nothing
    else names a directory — jax reads the variable itself. Unset, the
    fixed in-checkout :data:`DEFAULT_CACHE_DIR` is exported through the
    same variable, so child processes inherit it. Call before the first
    jax op; a jax that is already imported has read its environment and
    is updated through ``jax.config`` instead.
    """
    settings = {k: v for k, v in _CACHE_SETTINGS.items()
                if k.upper() not in os.environ}
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.environ[CACHE_DIR_ENV] = cache_dir
        settings["jax_compilation_cache_dir"] = cache_dir
    for name, value in settings.items():
        os.environ[name.upper()] = str(value)
    if "jax" in sys.modules:
        import jax

        for name, value in settings.items():
            jax.config.update(name, value)
    return cache_dir


def pin_cpu_platform() -> None:
    """Pin THIS process to the CPU backend before its first jax op.

    For children of a process that holds the accelerator (env workers,
    actor hosts) and for host-only modes:
    a chip belongs to one process, so a child that let jax pick its
    default backend would fail or hang on the parent's chip. The env
    var covers grandchildren; ``jax.config.update`` covers a jax that
    was imported before this call."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def jax_process_state() -> Dict[str, object]:
    """What this process asked jax for and which backends it actually
    opened, WITHOUT opening one: ``jax.devices()`` on a cold process
    initialises the default backend, which in a host-only process next
    to an accelerator owner would take (or fail on) the chip."""
    jax = sys.modules.get("jax")
    if jax is None:
        return {"jax_platforms": None, "backends": []}
    return {"jax_platforms": jax.config.jax_platforms,
            "backends": sorted(jax._src.xla_bridge._backends)}


def device_summary() -> Dict[str, object]:
    """The device every result line names, as jax reports it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_accelerator(what: str) -> Dict[str, object]:
    """Fail unless jax's default backend is an accelerator, or the
    caller explicitly set ``JAX_PLATFORMS=cpu``. JAX quietly picks the
    CPU when it finds no chip; an accelerator measurement must not.
    Returns :func:`device_summary`."""
    summary = device_summary()
    asked_for_cpu = (os.environ.get("JAX_PLATFORMS", "").strip().lower()
                     == "cpu")
    if summary["platform"] == "cpu" and not asked_for_cpu:
        raise RuntimeError(
            f"{what} needs an accelerator but jax's default backend is "
            f"cpu ({summary['device_kind']} x{summary['device_count']}); "
            "set JAX_PLATFORMS=cpu to run it on the CPU on purpose")
    return summary
