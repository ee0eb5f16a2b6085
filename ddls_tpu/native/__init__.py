"""Native (C++) hot-path kernels for the cluster simulator.

The reference delegates its accelerated work to torch/DGL/Ray
(SURVEY.md §2.9); its simulator hot loop is pure Python. This package is
the TPU-framework counterpart for the *host* side of that loop: the
per-step kernels that dominate env.step wall-clock (the lookahead tick
engine first — cluster.py:_run_lookahead) implemented in C++ with flat
array interfaces, loaded via ctypes (no pybind11 in the image).

The library is compiled lazily with g++ on first use and cached under
``_build/`` in a file named by the content hash of ``engine.cpp`` plus
the compile flags (``build_key``): a library built from another
commit's source, or with other flags, has another name and is never
loaded. Every entry point degrades (returns None /
``native_available() is False``) when no toolchain is present, so the
Python engines remain the source of truth and the fallback — with a
warning, because that fallback is ~50x slower.

Contract: kernels are bit-exact with the host engines (f64, identical
operation order) — golden stats tests must pass unchanged with the native
path enabled.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "engine.cpp")
_BUILD_DIR = os.path.join(_DIR, "_build")
_CXX_FLAGS = ("-O2", "-std=c++17", "-shared", "-fPIC")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

_f64 = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
_i32 = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_u8 = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")


def build_key() -> str:
    """Content hash of the kernel source plus the compile flags — the
    artefact's identity. File mtimes say nothing once a tree has been
    copied, so staleness is decided by content alone."""
    digest = hashlib.sha256(" ".join(_CXX_FLAGS).encode())
    with open(_SRC, "rb") as f:
        digest.update(f.read())
    return digest.hexdigest()[:16]


def lib_path() -> str:
    return os.path.join(_BUILD_DIR, f"libddls_native.{build_key()}.so")


def _warn_fallback(why: str) -> None:
    warnings.warn(f"native engine unavailable ({why}); falling back to "
                  "the ~50x slower Python engines")


def _compile(lib: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    if os.path.exists(lib):
        return True
    # per-pid temp + atomic replace: concurrent first-use across processes
    # (parallel env workers, multi-host tests) must not interleave output
    tmp = f"{lib}.tmp.{os.getpid()}"
    cmd = ["g++", *_CXX_FLAGS, _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0:
            _warn_fallback("g++ failed: " + proc.stderr.strip()[-500:])
            return False
        os.replace(tmp, lib)
        return True
    except (OSError, subprocess.TimeoutExpired) as err:
        _warn_fallback(f"build did not run: {err!r}")
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


_i64 = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.ddls_lookahead.restype = None
    lib.ddls_lookahead.argtypes = [
        ctypes.c_int64, _f64, _i32, _f64, _i32,        # ops
        ctypes.c_int64, _f64, _i32, _i32, _u8, _u8, _f64,  # deps
        ctypes.c_int64, _i32,                          # links, dep_channel
        ctypes.c_int64, ctypes.c_int64,                # workers, channels
        _f64,                                          # out[5]
    ]
    lib.ddls_first_fit_block.restype = ctypes.c_int64
    lib.ddls_first_fit_block.argtypes = [
        _i64, ctypes.c_int64,                          # shapes [n,3]
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # meta shape
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # ramp shape
        _f64, _u8,                                     # mem, blocked
        ctypes.c_double, ctypes.c_int32,               # op_size, check_mem
        ctypes.c_int32,                                # meta_scan
        _i64, _i32,                                    # out_origin, out
    ]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled kernel library, or None when unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            lib = lib_path()
            if _compile(lib):
                _lib = _bind(ctypes.CDLL(lib))
            else:
                _load_failed = True
        except OSError as err:
            _warn_fallback(f"load failed: {err!r}")
            _load_failed = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def run_first_fit_block(shapes, meta_shape, ramp_shape, mem, blocked,
                        op_size, meta_scan: bool):
    """First-fit block search on the C++ kernel.

    ``shapes``: [n, 3] int64 candidate shapes (search order preserved;
    -1 in the last slot selects the diagonal layout). ``mem``/``blocked``:
    C-order [C*R*S] views of the ramp snapshot. Returns
    (list of (c, r, s) coords in enumeration order, origin) or None when
    nothing fits, or the string "unavailable" when the library is absent
    (caller falls back to the Python search)."""
    lib = get_lib()
    if lib is None:
        return "unavailable"
    shapes = np.ascontiguousarray(shapes, np.int64)
    if shapes.size == 0:
        return None
    rC, rR, rS = ramp_shape
    if meta_scan and (meta_shape[0] > rC or meta_shape[1] > rR
                      or meta_shape[2] > rS):
        # a meta block larger than the ramp can never fit (find_meta_block's
        # span guard); bailing here also keeps the out buffer bound valid
        return None
    # worst-case servers a candidate block can cover: the kernel writes
    # C*R*S cells per attempt (diagonal shapes cover |C| cells; abs also
    # turns the -1 marker into a safe overestimate)
    max_block = int(np.abs(shapes).prod(axis=1).max())
    out = np.empty((max(rC * rR * rS, max_block), 3), np.int32)
    origin = np.zeros(3, np.int64)
    n = lib.ddls_first_fit_block(
        shapes, shapes.shape[0], meta_shape[0], meta_shape[1],
        meta_shape[2], rC, rR, rS,
        np.ascontiguousarray(mem, np.float64),
        np.ascontiguousarray(blocked, np.uint8),
        float(op_size) if op_size is not None else 0.0,
        1 if op_size is not None else 0,
        1 if meta_scan else 0, origin, out)
    if n == 0:
        return None
    block = [tuple(int(x) for x in row) for row in out[:n]]
    return block, (int(origin[0]), int(origin[1]), int(origin[2]))


def run_lookahead(arrays) -> Optional[Tuple[float, float, float, float]]:
    """Run the C++ lookahead on a ``LookaheadArrays``
    (``native/arrays.py``: f64, exact unpadded sizes). Returns
    (t, comm_overhead, comp_overhead, busy) for ONE training step, or
    None when the library is unavailable or the engine could not finish
    (caller falls back to the host engine, which raises with
    diagnostics)."""
    lib = get_lib()
    if lib is None:
        return None
    a = arrays
    out = np.zeros(5, dtype=np.float64)
    lib.ddls_lookahead(
        a.op_remaining.shape[0],
        np.ascontiguousarray(a.op_remaining, np.float64),
        np.ascontiguousarray(a.op_worker, np.int32),
        np.ascontiguousarray(a.op_score, np.float64),
        np.ascontiguousarray(a.num_parents, np.int32),
        a.dep_remaining.shape[0],
        np.ascontiguousarray(a.dep_remaining, np.float64),
        np.ascontiguousarray(a.dep_src, np.int32),
        np.ascontiguousarray(a.dep_dst, np.int32),
        np.ascontiguousarray(a.dep_mutual, np.uint8),
        np.ascontiguousarray(a.dep_is_flow, np.uint8),
        np.ascontiguousarray(a.dep_score, np.float64),
        a.dep_channel.shape[1],
        np.ascontiguousarray(a.dep_channel, np.int32),
        a.num_workers, a.num_channels, out)
    if out[4] != 1.0:
        return None
    return float(out[0]), float(out[1]), float(out[2]), float(out[3])
