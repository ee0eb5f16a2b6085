"""The comparisons that decide ``correct``, against plain references.

Training: a faster simulator must leave the simulated statistics alone.
The host oracle is ``RampJobPartitioningEnvironment`` on the Python
lookahead engine in float64; the system under test replays the
oracle's own action sequence and every decision must agree —
``accepted`` and the blocked ``cause`` exactly, times within the
tolerance the traffic file gives with its reason.

Serving: the served action against the argmax of one plain unbatched,
unbucketed ``model.apply`` at the highest matmul precision.

The oracle is the program's own reference engine, not a copy (PERF.md,
Open questions); the comparison itself lives here.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

from benchmarks import harness


def _close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b)) + 1e-12


def first_mismatch(a: Sequence[dict], b: Sequence[dict], rtol: float
                   ) -> Optional[dict]:
    """First index at which two event lists disagree: every field equal,
    floats within ``rtol`` (0 demands bit-equality); None when all
    agree and the lengths match."""
    for i, (ea, eb) in enumerate(zip(a, b)):
        if set(ea) != set(eb):
            return {"index": i, "field": "keys", "a": ea, "b": eb}
        for key, va in ea.items():
            vb = eb[key]
            floats = (isinstance(va, float) or isinstance(vb, float))
            same = (_close(float(va), float(vb), rtol)
                    if floats and not isinstance(va, bool)
                    and not isinstance(vb, bool) else va == vb)
            if not same:
                return {"index": i, "field": key, "a": ea, "b": eb}
    if len(a) != len(b):
        return {"index": min(len(a), len(b)), "field": "length",
                "a": len(a), "b": len(b)}
    return None


def _host_env(env_config: dict, native: bool):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    return RampJobPartitioningEnvironment(
        **env_config, use_native_lookahead=native)


def native_engine_fidelity(env_config: dict, decisions: int, seed: int,
                           rtol: float) -> Dict[str, Any]:
    """What a host-collection worker steps (the C++ lookahead engine)
    against the Python engine on the same seeded action sequence: the
    whole flight trace (arrivals, decisions, completions) compared."""
    from ddls_tpu.scenarios.conformance import run_recorded_episode
    from ddls_tpu.telemetry import flight

    oracle_events, actions = run_recorded_episode(
        _host_env(env_config, native=False), seed,
        max_decisions=decisions)
    native_env = _host_env(env_config, native=True)
    if not native_env.cluster.use_native_lookahead:
        return {"ok": False, "why": "the native engine did not load"}
    native_events, replayed = run_recorded_episode(
        native_env, seed, actions=actions, max_decisions=decisions)
    a = flight.comparable_events(oracle_events)
    b = flight.comparable_events(native_events)
    mismatch = first_mismatch(a, b, rtol)
    return {"ok": mismatch is None and len(replayed) == len(actions),
            "kind": "native_engine", "decisions": len(actions),
            "events": len(a), "rtol": rtol, "mismatch": mismatch}


def jitted_episode_fidelity(env_config: dict, decisions: int, seed: int,
                            rtol: float) -> Dict[str, Any]:
    """The in-kernel environment (the jitted episode kernel over the
    cell's own tables and pads, in float32 on the accelerator) replays
    the float64 host oracle's action sequence."""
    from ddls_tpu.scenarios.conformance import (decision_events,
                                                jitted_decision_events,
                                                run_recorded_episode)

    env = _host_env(env_config, native=False)
    host_events, actions = run_recorded_episode(
        env, seed, max_decisions=decisions)
    a = decision_events(host_events)
    b = jitted_decision_events(env, host_events, actions)
    mismatch = first_mismatch(a, b, rtol)
    return {"ok": mismatch is None, "kind": "jitted_episode",
            "decisions": len(actions), "rtol": rtol,
            "accepted": sum(bool(e["accepted"]) for e in a),
            "mismatch": mismatch}


def train_fidelity(env_config: dict, spec: dict, seed: int,
                   cell_name: str) -> Dict[str, Any]:
    """Run the cell's fidelity replay. The jitted episode is a program
    of its own (tens of seconds to compile and run), so it runs on the
    first run of a cell in a checkout only and leaves its verdict in a
    marker file under ``benchmarks/out/``; later runs there read it.
    The marker is named by the cell AND the device kind: a verdict a
    CPU rehearsal left behind is not the chip's."""
    if spec["kind"] == "native_engine":
        return native_engine_fidelity(env_config, spec["decisions"], seed,
                                      spec["rtol"])
    if spec["kind"] != "jitted_episode":
        raise ValueError(f"unknown fidelity kind {spec['kind']!r}")
    import jax

    kind = jax.devices()[0].device_kind.replace(" ", "_")
    marker = os.path.join(harness.OUT_DIR,
                          f"fidelity_{cell_name}_{kind}.json")
    if os.path.exists(marker):
        return dict(harness.read_json(marker), from_marker=True)
    verdict = jitted_episode_fidelity(env_config, spec["decisions"], seed,
                                      spec["rtol"])
    verdict["seed"] = seed
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(marker, "w") as fh:
        json.dump(verdict, fh, default=str)
    return verdict


# --------------------------------------------------------------- serving
def plain_forward(model, params, obs: dict):
    """One unbatched, unbucketed forward at the highest matmul
    precision: (logits, value) as numpy."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        logits, value = model.apply(
            params, {k: np.asarray(v) for k, v in obs.items()})
    return np.asarray(logits), float(value)


def serve_reference_check(model, params, served: List[dict],
                          atol: float) -> Dict[str, Any]:
    """``served`` holds, per sampled observation, the obs, the action
    the server answered and the logits its bucket program produced.
    Logits must agree within ``atol`` on the valid actions, and the
    action must be the reference's argmax wherever the reference's own
    top-2 margin is wider than ``atol`` (inside it, rounding decides)."""
    import numpy as np

    worst = scale = 0.0
    wrong_action = close_calls = 0
    for item in served:
        ref_logits, _ = plain_forward(model, params, item["obs"])
        valid = np.asarray(item["obs"]["action_mask"]).astype(bool)
        scale = max(scale, float(np.abs(ref_logits[valid]).max()))
        worst = max(worst, float(np.abs(
            ref_logits[valid] - np.asarray(item["logits"])[valid]).max()))
        order = np.sort(ref_logits[valid])
        margin = order[-1] - order[-2] if len(order) > 1 else np.inf
        if margin <= atol:
            close_calls += 1
        elif int(np.argmax(ref_logits)) != int(item["action"]):
            wrong_action += 1
    return {"ok": worst <= atol and wrong_action == 0,
            "sample": len(served), "max_logit_diff": worst, "atol": atol,
            "max_abs_logit": scale,
            "wrong_action": wrong_action, "close_calls": close_calls}
