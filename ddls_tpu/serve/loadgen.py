"""Trace-driven open-loop load for the serving stack (ISSUE 8).

A closed Poisson process at a constant rate is the friendliest load a
server ever sees. Production traffic is not that: rates follow a diurnal
cycle, bursts arrive on top of it, job sizes are heavy-tailed, and
tenants are skewed. This module generates such a trace — **seeded and
fingerprinted**, so a result can name exactly the load it measured and
two rounds are comparable. How a driver must use it:

* **Open-loop**: request *i* is scheduled at ``arrival_s[i]``
  regardless of how the server is doing — arrivals never wait for
  responses (the closed-loop trap that hides overload).
* **Coordinated-omission-correct**: latency is measured against the
  SCHEDULED arrival timestamp, not the instant the driving loop got
  around to submitting (the server stack supports backdated ``now=`` at
  submit precisely for this). A stalled server therefore charges its
  stall to every request that arrived during it — p99/p999 stay honest
  exactly in overload, where the naive measurement is most wrong.

The arrival process is a non-homogeneous Poisson approximation
(interarrival ``Exp(1)/rate(t)`` at the current instant's rate) with
``rate(t) = base_rps * diurnal(t) * burst(t)``; sizes draw a Pareto tail
mapped into ``[0, 1)`` ranks (a driver maps ranks onto its obs pool
sorted by graph size); tenants draw from a 1/(k+1) zipf-ish weighting.
Everything is a pure function of the seed + knobs: same seed, same
fingerprint, bit-same trace.

``python -m ddls_tpu.serve.loadgen --selftest`` validates the schema
machinery itself (tier-1, numpy-only — no jax import).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
from typing import Any, Dict, List, Optional

import numpy as np

TRACE_SCHEMA = "ddls_tpu.serve.trace/v1"

# knobs recorded in trace["meta"] and folded into the fingerprint; a new
# generator knob MUST be added here or two differently-shaped traces
# could fingerprint identically
_META_KEYS = ("seed", "n_requests", "base_rps", "diurnal_period_s",
              "diurnal_amplitude", "burst_factor", "burst_period_s",
              "burst_duty", "size_tail_alpha", "n_tenants")


def rate_at(t: float, base_rps: float, diurnal_period_s: float,
            diurnal_amplitude: float, burst_factor: float,
            burst_period_s: float, burst_duty: float) -> float:
    """Instantaneous offered rate: diurnal sinusoid times a periodic
    burst window (the first ``burst_duty`` fraction of every
    ``burst_period_s`` runs at ``burst_factor`` x)."""
    rate = base_rps
    if diurnal_amplitude and diurnal_period_s > 0:
        rate *= 1.0 + diurnal_amplitude * math.sin(
            2.0 * math.pi * t / diurnal_period_s)
    if burst_factor != 1.0 and burst_period_s > 0 and burst_duty > 0:
        if (t % burst_period_s) < burst_duty * burst_period_s:
            rate *= burst_factor
    return max(rate, 1e-9)


def generate_trace(n_requests: int, base_rps: float, seed: int = 0,
                   diurnal_period_s: float = 30.0,
                   diurnal_amplitude: float = 0.5,
                   burst_factor: float = 3.0,
                   burst_period_s: float = 10.0,
                   burst_duty: float = 0.2,
                   size_tail_alpha: float = 1.5,
                   n_tenants: int = 4) -> Dict[str, Any]:
    """One seeded open-loop trace. ``diurnal_amplitude=0`` and
    ``burst_factor=1`` degrade to a plain Poisson process at
    ``base_rps``, fingerprinted through the same machinery."""
    if n_requests < 1:
        raise ValueError(f"n_requests must be >= 1, got {n_requests}")
    if base_rps <= 0:
        raise ValueError(f"base_rps must be > 0, got {base_rps}")
    if not 0.0 <= diurnal_amplitude < 1.0:
        raise ValueError("diurnal_amplitude must be in [0, 1) (a full "
                         "amplitude would zero the rate)")
    rng = np.random.RandomState(int(seed))
    arrivals = np.empty(n_requests, dtype=np.float64)
    t = 0.0
    for i in range(n_requests):
        lam = rate_at(t, base_rps, diurnal_period_s, diurnal_amplitude,
                      burst_factor, burst_period_s, burst_duty)
        t += rng.exponential(1.0 / lam)
        arrivals[i] = t
    # heavy-tailed size rank in [0, 1): Pareto(alpha) mapped through
    # 1 - 1/x — most requests small, a fat tail of near-max graphs
    u = rng.uniform(0.0, 1.0, size=n_requests)
    x = np.power(1.0 - u, -1.0 / float(size_tail_alpha))
    size_frac = 1.0 - 1.0 / x
    # zipf-ish tenant skew: w_k ∝ 1/(k+1)
    weights = 1.0 / (np.arange(int(n_tenants)) + 1.0)
    weights /= weights.sum()
    tenant_idx = rng.choice(int(n_tenants), size=n_requests, p=weights)
    meta = {"seed": int(seed), "n_requests": int(n_requests),
            "base_rps": float(base_rps),
            "diurnal_period_s": float(diurnal_period_s),
            "diurnal_amplitude": float(diurnal_amplitude),
            "burst_factor": float(burst_factor),
            "burst_period_s": float(burst_period_s),
            "burst_duty": float(burst_duty),
            "size_tail_alpha": float(size_tail_alpha),
            "n_tenants": int(n_tenants)}
    return {
        "schema": TRACE_SCHEMA,
        "meta": meta,
        "arrival_s": arrivals,
        "size_frac": size_frac,
        "tenant": [f"tenant-{int(k)}" for k in tenant_idx],
    }


def trace_fingerprint(trace: Dict[str, Any]) -> str:
    """Stable 16-hex-digit content fingerprint: meta knobs + the arrival
    / size arrays (rounded to ns / 1e-12 so the fingerprint survives
    JSON round-trips) + tenants. Two result lines with equal fingerprints
    measured the identical offered load."""
    h = hashlib.sha256()
    meta = trace.get("meta") or {}
    h.update(json.dumps({k: meta.get(k) for k in _META_KEYS},
                        sort_keys=True).encode())
    h.update(np.round(np.asarray(trace["arrival_s"], dtype=np.float64),
                      9).tobytes())
    h.update(np.round(np.asarray(trace["size_frac"], dtype=np.float64),
                      12).tobytes())
    h.update("\x00".join(trace["tenant"]).encode())
    return h.hexdigest()[:16]


def validate_trace(trace: Dict[str, Any]) -> None:
    """Schema validator (the ``--selftest`` surface; run it before
    driving a trace): raises ``ValueError`` naming the
    first violated invariant."""
    if not isinstance(trace, dict):
        raise ValueError(f"trace must be a dict, got {type(trace)}")
    if trace.get("schema") != TRACE_SCHEMA:
        raise ValueError(f"unknown trace schema {trace.get('schema')!r} "
                         f"(expected {TRACE_SCHEMA!r})")
    meta = trace.get("meta")
    if not isinstance(meta, dict):
        raise ValueError("trace missing 'meta' dict")
    missing = [k for k in _META_KEYS if k not in meta]
    if missing:
        raise ValueError(f"trace meta missing keys {missing}")
    for key in ("arrival_s", "size_frac", "tenant"):
        if key not in trace:
            raise ValueError(f"trace missing {key!r}")
    arr = np.asarray(trace["arrival_s"], dtype=np.float64)
    size = np.asarray(trace["size_frac"], dtype=np.float64)
    tenants = trace["tenant"]
    n = int(meta["n_requests"])
    if not (arr.shape == size.shape == (n,)) or len(tenants) != n:
        raise ValueError(
            f"trace length mismatch: meta says {n}, arrays are "
            f"{arr.shape}/{size.shape}/{len(tenants)}")
    if not np.all(np.isfinite(arr)) or (n and arr[0] < 0):
        raise ValueError("arrival_s must be finite and non-negative")
    if np.any(np.diff(arr) < 0):
        raise ValueError("arrival_s must be non-decreasing (open-loop "
                         "schedule)")
    if not np.all(np.isfinite(size)) or np.any((size < 0) | (size >= 1)):
        raise ValueError("size_frac must lie in [0, 1)")
    if not all(isinstance(t, str) and t for t in tenants):
        raise ValueError("tenant entries must be non-empty strings")


def trace_to_jsonable(trace: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "schema": trace["schema"],
        "meta": trace["meta"],
        "arrival_s": [round(float(x), 9) for x in trace["arrival_s"]],
        "size_frac": [round(float(x), 12) for x in trace["size_frac"]],
        "tenant": list(trace["tenant"]),
    }


def trace_from_jsonable(obj: Dict[str, Any]) -> Dict[str, Any]:
    trace = {
        "schema": obj.get("schema"),
        "meta": obj.get("meta"),
        "arrival_s": np.asarray(obj.get("arrival_s", []), np.float64),
        "size_frac": np.asarray(obj.get("size_frac", []), np.float64),
        "tenant": list(obj.get("tenant", [])),
    }
    validate_trace(trace)
    return trace


# ------------------------------------------------------------------ selftest
def run_selftest() -> Dict[str, Any]:
    """Exercise the generator + validator + fingerprint invariants
    without touching jax (tier-1): determinism, seed sensitivity,
    modulation sanity, and that the validator actually rejects each
    class of malformed trace."""
    # periods scaled well inside the ~2.5 s the trace spans, so the
    # burst-share check below sees several full cycles (with the
    # defaults' 10 s burst period the whole trace would sit inside one
    # burst window and the check would pass vacuously)
    kwargs = dict(n_requests=512, base_rps=200.0, seed=7,
                  diurnal_period_s=1.6, burst_period_s=0.8)
    a = generate_trace(**kwargs)
    b = generate_trace(**kwargs)
    validate_trace(a)
    validate_trace(b)
    ok = trace_fingerprint(a) == trace_fingerprint(b)
    ok &= (trace_fingerprint(generate_trace(n_requests=512,
                                            base_rps=200.0, seed=8))
           != trace_fingerprint(a))
    # knob changes must change the fingerprint even when arrivals would
    # collide by luck (meta is folded in)
    ok &= (trace_fingerprint({**a, "meta": {**a["meta"],
                                            "size_tail_alpha": 9.9}})
           != trace_fingerprint(a))
    # JSON round trip preserves schema + fingerprint
    rt = trace_from_jsonable(json.loads(json.dumps(trace_to_jsonable(a))))
    ok &= trace_fingerprint(rt) == trace_fingerprint(a)
    # burst sanity: the burst windows hold a super-proportional share of
    # arrivals (rate modulation is real, not cosmetic)
    m = a["meta"]
    arr = np.asarray(a["arrival_s"])
    in_burst = (arr % m["burst_period_s"]) < (m["burst_duty"]
                                              * m["burst_period_s"])
    burst_share = float(np.mean(in_burst))
    # super-proportional but not degenerate: a share of ~1.0 would mean
    # the whole trace sat inside one burst window (periods mis-scaled)
    ok &= m["burst_duty"] * 1.5 < burst_share < 0.9
    # heavy tail sanity: the size distribution is skewed small with a
    # real tail
    size = np.asarray(a["size_frac"])
    ok &= float(np.median(size)) < 0.5 and float(size.max()) > 0.8
    # the validator rejects each malformation class
    rejected = 0
    bad_arr = dict(a, arrival_s=np.asarray(a["arrival_s"])[::-1].copy())
    bad_size = dict(a, size_frac=np.asarray(a["size_frac"]) + 1.5)
    bad_schema = dict(a, schema="bogus/v0")
    bad_meta = dict(a, meta={k: v for k, v in a["meta"].items()
                             if k != "seed"})
    for bad in (bad_arr, bad_size, bad_schema, bad_meta):
        try:
            validate_trace(bad)
        except ValueError:
            rejected += 1
    ok &= rejected == 4
    return {"selftest": "ok" if ok else "FAILED",
            "n_requests": int(m["n_requests"]),
            "fingerprint": trace_fingerprint(a),
            "burst_share": round(burst_share, 4),
            "rejected_malformed": rejected}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="Seeded, fingerprinted open-loop serving traces")
    parser.add_argument("--selftest", action="store_true",
                        help="validate the trace schema machinery "
                             "(numpy-only, tier-1)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--requests", type=int, default=256)
    parser.add_argument("--base-rps", type=float, default=200.0)
    parser.add_argument("--out", default=None,
                        help="write the generated trace as JSON here "
                             "(default: print meta + fingerprint only)")
    args = parser.parse_args(argv)
    if args.selftest:
        result = run_selftest()
        print(json.dumps(result), flush=True)
        return 0 if result["selftest"] == "ok" else 1
    trace = generate_trace(n_requests=args.requests,
                           base_rps=args.base_rps, seed=args.seed)
    validate_trace(trace)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(trace_to_jsonable(trace), f)
    print(json.dumps({"schema": trace["schema"], "meta": trace["meta"],
                      "fingerprint": trace_fingerprint(trace),
                      "out": args.out}), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
