"""Device-resident lookahead memo for the jitted environment (ISSUE 13).

The host simulator memoises the SRPT lookahead under an exact signature
(`cluster.py:452-520` ``_lookahead_cache_key``: the split/degree map, the
canonical first-appearance worker grouping, and the placed per-dep times)
and hits >80% past the ~300-step transient — the single biggest reason
the warmed host sim out-steps the in-kernel env at the canonical
degree-16 pads (docs/perf_round8.md). This module mirrors that memo into
a fixed-capacity, set-associative table of jax arrays carried through
the episode/segment scan, so the in-kernel env stops recomputing the
lookahead from scratch on every decision.

Key contract (the host signature, in-kernel form):

* ``cfg`` — the (model type, partition degree) config-row index. The
  split map is a pure function of (model, degree) (`config_tables_for`
  builds one table row per pair), so this one i32 subsumes the host
  key's ``(model, split)`` components.
* ``groups`` — the canonical first-appearance renumbering of the per-op
  server codes (:func:`canonical_groups`), the traced mirror of the
  host's vectorised ``np.unique``/argsort canonicalisation
  (cluster.py:468-476). Collapses physical server identity exactly like
  the host: all workers are identical and servers symmetric.
* ``times`` — the MOUNTED per-dep times (non-flow deps zeroed), byte-for
  -byte what the host keys on: ``_assemble_lookahead_key`` reads
  ``dep_init_run_time_arr`` AFTER ``_register_running_job`` (and
  candidate pricing after its own ``set_dep_init_run_times_bulk``)
  zeroed the non-flows.

Exactness: the jitted lookahead consumes, beyond cfg-static tables,
(op_worker, op_score, dep_remaining, is_flow, dep_score, dep_channel).
Given the key triple these are determined up to relabelings the engine
is invariant under: worker/channel ids enter only as occupancy indices
(one-hot rows / scatter-max buckets — permutation invariant), op scores
are a pure function of (cfg, grouping), and dep scores are compared only
BETWEEN flow deps, whose relative SRPT order is the descending order of
their own (mounted == raw) times — non-flow raw times shift all flow
ranks monotonically and cancel in every comparison the engine makes.
Hash collisions cannot break any of this: the probe compares the FULL
key residual bitwise (u32 bit patterns, so ``-0.0``/NaN can only miss,
never alias), so a collision is a miss, never a wrong entry.
All of it holds for a COMPLETE placement only: the key is made of the
ops that placed, so a placement that stopped two ops short has the key
of the complete one that puts those two on a server of their own, and
a lookahead of it ends stuck. The host never looks ahead at a job it
could not place; here such a probe is ``void`` (:func:`memo_lookahead`).

Bitwise-hit guarantee: a hit serves a value previously computed by the
SAME compiled ``jax_lookahead`` on bit-identical inputs, so memo-on and
memo-off episodes are indistinguishable in any precision mode — the x64
full-episode parity suites run with the memo enabled unchanged.

Wide-vmap probe (ISSUE 17): the probe is BATCHED, not branched. Each
lane gathers its hit value from its own table, then the lookahead runs
with the hit flag masked into its ``while_loop`` cond
(``jax_lookahead(..., skip=hit)``) and the result is where-selected
against the stored value. jax batches ``lax.while_loop`` to run while
ANY lane's cond holds (select-freezing finished lanes), so under a
multi-lane ``vmap`` the loop trips exactly to the max count over MISS
lanes — zero when every lane hits — and the per-lane ``.at[].set``
insertions scatter back through vmap's batching rule. The lanes=1
canonical 13x therefore generalises to every width, and
``resolve_memo_cfg``'s ``"auto"`` enables the memo at ALL widths
(es_device, multi-lane fused/collector lanes). Miss lanes
iterate under their own cond regardless of neighbours, so memo-on and
memo-off stay bit-identical at every width.

Persistence: the table rides the scan carry OUTSIDE the in-kernel
episode reset (`make_segment_fn` resets the env state to ``fresh`` but
never the memo), mirroring the host contract that
``cluster.lookahead_cache`` persists across ``reset()`` while the
workload signature is unchanged — the jitted env replays one fixed bank
per lane, so its workload signature never changes between resets.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import numpy as np

from ddls_tpu.telemetry import scopes

#: host key builders this module mirrors — the lint engine's
#: backend-surface-parity rule checks each still exists in
#: ``sim/cluster.py``, so a host key-builder rename fails at lint time
#: instead of silently diverging the in-kernel key contract.
HOST_KEY_SURFACE = ("lookahead_key_for", "_assemble_lookahead_key")

#: cumulative counter keys the memo-enabled segment kernel traces per
#: step alongside the ``ep_*`` episode counters (drained with them at
#: sync boundaries, never fetched per step).
MEMO_TRACE_KEYS = ("memo_hits", "memo_misses", "memo_evicts")

#: the wide-probe surface: the batched probe is only effective under
#: vmap while the hit flag keeps reaching the lookahead while_loop's
#: cond — ``memo_lookahead`` hands ``hit`` to ``compute(hit)`` and the
#: env's ``run_lookahead`` forwards it as the named keyword of the
#: named ``sim/jax_lookahead.py`` function. The lint engine's
#: backend-surface-parity rule pins both ends (a rename or a dropped
#: mask fails at lint time instead of silently reverting every
#: multi-lane caller to inert-memo behaviour).
WIDE_PROBE_SURFACE = ("jax_lookahead", "skip")


@dataclasses.dataclass(frozen=True)
class MemoConfig:
    """Table geometry: ``n_sets`` x ``n_ways`` entries, round-robin way
    eviction per set. The default 64x2 holds 128 keys — comfortably
    above the distinct (model, degree, grouping, times) population of a
    steady-state canonical episode, at ~14 MB of key residuals for the
    degree-16 pads in f64 (N=480 groups + M=13312 times per entry)."""
    n_sets: int = 64
    n_ways: int = 2


def resolve_memo_cfg(memo_cfg: Union[str, MemoConfig, None],
                     n_lanes: int) -> Optional[MemoConfig]:
    """The ONE resolution home for the ``use_jax_lookahead_memo`` knob:
    ``"auto"`` enables the memo at EVERY lane count — the batched probe
    masks hit lanes out of the lookahead while_loop, so wide-vmap lanes
    hit the cache too (ISSUE 17; the historical lanes=1-only auto
    predates the mask, when the cond probe was select-inert under
    vmap). An explicit MemoConfig/None still forces it on/off;
    ``n_lanes`` stays in the signature as the callers' resolution
    context (geometry may key on it later)."""
    if memo_cfg == "auto":
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        return MemoConfig()
    if memo_cfg is None or isinstance(memo_cfg, MemoConfig):
        return memo_cfg
    raise ValueError(f"memo_cfg must be 'auto', None or a MemoConfig, "
                     f"got {memo_cfg!r}")


def _hash_weights(n_words: int) -> np.ndarray:
    """Deterministic odd u32 multipliers for the key hash (embedded as
    program constants). The hash only picks the set — the
    bitwise residual compare makes its quality a perf knob, not a
    correctness one."""
    r = np.random.RandomState(0x5EED)
    w = r.randint(0, 1 << 31, size=n_words, dtype=np.int64).astype(
        np.uint32)
    return (w << np.uint32(1)) | np.uint32(1)


def memo_init(et, cfg: MemoConfig):
    """A fresh (empty) device-resident memo table sized to ``et``'s pads.

    Keys are stored as their raw components (cfg row, canonical groups,
    mounted times); values are exactly what the decision kernel consumes
    from ``jax_lookahead`` — the per-step time and the convergence flag.
    Counters are i32 scalars traced alongside the episode counters."""
    import jax.numpy as jnp

    N, M = et.pads.n_ops, et.pads.n_deps
    dt = et.tables["dep_size"].dtype
    S, W = cfg.n_sets, cfg.n_ways
    return {
        "key_cfg": jnp.full((S, W), -1, jnp.int32),
        "key_groups": jnp.zeros((S, W, N), jnp.int32),
        "key_times": jnp.zeros((S, W, M), dt),
        "val_t": jnp.zeros((S, W), dt),
        "val_ok": jnp.zeros((S, W), bool),
        "rr": jnp.zeros((S,), jnp.int32),
        "hits": jnp.zeros((), jnp.int32),
        "misses": jnp.zeros((), jnp.int32),
        "evicts": jnp.zeros((), jnp.int32),
    }


def canonical_groups(ots, valid):
    """First-appearance renumbering of the per-op server codes — the
    traced mirror of the host's canonicalisation (cluster.py:468-476:
    ``np.unique(return_index, return_inverse)`` + double argsort).
    ``ots`` [N] i32 server codes; ``valid`` [N] bool. Invalid slots map
    to -1 (their count and positions are cfg-static, so they can never
    distinguish two placements of the same cfg)."""
    import jax.numpy as jnp

    n = ots.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    same = ((ots[None, :] == ots[:, None])
            & valid[None, :] & valid[:, None])
    # first[i] = smallest j with the same server as op i (== i when op i
    # is its server's first appearance)
    first = jnp.min(jnp.where(same, idx[None, :], jnp.int32(n)), axis=1)
    is_first = valid & (first == idx)
    rank_at = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    return jnp.where(valid, rank_at[jnp.clip(first, 0, n - 1)],
                     jnp.int32(-1)).astype(jnp.int32)


def _bits(x):
    """Raw u32 bit pattern of a float array, flattened over the trailing
    word axis bitcast introduces for 64-bit dtypes — the ONLY equality
    the probe uses (bitwise: ``-0.0 != 0.0``, NaN never matches, exactly
    the host's ``arr.tobytes()`` key semantics)."""
    import jax
    import jax.numpy as jnp

    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return b.reshape(x.shape[:-1] + (-1,)) if b.ndim > x.ndim else b


#: the leaves of a probe's PENDING ENTRY (:func:`memo_probe`): the one
#: row a lane-step would insert and the flags that gate it — what comes
#: out of a decision's ``lax.cond`` in the table's place
PENDING_KEYS = ("set_idx", "cfg", "groups", "times", "t", "ok", "hit",
                "miss")


def memo_pending_none(memo: Optional[dict]) -> Optional[dict]:
    """The pending entry of a lane-step that probed nothing (the zero
    path, ``has_job`` false): zeros with ``hit`` and ``miss`` false, so
    :func:`memo_commit` rewrites set 0's round-robin way with what it
    holds and adds 0 to every counter — every leaf stays bit-equal.
    None where the memo is off (``memo`` None)."""
    import jax.numpy as jnp

    if memo is None:
        return None
    i32 = jnp.zeros((), jnp.int32)
    no = jnp.zeros((), bool)
    return {"set_idx": i32, "cfg": i32,
            "groups": jnp.zeros(memo["key_groups"].shape[-1:], jnp.int32),
            "times": jnp.zeros(memo["key_times"].shape[-1:],
                               memo["key_times"].dtype),
            "t": jnp.zeros((), memo["val_t"].dtype), "ok": no,
            "hit": no, "miss": no}


def memo_probe(memo: dict, cfg, groups, times,
               compute: Callable[..., Tuple], void=None):
    """The memo's READING half: probe-or-compute one lookahead under the
    key (cfg, groups, times); returns ``((t, ok, *extra), pending)`` and
    writes nothing. ``pending`` (:data:`PENDING_KEYS`) is the one entry
    :func:`memo_commit` would insert, row-sized, so a caller under a
    ``lax.cond`` hands IT out of the branch and never the tables (under
    ``vmap`` a ``cond`` is both branches and a select over every
    output: a table among them is selected, and copied, whole). The
    contract is :func:`memo_lookahead`'s."""
    import jax
    import jax.numpy as jnp

    S = memo["key_cfg"].shape[0]
    n_groups = memo["key_groups"].shape[-1]

    with jax.named_scope(scopes.SIM_MEMO_PROBE):
        cfg = jnp.asarray(cfg, jnp.int32)
        tbits = _bits(times).reshape(-1)
        payload = jnp.concatenate([
            cfg.astype(jnp.uint32).reshape(1),
            groups.astype(jnp.uint32),
            tbits,
        ])
        weights = jnp.asarray(_hash_weights(1 + n_groups + tbits.shape[0]))
        h = jnp.sum(payload * weights, dtype=jnp.uint32)
        set_idx = (h % jnp.uint32(S)).astype(jnp.int32)

        way_cfg = memo["key_cfg"][set_idx]          # [W]
        way_groups = memo["key_groups"][set_idx]    # [W, N]
        way_times = memo["key_times"][set_idx]      # [W, M]
        eq = ((way_cfg == cfg)
              & jnp.all(way_groups == groups[None], axis=-1)
              & jnp.all(_bits(way_times) == _bits(times)[None],
                        axis=tuple(range(1, _bits(way_times).ndim))))
        hit = eq.any()
        miss = ~hit
        if void is not None:
            hit, miss = hit & ~void, miss & ~void
        way_hit = jnp.argmax(eq).astype(jnp.int32)

    # batched gather/mask/select: serve the hit value from the table,
    # run the (skip-masked) lookahead for the miss case, keep whichever
    # the hit flag says. Bitwise-hit guarantee is preserved at every
    # width — hits serve previously computed bits verbatim, misses run
    # the loop under their own cond exactly as unbatched. The lookahead
    # keeps its own scope: the probe's scope is opened around it twice.
    t_c, ok_c, *extra = compute(hit)

    with jax.named_scope(scopes.SIM_MEMO_PROBE):
        t = jnp.where(hit, memo["val_t"][set_idx, way_hit], t_c)
        ok = jnp.where(hit, memo["val_ok"][set_idx, way_hit], ok_c)
    pending = {"set_idx": set_idx, "cfg": cfg,
               "groups": groups.astype(jnp.int32), "times": times,
               "t": t, "ok": ok, "hit": hit, "miss": miss}
    return (t, ok, *extra), pending


def memo_commit(memo: Optional[dict],
                pending: Optional[dict]) -> Optional[dict]:
    """The memo's WRITING half: insert a probe's ``pending`` entry where
    it missed, at its set's round-robin way (deterministic eviction —
    same decision stream, same table, every run; per-lane ``.at[].set``
    writes scatter back through vmap batching), and count the probe.
    The write is a handful of where-gated row updates, cheap either way
    (on a hit, a void probe and :func:`memo_pending_none` it rewrites
    identical state). Called OUTSIDE every ``lax.cond``, on the carried
    table itself, so the scatter lands in place. None where the memo
    is off (``memo`` None)."""
    import jax
    import jax.numpy as jnp

    if memo is None:
        return None
    W = memo["key_cfg"].shape[1]
    set_idx, hit, miss = pending["set_idx"], pending["hit"], pending["miss"]

    with jax.named_scope(scopes.SIM_MEMO_PROBE):
        way_ins = memo["rr"][set_idx] % jnp.int32(W)
        evict = miss & (memo["key_cfg"][set_idx, way_ins] >= 0)

        def upd(arr, val):
            old = arr[set_idx, way_ins]
            return arr.at[set_idx, way_ins].set(jnp.where(miss, val, old))

        return {
            "key_cfg": upd(memo["key_cfg"], pending["cfg"]),
            "key_groups": upd(memo["key_groups"], pending["groups"]),
            "key_times": upd(memo["key_times"], pending["times"]),
            "val_t": upd(memo["val_t"], pending["t"]),
            "val_ok": upd(memo["val_ok"], pending["ok"]),
            "rr": memo["rr"].at[set_idx].add(miss.astype(jnp.int32)),
            "hits": memo["hits"] + hit.astype(jnp.int32),
            "misses": memo["misses"] + miss.astype(jnp.int32),
            "evicts": memo["evicts"] + evict.astype(jnp.int32),
        }


def memo_lookahead(memo: dict, cfg, groups, times,
                   compute: Callable[..., Tuple], void=None):
    """Probe-or-compute one lookahead under the memo key (cfg, groups,
    times); returns ``((t, ok, *extra), memo')`` — whatever ``compute``
    returns beyond ``(t, ok)`` (the loop's trip count, 0 on a masked
    hit lane) passes through untouched. ``void`` (bool, optional) marks
    a probe whose key does not determine the engine's inputs (a job
    that did not place: the key holds the placed ops alone) or whose
    result nobody reads: it counts as neither hit nor miss and inserts
    nothing; what it returns is the caller's to throw away.

    :func:`memo_probe` then :func:`memo_commit`, for a caller that
    stands under no ``lax.cond`` (one that does commits after it: NO
    ``cond`` returns a memo).

    Probe (batched — the wide-vmap form, ISSUE 17): hash the key onto a
    set, compare the FULL residual bitwise against every way, gather the
    matching way's stored value, then call ``compute(hit)`` — the
    caller must thread the flag into the lookahead while_loop's cond
    (``jax_lookahead(..., skip=hit)``; :data:`WIDE_PROBE_SURFACE`) so a
    hit lane exits before its first iteration — and where-select the
    stored value over the (garbage) masked-out result. At lanes=1 a hit
    costs one cond evaluation; under a multi-lane vmap the loop trips
    to the max count over MISS lanes only. Miss: the computed (key,
    value) is inserted at the set's round-robin way (deterministic
    eviction — same decision stream, same table, every run; per-lane
    ``.at[].set`` writes scatter back through vmap batching)."""
    result, pending = memo_probe(memo, cfg, groups, times, compute, void)
    return result, memo_commit(memo, pending)


#: start-up gauge (`table_bytes`): what the lanes' memo tables hold on
#: the device. The epoch program's scratch over it reads under a half
#: (the program's other temporaries) while the tables are updated in
#: place; near 1, a whole copy of them is back
#: (a ``cond`` or a select that returns a memo: `memo_probe`)
TABLE_GAUGE = "sim.memo.table_bytes"


def table_bytes(memo: Optional[dict]) -> int:
    """Bytes of a carried (possibly lane-stacked) memo state, 0 with the
    memo off: its leaves' shapes, no trace and no fetch."""
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(memo))


def memo_trace_counters(memo: dict) -> dict:
    """The per-step cumulative counter snapshot the segment/episode
    kernels trace under :data:`MEMO_TRACE_KEYS` order."""
    return {"memo_hits": memo["hits"], "memo_misses": memo["misses"],
            "memo_evicts": memo["evicts"]}


#: the memo state's cumulative counters (``[lanes]`` i32 each, or
#: scalars for one lane)
COUNTER_KEYS = ("hits", "misses", "evicts")


def counter_arrays(memo: dict) -> dict:
    """The carried memo state's counter arrays, still on the device: no
    fetch. A drain boundary that already fetches something batches
    them in (train/loops.py)."""
    return {k: memo[k] for k in COUNTER_KEYS}


def summarize_fetched(vals: dict) -> dict:
    """{hits, misses, evicts, hit_rate} from FETCHED counter arrays,
    summed over lanes: host arithmetic alone."""
    out = {k: int(np.sum(vals[k])) for k in COUNTER_KEYS}
    total = out["hits"] + out["misses"]
    out["hit_rate"] = out["hits"] / total if total else 0.0
    return out


def summarize_counters(memo: dict) -> dict:
    """{hits, misses, evicts, hit_rate} from a carried (possibly
    lane-stacked) memo state. One explicit device fetch of three small
    arrays; call at drain/reporting boundaries only (result lines,
    logging), never on a per-collect/per-epoch hot path."""
    import jax

    return summarize_fetched(jax.device_get(counter_arrays(memo)))


class MemoCounters:
    """The memo-counter readbacks of a driver that carries ``(sim state,
    memo state)`` as ``self._state`` and its ``self.memo_cfg`` — the ONE
    home shared by `DevicePPOCollector`, `FusedEpochDriver` and
    `SebulbaCollector`."""

    def memo_counters(self) -> Optional[dict]:
        """Cumulative in-kernel memo counters {hits, misses, evicts,
        hit_rate} summed over lanes (`summarize_counters`: one fetch,
        drain/reporting boundaries only); None when the memo is off."""
        if self.memo_cfg is None:
            return None
        return summarize_counters(self._state[1])

    def memo_counter_arrays(self) -> Optional[dict]:
        """The same counters as device arrays, unfetched, for a drain
        boundary to batch into the fetch it already makes
        (train/loops.py); None when the memo is off."""
        if self.memo_cfg is None:
            return None
        return counter_arrays(self._state[1])
