"""The memo's ONE-FUNCTION form, kept as the bitwise reference of
`ddls_tpu/sim/jax_memo.py:memo_probe` + `memo_commit` (test code only):
`memo_lookahead` as the package had it up to PR 48 (commit 8eca86b,
its body verbatim) — probe, compute, select and the five where-gated row writes
in one body that RETURNS THE TABLES, which is what made a decision's
``lax.cond`` select and copy them whole under ``vmap`` (PR 49). The
hash, the bit patterns and the scope name are the package's own
(imported, not copied), so the two differ only in where the write is
issued. tests/test_jax_memo.py holds probe-then-commit to it leaf for
leaf."""
from ddls_tpu.sim.jax_memo import _bits, _hash_weights
from ddls_tpu.telemetry import scopes


def memo_lookahead(memo, cfg, groups, times, compute, void=None):
    import jax
    import jax.numpy as jnp

    S, W = memo["key_cfg"].shape
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

    t_c, ok_c, *extra = compute(hit)

    with jax.named_scope(scopes.SIM_MEMO_PROBE):
        t = jnp.where(hit, memo["val_t"][set_idx, way_hit], t_c)
        ok = jnp.where(hit, memo["val_ok"][set_idx, way_hit], ok_c)

        way_ins = memo["rr"][set_idx] % jnp.int32(W)
        evict = miss & (memo["key_cfg"][set_idx, way_ins] >= 0)

        def upd(arr, val):
            old = arr[set_idx, way_ins]
            return arr.at[set_idx, way_ins].set(jnp.where(miss, val, old))

        memo = {
            "key_cfg": upd(memo["key_cfg"], cfg),
            "key_groups": upd(memo["key_groups"], groups),
            "key_times": upd(memo["key_times"], times),
            "val_t": upd(memo["val_t"], t),
            "val_ok": upd(memo["val_ok"], ok),
            "rr": memo["rr"].at[set_idx].add(miss.astype(jnp.int32)),
            "hits": memo["hits"] + hit.astype(jnp.int32),
            "misses": memo["misses"] + miss.astype(jnp.int32),
            "evicts": memo["evicts"] + evict.astype(jnp.int32),
        }
    return (t, ok, *extra), memo
