"""Sharded semantic-cache lookup: shard_map over the mesh 'data' axis.

The cache's (C, D) embedding bank is row-sharded across data devices (the
TPU-native replacement for Milvus's IVF partitions — see DESIGN.md §3).
Each device scans its local shard with the cosine-top-k kernel, then the
tiny (B, k) per-shard winners are all-gathered and merged to a global
top-k.  Communication: B * k * 8 bytes per shard — microscopic next to the
HBM-bound local scan, so the lookup scales linearly in device count.

Insertion routes an entry to shard ``slot // local_capacity`` (globally
rotating pointer), keeping shards balanced.

The clustered (IVF) index composes with this (DESIGN.md §7): centroids
are replicated, and the member table is row-sharded WITH the bank — each
shard keeps its own (nclusters, bucket) table whose entries are LOCAL
slot ids, so the probe gathers never cross shards.  The table array is
(n_shards * nclusters, bucket) with shard s owning row block s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.cosine_topk.ops import cosine_topk, cosine_topk_gather
from . import cache as cache_lib
from . import index as index_lib
from . import router as router_lib


def shard_cache_state(state, mesh: Mesh, axis: str = "data"):
    """Places cache buffers row-sharded over ``axis`` (others replicated).

    IVF states must go through :func:`shard_ivf_cache_state` instead —
    the member table needs a layout conversion, not just placement.
    """
    row_sharded = {"emb", "q_tokens", "q_mask", "r_tokens", "r_mask", "valid",
                   "last_used", "hits", "ivf_assign", "ivf_pos"}
    out = {}
    for k, v in state.items():
        spec = P(axis) if k in row_sharded else P()
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def shard_ivf_cache_state(state, mesh: Mesh, cfg: cache_lib.CacheConfig,  # hostsync: ok host-side regroup after init/rebuild, not the hot loop
                          axis: str = "data"):
    """Converts a local-layout IVF cache state to the sharded layout.

    Host-side regroup (called after init/build_index, not in the hot
    loop): each shard's member rows are rebuilt from ``(valid, assign)``
    restricted to its bank rows, with entries rewritten to LOCAL slot
    ids.  Centroids and the pending/overflow scalars replicate; the
    (n_shards * nclusters, bucket) table and the assign/pos back-pointers
    shard over ``axis`` alongside the bank.
    """
    n_shards = mesh.shape[axis]
    assert cfg.capacity % n_shards == 0, (cfg.capacity, n_shards)
    local_c = cfg.capacity // n_shards
    p = index_lib.resolve(cfg)
    # an overflowed table can carry MORE than `bucket` valid rows per
    # cluster (the overflow overwrite leaves duplicates in `assign`);
    # regrouping such a state would have to drop rows and silently break
    # the flat-scan equivalence — demand a rebuild instead
    if bool(state["ivf_overflow"]):
        raise ValueError("IVF member table overflowed; run "
                         "index.build_index(state, cfg) before sharding")
    valid = np.asarray(state["valid"])
    assign = np.asarray(state["ivf_assign"])
    members = np.full((n_shards * p.nclusters, p.bucket), -1, np.int32)
    count = np.zeros((n_shards * p.nclusters,), np.int32)
    pos = np.full((cfg.capacity,), -1, np.int32)
    for r in np.nonzero(valid & (assign >= 0))[0]:
        row = (r // local_c) * p.nclusters + assign[r]
        assert count[row] < p.bucket, \
            (row, "per-shard member row overflow despite table slack")
        members[row, count[row]] = r % local_c
        pos[r] = count[row]
        count[row] += 1
    out = dict(state)
    out["ivf_pos"] = jnp.asarray(pos)
    # drop the stale local-layout table before placement (no point
    # replicating arrays that are replaced right after)
    del out["ivf_members"], out["ivf_count"]
    out = shard_cache_state(out, mesh, axis)
    sh = NamedSharding(mesh, P(axis))
    out["ivf_members"] = jax.device_put(jnp.asarray(members), sh)
    out["ivf_count"] = jax.device_put(jnp.asarray(count), sh)
    return out


def _merge_shard_topk(s, gi, axis: str, n_shards: int, k: int):
    """All-gather the (B, k) per-shard winners and merge to a global top-k."""
    all_s = jax.lax.all_gather(s, axis)                # (n_shards, B, k)
    all_i = jax.lax.all_gather(gi, axis)
    b = s.shape[0]
    flat_s = jnp.moveaxis(all_s, 0, 1).reshape(b, n_shards * k)
    flat_i = jnp.moveaxis(all_i, 0, 1).reshape(b, n_shards * k)
    top_s, sel = jax.lax.top_k(flat_s, k)
    top_i = jnp.take_along_axis(flat_i, sel, axis=1)
    return top_s, top_i


def _flat_shard_lookup(mesh: Mesh, cfg: cache_lib.CacheConfig, axis: str):
    """shard_map'd flat per-shard scan + merge: ``(emb, valid, q) -> (s, i)``."""
    n_shards = mesh.shape[axis]
    assert cfg.capacity % n_shards == 0, (cfg.capacity, n_shards)
    local_c = cfg.capacity // n_shards
    k = cfg.topk

    def local_lookup(emb, valid, q):
        # emb: (local_c, D); q: (B, D) replicated
        s, i = cosine_topk(q, emb, valid, k=k, impl=cfg.lookup_impl,
                           block_n=min(cfg.block_n, local_c))
        shard = jax.lax.axis_index(axis)
        gi = jnp.where(i >= 0, i + shard * local_c, -1)
        return _merge_shard_topk(s, gi, axis, n_shards, k)

    return jax.shard_map(
        local_lookup, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False)


def _ivf_shard_lookup(mesh: Mesh, cfg: cache_lib.CacheConfig, axis: str):
    """shard_map'd IVF probe + merge over the 7 IVF state arrays + queries."""
    n_shards = mesh.shape[axis]
    assert cfg.capacity % n_shards == 0, (cfg.capacity, n_shards)
    local_c = cfg.capacity // n_shards
    p = index_lib.resolve(cfg)
    k = min(cfg.topk, local_c)

    def local_lookup(emb, valid, members, count, assign, pos, centroids, q):
        # members (nclusters, bucket): this shard's table, LOCAL slot ids
        cand, live = index_lib.candidates(members, count, valid, assign,
                                          pos, centroids, q, p.nprobe)
        s, i = cosine_topk_gather(q, emb, cand, live, k=k,
                                  impl=cfg.lookup_impl,
                                  block_m=min(cfg.block_n, cand.shape[1]))
        shard = jax.lax.axis_index(axis)
        gi = jnp.where(i >= 0, i + shard * local_c, -1)
        top_s, top_i = _merge_shard_topk(s, gi, axis, n_shards, k)
        return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)

    return jax.shard_map(
        local_lookup, mesh=mesh,
        in_specs=(P(axis),) * 6 + (P(), P()),
        out_specs=(P(), P()),
        check_vma=False)


def _sharded_lookup_call(sm, state, q_embs, ivf: bool):
    """Applies a shard-mapped lookup to the state dict's arrays."""
    if ivf:
        return sm(state["emb"], state["valid"], state["ivf_members"],
                  state["ivf_count"], state["ivf_assign"], state["ivf_pos"],
                  state["ivf_centroids"], q_embs)
    return sm(state["emb"], state["valid"], q_embs)


def make_distributed_lookup(mesh: Mesh, cfg: cache_lib.CacheConfig,
                            axis: str = "data"):
    """Builds a jitted (state, q_embs) -> (scores, idx) sharded lookup."""
    sm = _flat_shard_lookup(mesh, cfg, axis)

    @jax.jit
    def lookup(state, q_embs):
        return _sharded_lookup_call(sm, state, q_embs, ivf=False)

    return lookup


def make_distributed_ivf_lookup(mesh: Mesh, cfg: cache_lib.CacheConfig,
                                axis: str = "data"):
    """Sharded two-stage IVF lookup (state from shard_ivf_cache_state).

    Every shard routes the (replicated) queries through the (replicated)
    centroids — same top-``nprobe`` everywhere — then probes its LOCAL
    member rows and scans only its own bank slots with the gather kernel.
    The (B, k) per-shard winners merge exactly like the flat sharded
    lookup; per-shard scan cost is ``nprobe * bucket`` rows instead of
    ``local_capacity``.
    """
    assert cfg.index == "ivf", "use make_distributed_lookup for flat caches"
    sm = _ivf_shard_lookup(mesh, cfg, axis)

    @jax.jit
    def lookup(state, q_embs):
        return _sharded_lookup_call(sm, state, q_embs, ivf=True)

    return lookup


def make_distributed_lookup_and_touch(mesh: Mesh, cfg: cache_lib.CacheConfig,
                                      router_cfg, axis: str = "data"):
    """Sharded analogue of :func:`repro.core.cache.lookup_route_touch`.

    One jitted device call per serve batch, exactly like the local fused
    path (DESIGN.md §5): the shard-mapped scan (flat or IVF per
    ``cfg.index``) merges per-shard winners to a replicated global top-k,
    and everything downstream — the calibrated cascade routing, the
    hit-accounting scatter, and the admission EMA — is the SAME
    ``cache.route_touch_core`` the local path runs, applied AFTER the
    all_gather merge on replicated (B, k) winners.  That ordering is what
    keeps sharded and local routing decision-identical: the cascade only
    ever sees the merged global shortlist, never per-shard partial top-k
    (stage 2 likewise runs post-merge, see ``SharedCacheBank``).  The
    touch scatters land on the row-sharded arrays with replicated indices
    — GSPMD routes each update to the owning shard — while the admission
    arrays replicate (identical update everywhere).  State is donated for
    in-place update.
    """
    ivf = cfg.index == "ivf"
    sm = (_ivf_shard_lookup if ivf else _flat_shard_lookup)(mesh, cfg, axis)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def lookup_touch(state, q_embs, cost):
        scores, idx = _sharded_lookup_call(sm, state, q_embs, ivf=ivf)
        new, decisions, tau, cluster, admit = cache_lib.route_touch_core(
            state, cfg, router_cfg, q_embs, scores, idx, cost)
        return new, scores, idx, decisions, tau, cluster, admit

    return lookup_touch


def make_distributed_insert(mesh: Mesh, cfg: cache_lib.CacheConfig,
                            axis: str = "data"):
    """Jitted ring-buffer insert against the sharded state (FIFO policy)."""
    # the single-entry path has no sharded IVF maintenance — refuse loudly
    # rather than silently filing nothing in the member table
    assert cfg.index != "ivf", \
        "use make_distributed_insert_batch for IVF caches"

    @jax.jit
    def insert(state, emb, q_tokens, q_mask, r_tokens, r_mask):
        return cache_lib.insert(state, cfg, emb, q_tokens, q_mask,
                                r_tokens, r_mask)

    return insert


def make_distributed_insert_batch(mesh: Mesh, cfg: cache_lib.CacheConfig,
                                  axis: str = "data"):
    """Batched sharded FIFO insert, shard-routed by global slot.

    The globally rotating ring pointer assigns entry i the slot
    ``(ptr + i) % capacity``; shard ``slot // local_capacity`` owns it —
    the same row partitioning the sharded lookup scans.  Each shard
    receives the (replicated, fixed-shape) entry batch, keeps only its own
    rows, and scatters them locally: no cross-shard traffic at all, and
    one dispatch for the whole batch.

    Returns a jitted ``(state, embs, qt, qm, rt, rm, count) ->
    (new_state, slots)`` with the same semantics as
    :func:`repro.core.cache.insert_batch` (padding rows >= count ignored,
    slots[i] = -1 for padding).
    """
    assert cfg.policy == "fifo", "sharded insert_batch is FIFO-only"
    n_shards = mesh.shape[axis]
    assert cfg.capacity % n_shards == 0, (cfg.capacity, n_shards)
    local_c = cfg.capacity // n_shards
    ivf = cfg.index == "ivf"

    def local_insert(emb_buf, qt_buf, qm_buf, rt_buf, rm_buf, valid,
                     last_used, hits, ptr, clock, size,
                     embs, qt, qm, rt, rm, count, *ivf_bufs):
        shard = jax.lax.axis_index(axis)
        row = jnp.arange(embs.shape[0], dtype=jnp.int32)
        gslot, keep, active = cache_lib._fifo_batch_plan(
            ptr, row, count, cfg.capacity)
        mine = keep & (gslot // local_c == shard)
        lslot = (gslot % local_c).astype(jnp.int32)
        w = jnp.where(mine, lslot, local_c)            # OOB -> dropped
        embs = cache_lib._normalize(embs)
        upd = lambda buf, val: buf.at[w].set(val.astype(buf.dtype),
                                             mode="drop")
        out = (upd(emb_buf, embs), upd(qt_buf, qt), upd(qm_buf, qm),
               upd(rt_buf, rt), upd(rm_buf, rm),
               valid.at[w].set(True, mode="drop"),
               last_used.at[w].set(clock + row, mode="drop"),
               hits.at[w].set(0, mode="drop"),
               ptr + count, clock + count,
               jnp.minimum(size + count, cfg.capacity),
               jnp.where(active, gslot, -1))
        if not ivf:
            return out
        # file this shard's rows in its LOCAL member table; only the
        # owning shard appends, so divergent fallback choices can't race
        state_ivf = dict(zip(index_lib.IVF_KEYS, ivf_bufs))
        pending_in = state_ivf["ivf_pending"]
        cn = index_lib.nearest_clusters(state_ivf["ivf_centroids"], embs)

        def step(carry, x):
            c_near, ls, on = x
            return index_lib.file_row(carry, c_near, ls, on), None

        state_ivf, _ = jax.lax.scan(step, state_ivf, (cn, lslot, mine))
        # pending/overflow are replicated scalars: count ALL kept rows
        # (identical everywhere) and pmax the local overflow flags
        state_ivf["ivf_pending"] = \
            pending_in + jnp.sum(keep.astype(jnp.int32))
        state_ivf["ivf_overflow"] = jax.lax.pmax(
            state_ivf["ivf_overflow"].astype(jnp.int32), axis) > 0
        return out + tuple(state_ivf[k] for k in index_lib.IVF_KEYS)

    n_ivf = len(index_lib.IVF_KEYS) if ivf else 0
    # centroids + pending + overflow replicate; table + back-ptrs shard
    ivf_in = (P(), P(axis), P(axis), P(axis), P(axis), P(), P())[:n_ivf]
    sm = jax.shard_map(
        local_insert, mesh=mesh,
        in_specs=(P(axis),) * 8 + (P(),) * 3 + (P(),) * 6 + ivf_in,
        out_specs=(P(axis),) * 8 + (P(),) * 4 + ivf_in,
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def insert_batch(state, embs, q_tokens, q_mask, r_tokens, r_mask,
                     count):
        count = jnp.minimum(jnp.asarray(count, jnp.int32), embs.shape[0])
        res = sm(
            state["emb"], state["q_tokens"], state["q_mask"],
            state["r_tokens"], state["r_mask"], state["valid"],
            state["last_used"], state["hits"],
            state["ptr"], state["clock"], state["size"],
            embs, q_tokens, q_mask, r_tokens, r_mask, count,
            *((state[k] for k in index_lib.IVF_KEYS) if ivf else ()))
        (emb, qt, qm, rt, rm, valid, last_used, hits,
         ptr, clock, size, slots) = res[:12]
        new = dict(state)
        new.update(emb=emb, q_tokens=qt, q_mask=qm, r_tokens=rt, r_mask=rm,
                   valid=valid, last_used=last_used, hits=hits,
                   ptr=ptr, clock=clock, size=size)
        new.update(zip(index_lib.IVF_KEYS, res[12:]))
        return new, slots

    return insert_batch
