"""Functional semantic vector cache — the TweakLLM vector DB.

Fixed-capacity, fully JAX (fixed shapes, jit-safe): unit-norm embeddings,
token buffers for cached query/response texts, validity mask, and an
insertion policy.  The paper ships append-only (== ring/FIFO here, which is
append-only until capacity); LRU and LFU are implemented as the
§6.2 "cache eviction policies" extension.

Lookup dispatches to the Pallas ``cosine_topk`` kernel or its XLA path
(the reference's scores, selected by an exact chunked top-k);
``repro.core.distributed`` wraps it in shard_map for the sharded
production cache.

Write path (DESIGN.md §5): ``insert`` is the one-entry reference;
``insert_batch`` commits a whole miss batch in a single jitted step (fixed
shapes + a traced ``count``, so one compile serves every batch bucket) and
``lookup_and_touch`` fuses lookup, routing, and hit accounting so a serve
batch costs one host↔device round-trip instead of one per entry.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.cosine_topk.ops import cosine_topk

from . import index as index_lib
from . import router as router_lib

POLICIES = ("fifo", "lru", "lfu")
INDEXES = ("flat", "ivf")

# admission-control state (IVF caches): per-cluster hit EMA + observation
# count.  Deliberately NOT part of index.IVF_KEYS — the arrays replicate
# in the sharded layout (updated identically everywhere from replicated
# routing results), so the shard-routed insert specs never see them.
ADM_KEYS = ("adm_ema", "adm_count")


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    capacity: int = 4096
    dim: int = 384
    max_query_tokens: int = 64
    max_response_tokens: int = 256
    policy: str = "fifo"
    topk: int = 4
    lookup_impl: str = "xla"  # xla | pallas
    block_n: int = 1024
    # clustered (IVF) index — DESIGN.md §7.  0 = auto-resolve from capacity
    # (see index.resolve): nclusters ~ capacity/128 (capped 2048), bucket
    # = ceil(capacity/nclusters) with 2x slack.
    index: str = "flat"       # flat | ivf
    nclusters: int = 0
    nprobe: int = 8
    ivf_bucket: int = 0
    reindex_every: int = 0    # writes between k-means rebuilds (0 = auto)
    kmeans_iters: int = 10


def init_cache(cfg: CacheConfig):
    c = cfg.capacity
    state = {
        "emb": jnp.zeros((c, cfg.dim), jnp.float32),
        "q_tokens": jnp.zeros((c, cfg.max_query_tokens), jnp.int32),
        "q_mask": jnp.zeros((c, cfg.max_query_tokens), jnp.float32),
        "r_tokens": jnp.zeros((c, cfg.max_response_tokens), jnp.int32),
        "r_mask": jnp.zeros((c, cfg.max_response_tokens), jnp.float32),
        "valid": jnp.zeros((c,), bool),
        "ptr": jnp.zeros((), jnp.int32),          # ring pointer (fifo)
        "last_used": jnp.zeros((c,), jnp.int32),  # lru clock
        "hits": jnp.zeros((c,), jnp.int32),       # lfu counter
        "clock": jnp.zeros((), jnp.int32),
        "size": jnp.zeros((), jnp.int32),
    }
    if cfg.index == "ivf":
        state.update(index_lib.init_ivf(cfg))
        state.update(init_admission(cfg))
    return state


def init_admission(cfg: CacheConfig):
    """Fresh per-cluster admission state: optimistic (every cluster admits
    until ``admit_min`` observations say otherwise)."""
    p = index_lib.resolve(cfg)
    return {
        "adm_ema": jnp.ones((p.nclusters,), jnp.float32),
        "adm_count": jnp.zeros((p.nclusters,), jnp.int32),
    }


def _victim_slot(state, cfg: CacheConfig):
    full = state["size"] >= cfg.capacity
    if cfg.policy == "fifo":
        return state["ptr"] % cfg.capacity
    score = jnp.where(state["valid"],
                      state["last_used"] if cfg.policy == "lru" else state["hits"],
                      -1)
    evict = jnp.argmin(jnp.where(state["valid"], score, jnp.iinfo(jnp.int32).max))
    return jnp.where(full, evict.astype(jnp.int32), state["ptr"] % cfg.capacity)


def _normalize(emb):
    """Unit-normalize along the last axis.

    The sum of squares is a fixed pairwise tree of elementwise adds, not
    a reduce: XLA orders a reduce differently for a (D,) row than for a
    (B, D) batch, so a row's stored embedding would depend on the batch
    it was inserted with.  Elementwise adds have one order, so batched
    and single-entry inserts write the same bits.
    """
    sq = emb * emb
    d = sq.shape[-1]
    width = 1 << (d - 1).bit_length()
    sq = jnp.pad(sq, [(0, 0)] * (sq.ndim - 1) + [(0, width - d)])
    while sq.shape[-1] > 1:
        half = sq.shape[-1] // 2
        sq = sq[..., :half] + sq[..., half:]
    return emb / jnp.maximum(jnp.sqrt(sq), 1e-8)


def _fifo_batch_plan(ptr, row, count, capacity: int):
    """Slot plan for a FIFO batch: (slots, keep, active).

    Entry i lands at ring slot ``(ptr + i) % capacity``; when the batch
    laps the ring the later duplicate must win, so row i is dropped when
    row ``i + capacity`` is also active.  Shared by the local and sharded
    insert_batch so the semantics cannot drift.
    """
    active = row < count
    slots = (ptr + row) % capacity
    keep = active & (row + capacity >= count)
    return slots, keep, active


def insert(state, cfg: CacheConfig, emb, q_tokens, q_mask, r_tokens, r_mask):
    """Insert ONE entry (emb (D,), tokens already padded to cfg lengths)."""
    slot = _victim_slot(state, cfg)
    emb = _normalize(emb)
    upd = lambda buf, val: buf.at[slot].set(val.astype(buf.dtype))
    new = dict(state)
    new["emb"] = upd(state["emb"], emb)
    new["q_tokens"] = upd(state["q_tokens"], q_tokens)
    new["q_mask"] = upd(state["q_mask"], q_mask)
    new["r_tokens"] = upd(state["r_tokens"], r_tokens)
    new["r_mask"] = upd(state["r_mask"], r_mask)
    new["valid"] = state["valid"].at[slot].set(True)
    new["last_used"] = state["last_used"].at[slot].set(state["clock"])
    new["hits"] = state["hits"].at[slot].set(0)
    new["ptr"] = state["ptr"] + 1
    new["clock"] = state["clock"] + 1
    new["size"] = jnp.minimum(state["size"] + 1, cfg.capacity)
    if cfg.index == "ivf":
        new.update(index_lib.append_one(
            {k: new[k] for k in index_lib.IVF_KEYS}, emb,
            slot.astype(jnp.int32), jnp.asarray(True)))
    return new


def insert_batch(state, cfg: CacheConfig, embs, q_tokens, q_mask,
                 r_tokens, r_mask, count=None):
    """Insert up to B entries in one fused device step.

    embs (B, D); q_tokens/q_mask (B, max_query_tokens); r_tokens/r_mask
    (B, max_response_tokens).  Rows at index >= ``count`` are padding and
    are ignored — ``count`` is a traced scalar, so one compiled artifact
    serves every batch bucket of the same padded shape B.

    State-equivalent to B sequential :func:`insert` calls for all three
    policies.  Returns ``(new_state, slots)`` where ``slots`` (B,) int32
    holds the ring/victim slot each active row landed in (-1 for padding).

    FIFO places rows at consecutive ring slots, so victim selection is a
    single vectorized scatter.  LRU/LFU victims depend on every preceding
    insert in the batch, so those run as an on-device ``lax.scan`` — still
    a single dispatch, no per-entry host sync.
    """
    b = embs.shape[0]
    # clamp so ptr/clock/size never advance past the rows actually written
    count = jnp.minimum(jnp.asarray(b if count is None else count, jnp.int32), b)
    embs = _normalize(embs)
    row = jnp.arange(b, dtype=jnp.int32)
    active = row < count

    if cfg.policy == "fifo":
        # scatter target `capacity` is out-of-bounds; mode="drop" discards it
        slots, keep, active = _fifo_batch_plan(state["ptr"], row, count,
                                               cfg.capacity)
        w = jnp.where(keep, slots, cfg.capacity)
        upd = lambda buf, val: buf.at[w].set(val.astype(buf.dtype), mode="drop")
        new = dict(state)
        new["emb"] = upd(state["emb"], embs)
        new["q_tokens"] = upd(state["q_tokens"], q_tokens)
        new["q_mask"] = upd(state["q_mask"], q_mask)
        new["r_tokens"] = upd(state["r_tokens"], r_tokens)
        new["r_mask"] = upd(state["r_mask"], r_mask)
        new["valid"] = state["valid"].at[w].set(True, mode="drop")
        new["last_used"] = state["last_used"].at[w].set(
            state["clock"] + row, mode="drop")
        new["hits"] = state["hits"].at[w].set(0, mode="drop")
        new["ptr"] = state["ptr"] + count
        new["clock"] = state["clock"] + count
        new["size"] = jnp.minimum(state["size"] + count, cfg.capacity)
        if cfg.index == "ivf":
            # lapped duplicates (keep=False) were dropped from the buffers,
            # so they must not be filed in the member table either
            new = index_lib.update_batch(new, cfg, embs,
                                         jnp.where(keep, slots, -1))
        return new, jnp.where(active, slots, -1)

    # nearest-centroid routing hoisted to one (B, nclusters) GEMM; only
    # the table filing itself needs to stay sequential in the scan
    cn = index_lib.nearest_clusters(state["ivf_centroids"], embs) \
        if cfg.index == "ivf" else jnp.zeros((b,), jnp.int32)

    def step(carry, x):
        emb_i, qt_i, qm_i, rt_i, rm_i, on, cn_i = x
        slot = _victim_slot(carry, cfg)
        w = jnp.where(on, slot, cfg.capacity)  # OOB -> dropped when padding
        upd = lambda buf, val: buf.at[w].set(val.astype(buf.dtype), mode="drop")
        new = dict(carry)
        new["emb"] = upd(carry["emb"], emb_i)
        new["q_tokens"] = upd(carry["q_tokens"], qt_i)
        new["q_mask"] = upd(carry["q_mask"], qm_i)
        new["r_tokens"] = upd(carry["r_tokens"], rt_i)
        new["r_mask"] = upd(carry["r_mask"], rm_i)
        new["valid"] = carry["valid"].at[w].set(True, mode="drop")
        new["last_used"] = carry["last_used"].at[w].set(carry["clock"],
                                                        mode="drop")
        new["hits"] = carry["hits"].at[w].set(0, mode="drop")
        inc = on.astype(jnp.int32)
        new["ptr"] = carry["ptr"] + inc
        new["clock"] = carry["clock"] + inc
        new["size"] = jnp.minimum(carry["size"] + inc, cfg.capacity)
        if cfg.index == "ivf":
            new.update(index_lib.file_row(
                {k: new[k] for k in index_lib.IVF_KEYS}, cn_i,
                slot.astype(jnp.int32), on))
        return new, jnp.where(on, slot, -1)

    return jax.lax.scan(
        step, dict(state),
        (embs, q_tokens, q_mask, r_tokens, r_mask, active, cn))


# the module's function under a name the closure below does not shadow
_insert_batch = insert_batch


def make_insert_batch(cfg: CacheConfig, donate: bool = True):
    """Jit-compiled ``(state, embs, qt, qm, rt, rm, count) -> (state, slots)``.

    Cache buffers are donated so the update happens in place on device —
    the caller must drop its reference to the input state.
    """
    def insert_batch(state, embs, qt, qm, rt, rm, count):
        # named for the program it becomes in profiler traces
        return _insert_batch(state, cfg, embs, qt, qm, rt, rm, count)

    return jax.jit(insert_batch, donate_argnums=(0,) if donate else ())


def lookup(state, cfg: CacheConfig, q_embs):
    """q_embs (B, D) unit vectors -> (scores (B,k), indices (B,k)).

    ``cfg.index`` picks the scan: "flat" brute-forces the whole bank,
    "ivf" probes the top-``nprobe`` clusters of the member table
    (DESIGN.md §7; identical results at ``nprobe == nclusters``).

    The flat scan with ``lookup_impl="xla"`` scores every row at float32
    ``HIGHEST`` precision and selects the top-k exactly, ties included,
    in two stages: the best score of each chunk of ``chunk_width(N)``
    rows (``sqrt(N)`` rounded to a power of two, at least 128; 1,024 for
    a 1,048,576-row bank), then one top-k over the k best chunks.  A bank
    under 16,384 rows, or of fewer than 4k chunks, takes one plain top-k
    (``kernels/cosine_topk/ops.py``).
    """
    if cfg.index == "ivf":
        return index_lib.lookup(state, cfg, q_embs)
    k = min(cfg.topk, cfg.capacity)
    return cosine_topk(q_embs, state["emb"], state["valid"], k=k,
                       impl=cfg.lookup_impl, block_n=min(cfg.block_n, cfg.capacity))


def _stamp_hits(state, w):
    """Hit accounting at rows ``w``: stamp ``last_used`` with the clock,
    bump ``hits``, tick the clock.  Out-of-bounds rows are dropped.

    Each scatter names its output sharding as its operand's own.  On a
    row-sharded bank the operand is sharded and ``w`` replicated, and
    JAX does not pick the output sharding of such a scatter by itself.
    """
    lu, hits = state["last_used"], state["hits"]
    new = dict(state)
    new["last_used"] = lu.at[w].set(state["clock"], mode="drop",
                                    out_sharding=jax.typeof(lu).sharding)
    new["hits"] = hits.at[w].add(1, mode="drop",
                                 out_sharding=jax.typeof(hits).sharding)
    new["clock"] = state["clock"] + 1
    return new


def touch(state, cfg: CacheConfig, indices):
    """Record cache hits for LRU/LFU accounting.  indices: (B,) top-1 hits.

    A -1 index (empty/all-invalid cache, or a padded row) must be a
    no-op: raw negative indices WRAP in jax scatters, so an unguarded
    ``.at[-1]`` would silently touch the LAST slot and corrupt LRU/LFU
    ordering.  Route them out of bounds and drop, like lookup_and_touch.
    """
    indices = jnp.asarray(indices)
    w = jnp.where(indices >= 0, indices, cfg.capacity)
    return _stamp_hits(state, w)


def lookup_and_touch(state, cfg: CacheConfig,
                     router_cfg: "router_lib.RouterConfig", q_embs):
    """Fused lookup + routing + hit accounting (one device round-trip).

    Every row routed EXACT or TWEAK touches its top-1 entry (updating
    ``last_used``/``hits`` exactly like :func:`touch` on the hit subset),
    so LRU/LFU see every hit — including the EXACT fast path.

    Returns ``(new_state, scores (B,k), indices (B,k), decisions (B,))``.
    """
    scores, idx = lookup(state, cfg, q_embs)
    decisions = router_lib.route(scores[:, 0], router_cfg)
    top1 = idx[:, 0]
    hit = (decisions != router_lib.MISS) & (top1 >= 0)
    w = jnp.where(hit, top1, cfg.capacity)  # OOB -> dropped for misses
    new = _stamp_hits(state, w)
    return new, scores, idx, decisions


def route_touch_core(state, cfg: CacheConfig, router_cfg, q_embs, scores,
                     idx, cost):
    """Post-lookup stage-1 core, shared by the local and sharded fused
    paths (so their routing/accounting semantics cannot drift).

    Routes the merged top-k through the calibrated cascade
    (``router.route_cascade`` at the per-row operating points), touches
    only rows COMMITTED as hits (UNCERTAIN rows wait for stage 2), and —
    for IVF caches — reads the per-cluster admission flag and folds the
    batch's certain outcomes into the admission EMA.

    Returns ``(new_state, decisions, tau, cluster, admit)``.
    """
    tau = router_lib.threshold_for(cost, router_cfg)
    decisions = router_lib.route_cascade(scores[:, 0], tau, router_cfg)
    top1 = idx[:, 0]
    hit = ((decisions == router_lib.TWEAK)
           | (decisions == router_lib.EXACT)) & (top1 >= 0)
    w = jnp.where(hit, top1, cfg.capacity)  # OOB -> dropped for misses
    new = _stamp_hits(state, w)
    b = scores.shape[0]
    if cfg.index == "ivf":
        # centroids are replicated in the sharded layout, so the cluster
        # ids (and everything downstream) agree between sharded and local
        # routing.  A cold index (zero centroids) files everything under
        # cluster 0 — harmless: the EMA starts optimistic.
        cluster = index_lib.nearest_clusters(state["ivf_centroids"], q_embs)
        admit = router_lib.admission_admit(
            state["adm_ema"], state["adm_count"], cluster, router_cfg)
        certain = decisions != router_lib.UNCERTAIN
        ema, cnt = router_lib.admission_update(
            state["adm_ema"], state["adm_count"], cluster, hit, certain,
            router_cfg)
        new["adm_ema"], new["adm_count"] = ema, cnt
    else:
        cluster = jnp.full((b,), -1, jnp.int32)
        admit = jnp.ones((b,), bool)
    return new, decisions, tau, cluster, admit


def lookup_route_touch(state, cfg: CacheConfig, router_cfg, q_embs, cost):
    """Fused stage-1 of the calibrated cascade (one device round-trip).

    Like :func:`lookup_and_touch`, plus: per-request ``cost`` (B,) picks
    each row's operating point, rows near the boundary come back
    ``router.UNCERTAIN`` (untouched — stage 2 commits them), and IVF
    caches surface the query's cluster id and admission flag.

    Returns ``(new_state, scores (B,k), indices (B,k), decisions (B,),
    tau (B,), cluster (B,), admit (B,) bool)``.
    """
    scores, idx = lookup(state, cfg, q_embs)
    new, decisions, tau, cluster, admit = route_touch_core(
        state, cfg, router_cfg, q_embs, scores, idx, cost)
    return new, scores, idx, decisions, tau, cluster, admit


def make_second_stage(cfg: CacheConfig, router_cfg, rr_params, rr_cfg,
                      donate: bool = True):
    """Builds the jitted stage-2 resolver for UNCERTAIN rows.

    ``(state, q_tokens, q_mask, scores, idx, decisions, tau, cluster) ->
    (new_state, final_decisions, slot (B,), conf (B,))``

    Gathers the shortlist candidates' cached query tokens, scores them
    with the cross-encoder reranker against the live query, and combines
    reranker evidence with multi-probe top-k agreement
    (``router.stage2_combine``) to commit TWEAK or MISS.  The serving
    ``slot`` for committed rows is the RERANKER argmax candidate, not
    necessarily the top-1 cosine neighbour — the misroute recovery.
    Committed rows are touched here (stage 1 skipped them; the clock
    ticks once more for the batch) and uncertain outcomes fold into the
    admission EMA.  Works unchanged on sharded states: the token gather
    and touch scatters run in the GSPMD region with replicated indices.
    """
    from repro.models import reranker as rr_lib

    def second_stage(state, q_tokens, q_mask, scores, idx, decisions, tau,
                     cluster):
        live = idx >= 0
        safe = jnp.clip(idx, 0, cfg.capacity - 1)
        cand_t = jnp.take(state["q_tokens"], safe, axis=0)   # (B, K, S)
        cand_m = jnp.take(state["q_mask"], safe, axis=0) \
            * live[..., None].astype(state["q_mask"].dtype)
        rr = rr_lib.score_shortlist(rr_params, q_tokens, q_mask,
                                    cand_t, cand_m, rr_cfg)
        commit, best, conf = router_lib.stage2_combine(
            scores, rr, live, tau, router_cfg)
        unc = decisions == router_lib.UNCERTAIN
        final = jnp.where(
            unc, jnp.where(commit, router_lib.TWEAK, router_lib.MISS),
            decisions).astype(jnp.int32)
        chosen = jnp.take_along_axis(idx, best[:, None], axis=1)[:, 0]
        slot = jnp.where(unc & commit, chosen, idx[:, 0])
        touch = unc & commit & (slot >= 0)
        w = jnp.where(touch, slot, cfg.capacity)
        new = _stamp_hits(state, w)
        if cfg.index == "ivf":
            ema, cnt = router_lib.admission_update(
                state["adm_ema"], state["adm_count"], cluster, commit, unc,
                router_cfg)
            new["adm_ema"], new["adm_count"] = ema, cnt
        return new, final, slot, conf

    return jax.jit(second_stage, donate_argnums=(0,) if donate else ())


def fetch(state, indices):
    """Gather cached (q_tokens, q_mask, r_tokens, r_mask) rows for indices (B,)."""
    g = lambda buf: jnp.take(buf, indices, axis=0)
    return g(state["q_tokens"]), g(state["q_mask"]), g(state["r_tokens"]), g(state["r_mask"])
