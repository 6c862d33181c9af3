"""TweakLLMEngine — the paper's Figure-1 pipeline, end to end.

Per incoming batch of text queries:
  1. tokenize + embed (MiniLM-class embedder, unit vectors)
  2. semantic-cache lookup (Pallas cosine top-k / sharded variant)
  3. threshold routing -> EXACT | TWEAK | MISS sub-batches (host split —
     the TPU analogue of per-request branching; see DESIGN.md §3)
  4. MISS  -> Big LLM generates; (query, response) inserted into the cache
     TWEAK -> Small LLM prefills the Appendix-A tweak prompt and decodes
     EXACT -> cached response returned verbatim (§6.1 fast path)

Step 2/3 run as one fused ``lookup_and_touch`` device call (EXACT and
TWEAK hits update LRU/LFU bookkeeping in the same step), and a miss batch
commits to the cache through one jitted ``insert_batch`` call with donated
buffers — O(1) host↔device syncs per serve batch (DESIGN.md §5).

The TWEAK path is prefix-cached (DESIGN.md §9): the byte-identical
Appendix-A instruction prefix is prefilled once per (small model, batch
bucket) and reused as KV by every tweak request, which then prefills
only its variable suffix, length-bucketed by REAL suffix length instead
of padded to the worst-case tweak budget.  Small models whose
architecture can't guarantee byte-identical prefix reuse (recurrent
mixers, sliding windows, enc-dec, naive-softmax attention) fall back to
the full-prompt prefill explicitly.

Cost accounting mirrors the paper's §5.2.3 analysis: per-token cost ratio
``big_cost_per_token`` : ``small_cost_per_token`` defaults to 25:1.
Token counts are REAL generated tokens (up to and including each row's
first EOS), never the padded bucket length, and prompt (input) tokens
are billed at real unpadded lengths alongside generated ones.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import embedder as embedder_lib
from repro.serving import trace
from repro.serving.batcher import (bucket_batch, bucket_len, floor_len_bucket,
                                   pad_to_buckets)
from repro.serving.generate import Generator
from repro.tokenizer import HashWordTokenizer

from . import cache as cache_lib
from . import index as index_lib
from . import router as router_lib
from . import tweak as tweak_lib


@dataclasses.dataclass
class EngineStats:
    total: int = 0
    miss: int = 0
    tweak: int = 0
    exact: int = 0
    # calibrated-cascade counters (DESIGN.md §13): rows that entered the
    # stage-2 uncertainty band, how many of those committed as TWEAK
    # (recovered hits), and inserts suppressed by cluster admission.
    uncertain: int = 0
    recovered: int = 0
    suppressed_inserts: int = 0
    big_tokens: int = 0             # REAL generated tokens, Big LLM
    small_tokens: int = 0           # REAL generated tokens, Small LLM
    # The paper's §5.2.3 cost analysis bills INPUT tokens too.  Prompt
    # counts are real (unpadded) prefilled lengths, never the padded
    # bucket: the Big LLM's prompt is the bare query, the Small LLM's is
    # the Appendix-A tweak prompt (shared prefix included — the KV may be
    # cached, but a provider still bills the tokens).
    big_prompt_tokens: int = 0
    small_prompt_tokens: int = 0
    # Real query tokens across ALL requests: the prompt volume an
    # uncached all-Big deployment would have ingested (baseline input).
    baseline_prompt_tokens: int = 0
    # speculative-decode counters (DESIGN.md §14): cached-response draft
    # tokens fed to TWEAK verify blocks, how many were accepted (emitted
    # without a plain decode step), and verify-block iterations run.
    proposed: int = 0
    accepted: int = 0
    spec_steps: int = 0
    big_cost_per_token: float = 25.0
    small_cost_per_token: float = 1.0

    @property
    def cost(self) -> float:
        return ((self.big_tokens + self.big_prompt_tokens)
                * self.big_cost_per_token
                + (self.small_tokens + self.small_prompt_tokens)
                * self.small_cost_per_token)

    @property
    def baseline_cost(self) -> float:
        """What the same traffic would cost all-Big: every query's prompt
        plus the same generated-token volume, at the Big rate."""
        return (self.big_tokens + self.small_tokens
                + self.baseline_prompt_tokens) * self.big_cost_per_token

    @property
    def hit_rate(self) -> float:
        return (self.tweak + self.exact) / max(self.total, 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify loop accepted (§14)."""
        return self.accepted / max(self.proposed, 1)

    @classmethod
    def aggregate(cls, parts) -> "EngineStats":
        """Sum counters across replicas (DESIGN.md §12).

        Cost rates must agree — silently averaging them would make the
        aggregate ``cost`` property meaningless.
        """
        parts = list(parts)
        if not parts:
            return cls()
        rates = {(p.big_cost_per_token, p.small_cost_per_token)
                 for p in parts}
        if len(rates) != 1:
            raise ValueError(
                f"replicas disagree on cost rates: {sorted(rates)}")
        big_rate, small_rate = rates.pop()
        out = cls(big_cost_per_token=big_rate,
                  small_cost_per_token=small_rate)
        for f in ("total", "miss", "tweak", "exact", "uncertain",
                  "recovered", "suppressed_inserts", "big_tokens",
                  "small_tokens", "big_prompt_tokens", "small_prompt_tokens",
                  "baseline_prompt_tokens", "proposed", "accepted",
                  "spec_steps"):
            setattr(out, f, sum(getattr(p, f) for p in parts))
        return out


@dataclasses.dataclass
class BatchResult:
    """Per-batch serve result with per-request metadata.

    The continuous-batching scheduler (serving/scheduler.py, DESIGN.md §6)
    consumes this instead of the bare response list: ``meta`` rows carry
    the routing decision, top-1 similarity, similarity band, and the REAL
    generated-token count for each request, and the token deltas let the
    caller attribute cost to a dispatch without diffing ``EngineStats``.
    """
    responses: List[str]
    meta: List[dict]            # per row: sim, decision, band, gen_tokens
    big_tokens: int = 0         # tokens the Big LLM generated for this batch
    small_tokens: int = 0      # tokens the Small LLM generated for this batch
    big_prompt_tokens: int = 0   # real (unpadded) prompt tokens, Big LLM
    small_prompt_tokens: int = 0  # real (unpadded) prompt tokens, Small LLM


class SharedCacheBank:
    """The semantic cache as a first-class shareable object (DESIGN.md §12).

    Owns the device-side cache state, the host text mirror, and the two
    jitted state-mutating entry points — the fused lookup+route+touch and
    the batched miss commit.  One bank serves ONE engine (the PR 1–7
    topology, ``mesh=None``) or N replicas through a :class:`ReplicaGroup`:
    every replica routes lookups and commits misses through the same
    object, so a response cached by replica A is visible to replica B on
    B's very next lookup.

    With a ``mesh``, the embedding bank, token buffers, and IVF member
    tables are row-sharded over ``axis`` (centroids and ring scalars
    replicated) and the entry points come from ``repro.core.distributed``:
    lookups merge per-shard top-k winners, and inserts are
    single-writer-per-shard — the globally rotating ring pointer names the
    owning shard for every slot, so concurrent replica commits serialize
    through the bank with no cross-shard write traffic.
    """

    def __init__(self, cache_cfg: cache_lib.CacheConfig,
                 router_cfg: Optional[router_lib.RouterConfig] = None, *,
                 mesh=None, axis: str = "data", state=None, reranker=None):
        if router_cfg is None:
            router_cfg = router_lib.RouterConfig()
        if router_cfg.band > 0.0 and reranker is None:
            raise ValueError(
                "router band > 0 enables the stage-2 cascade, which needs "
                "reranker=(params, model_cfg) on the bank")
        self.cfg = cache_cfg
        self.router_cfg = router_cfg
        self.mesh = mesh
        self.axis = axis
        # host-side mirror of cached texts (display only; tokens are truth)
        self.text_store: Dict[int, Tuple[str, str]] = {}
        # host-side mirror of cached-response TOKEN ids, the speculation
        # drafts (DESIGN.md §14): the exact ids generation produced (a
        # text round-trip through the tokenizer need not be identity, and
        # draft quality is acceptance rate).  Overwritten on slot reuse
        # alongside text_store.
        self.draft_store: Dict[int, List[int]] = {}
        self.insert_seq = 0
        # per-batch-size default-cost arrays (explicit device_put once per
        # size — the hot loop must not transfer implicitly per dispatch)
        self._default_costs: Dict[int, jnp.ndarray] = {}
        if state is None:
            state = cache_lib.init_cache(cache_cfg)
        if mesh is None:
            self.state = state
            # fused lookup + calibrated route + hit-accounting; cache state
            # donated so the touch happens in place (DESIGN.md §5).  The
            # function's name names the program in profiler traces.
            def lookup_route_touch(s, q, c):
                return cache_lib.lookup_route_touch(s, cache_cfg, router_cfg,
                                                    q, c)

            self._lookup_touch = jax.jit(lookup_route_touch,
                                         donate_argnums=(0,))
            self._insert = cache_lib.make_insert_batch(cache_cfg)
        else:
            from . import distributed as dist_lib
            if cache_cfg.index == "ivf":
                self.state = dist_lib.shard_ivf_cache_state(
                    state, mesh, cache_cfg, axis)
            else:
                self.state = dist_lib.shard_cache_state(state, mesh, axis)
            self._lookup_touch = dist_lib.make_distributed_lookup_and_touch(
                mesh, cache_cfg, router_cfg, axis)
            self._insert = dist_lib.make_distributed_insert_batch(
                mesh, cache_cfg, axis)
        # stage-2 resolver (shared by local and sharded states: the token
        # gather + touch run in the GSPMD region with replicated indices)
        self._second_stage = None
        if reranker is not None:
            rr_params, rr_cfg = reranker
            self._second_stage = cache_lib.make_second_stage(
                cache_cfg, router_cfg, rr_params, rr_cfg)

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def cascading(self) -> bool:
        """Is the stage-2 cascade active (band > 0 + reranker wired)?"""
        return self.router_cfg.band > 0.0 and self._second_stage is not None

    def default_cost(self, batch: int):
        """The (batch,)-shaped default-cost array, device-put once."""
        c = self._default_costs.get(batch)
        if c is None:
            c = jax.device_put(np.full((batch,), self.router_cfg.default_cost,
                                       np.float32))
            self._default_costs[batch] = c
        return c

    def route_batch(self, q_embs, cost=None):
        """Stage-1 fused device call at per-request operating points.

        ``cost`` (B,) float32 on device, or None for the config default.
        Returns the device-array tuple ``(scores, idx, decisions, tau,
        cluster, admit)`` — decisions may contain ``router.UNCERTAIN``
        when the cascade is on; resolve those with :meth:`second_stage`.
        """
        if cost is None:
            cost = self.default_cost(q_embs.shape[0])
        (self.state, scores, idx, dec, tau, cluster,
         admit) = self._lookup_touch(self.state, q_embs, cost)
        return scores, idx, dec, tau, cluster, admit

    def lookup_and_touch(self, q_embs):
        """One fused device call: returns (scores, idx, decisions).

        The fixed-operating-point wrapper around :meth:`route_batch`
        (kept for single-stage callers; at the default config it is
        decision-identical to the legacy two-threshold router).
        """
        scores, idx, dec, *_ = self.route_batch(q_embs)
        return scores, idx, dec

    def second_stage(self, q_tokens, q_mask, scores, idx, decisions, tau,
                     cluster):
        """Resolve UNCERTAIN rows: returns (final_decisions, slot, conf).

        All inputs are device arrays (stage-1 outputs pass through
        unconverted); ``slot`` (B,) is the per-row serving slot — the
        reranker's pick for committed uncertain rows, top-1 otherwise.
        """
        if self._second_stage is None:
            raise ValueError("bank built without a reranker; stage 2 "
                             "unavailable")
        self.state, final, slot, conf = self._second_stage(
            self.state, q_tokens, q_mask, scores, idx, decisions, tau,
            cluster)
        return final, slot, conf

    def insert_batch(self, embs, q_tokens, q_mask, r_tokens, r_mask, count):
        """One jitted commit; returns the device ``slots`` array."""
        self.state, slots = self._insert(self.state, embs, q_tokens, q_mask,
                                         r_tokens, r_mask, count)
        return slots

    def maybe_reindex(self) -> bool:
        """IVF maintenance after a commit; no-op for flat caches.

        Always advances ``insert_seq`` (the reindex seed stream) so
        local and sharded banks rebuild from the same seed sequence.
        """
        rebuilt = False
        if self.cfg.index == "ivf":
            if self.mesh is None:
                self.state, rebuilt = index_lib.maybe_reindex(
                    self.state, self.cfg, seed=self.insert_seq)
            else:
                rebuilt = self._maybe_reindex_sharded()
        self.insert_seq += 1
        return rebuilt

    def _maybe_reindex_sharded(self) -> bool:  # hostsync: ok host-driven maintenance, mirrors index.maybe_reindex
        """Gather -> rebuild -> reshard, the sharded k-means recluster.

        ``build_index`` resets the IVF arrays to a fresh LOCAL layout, so
        pulling the (tiny, capacity-bounded) bank to host, rebuilding, and
        resharding reproduces exactly what a local bank would hold — at a
        maintenance cadence, not per request.
        """
        overflow, pending = jax.device_get(
            (self.state["ivf_overflow"], self.state["ivf_pending"]))
        p = index_lib.resolve(self.cfg)
        if not (bool(overflow) or int(pending) >= p.reindex_every):
            return False
        from . import distributed as dist_lib
        host = jax.device_get(self.state)
        rebuilt = index_lib.build_index(host, self.cfg, seed=self.insert_seq)
        self.state = dist_lib.shard_ivf_cache_state(
            rebuilt, self.mesh, self.cfg, self.axis)
        return True


class TweakLLMEngine:
    def __init__(self, *, tokenizer: HashWordTokenizer,
                 embedder_params, embedder_cfg,
                 big: Generator, small: Generator,
                 cache_cfg: Optional[cache_lib.CacheConfig] = None,
                 router_cfg: Optional[router_lib.RouterConfig] = None,
                 max_query_len: int = 64, use_prefix_cache: bool = True,
                 bank: Optional[SharedCacheBank] = None,
                 replica_id: int = 0, reranker=None):
        if bank is None:
            if cache_cfg is None:
                raise ValueError("pass cache_cfg or a SharedCacheBank")
            bank = SharedCacheBank(cache_cfg, router_cfg, reranker=reranker)
        else:
            if cache_cfg is not None and cache_cfg != bank.cfg:
                raise ValueError("cache_cfg disagrees with the shared bank")
            if router_cfg is not None and router_cfg != bank.router_cfg:
                raise ValueError("router_cfg disagrees with the shared bank")
        self.bank = bank
        self.replica_id = replica_id
        self.tok = tokenizer
        self.embedder_params = embedder_params
        self.embedder_cfg = embedder_cfg
        self.big = big
        self.small = small
        self.cache_cfg = bank.cfg
        self.router_cfg = bank.router_cfg
        self.max_query_len = max_query_len
        self.use_prefix_cache = use_prefix_cache
        self.stats = EngineStats()
        # Shared tweak-instruction prefix KV, one PrefixCache per batch
        # bucket (DESIGN.md §9), invalidated when the small generator's
        # model/sampler config or the prefix tokens change.
        self._prefix_ids: Optional[Tuple[int, ...]] = None
        self._prefix_caches: Dict[int, object] = {}
        self._prefix_sig = None
        self._static_counts: Optional[Tuple[int, int]] = None
        # per-batch seed counter threaded into every Big/Small generate
        # call: distinct serve batches sample from distinct key streams
        # (the seed replayed PRNGKey(0) for every batch)
        self._seed_seq = itertools.count()

        def embed_encode(p, t, m):
            return embedder_lib.encode(p, t, m, embedder_cfg)

        self._embed = jax.jit(embed_encode)

    # cache state + text mirror live on the bank (shared across replicas);
    # these aliases keep the single-engine API unchanged
    @property
    def state(self):
        return self.bank.state

    @state.setter
    def state(self, value):
        self.bank.state = value

    @property
    def _text_store(self) -> Dict[int, Tuple[str, str]]:
        return self.bank.text_store

    # ------------------------------------------------------------- embed
    def embed_texts(self, texts: List[str]) -> jnp.ndarray:
        return self._embed_tokens(self._tokenize(texts)[0])

    def _tokenize(self, texts: List[str]):
        """Host half of the embed: ``((tokens, mask, n), real query-token
        lengths, host tokens, host mask)``, the first padded to their
        bucket and put on the device.

        Lengths come from the host-side tokenizer mask, not the device;
        the (n, max_query_len) host token arrays are what the stage-2
        cascade device_puts, only when uncertain rows exist."""
        toks, mask = self.tok.encode_batch(texts, self.max_query_len)
        qlens = mask.sum(axis=1).astype(np.int64).tolist()
        ptoks, pmask, b = pad_to_buckets(toks, mask)
        return (jnp.asarray(ptoks), jnp.asarray(pmask), b), qlens, toks, mask

    def _embed_tokens(self, dev):
        ptoks, pmask, b = dev
        return self._embed(self.embedder_params, ptoks, pmask)[:b]

    # ------------------------------------------------------------- serve
    def handle_batch(self, queries: List[str], *, max_new_tokens: int = 32,
                     collect_meta: bool = False, cost_thresholds=None):
        res = self.handle_batch_result(queries, max_new_tokens=max_new_tokens,
                                       cost_thresholds=cost_thresholds)
        if collect_meta:
            return res.responses, res.meta
        return res.responses

    def _resolve_costs(self, n: int, cost_thresholds) -> List[float]:
        """Per-row cost thresholds: scalar, per-row list (None entries ->
        config default), or None for the all-default batch."""
        dc = self.router_cfg.default_cost
        if cost_thresholds is None:
            return [dc] * n
        if np.isscalar(cost_thresholds):
            return [float(cost_thresholds)] * n  # hostsync: ok caller-provided host scalar
        if len(cost_thresholds) != n:
            raise ValueError(f"{len(cost_thresholds)} cost thresholds for "
                             f"{n} queries")
        return [dc if c is None else float(c) for c in cost_thresholds]  # hostsync: ok caller-provided host scalars

    def handle_batch_result(self, queries: List[str], *,
                            max_new_tokens: int = 32,
                            cost_thresholds=None) -> BatchResult:
        """Serve a batch and return responses plus per-request metadata.

        ``cost_thresholds`` selects each request's operating point on the
        calibrated routing curve (scalar, per-row list with None = config
        default, or None for all-default).
        """
        with trace.span("engine.dispatch", rows=len(queries)):
            return self._handle_batch(queries, max_new_tokens,
                                      cost_thresholds)

    def _handle_batch(self, queries, max_new_tokens, cost_thresholds):
        n = len(queries)
        if n == 0:
            return BatchResult([], [])
        # fail fast on an unservable max_new_tokens BEFORE any state
        # mutation (lookup touches recency on device; EXACT rows bill
        # stats) so a ValueError cannot leave half-served accounting
        self._tweak_encode_len(max_new_tokens)
        with trace.span("engine.tokenize"):
            queries = [tweak_lib.preprocess_query(q) for q in queries]
            cost_l = self._resolve_costs(n, cost_thresholds)
            dev, qlens, qtoks, qmask = self._tokenize(queries)
            cost_dev = (self.bank.default_cost(n) if cost_thresholds is None
                        else jax.device_put(np.asarray(cost_l, np.float32)))  # hostsync: ok host list H2D, explicit put
        self.stats.baseline_prompt_tokens += sum(qlens)
        with trace.span("engine.route"):
            embs = self._embed_tokens(dev)
            d_scores, d_idx, d_dec, d_tau, d_cluster, d_admit = \
                self.bank.route_batch(embs, cost_dev)
        # THE per-serve-batch device->host sync (DESIGN.md §5): scores,
        # slots, routing decisions, and admission flags pulled in one
        # device_get; the top-1 column is sliced on host (device-side
        # `[:, 0]` would dispatch its index as an H2D transfer) and
        # everything below works on host scalars.  The stage-2 resolve
        # below adds a SECOND sync, but only on batches that actually
        # carry uncertain rows — the certain path stays O(1).
        with trace.span("engine.route_sync"):
            scores, idxs, decisions, admit = jax.device_get(  # hostsync: ok the one per-batch sync
                (d_scores, d_idx, d_dec, d_admit))
        top1 = scores[:, 0]
        slot_arr = idxs[:, 0]
        stage2_rows = decisions == router_lib.UNCERTAIN
        n_unc = int(stage2_rows.sum())  # hostsync: ok numpy after the batch sync
        if n_unc:
            with trace.span("engine.stage2", rows=n_unc):
                final, slot, _conf = self.bank.second_stage(
                    jax.device_put(qtoks), jax.device_put(qmask),
                    d_scores, d_idx, d_dec, d_tau, d_cluster)
                decisions, slot_arr = jax.device_get(  # hostsync: ok stage-2 resolve, fires only when uncertain rows exist
                    (final, slot))
            self.stats.uncertain += n_unc
            self.stats.recovered += int(  # hostsync: ok numpy after the stage-2 sync
                (decisions[stage2_rows] == router_lib.TWEAK).sum())
        top1_l = top1.tolist()
        slot_l = slot_arr.tolist()
        dec_l = decisions.tolist()

        responses: List[Optional[str]] = [None] * n
        gen_tokens = [0] * n
        prompt_tokens = [0] * n

        # EXACT: verbatim cached response
        with trace.span("engine.exact"):
            for i in np.nonzero(decisions == router_lib.EXACT)[0]:
                slot = slot_l[i]
                cached = self._text_store.get(slot)
                responses[i] = (cached[1] if cached
                                else self._decode_cached(slot))
                self.stats.exact += 1
        # TWEAK: small LLM refines cached response
        tweak_ids = np.nonzero(decisions == router_lib.TWEAK)[0]
        if len(tweak_ids):
            with trace.span("engine.tweak", rows=len(tweak_ids)):
                self._run_tweak(queries, tweak_ids, slot_l, responses,
                                max_new_tokens, gen_tokens, prompt_tokens)
        # MISS: big LLM generates from scratch + cache insert (suppressed
        # for rows whose query cluster the admission EMA has shut)
        miss_ids = np.nonzero(decisions == router_lib.MISS)[0]
        if len(miss_ids):
            with trace.span("engine.miss", rows=len(miss_ids)):
                self._run_miss(queries, miss_ids, embs, responses,
                               max_new_tokens, gen_tokens, prompt_tokens,
                               admit)

        self.stats.total += n
        # band_of mirrored on host with the ACTIVE config's edges: top1 is
        # already here, so no extra device dispatch + sync just for meta
        bands = np.full(n, -1, np.int32)
        for bi, (lo, hi) in enumerate(router_lib.bands_for(self.router_cfg)):
            bands[(top1 >= lo) & (top1 < hi)] = bi
        band_l = bands.tolist()
        meta = [{"sim": top1_l[i], "decision": dec_l[i],
                 "band": band_l[i], "gen_tokens": gen_tokens[i],
                 "cost": cost_l[i],
                 "stage2": bool(stage2_rows[i])}  # hostsync: ok numpy after sync
                for i in range(n)]
        miss_mask = decisions == router_lib.MISS
        return BatchResult(
            responses, meta,
            big_tokens=sum(t for i, t in enumerate(gen_tokens)
                           if miss_mask[i]),
            small_tokens=sum(t for i, t in enumerate(gen_tokens)
                             if not miss_mask[i]),
            big_prompt_tokens=sum(t for i, t in enumerate(prompt_tokens)
                                  if miss_mask[i]),
            small_prompt_tokens=sum(t for i, t in enumerate(prompt_tokens)
                                    if not miss_mask[i]))

    # ------------------------------------------------------------- paths
    def _next_seed(self) -> int:
        return next(self._seed_seq)

    def _decode_cached(self, slot: int) -> str:  # hostsync: ok cold fallback when the host text mirror lacks a slot
        toks, mask = jax.device_get((self.state["r_tokens"][slot],
                                     self.state["r_mask"][slot]))
        return self.tok.decode_ids(
            [t for t, m in zip(toks.tolist(), mask.tolist()) if m > 0])

    def _decode_cached_query(self, slot: int) -> str:  # hostsync: ok cold fallback, see _decode_cached
        """Decode a slot's cached QUERY tokens (BOS stripped)."""
        toks, mask = jax.device_get((self.state["q_tokens"][slot],
                                     self.state["q_mask"][slot]))
        return self.tok.decode_ids(
            [t for t, m in zip(toks.tolist(), mask.tolist())
             if m > 0 and t != self.tok.bos])

    @staticmethod
    def _visible_ids(row: np.ndarray, n_gen: int, ended: bool) -> List[int]:
        """Visible ids of a generated row from its device-reported length.

        ``n_gen`` counts real generated tokens including the terminating
        EOS when ``ended``; the visible response is everything before it.
        The lengths come back from the fused decode loop, so no per-row
        EOS scan is needed here.  ``row`` is already host-resident.
        """
        return row[:n_gen - 1 if ended else n_gen].tolist()

    def _tweak_static_tokens(self, suffix_only: bool = False) -> int:
        if self._static_counts is None:
            self._static_counts = (
                tweak_lib.static_token_count(self.tok),
                tweak_lib.static_token_count(self.tok, suffix_only=True))
        return self._static_counts[1 if suffix_only else 0]

    def _tweak_encode_len(self, max_new_tokens: int) -> int:
        """Prompt-token budget for the tweak path, bucket-rounding-safe.

        The naive budget ``max_seq_len - max_new_tokens - 1`` goes
        non-positive when ``max_new_tokens + 1 >= max_seq_len``, and even a
        positive budget can be rounded back past ``max_seq_len`` by
        ``pad_to_buckets`` (length buckets round UP).  Clamp to the largest
        length bucket that still fits; raise when nothing fits.  The budget
        must also cover the static prompt segments (instruction + cues),
        which cue-preserving truncation never drops — validating that HERE
        keeps the handle_batch fail-fast guarantee: the alternative is a
        ``ValueError`` out of ``_truncate_fields`` mid-serve, after lookup
        already touched recency and EXACT rows billed stats.
        """
        msl = self.small.model.cfg.max_seq_len
        budget = msl - max_new_tokens - 1
        if budget < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves no room for the "
                f"tweak prompt: small model max_seq_len={msl} requires "
                f"max_new_tokens <= {msl - 2}")
        if bucket_len(budget) + max_new_tokens + 1 > msl:
            budget = floor_len_bucket(budget)
            if bucket_len(budget) + max_new_tokens + 1 > msl:
                raise ValueError(
                    f"max_new_tokens={max_new_tokens} leaves no length "
                    f"bucket for the tweak prompt within small model "
                    f"max_seq_len={msl} (smallest bucket rounds past it)")
        statics = self._tweak_static_tokens()
        if budget < statics:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves a {budget}-token "
                f"tweak prompt budget, below the {statics} tokens the "
                f"static Appendix-A segments need — lower max_new_tokens "
                f"or raise the small model's max_seq_len={msl}")
        return budget

    # ------------------------------------------------- tweak prefix cache
    def _tweak_prefix_ids(self) -> Tuple[int, ...]:
        if self._prefix_ids is None:
            self._prefix_ids = tuple(tweak_lib.tweak_prefix_ids(self.tok))
        return self._prefix_ids

    def _prefix_path_available(self) -> bool:
        """Can the TWEAK path prefill over a shared-prefix KV cache?

        Requires the small generator to expose the prefix API (wrapped
        generators may not) and its architecture to support byte-identical
        prefix prefill; recurrent / windowed / enc-dec small models fall
        back to the full prefill explicitly (DESIGN.md §9).
        """
        return (self.use_prefix_cache
                and getattr(self.small, "supports_prefix_prefill", False)
                and callable(getattr(self.small, "build_prefix_cache", None)))

    def _small_prefix_cache(self, batch: int):
        """The tweak-instruction PrefixCache for one batch bucket.

        Rebuilt from scratch whenever the small GENERATOR OBJECT, its
        model config, sampler/generate config, or the prefix token ids
        change — a stale prefix KV would silently corrupt every tweak
        response.  The object identity term catches the config-identical
        swap (same architecture, new checkpoint weights) that config
        comparison alone would miss.
        """
        ids = self._tweak_prefix_ids()
        sig = (id(self.small), self.small.model.cfg,
               getattr(self.small, "cfg", None), ids)
        if sig != self._prefix_sig:
            self._prefix_caches.clear()
            self._prefix_sig = sig
        pc = self._prefix_caches.get(batch)
        if pc is None:
            pc = self.small.build_prefix_cache(ids, batch)
            self._prefix_caches[batch] = pc
        return pc

    def _tweak_suffix_budget(self, max_new_tokens: int,
                             prefix_len: int) -> Optional[int]:
        """Per-row suffix-token budget for prefix-cached tweak prefill.

        Same bucket-rounding discipline as ``_tweak_encode_len``, with the
        prefix length reserved on top: any real suffix length within the
        budget keeps ``prefix + bucket_len(suffix) + max_new_tokens + 1``
        inside the small model's ``max_seq_len``.  Returns None when no
        bucket fits — the caller then falls back to the full prefill path
        (which ``_tweak_encode_len`` has already validated).
        """
        msl = self.small.model.cfg.max_seq_len
        budget = msl - max_new_tokens - 1 - prefix_len
        if budget < 1:
            return None
        if bucket_len(budget) + prefix_len + max_new_tokens + 1 > msl:
            budget = floor_len_bucket(budget)
            if bucket_len(budget) + prefix_len + max_new_tokens + 1 > msl:
                return None
        # the suffix's own static cues are untruncatable — if they don't
        # fit, this path can't serve the request (the full path might)
        if budget < self._tweak_static_tokens(suffix_only=True):
            return None
        return budget

    def _run_tweak(self, queries, ids, slot_l, responses, max_new_tokens,
                   gen_tokens, prompt_tokens):
        slots = [slot_l[i] for i in ids]
        # The device cache is the source of truth: a slot can be live there
        # but absent from the host text mirror (offline-populated state,
        # restored checkpoint, distributed shard).  Fall back to decoding
        # the cached tokens so the Appendix-A tweak prompt is never built
        # from empty strings.
        cached = []
        for s in slots:
            c = self._text_store.get(s)
            if c is None:
                c = (self._decode_cached_query(s), self._decode_cached(s))
            cached.append(c)
        new_qs = [queries[i] for i in ids]
        cqs = [cq for cq, _ in cached]
        crs = [cr for _, cr in cached]
        drafts = self._tweak_drafts(slots, crs, max_new_tokens)

        suffix_budget = None
        if self._prefix_path_available():
            suffix_budget = self._tweak_suffix_budget(
                max_new_tokens, len(self._tweak_prefix_ids()))
        if suffix_budget is None:
            self._run_tweak_full(new_qs, cqs, crs, ids, responses,
                                 max_new_tokens, gen_tokens, prompt_tokens,
                                 drafts)
        else:
            self._run_tweak_prefixed(new_qs, cqs, crs, ids, responses,
                                     max_new_tokens, suffix_budget,
                                     gen_tokens, prompt_tokens, drafts)

    def _tweak_drafts(self, slots, crs, max_new_tokens):
        """Per-row speculation drafts for a TWEAK sub-batch, or None.

        The tweak prompt asks the small model to minimally edit the cached
        response, so the cached response's own token ids (plus the
        terminating EOS) are the natural draft for the verify loop
        (DESIGN.md §14).  Ids come from the bank's draft store (the exact
        generated ids) with a tokenize-the-mirror fallback for slots
        populated outside this process.  Returns ``(ids (B, D), lens
        (B,))`` or None when the small generator is not speculation-ready
        (wrong config/arch/sampler — ``getattr`` so wrapped generators
        degrade gracefully) or the per-call budget is below ``spec_k``.
        """
        if not getattr(self.small, "speculation_ready", False):
            return None
        if self.small.cfg.spec_k > max_new_tokens:
            return None
        eos = self.small.cfg.eos_id
        rows = []
        for s, cr in zip(slots, crs):
            ids = self.bank.draft_store.get(s)
            if ids is None:
                t, m = self.tok.encode_batch(
                    [cr], self.cache_cfg.max_response_tokens, add_bos=False)
                ids = [tt for tt, mm in zip(t[0].tolist(), m[0].tolist())
                       if mm > 0]
            rows.append(list(ids) + [eos])
        width = max(len(r) for r in rows)
        did = np.full((len(rows), width), eos, np.int32)
        for j, r in enumerate(rows):
            did[j, :len(r)] = r
        return did, np.asarray([len(r) for r in rows], np.int32)  # hostsync: ok drafts are host-resident cached-response ids

    def _bill_spec_stats(self):
        """Fold the small generator's last speculative call into stats."""
        st = getattr(self.small, "last_spec_stats", None)
        if st:
            self.stats.proposed += st["proposed"]
            self.stats.accepted += st["accepted"]
            self.stats.spec_steps += st["spec_steps"]

    def _emit_tweak_rows(self, rows, ids, out, lengths, ended, responses,
                         gen_tokens):
        """Decode generated rows back into their batch positions + billing."""
        lengths = lengths.tolist()
        ended = ended.tolist()
        for j, row in enumerate(rows):
            i = ids[row]
            n_gen = lengths[j]
            responses[i] = self.tok.decode_ids(
                self._visible_ids(out[j], n_gen, ended[j]))
            self.stats.small_tokens += n_gen
            self.stats.tweak += 1
            gen_tokens[i] = n_gen

    def _run_tweak_full(self, new_qs, cqs, crs, ids, responses,
                        max_new_tokens, gen_tokens, prompt_tokens,
                        drafts=None):
        """Fallback: prefill the whole Appendix-A prompt (no prefix reuse)."""
        toks, mask = tweak_lib.build_tweak_batch(
            self.tok, new_qs, cqs, crs, self._tweak_encode_len(max_new_tokens))
        real_lens = mask.sum(axis=1).astype(np.int64).tolist()
        toks, mask, b = pad_to_buckets(toks, mask)
        kw = {}
        if drafts is not None:
            # bucket padding added rows: give them empty drafts
            did, dlen = drafts
            pad = toks.shape[0] - did.shape[0]
            if pad:
                did = np.concatenate(
                    [did, np.zeros((pad, did.shape[1]), did.dtype)])
                dlen = np.concatenate([dlen, np.zeros((pad,), dlen.dtype)])
            kw["drafts"] = (did, dlen)
        out, lengths, ended = self.small.generate_with_lengths(
            {"tokens": jnp.asarray(toks)}, max_new_tokens=max_new_tokens,
            seed=self._next_seed(), **kw)
        if drafts is not None:
            self._bill_spec_stats()
        self._emit_tweak_rows(range(len(ids)), ids, out, lengths, ended,
                              responses, gen_tokens)
        for j, i in enumerate(ids):
            prompt_tokens[i] = real_lens[j]
            self.stats.small_prompt_tokens += real_lens[j]

    def _run_tweak_prefixed(self, new_qs, cqs, crs, ids, responses,
                            max_new_tokens, suffix_budget, gen_tokens,
                            prompt_tokens, drafts=None):
        """Hot path: shared-prefix KV reuse + length-bucketed suffixes.

        Each row prefills only its variable suffix over the cached
        instruction-prefix KV, and rows are grouped by ``bucket_len`` of
        their REAL suffix length instead of all padding to the worst-case
        tweak budget — short cached responses stop paying attention FLOPs
        for the full ``_tweak_encode_len`` bucket (DESIGN.md §9).
        """
        prefix_ids = self._tweak_prefix_ids()
        toks, mask = tweak_lib.build_tweak_suffix_batch(
            self.tok, new_qs, cqs, crs, suffix_budget)
        real_lens = mask.sum(axis=1).astype(np.int64).tolist()
        groups: Dict[int, List[int]] = {}
        for row, rl in enumerate(real_lens):
            groups.setdefault(bucket_len(max(rl, 1)), []).append(row)
        for bucket in sorted(groups):
            rows = groups[bucket]
            sub_t = toks[rows][:, :bucket]
            sub_m = mask[rows][:, :bucket]
            sub_t = pad_to_buckets(sub_t, sub_m)[0]
            pc = self._small_prefix_cache(sub_t.shape[0])
            kw = {}
            if drafts is not None:
                did, dlen = drafts
                sub_d, sub_l = did[rows], dlen[rows]
                pad = sub_t.shape[0] - sub_d.shape[0]
                if pad:
                    sub_d = np.concatenate(
                        [sub_d, np.zeros((pad, sub_d.shape[1]), sub_d.dtype)])
                    sub_l = np.concatenate(
                        [sub_l, np.zeros((pad,), sub_l.dtype)])
                kw["drafts"] = (sub_d, sub_l)
            out, lengths, ended = self.small.generate_with_lengths(
                {"tokens": jnp.asarray(sub_t)},
                max_new_tokens=max_new_tokens, seed=self._next_seed(),
                prefix_cache=pc, **kw)
            if drafts is not None:
                self._bill_spec_stats()
            self._emit_tweak_rows(rows, ids, out, lengths, ended,
                                  responses, gen_tokens)
            for row in rows:
                i = ids[row]
                real = len(prefix_ids) + real_lens[row]
                prompt_tokens[i] = real
                self.stats.small_prompt_tokens += real

    def _insert_entries(self, texts, resp_tokens, resp_texts, embs):
        """Commit entries to the cache in ONE jitted device call.

        texts/resp_texts: host strings; resp_tokens: per-row visible ids;
        embs (n, D) on device.  Pads to the batch bucket so compiles stay
        bounded; the single ``slots`` pull is the only host sync.
        """
        n = len(texts)
        ccfg = self.cache_cfg
        qt, qm = self.tok.encode_batch(texts, ccfg.max_query_tokens)
        rt = np.zeros((n, ccfg.max_response_tokens), np.int32)
        rm = np.zeros((n, ccfg.max_response_tokens), np.float32)
        for j, ids in enumerate(resp_tokens):
            rl = min(len(ids), ccfg.max_response_tokens)
            rt[j, :rl] = ids[:rl]
            rm[j, :rl] = 1.0
        nb = bucket_batch(n)
        pad = lambda a: np.concatenate(
            [a, np.zeros((nb - n,) + a.shape[1:], a.dtype)]) if nb > n else a
        embs = jnp.concatenate(
            [embs, jnp.zeros((nb - n, embs.shape[1]), embs.dtype)]) \
            if nb > n else embs
        # the traced `count` scalar is device_put explicitly — passing the
        # bare python int would transfer it implicitly at every dispatch
        slots = self.bank.insert_batch(
            embs, jnp.asarray(pad(qt)), jnp.asarray(pad(qm)),
            jnp.asarray(pad(rt)), jnp.asarray(pad(rm)),
            jax.device_put(np.int32(n)))
        # single device->host sync per insert batch
        slots = jax.device_get(slots).tolist()  # hostsync: ok the one per-insert sync
        for j in range(n):
            self._text_store[slots[j]] = (texts[j], resp_texts[j])
            self.bank.draft_store[slots[j]] = list(resp_tokens[j])
        # IVF maintenance: k-means recluster when enough writes piled up
        # (or the member table overflowed).  No-op for flat caches.
        self.bank.maybe_reindex()

    def _run_miss(self, queries, ids, embs, responses, max_new_tokens,
                  gen_tokens, prompt_tokens, admit=None):
        texts = [queries[i] for i in ids]
        toks, mask = self.tok.encode_batch(texts, self.max_query_len)
        real_lens = mask.sum(axis=1).astype(np.int64).tolist()
        toks, mask, b = pad_to_buckets(toks, mask)
        out, lengths, ended = self.big.generate_with_lengths(
            {"tokens": jnp.asarray(toks)}, max_new_tokens=max_new_tokens,
            seed=self._next_seed())
        lengths = lengths.tolist()
        ended = ended.tolist()
        resp_tokens, resp_texts = [], []
        for j, i in enumerate(ids):
            n_gen = lengths[j]
            visible = self._visible_ids(out[j], n_gen, ended[j])
            resp_text = self.tok.decode_ids(visible)
            responses[i] = resp_text
            resp_tokens.append(visible)
            resp_texts.append(resp_text)
            self.stats.big_tokens += n_gen
            self.stats.big_prompt_tokens += real_lens[j]
            self.stats.miss += 1
            gen_tokens[i] = n_gen
            prompt_tokens[i] = real_lens[j]
        # admission control (DESIGN.md §13): the response is still served,
        # but clusters the hit EMA has shut don't pollute the cache
        keep = list(range(len(ids))) if admit is None else \
            [j for j, i in enumerate(ids) if bool(admit[i])]  # hostsync: ok numpy after the batch sync
        self.stats.suppressed_inserts += len(ids) - len(keep)
        if not keep:
            return
        kept_ids = np.asarray([ids[j] for j in keep])  # hostsync: ok host list of slot ids
        # explicit device_put of the row indices: a host-array gather
        # would move them implicitly (transfer-guard unsafe)
        with trace.span("engine.insert", rows=len(keep)):
            self._insert_entries([texts[j] for j in keep],
                                 [resp_tokens[j] for j in keep],
                                 [resp_texts[j] for j in keep],
                                 jnp.take(embs, jax.device_put(kept_ids),
                                          axis=0))

    # ------------------------------------------------- offline population
    def populate(self, queries: List[str], responses: List[str]):
        """Bulk-insert known (query, response) pairs (dataset simulation)."""
        if len(queries) != len(responses):
            raise ValueError(f"populate got {len(queries)} queries but "
                             f"{len(responses)} responses")
        if not queries:
            return
        queries = [tweak_lib.preprocess_query(q) for q in queries]
        embs = self.embed_texts(queries)
        rt, rm = self.tok.encode_batch(responses, self.cache_cfg.max_response_tokens,
                                       add_bos=False)
        rt_l, rm_l = rt.tolist(), rm.tolist()
        resp_tokens = [[t for t, m in zip(rt_l[i], rm_l[i]) if m > 0]
                       for i in range(len(queries))]
        self._insert_entries(queries, resp_tokens, responses, embs)


class ReplicaGroup:
    """N engine replicas over shared (or deliberately private) cache banks.

    The replica topology (DESIGN.md §12): model weights are per-replica
    handles (replicated params, or TP-sharded via launch/sharding.py param
    specs — the Generator objects may even be shared when the caller wants
    one set of compiled functions), while the cache bank is ONE
    :class:`SharedCacheBank` serving every replica.  ``shared=False``
    builds a private bank per replica instead — the degraded baseline the
    replica bench compares against (hit rate then converges per replica
    stream, not per aggregate stream).
    """

    def __init__(self, engines: List[TweakLLMEngine]):
        if not engines:
            raise ValueError("ReplicaGroup needs at least one engine")
        self.engines = list(engines)

    @classmethod
    def build(cls, n: int, *, tokenizer, embedder_params, embedder_cfg,
              big, small, cache_cfg: cache_lib.CacheConfig,
              router_cfg: Optional[router_lib.RouterConfig] = None,
              shared: bool = True, mesh=None, axis: str = "data",
              reranker=None, **engine_kw) -> "ReplicaGroup":
        """Builds ``n`` replicas.  ``big``/``small`` are Generators shared
        by every replica, or callables ``replica_id -> Generator`` for
        per-replica handles (distinct KV pools)."""
        bank = (SharedCacheBank(cache_cfg, router_cfg, mesh=mesh, axis=axis,
                                reranker=reranker)
                if shared else None)
        engines = []
        for rid in range(n):
            engines.append(TweakLLMEngine(
                tokenizer=tokenizer, embedder_params=embedder_params,
                embedder_cfg=embedder_cfg,
                big=big(rid) if callable(big) else big,
                small=small(rid) if callable(small) else small,
                bank=bank if shared else SharedCacheBank(
                    cache_cfg, router_cfg, mesh=mesh, axis=axis,
                    reranker=reranker),
                replica_id=rid, **engine_kw))
        return cls(engines)

    def __len__(self) -> int:
        return len(self.engines)

    def __getitem__(self, rid: int) -> TweakLLMEngine:
        return self.engines[rid]

    @property
    def shared(self) -> bool:
        return all(e.bank is self.engines[0].bank for e in self.engines)

    @property
    def bank(self) -> SharedCacheBank:
        if not self.shared:
            raise ValueError("replicas hold private banks; no single bank")
        return self.engines[0].bank

    @property
    def stats(self) -> EngineStats:
        """Aggregate serve counters across every replica."""
        return EngineStats.aggregate(e.stats for e in self.engines)

    def leaked_kv_pages(self) -> List[int]:
        """Per-replica leaked (live minus pinned) KV pages, paged pools
        only — every entry must be 0 once all work is harvested."""
        from repro.serving.continuous import leaked_pages
        return [leaked_pages(e.big, e.small) for e in self.engines]
