"""Central registry of jitted entry points and jit-hot modules (DESIGN.md §10).

Every ``jax.jit`` call site in ``src/repro`` MUST appear here with its
declared donation and static-argument policy.  The AST lint
(``repro.analysis.lint``) cross-checks this table against the real call
sites: an unregistered jit, a policy drift (donation silently dropped,
static argnames changed), or a stale entry each fails ``make analyze``.
The contract checker (``repro.analysis.contracts``) uses the same table
to know which hot paths to trace against their bucket sets.

Why a registry instead of grepping?  Donation and static-argnum choices
are *load-bearing* serving invariants (PRs 1, 4, 5): dropping
``donate_argnums=(0,)`` from the cache write path doubles peak memory and
adds a copy per serve batch; losing a ``static_argnames`` entry turns a
bounded compile-bucket family into a per-value retrace.  Declaring the
policy next to a prose note makes every future refactor diff the *intent*
alongside the code.

Conventions
-----------
* ``file`` is the path relative to ``src/repro`` (posix separators).
* ``qualname`` is the enclosing scope chain at the call site
  (``Class.method`` / ``outer_fn.inner_fn``); for a decorated function it
  is the decorated function's own qualified name.  Several sites in one
  qualname are declared in SOURCE ORDER.
* ``donate`` / ``static`` declare the expected literal value of
  ``donate_argnums`` / ``static_argnums``+``static_argnames`` at the
  site; ``None`` means the site computes the policy dynamically (the
  note must say why) and the lint only checks the site is named here.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

# --------------------------------------------------------------- hot set
# Modules whose code runs on (or orchestrates) the serve hot path.  The
# hostsync lint rules (HS1xx) apply only inside these: a stray `.item()`,
# `int()` on a device value, or `np.asarray` here is a per-request
# host<->device round-trip that silently defeats the O(1)-syncs-per-batch
# design (DESIGN.md §5).  Entries ending in "/" are directory prefixes.
HOT_MODULES: Tuple[str, ...] = (
    "core/cache.py",
    "core/router.py",
    "core/index.py",
    "core/engine.py",
    "core/distributed.py",
    "serving/generate.py",
    "serving/scheduler.py",
    "serving/paged_kv.py",
    "serving/continuous.py",
    "models/",
    "kernels/",
)


def is_hot(rel: str) -> bool:
    """Is ``rel`` (path relative to src/repro) a jit-hot module?"""
    rel = rel.replace("\\", "/")
    for m in HOT_MODULES:
        if m.endswith("/"):
            if rel.startswith(m):
                return True
        elif rel == m:
            return True
    return False


# ------------------------------------------------------------- jit sites
Argnums = Optional[Tuple[Union[int, str], ...]]


@dataclasses.dataclass(frozen=True)
class JitSite:
    """One declared ``jax.jit`` call site and its compilation policy."""
    file: str            # path relative to src/repro
    qualname: str        # enclosing scope chain ("<module>" for top level)
    donate: Argnums = () # expected donate_argnums; None = dynamic (see note)
    static: Argnums = () # expected static_argnums + static_argnames
    note: str = ""       # why this policy — shown in lint failures


JIT_REGISTRY: Tuple[JitSite, ...] = (
    # ---- core: the serve hot path -----------------------------------
    JitSite("core/cache.py", "make_insert_batch", donate=None,
            note="miss-batch commit; donates the cache state for in-place "
                 "update (DESIGN.md §5) unless the caller opts out "
                 "(contract tests build the no-donate variant on purpose)"),
    JitSite("core/cache.py", "make_second_stage", donate=None,
            note="cascade stage 2 (DESIGN.md §13): reranker shortlist "
                 "scoring + uncertain-row resolution; donates state for "
                 "in-place touch/admission updates unless the caller opts "
                 "out (byte-identity tests keep the pre-state alive)"),
    JitSite("core/engine.py", "TweakLLMEngine.__init__",
            note="embedder encode; params/tokens are read-only"),
    JitSite("core/engine.py", "SharedCacheBank.__init__", donate=(0,),
            note="fused lookup+route+touch on the shared bank; donates "
                 "cache state so hit accounting happens in place "
                 "(DESIGN.md §5/§12)"),
    JitSite("core/baseline.py", "GPTCacheBaseline.__init__",
            note="baseline embedder encode"),
    JitSite("core/baseline.py", "GPTCacheBaseline.__init__",
            note="baseline flat lookup (no touch fusion — GPTCache "
                 "semantics keep lookup read-only)"),
    JitSite("core/baseline.py", "GPTCacheBaseline.__init__",
            note="optional cross-encoder rerank of the shortlist"),
    JitSite("core/index.py", "_spherical_kmeans",
            note="maintenance path: k-means assignment GEMM, host-driven"),
    JitSite("core/index.py", "build_index",
            note="maintenance path: bank-to-centroid similarity GEMM"),
    JitSite("core/distributed.py", "make_distributed_lookup.lookup",
            note="shard_map flat lookup; state rows sharded, queries "
                 "replicated, read-only"),
    JitSite("core/distributed.py", "make_distributed_ivf_lookup.lookup",
            note="shard_map IVF lookup; read-only"),
    JitSite("core/distributed.py", "make_distributed_insert.insert",
            note="single-entry sharded insert (reference path, no "
                 "donation: keeps the differential oracle's inputs alive)"),
    JitSite("core/distributed.py",
            "make_distributed_lookup_and_touch.lookup_touch", donate=(0,),
            note="sharded fused lookup+route+touch: per-shard scan + "
                 "winner merge + replicated-index scatter on the sharded "
                 "recency arrays, one device call per serve batch "
                 "(DESIGN.md §12)"),
    JitSite("core/distributed.py", "make_distributed_insert_batch.insert_batch",
            donate=(0,),
            note="sharded miss-batch commit; donates state like the local "
                 "insert_batch"),
    # ---- serving: prefill + fused decode ----------------------------
    JitSite("serving/generate.py", "Generator.__init__._prefill",
            static=("capacity",),
            note="KV capacity fixes the cache allocation; one compile per "
                 "(batch, prompt, capacity) bucket"),
    JitSite("serving/generate.py", "Generator.__init__._prefill_with_prefix",
            static=("capacity",),
            note="suffix prefill over the shared prefix KV (DESIGN.md §9)"),
    JitSite("serving/generate.py", "Generator.__init__._prefill_prefix",
            note="one-time shared-prefix KV build per (model, batch bucket)"),
    JitSite("serving/generate.py", "Generator.__init__._step",
            note="host-loop decode step — the differential oracle "
                 "(DESIGN.md §8); caches threaded functionally, not donated, "
                 "so the oracle can re-run a step"),
    JitSite("serving/generate.py", "Generator.__init__._decode_fused",
            static=("mnt",),
            note="whole decode loop in one device call; mnt bounds the "
                 "while_loop trip count and the output block shape"),
    JitSite("serving/generate.py", "Generator.__init__._decode_fused_spec",
            static=("mnt", "k"),
            note="draft-verify speculative decode (DESIGN.md §14): verify "
                 "phase + per-row fallback phase in one device call; mnt "
                 "and the verify block width k fix every carried shape; "
                 "greedy-only so no PRNG key is carried"),
    # ---- serving: paged KV pool + persistent decode session ---------
    JitSite("serving/paged_kv.py", "pack_caches", donate=(0,),
            note="dense prefill KV -> pool pages; donates the pool storage "
                 "so page writes alias in place (DESIGN.md §11); pinned "
                 "block-table entries are redirected to the TRASH page"),
    JitSite("serving/paged_kv.py", "write_pinned", donate=(0,),
            note="one-time shared-prefix pin into reserved pages; donates "
                 "pool storage like pack_caches"),
    JitSite("serving/continuous.py", "DecodeSession._build_ops._admit",
            donate=(0,),
            note="splice a prefilled cohort into free slots; donates the "
                 "session state (the pool lives inside it) so the splice "
                 "is a true in-place join (DESIGN.md §11)"),
    JitSite("serving/continuous.py", "DecodeSession._build_ops._chunk",
            donate=(1,), static=("steps",),
            note="up to `steps` decode steps in one device call; steps "
                 "bounds the while_loop and is a small bucket set "
                 "(chunk size), state donated like the fused decode loop"),
    JitSite("serving/continuous.py", "DecodeSession._build_ops._step_once",
            donate=(1,),
            note="single decode step — the host-stepped differential "
                 "oracle for the chunked loop (DESIGN.md §8/§11)"),
    JitSite("serving/continuous.py", "DecodeSession._build_ops._evict",
            donate=(0,),
            note="clear harvested slots: block tables -> TRASH page in "
                 "place so freed pages can be re-issued safely"),
    # ---- kernels: jit'd public wrappers -----------------------------
    JitSite("kernels/cosine_topk/ops.py", "cosine_topk",
            static=("k", "impl", "block_n"),
            note="kernel meta-params select the Pallas/XLA lowering"),
    JitSite("kernels/cosine_topk/ops.py", "cosine_topk_gather",
            static=("k", "impl", "block_m"),
            note="gathered-shortlist variant for the IVF probe"),
    JitSite("kernels/decode_attention/ops.py", "decode_attention",
            static=("block_t", "impl"),
            note="decode attention over the KV cache"),
    JitSite("kernels/decode_attention/ops.py", "decode_attention_block",
            static=("block_t", "impl"),
            note="speculative verify q-block (DESIGN.md §14): K draft "
                 "queries per row in one pass with in-block causal masking"),
    JitSite("kernels/paged_attention/ops.py", "paged_decode_attention",
            static=("impl",),
            note="decode attention gathered through the page block table "
                 "(DESIGN.md §11)"),
    JitSite("kernels/paged_attention/ops.py", "paged_decode_attention_block",
            static=("impl",),
            note="speculative verify q-block over the page pool "
                 "(DESIGN.md §14); per-query causality via slot positions"),
    JitSite("kernels/flash_attention/ops.py", "flash_attention",
            static=("causal", "window", "block_q", "block_k", "impl"),
            note="prefill flash attention; window/causal change the "
                 "lowered kernel"),
    # ---- analyzer self-probes ---------------------------------------
    JitSite("analysis/contracts.py", "contract_lookup_and_touch",
            donate=(0,),
            note="contract probe: mirrors the engine's fused lookup jit "
                 "policy so donation is checked exactly as deployed"),
    JitSite("analysis/contracts.py", "contract_ivf_lookup",
            note="contract probe: read-only IVF lookup"),
    # ---- offline / maintenance / tooling ----------------------------
    JitSite("eval/judge.py", "make_loglik_scorer._score",
            note="eval-only loglik scorer"),
    JitSite("training/embedder_train.py", "train_embedder.step",
            note="contrastive embedder training step (offline)"),
    JitSite("training/reranker_train.py", "train_reranker.step",
            note="cross-encoder reranker training step (offline; feeds "
                 "the cascade's second stage, DESIGN.md §13)"),
    JitSite("launch/train.py", "main",
            note="CLI training step; params/opt threaded functionally"),
    JitSite("launch/serve.py", "build_stack",
            note="one-time seeded init of a full-width model, compiled so "
                 "weights are made on the device in their own dtype"),
    JitSite("launch/dryrun.py", "run_one", donate=None, static=None,
            note="train-step lowering probe; donation gated on --donate "
                 "to measure aliasing impact, shardings vary per arch"),
    JitSite("launch/dryrun.py", "run_one", donate=None, static=None,
            note="prefill lowering probe (no donation: cache is an output)"),
    JitSite("launch/dryrun.py", "run_one", donate=None, static=None,
            note="decode lowering probe; cache donation gated on --donate"),
)


def sites_for(rel: str, qualname: str) -> Tuple[JitSite, ...]:
    """Declared sites for one (file, qualname), in declaration order."""
    rel = rel.replace("\\", "/")
    return tuple(s for s in JIT_REGISTRY
                 if s.file == rel and s.qualname == qualname)


def registered_files() -> Tuple[str, ...]:
    return tuple(sorted({s.file for s in JIT_REGISTRY}))
