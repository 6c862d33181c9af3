"""Jit'd public wrapper for the cosine top-k lookup.

``impl="pallas"`` runs the Pallas kernel (interpret mode off a TPU).
``impl="xla"`` scores the whole bank with the reference's float32
``HIGHEST`` einsum and selects with :func:`exact_top_k`: the same result
as ``cosine_topk_ref``, bit for bit, without the full sort of every
score row that XLA lowers one wide ``lax.top_k`` to on a TPU.  A bank of
N >= ``CHUNKED_MIN_N`` (16,384) rows is cut into chunks of
:func:`chunk_width` ``(N)`` rows (``sqrt(N)`` rounded to a power of two,
so at least 128); the top-k runs over the k chunks with the best
maxima.  Under 16,384 rows, or under 4k chunks, one plain ``lax.top_k``
selects.  This is the op the semantic cache calls;
``repro.core.distributed`` shards it with shard_map.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .kernel import cosine_topk_gather_pallas, cosine_topk_pallas
from .ref import HIGHEST, cosine_topk_gather_ref

# Rows narrower than this keep one plain lax.top_k: its sort is cheap.
CHUNKED_MIN_N = 16_384


def chunk_width(n: int) -> int:
    """Chunk width for an ``n``-column row: ``sqrt(n)`` rounded to a
    power of two (1,024 for 1,048,576 columns; at least 128 from
    ``CHUNKED_MIN_N`` up)."""
    return 1 << round(math.log2(n) / 2)


def _order_key(x):
    """float32 -> int32 key whose integer order is the total order that
    ``lax.top_k`` sorts by (-0.0 below +0.0, NaNs at the ends)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def chunked_top_k(scores, k: int, chunk: int):
    """Exact ``lax.top_k(scores, k)`` over the last axis of (B, N) scores,
    by chunks of ``chunk`` columns (at least ``k`` chunks).

    Entries are ordered by (score descending, column ascending), as
    ``lax.top_k`` orders them.  A chunk's rank is that of its best entry:
    (chunk max descending, chunk id ascending), since chunks are
    contiguous.  Each of the true top k lies in a chunk ranked among the
    top k chunks, so a top-k over those chunks' columns, kept in ascending
    column order so that ties break as before, is the full top-k.
    """
    b, n = scores.shape
    nc = -(-n // chunk)
    if nc * chunk != n:   # -inf after every real column
        scores = jnp.pad(scores, ((0, 0), (0, nc * chunk - n)),
                         constant_values=-jnp.inf)
    blocks = scores.reshape(b, nc, chunk)
    _, cid = jax.lax.top_k(_order_key(blocks).max(axis=2), k)
    cid = jnp.sort(cid, axis=1)
    cand = jnp.take_along_axis(blocks, cid[:, :, None], axis=1)
    top_s, pos = jax.lax.top_k(cand.reshape(b, k * chunk), k)
    rows = jnp.take_along_axis(cid, pos // chunk, axis=1) * chunk
    return top_s, rows + pos % chunk


def exact_top_k(scores, k: int):
    """``lax.top_k`` over the last axis of (B, N) scores, by
    :func:`chunked_top_k` at :func:`chunk_width` ``(N)`` where N is at
    least ``CHUNKED_MIN_N`` and spans at least 4k chunks; one plain
    ``lax.top_k`` otherwise."""
    n = scores.shape[-1]
    chunk = chunk_width(n)
    if n < CHUNKED_MIN_N or n // chunk < 4 * k:
        return jax.lax.top_k(scores, k)
    return chunked_top_k(scores, k, chunk)


@functools.partial(jax.jit, static_argnames=("k", "impl", "block_n"))
def cosine_topk(queries, db, valid=None, *, k: int = 4, impl: str = "xla",
                block_n: int = 1024):
    """queries (B,D) x db (N,D) -> (scores (B,k), indices (B,k)).

    ``valid``: optional (N,) bool; invalid rows score -inf.
    """
    if impl == "pallas":
        s, i = cosine_topk_pallas(queries, db, k, valid, block_n=block_n,
                                  interpret=jax.default_backend() != "tpu")
        # kernel reports NEG for sub-k matches; normalize to -inf like ref
        return jnp.where(i >= 0, s, -jnp.inf), i
    scores = jnp.einsum("bd,nd->bn", queries.astype(jnp.float32),
                        db.astype(jnp.float32), precision=HIGHEST)
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    top_s, top_i = exact_top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "impl", "block_m"))
def cosine_topk_gather(queries, db, cand_idx, cand_valid, *, k: int = 4,
                       impl: str = "xla", block_m: int = 256):
    """Gather-then-scan: score only a per-query shortlist of db rows.

    queries (B,D) x db (N,D), cand_idx (B,M) i32 row ids (-1 = padding),
    cand_valid (B,M) bool -> (scores (B,k), indices (B,k) GLOBAL rows).
    The shortlist gather runs in XLA (one (B,M,D) take); scoring + top-k
    dispatch to the Pallas tile kernel on TPU or the jnp oracle elsewhere.
    """
    b, m = cand_idx.shape
    cand_valid = cand_valid & (cand_idx >= 0)
    cand_emb = jnp.take(db, jnp.clip(cand_idx, 0, None), axis=0)  # (B,M,D)
    if impl == "pallas":
        block_m = min(block_m, m)
        pad = (-m) % block_m
        if pad:
            zcol = jnp.zeros((b, pad), jnp.int32)
            cand_idx = jnp.concatenate([cand_idx, zcol - 1], axis=1)
            cand_valid = jnp.concatenate(
                [cand_valid, jnp.zeros((b, pad), bool)], axis=1)
            cand_emb = jnp.concatenate(
                [cand_emb, jnp.zeros((b, pad, db.shape[1]), cand_emb.dtype)],
                axis=1)
        s, i = cosine_topk_gather_pallas(
            queries, cand_emb, cand_idx, cand_valid, k, block_m=block_m,
            interpret=jax.default_backend() != "tpu")
        return jnp.where(i >= 0, s, -jnp.inf), i
    return cosine_topk_gather_ref(queries, cand_emb, cand_idx, cand_valid, k)
