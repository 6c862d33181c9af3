"""Pure-jnp oracle for the cosine top-k cache-lookup kernel."""
from __future__ import annotations

import jax.numpy as jnp
import jax

# Scores at float32 precision on every backend.  On a TPU the default
# runs one bf16 MXU pass, which scores a stored row against its own
# verbatim resend ~1e-3 below 1.0: under the router's 0.9999 EXACT
# threshold, so EXACT would never fire there.
HIGHEST = jax.lax.Precision.HIGHEST


def cosine_topk_ref(queries, db, k: int, valid=None):
    """queries: (B, D) unit vectors; db: (N, D) unit vectors.

    Returns (scores (B, k) f32 desc-sorted, indices (B, k) i32).
    ``valid``: optional (N,) bool; invalid entries score -inf.
    """
    scores = jnp.einsum("bd,nd->bn", queries.astype(jnp.float32),
                        db.astype(jnp.float32), precision=HIGHEST)
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)


def cosine_topk_gather_ref(queries, cand_emb, cand_idx, cand_valid, k: int):
    """Shortlist variant: score per-query candidate sets (the IVF probe).

    queries (B, D); cand_emb (B, M, D) pre-gathered candidate rows;
    cand_idx (B, M) i32 global row ids (-1 for padding); cand_valid (B, M)
    bool.  Returns (scores (B, k) f32 desc-sorted, indices (B, k) i32);
    slots with no live candidate score -inf with index -1.
    """
    scores = jnp.einsum("bd,bmd->bm", queries.astype(jnp.float32),
                        cand_emb.astype(jnp.float32), precision=HIGHEST)
    scores = jnp.where(cand_valid, scores, -jnp.inf)
    top_s, pos = jax.lax.top_k(scores, k)
    top_i = jnp.take_along_axis(cand_idx, pos, axis=1).astype(jnp.int32)
    return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)
