"""Pallas TPU kernel: tiled cosine-similarity scan with running top-k.

The semantic-cache lookup hot loop.  The (N, D) embedding shard streams
through VMEM in (block_n, D) tiles; each tile's (B, block_n) score panel is
one MXU matmul; a per-query running top-k lives in VMEM scratch across the
sequential grid.  Top-k update is k rounds of masked max (k is small — a
sort network is a poor fit for the VPU).

Grid: (N // block_n,) — sequential on TPU, so scratch persists across steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # python float: jnp constants get captured as kernel consts


def _kernel(q_ref, db_ref, valid_ref, out_s_ref, out_i_ref,
            run_s, run_i, *, k: int, block_n: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, NEG)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)              # (B, D)
    db = db_ref[...].astype(jnp.float32)            # (block_n, D)
    # float32 passes on the MXU: a bf16 pass scores a row against itself
    # ~1e-3 off 1.0, below the router's EXACT threshold
    scores = jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (B, block_n)
    base = step * block_n
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    scores = jnp.where(valid_ref[...][None, :] != 0, scores, NEG)

    rs, ri = _topk_merge(run_s[...], run_i[...], scores, col, k)
    run_s[...] = rs
    run_i[...] = ri

    @pl.when(step == pl.num_programs(0) - 1)
    def _final():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def _topk_merge(rs, ri, s, idx, k: int):
    """Fold a (B, m) score/index tile into the (B, k) running top-k.

    k rounds of masked max; the loser of each slot comparison is
    re-injected into the pool to compete for the next slot (VPU-friendly:
    no gather, no sort network).  Slot j of the running top-k is written
    through a column mask, and the winner is the lowest lane holding the
    max (a min over a masked iota): Mosaic lowers neither a
    dynamic_update_slice nor an argmax.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, rs.shape, 1)
    m = s.shape[1]
    for j in range(k):
        best = jnp.max(s, axis=1, keepdims=True)                    # (B,1)
        bidx = jnp.min(jnp.where(s == best, lane, m), axis=1, keepdims=True)
        consumed = lane == bidx
        bcol = jnp.sum(jnp.where(consumed, idx, 0), axis=1, keepdims=True)
        slot_s = rs[:, j:j + 1]
        slot_i = ri[:, j:j + 1]
        take_new = best > slot_s
        at_j = col == j
        rs = jnp.where(at_j, jnp.where(take_new, best, slot_s), rs)
        ri = jnp.where(at_j, jnp.where(take_new, bcol, slot_i), ri)
        s = jnp.where(consumed & take_new, jnp.broadcast_to(slot_s, s.shape), s)
        idx = jnp.where(consumed & take_new, jnp.broadcast_to(slot_i, idx.shape), idx)
    return rs, ri


def _gather_kernel(q_ref, cand_ref, idx_ref, valid_ref, out_s_ref, out_i_ref,
                   run_s, run_i, *, k: int, block_m: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, NEG)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)               # (B, D)
    cand = cand_ref[...].astype(jnp.float32)         # (B, block_m, D)
    # per-query candidate sets: one (1, D) x (block_m, D) matvec per
    # query, as a VPU multiply and a lane reduce (Mosaic has no batched
    # dot with a (B, D) operand)
    scores = jnp.sum(q[:, None, :] * cand, axis=2)   # (B, block_m)
    scores = jnp.where(valid_ref[...] != 0, scores, NEG)
    idx = idx_ref[...]                                # (B, block_m)

    rs, ri = _topk_merge(run_s[...], run_i[...], scores, idx, k)
    run_s[...] = rs
    run_i[...] = ri

    @pl.when(step == pl.num_programs(0) - 1)
    def _final():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def cosine_topk_gather_pallas(queries, cand_emb, cand_idx, cand_valid, k: int,
                              *, block_m: int = 256, interpret: bool = True):
    """Shortlist scan: queries (B, D) x cand_emb (B, M, D) -> top-k.

    The IVF probe path — the (B, M, D) candidate tensor (gathered by XLA
    outside the kernel) streams through VMEM in (B, block_m, D) tiles;
    indices come from ``cand_idx`` instead of a column iota, so the kernel
    reports GLOBAL bank rows.  Padding/stale candidates (``cand_valid``
    false) score NEG and never surface.
    """
    b, m, d = cand_emb.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, f"M={m} not divisible by block_m={block_m}"
    grid = (m // block_m,)
    out_s, out_i = pl.pallas_call(
        functools.partial(_gather_kernel, k=k, block_m=block_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((b, block_m, d), lambda i: (0, i, 0)),
            pl.BlockSpec((b, block_m), lambda i: (0, i)),
            pl.BlockSpec((b, block_m), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),
            pl.BlockSpec((b, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, cand_emb, cand_idx, cand_valid.astype(jnp.int32))
    return out_s, out_i


def cosine_topk_pallas(queries, db, k: int, valid=None, *,
                       block_n: int = 1024, interpret: bool = True):
    b, d = queries.shape
    n = db.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0, f"N={n} not divisible by block_n={block_n}"
    if valid is None:
        valid = jnp.ones((n,), jnp.int32)
    else:
        valid = valid.astype(jnp.int32)
    grid = (n // block_n,)
    out_s, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),
            pl.BlockSpec((b, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, db, valid)
    return out_s, out_i
