"""Pallas TPU paged decode attention: one query token vs block-table KV.

Same flash-decoding recurrence as ``kernels/decode_attention`` — running
max/sum-exp over KV tiles, all q-heads of a KV group as one (g, dh)
panel — but the KV tile for grid step (row b, logical page j) is DMA'd
straight from physical page ``block_tbl[b, j]`` of the shared pool.
The block table rides in as a SCALAR-PREFETCH argument
(``pltpu.PrefetchScalarGridSpec``): it is resident in SMEM before the
body runs, so the BlockSpec index_maps can compute each step's DMA
source from it — the gather never materialises the (B, cap) dense
cache, which is the entire point of paging (DESIGN.md §11).

Grid: (B * Hk, npg); the page axis is sequential, scratch persists.
Validity comes from ``slot_pos`` (B, npg*page): slots < 0 are masked —
that single mask covers empty slots, the sliced tail of the last page,
and rows parked on the TRASH page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(tbl_ref, q_ref, k_ref, v_ref, sp_ref, o_ref, m_scr, l_scr,
            acc_scr):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (g, dh)
    k = k_ref[0].astype(jnp.float32)            # (page, dh)
    v = v_ref[0].astype(jnp.float32)
    dh = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (dh ** -0.5)
    sp = sp_ref[0, pl.ds(j, 1), :]              # (1, page)
    s = jnp.where(sp >= 0, s, NEG)              # (g, page)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _kernel_block(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, g: int):
    """Q-block variant (DESIGN.md §14): the panel carries K*g rows — K
    speculative queries × g grouped heads.  Query i (panel rows i*g ..)
    sits at absolute position ``q_pos + i`` and masks keys by position:
    ``slot_pos <= q_pos + i`` — causality inside the block falls out of
    the same comparison that orders it against the cache."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (K*g, dh)
    k = k_ref[0].astype(jnp.float32)            # (page, dh)
    v = v_ref[0].astype(jnp.float32)
    dh = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (dh ** -0.5)
    sp = sp_ref[0, pl.ds(j, 1), :]              # (1, page)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
    qpos = qpos_ref[pl.program_id(0)]
    s = jnp.where((sp >= 0) & (sp <= qpos + row), s, NEG)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _head_lanes(pages):
    """(P+1, page, Hk, dh) -> (P+1, page, Hk*dh), a free reshape.

    A K/V block then selects one head as a lane-aligned (page, dh) tile;
    a (page, 1, dh) block of the 4-D pool breaks the TPU tiling rule (the
    second-minor block dim must divide by 8 or span the array).
    """
    n, page, hk, dh = pages.shape
    return pages.reshape(n, page, hk * dh)


def paged_decode_attention_block_pallas(q, kp, vp, block_tbl, slot_pos,
                                        q_pos, *, interpret: bool = True):
    """q: (B,K,H,dh); kp/vp: (P+1,page,Hk,dh); block_tbl: (B,npg) int32;
    slot_pos: (B,cap) int32 (-1 = invalid); q_pos: (B,) absolute position
    of each row's first query.  Returns (B,K,H,dh)."""
    b, kq, h, dh = q.shape
    page, hk = kp.shape[1], kp.shape[2]
    npg = block_tbl.shape[1]
    cap = slot_pos.shape[1]
    g = h // hk
    qt = jnp.moveaxis(q.reshape(b, kq, hk, g, dh), 2, 1).reshape(
        b * hk, kq * g, dh)
    sp = jnp.pad(slot_pos, ((0, 0), (0, npg * page - cap)),
                 constant_values=-1).reshape(b, npg, page)
    tbl = block_tbl.astype(jnp.int32)
    qpos = jnp.broadcast_to(q_pos[:, None], (b, hk)).reshape(
        b * hk).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + query positions
        grid=(b * hk, npg),
        in_specs=[
            pl.BlockSpec((1, kq * g, dh),
                         lambda bh, j, tbl, qpos: (bh, 0, 0)),
            pl.BlockSpec((1, page, dh),
                         lambda bh, j, tbl, qpos: (tbl[bh // hk, j], 0,
                                                   bh % hk)),
            pl.BlockSpec((1, page, dh),
                         lambda bh, j, tbl, qpos: (tbl[bh // hk, j], 0,
                                                   bh % hk)),
            pl.BlockSpec((1, npg, page),
                         lambda bh, j, tbl, qpos: (bh // hk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kq * g, dh),
                               lambda bh, j, tbl, qpos: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kq * g, 1), jnp.float32),
            pltpu.VMEM((kq * g, 1), jnp.float32),
            pltpu.VMEM((kq * g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_block, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hk, kq * g, dh), q.dtype),
        interpret=interpret,
    )(tbl, qpos, qt, _head_lanes(kp), _head_lanes(vp), sp)
    return jnp.moveaxis(out.reshape(b, hk, kq, g, dh), 1, 2).reshape(
        b, kq, h, dh)


def paged_decode_attention_pallas(q, kp, vp, block_tbl, slot_pos, *,
                                  interpret: bool = True):
    """q: (B,H,dh); kp/vp: (P+1,page,Hk,dh); block_tbl: (B,npg) int32;
    slot_pos: (B,cap) int32, -1 = invalid slot.  Returns (B,H,dh)."""
    b, h, dh = q.shape
    page, hk = kp.shape[1], kp.shape[2]
    npg = block_tbl.shape[1]
    cap = slot_pos.shape[1]
    g = h // hk
    qt = q.reshape(b, hk, g, dh).reshape(b * hk, g, dh)
    # Pad slot_pos out to whole pages with -1: the tail of the last page
    # beyond ``cap`` masks out exactly like an empty slot.
    sp = jnp.pad(slot_pos, ((0, 0), (0, npg * page - cap)),
                 constant_values=-1).reshape(b, npg, page)
    tbl = block_tbl.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # the block table, SMEM-resident
        grid=(b * hk, npg),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda bh, j, tbl: (bh, 0, 0)),
            # K/V tile: physical page tbl[row, j], lanes of this row's KV
            # head — the block table indirection happens HERE, in the DMA
            # source.
            pl.BlockSpec((1, page, dh),
                         lambda bh, j, tbl: (tbl[bh // hk, j], 0, bh % hk)),
            pl.BlockSpec((1, page, dh),
                         lambda bh, j, tbl: (tbl[bh // hk, j], 0, bh % hk)),
            # the row's whole slot_pos, fetched once per row: a (1, page)
            # block would break the (8, 128) tiling rule
            pl.BlockSpec((1, npg, page), lambda bh, j, tbl: (bh // hk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda bh, j, tbl: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hk, g, dh), q.dtype),
        interpret=interpret,
    )(tbl, qt, _head_lanes(kp), _head_lanes(vp), sp)
    return out.reshape(b, hk, g, dh).reshape(b, h, dh)
