"""Pallas TPU decode attention: one query token vs a long KV cache.

Memory-bound by design (the cache read IS the cost), so the kernel streams
(block_t, dh) KV tiles through VMEM with a running max/sum-exp — the
flash-decoding inner loop.  All q-heads of one KV group are processed
together as a (g, dh) panel per KV head: the KV tile is read ONCE per
group, not once per q-head — on GQA archs this divides HBM traffic by
H/Hk (e.g. 7x for deepseek-coder-33b).

Grid: (B * Hk, T/block_t), KV axis sequential, scratch persists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_t: int):
    t_step = pl.program_id(1)

    @pl.when(t_step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (g, dh)
    k = k_ref[0].astype(jnp.float32)          # (block_t, dh)
    v = v_ref[0].astype(jnp.float32)
    dh = q.shape[-1]
    cache_len = len_ref[pl.program_id(0)]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (dh ** -0.5)
    tp = t_step * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(tp < cache_len, s, NEG)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(t_step == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _kernel_block(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, block_t: int, g: int):
    """Q-block variant: the panel carries K*g rows — K speculative queries
    × g grouped heads — and each query masks its own causal limit
    ``cache_len + i`` (the block's keys are already in the cache at slots
    ``cache_len + i``, DESIGN.md §14).  Same flash recurrence otherwise.
    """
    t_step = pl.program_id(1)

    @pl.when(t_step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (K*g, dh)
    k = k_ref[0].astype(jnp.float32)          # (block_t, dh)
    v = v_ref[0].astype(jnp.float32)
    dh = q.shape[-1]
    cache_len = len_ref[pl.program_id(0)]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (dh ** -0.5)
    tp = t_step * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
    s = jnp.where(tp < cache_len + row + 1, s, NEG)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(t_step == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_block_pallas(q, k, v, cache_len, *, block_t: int = 1024,
                                  interpret: bool = True):
    """q: (B,K,H,dh); k/v: (B,T,Hk,dh); cache_len: (B,) pre-block slots.
    Returns (B,K,H,dh).  KV tiles are still read once per KV group — the
    K speculative queries ride in the same panel, so the HBM traffic of a
    verify step equals ONE decode step, the whole point of speculation."""
    b, kq, h, dh = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = h // hk
    block_t = min(block_t, t)
    pt = (-t) % block_t
    qt = jnp.moveaxis(q.reshape(b, kq, hk, g, dh), 2, 1).reshape(
        b * hk, kq * g, dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * hk, t, dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * hk, t, dh)
    if pt:
        kt = jnp.pad(kt, ((0, 0), (0, pt), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pt), (0, 0)))
    nt = (t + pt) // block_t
    grid = (b * hk, nt)
    lens = jnp.broadcast_to(cache_len[:, None], (b, hk)).reshape(
        b * hk).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # per-(row, kv head) lengths, SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kq * g, dh), lambda bk, j, lens: (bk, 0, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bk, j, lens: (bk, j, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bk, j, lens: (bk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, kq * g, dh), lambda bk, j, lens: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kq * g, 1), jnp.float32),
            pltpu.VMEM((kq * g, 1), jnp.float32),
            pltpu.VMEM((kq * g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_block, block_t=block_t, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hk, kq * g, dh), q.dtype),
        interpret=interpret,
    )(lens, qt, kt, vt)
    return jnp.moveaxis(out.reshape(b, hk, kq, g, dh), 1, 2).reshape(
        b, kq, h, dh)


def decode_attention_pallas(q, k, v, cache_len, *, block_t: int = 1024,
                            interpret: bool = True):
    """q: (B,H,dh); k/v: (B,T,Hk,dh); cache_len: (B,) -> (B,H,dh)."""
    b, h, dh = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = h // hk
    block_t = min(block_t, t)
    pt = (-t) % block_t
    qt = q.reshape(b, hk, g, dh).reshape(b * hk, g, dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * hk, t, dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * hk, t, dh)
    if pt:
        kt = jnp.pad(kt, ((0, 0), (0, pt), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pt), (0, 0)))
    nt = (t + pt) // block_t
    grid = (b * hk, nt)
    lens = jnp.broadcast_to(cache_len[:, None], (b, hk)).reshape(
        b * hk).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # per-(row, kv head) lengths, SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda bk, j, lens: (bk, 0, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bk, j, lens: (bk, j, 0)),
            pl.BlockSpec((1, block_t, dh), lambda bk, j, lens: (bk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda bk, j, lens: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hk, g, dh), q.dtype),
        interpret=interpret,
    )(lens, qt, kt, vt)
    return out.reshape(b, hk, g, dh).reshape(b, h, dh)
