"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
always against the pure-jnp ref.py oracles (interpret mode on CPU)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.kernels.cosine_topk.ops import (CHUNKED_MIN_N, chunk_width,
                                           chunked_top_k, cosine_topk,
                                           cosine_topk_gather)
from repro.kernels.cosine_topk.ref import (cosine_topk_gather_ref,
                                           cosine_topk_ref)
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_block)
from repro.kernels.decode_attention.ref import (decode_attention_block_ref,
                                                decode_attention_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                               paged_decode_attention_block)
from repro.kernels.paged_attention.ref import (
    paged_decode_attention_block_ref, paged_decode_attention_ref)


def _unit(key, shape, dtype=jnp.float32):
    x = jax.random.normal(key, shape, dtype)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


# ------------------------------------------------------------ cosine_topk

@pytest.mark.parametrize("b,n,d,k,bn", [
    (1, 128, 16, 1, 64), (4, 256, 64, 4, 64), (2, 512, 384, 8, 128),
    (3, 256, 32, 16, 256), (8, 1024, 128, 2, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cosine_topk_matches_ref(b, n, d, k, bn, dtype):
    q = _unit(jax.random.PRNGKey(0), (b, d)).astype(dtype)
    db = _unit(jax.random.PRNGKey(1), (n, d)).astype(dtype)
    valid = jax.random.bernoulli(jax.random.PRNGKey(2), 0.85, (n,))
    s1, i1 = cosine_topk(q, db, valid, k=k, impl="pallas", block_n=bn)
    s2, i2 = cosine_topk_ref(q, db, k, valid)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 4), logn=st.integers(6, 9), d=st.sampled_from([8, 32, 128]),
       k=st.integers(1, 8), seed=st.integers(0, 2 ** 16))
def test_cosine_topk_property(b, logn, d, k, seed):
    n = 2 ** logn
    q = _unit(jax.random.PRNGKey(seed), (b, d))
    db = _unit(jax.random.PRNGKey(seed + 1), (n, d))
    s1, i1 = cosine_topk(q, db, None, k=k, impl="pallas", block_n=min(n, 128))
    s2, i2 = cosine_topk_ref(q, db, k, None)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    # scores sorted descending; indices in range
    s1 = np.asarray(s1)
    assert np.all(np.diff(s1, axis=1) <= 1e-6)
    assert np.all((np.asarray(i1) >= 0) & (np.asarray(i1) < n))


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and np.array_equal(a.view(np.uint8),
                                                 b.view(np.uint8))


@pytest.mark.parametrize("n", [1000, CHUNKED_MIN_N, 20_000])
@pytest.mark.parametrize("case", ["distinct", "dupes", "some_invalid",
                                  "all_invalid"])
@pytest.mark.parametrize("b,k", [(1, 1), (32, 2), (1, 3), (32, 4)])
def test_cosine_topk_xla_is_exact(n, case, b, k):
    """The XLA lookup's chunked selection equals one full lax.top_k bit
    for bit, scores and indices, ties and -inf rows included; at 1,000
    rows the lookup takes the plain top_k, so the helper is also run at
    chunk widths that do (8) and do not (24) divide the bank, on the
    scores and on the scores rounded to quarters (ties at every rank)."""
    d = 64
    key = jax.random.PRNGKey(n + 7 * k + b)
    db = _unit(key, (n, d))
    valid = jnp.ones((n,), bool)
    if case == "dupes":       # every row repeated ~10x across chunks
        rows = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, n // 10)
        db = db[rows]
    elif case == "some_invalid":
        valid = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.3, (n,))
    elif case == "all_invalid":
        valid = jnp.zeros((n,), bool)
    # half the queries are resends of bank rows, so the top scores tie
    picks = jax.random.randint(jax.random.fold_in(key, 3), (b,), 0, n)
    q = jnp.where((jnp.arange(b) % 2 == 0)[:, None], db[picks],
                  _unit(jax.random.fold_in(key, 4), (b, d)))
    s1, i1 = cosine_topk(q, db, valid, k=k, impl="xla")
    s2, i2 = jax.jit(cosine_topk_ref, static_argnums=2)(q, db, k, valid)
    assert _same_bits(s1, s2) and _same_bits(i1, i2)
    if n >= CHUNKED_MIN_N:
        assert n // chunk_width(n) >= 4 * k      # the chunked path ran
    scores = jnp.where(valid[None, :], q @ db.T, -jnp.inf)
    coarse = jnp.round(scores * 4) / 4      # ties below each row's best
    for panel, chunk in itertools.product((scores, coarse), (8, 24)):
        want_s, want_i = jax.lax.top_k(panel, k)
        got_s, got_i = chunked_top_k(panel, k, chunk)
        assert _same_bits(got_s, want_s)
        assert _same_bits(got_i, want_i.astype(got_i.dtype))


@pytest.mark.parametrize("b,n,m,d,k,bm", [
    (1, 128, 32, 16, 1, 16), (4, 256, 96, 64, 4, 32),
    (2, 512, 100, 384, 8, 64),  # M not divisible by block_m -> pad path
    (3, 256, 48, 32, 16, 48),
])
def test_cosine_topk_gather_matches_ref(b, n, m, d, k, bm):
    q = _unit(jax.random.PRNGKey(0), (b, d))
    db = _unit(jax.random.PRNGKey(1), (n, d))
    # distinct candidate rows per query, some marked stale, some padding
    rng = np.random.default_rng(2)
    cand = np.stack([rng.choice(n, size=m, replace=False) for _ in range(b)])
    cand_valid = rng.random((b, m)) < 0.8
    cand[rng.random((b, m)) < 0.1] = -1
    cand = jnp.asarray(cand, jnp.int32)
    cand_valid = jnp.asarray(cand_valid)
    s1, i1 = cosine_topk_gather(q, db, cand, cand_valid, k=k, impl="pallas",
                                block_m=bm)
    emb = jnp.take(db, jnp.clip(cand, 0, None), axis=0)
    s2, i2 = cosine_topk_gather_ref(q, emb, cand, cand_valid & (cand >= 0), k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_cosine_topk_gather_full_shortlist_matches_flat():
    """With every row shortlisted, the gather path must equal the flat scan."""
    b, n, d, k = 3, 128, 32, 4
    q = _unit(jax.random.PRNGKey(5), (b, d))
    db = _unit(jax.random.PRNGKey(6), (n, d))
    valid = jax.random.bernoulli(jax.random.PRNGKey(7), 0.9, (n,))
    cand = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    cand_valid = jnp.broadcast_to(valid, (b, n))
    for impl in ("xla", "pallas"):
        s1, i1 = cosine_topk_gather(q, db, cand, cand_valid, k=k, impl=impl,
                                    block_m=32)
        s2, i2 = cosine_topk_ref(q, db, k, valid)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5, atol=1e-5)
        finite = np.isfinite(np.asarray(s2))
        assert np.array_equal(np.asarray(i1)[finite], np.asarray(i2)[finite])


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), m=st.sampled_from([16, 40, 64]),
       k=st.integers(1, 6), seed=st.integers(0, 2 ** 16))
def test_cosine_topk_gather_property(b, m, k, seed):
    n, d = 256, 32
    q = _unit(jax.random.PRNGKey(seed), (b, d))
    db = _unit(jax.random.PRNGKey(seed + 1), (n, d))
    cand = jax.random.randint(jax.random.PRNGKey(seed + 2), (b, m), 0, n)
    cand_valid = jax.random.bernoulli(jax.random.PRNGKey(seed + 3), 0.7, (b, m))
    s1, i1 = cosine_topk_gather(q, db, cand, cand_valid, k=k, impl="pallas",
                                block_m=16)
    emb = jnp.take(db, cand, axis=0)
    s2, i2 = cosine_topk_gather_ref(q, emb, cand, cand_valid, k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    s1 = np.asarray(s1)
    assert np.all(np.diff(np.where(np.isfinite(s1), s1, -1e30), axis=1) <= 1e-6)


def test_cosine_topk_self_retrieval():
    """Property: a db vector queried against its own bank wins top-1."""
    db = _unit(jax.random.PRNGKey(3), (64, 32))
    s, i = cosine_topk(db[:8], db, None, k=1, impl="pallas", block_n=64)
    assert np.array_equal(np.asarray(i)[:, 0], np.arange(8))
    np.testing.assert_allclose(np.asarray(s)[:, 0], 1.0, atol=1e-5)


def _dot_precisions(jaxpr):
    """Precision of every dot_general in a jaxpr, sub-jaxprs included
    (a pallas_call's kernel body among them)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)      # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    found += _dot_precisions(sub)
    return found


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("gather", [False, True])
def test_cosine_scores_run_at_float32_precision(impl, gather):
    """Cache scores must not take a TPU's default single bf16 MXU pass:
    that scores a verbatim resend ~1e-3 below 1.0, under the router's
    0.9999 EXACT threshold.  The CPU computes float32 either way, so the
    precision is checked in the program."""
    q, db = jnp.ones((8, 64)), jnp.ones((256, 64))
    if gather:
        fn = lambda q, db: cosine_topk_gather(
            q, db, jnp.zeros((8, 128), jnp.int32), jnp.ones((8, 128), bool),
            k=4, impl=impl, block_m=128)
    else:
        fn = lambda q, db: cosine_topk(q, db, None, k=4, impl=impl,
                                       block_n=128)
    precisions = _dot_precisions(jax.make_jaxpr(fn)(q, db).jaxpr)
    # the Pallas gather kernel scores on the VPU in float32: no dot at all
    assert precisions or (impl, gather) == ("pallas", True)
    hi = jax.lax.Precision.HIGHEST
    assert all(p is not None and tuple(p) == (hi, hi) for p in precisions)


# --------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,sq,sk,h,hk,dh,bq,bk,causal,win", [
    (2, 64, 64, 4, 2, 32, 16, 16, True, 0),
    (1, 48, 48, 6, 6, 16, 32, 16, True, 12),
    (2, 33, 33, 4, 1, 8, 16, 16, True, 0),
    (1, 16, 40, 2, 2, 16, 16, 8, False, 0),
    (1, 128, 128, 8, 4, 64, 64, 32, True, 32),
])
def test_flash_matches_ref(b, sq, sk, h, hk, dh, bq, bk, causal, win):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, hk, dh))
    o1 = flash_attention(q, k, v, causal=causal, window=win,
                         block_q=bq, block_k=bk)
    o2 = flash_attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([16, 32, 48]), h=st.sampled_from([2, 4]),
       g=st.sampled_from([1, 2]), dh=st.sampled_from([8, 16]),
       causal=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_flash_property(s, h, g, dh, causal, seed):
    hk = h // g
    q = jax.random.normal(jax.random.PRNGKey(seed), (1, s, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, s, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (1, s, hk, dh))
    o1 = flash_attention(q, k, v, causal=causal, window=0, block_q=16, block_k=16)
    o2 = flash_attention_ref(q, k, v, causal=causal, window=0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 4, 16), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 2, 16), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 2, 16), jnp.bfloat16)
    o1 = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    o2 = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=3e-2, atol=3e-2)


# --------------------------------------------------------- decode attention

@pytest.mark.parametrize("b,t,h,hk,dh,bt", [
    (2, 128, 8, 2, 32, 32), (3, 100, 4, 4, 16, 64), (1, 64, 6, 1, 8, 16),
    (4, 256, 16, 8, 64, 128),
])
def test_decode_matches_ref(b, t, h, hk, dh, bt):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, hk, dh))
    cl = jax.random.randint(jax.random.PRNGKey(3), (b,), 1, t + 1)
    o1 = decode_attention(q, k, v, cl, block_t=bt)
    o2 = decode_attention_ref(q, k, v, cl)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(t=st.sampled_from([32, 64, 96]), g=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 2 ** 16))
def test_decode_property(t, g, seed):
    b, hk, dh = 2, 2, 16
    h = hk * g
    q = jax.random.normal(jax.random.PRNGKey(seed), (b, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (b, t, hk, dh))
    cl = jnp.asarray([1, t])
    o1 = decode_attention(q, k, v, cl, block_t=32)
    o2 = decode_attention_ref(q, k, v, cl)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
    # cache_len=1 row attends only to slot 0 -> output == v[:, 0] broadcast
    np.testing.assert_allclose(
        np.asarray(o1)[0], np.asarray(v)[0, 0].repeat(g, axis=0), rtol=1e-4)


# -------------------------------------------- q-block (speculative) decode

@pytest.mark.parametrize("b,kq,t,h,hk,dh,bt", [
    (2, 4, 128, 8, 2, 32, 32), (3, 2, 100, 4, 4, 16, 64),
    (1, 8, 64, 6, 1, 8, 16), (2, 1, 96, 4, 2, 16, 32),
])
def test_decode_block_matches_ref(b, kq, t, h, hk, dh, bt):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, kq, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, hk, dh))
    cl = jax.random.randint(jax.random.PRNGKey(3), (b,), 1, t - kq)
    o1 = decode_attention_block(q, k, v, cl, block_t=bt)
    o2 = decode_attention_block_ref(q, k, v, cl)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_decode_block_k1_equals_single_decode():
    """A 1-wide verify block IS single-token decode (limit cache_len + 1)."""
    b, t, h, hk, dh = 3, 64, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, hk, dh))
    cl = jnp.asarray([5, 31, 62])
    o1 = decode_attention_block(q, k, v, cl, block_t=32)[:, 0]
    o2 = decode_attention(q[:, 0], k, v, cl + 1, block_t=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(kq=st.sampled_from([1, 2, 4, 8]), g=st.sampled_from([1, 2]),
       seed=st.integers(0, 2 ** 16))
def test_decode_block_rowwise_equals_sequential(kq, g, seed):
    """Each block query i must equal a single-token decode over the prefix
    grown by i — the in-block causal mask IS the sequential semantics."""
    b, hk, dh, t = 2, 2, 16, 64
    h = hk * g
    q = jax.random.normal(jax.random.PRNGKey(seed), (b, kq, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t, hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (b, t, hk, dh))
    cl = jnp.asarray([3, t - kq - 1])
    blk = decode_attention_block(q, k, v, cl, block_t=32)
    for i in range(kq):
        one = decode_attention(q[:, i], k, v, cl + i + 1, block_t=32)
        np.testing.assert_allclose(np.asarray(blk[:, i]), np.asarray(one),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------- paged decode attention

def _paged_case(b, h, hk, dh, page, npg, num_pages, cap, lens, seed):
    """Random pool + RAGGED block tables (a permutation slice per batch):
    physically scattered pages, garbage in unallocated/trash pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, dh))
    kp = jax.random.normal(ks[1], (num_pages + 1, page, hk, dh))
    vp = jax.random.normal(ks[2], (num_pages + 1, page, hk, dh))
    rng = np.random.default_rng(seed)
    tbl = rng.permutation(num_pages)[:b * npg].reshape(b, npg).astype(np.int32)
    sp = np.full((b, cap), -1, np.int32)
    for i, ln in enumerate(lens):
        sp[i, :ln] = np.arange(ln)
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(sp)


@pytest.mark.parametrize("b,h,hk,dh,page,npg,num_pages,cap,lens", [
    # partially filled last page + ragged per-row lengths
    (3, 4, 2, 16, 8, 4, 32, 30, (30, 17, 5)),
    # degenerate one-page sequence
    (2, 2, 1, 8, 16, 1, 8, 13, (13, 1)),
    # GQA g=4, cap == npg * page exactly (no tail slice)
    (2, 8, 2, 32, 4, 8, 64, 32, (32, 9)),
    # page_size=1 pathological: one slot per page
    (2, 2, 2, 8, 1, 12, 24, 12, (12, 7)),
])
def test_paged_decode_matches_ref(b, h, hk, dh, page, npg, num_pages, cap,
                                  lens):
    q, kp, vp, tbl, sp = _paged_case(b, h, hk, dh, page, npg, num_pages,
                                     cap, lens, seed=b * 7 + npg)
    o1 = paged_decode_attention(q, kp, vp, tbl, sp)
    o2 = paged_decode_attention_ref(q, kp, vp, tbl, sp)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_matches_dense_decode_kernel():
    """Paging is pure indirection: gathering the pages back into a dense
    cache and running the DENSE decode kernel gives the same answer."""
    from repro.kernels.paged_attention.ref import gather_pages
    q, kp, vp, tbl, sp = _paged_case(2, 4, 2, 16, 8, 3, 16, 20, (20, 11),
                                     seed=5)
    o1 = paged_decode_attention(q, kp, vp, tbl, sp)
    kd = gather_pages(kp, tbl, 20)
    vd = gather_pages(vp, tbl, 20)
    o2 = decode_attention(q, kd, vd, jnp.asarray([20, 11]), block_t=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kq,page,npg", [(1, 8, 4), (2, 4, 6), (4, 8, 4),
                                         (8, 1, 16)])
def test_paged_decode_block_matches_ref(kq, page, npg):
    b, h, hk, dh = 2, 4, 2, 16
    num_pages = max(b * npg, 8)
    cap = npg * page
    rng = np.random.default_rng(kq * 13 + page)
    lens = tuple(int(x) for x in rng.integers(kq, cap + 1, size=b))
    q1, kp, vp, tbl, sp = _paged_case(b, h, hk, dh, page, npg, num_pages,
                                      cap, lens, seed=kq + page)
    q = jax.random.normal(jax.random.PRNGKey(99), (b, kq, h, dh))
    qpos = jnp.asarray([ln - kq for ln in lens], jnp.int32)
    o1 = paged_decode_attention_block(q, kp, vp, tbl, sp, qpos)
    o2 = paged_decode_attention_block_ref(q, kp, vp, tbl, sp, qpos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_block_matches_dense_block_kernel():
    """Paging is pure indirection for the block variant too: gather the
    pages dense and the DENSE block kernel must agree."""
    from repro.kernels.paged_attention.ref import gather_pages
    kq = 4
    lens = (20, 11)
    q1, kp, vp, tbl, sp = _paged_case(2, 4, 2, 16, 8, 3, 16, 20, lens,
                                      seed=5)
    q = jax.random.normal(jax.random.PRNGKey(42), (2, kq, 4, 16))
    qpos = jnp.asarray([ln - kq for ln in lens], jnp.int32)
    o1 = paged_decode_attention_block(q, kp, vp, tbl, sp, qpos)
    kd = gather_pages(kp, tbl, 20)
    vd = gather_pages(vp, tbl, 20)
    o2 = decode_attention_block(q, kd, vd, qpos, block_t=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(page=st.sampled_from([1, 4, 8]), npg=st.integers(1, 6),
       g=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2 ** 16))
def test_paged_decode_property(page, npg, g, seed):
    b, hk, dh = 2, 2, 16
    h = hk * g
    num_pages = max(b * npg, 4)
    cap = npg * page
    rng = np.random.default_rng(seed)
    lens = tuple(int(x) for x in rng.integers(1, cap + 1, size=b))
    q, kp, vp, tbl, sp = _paged_case(b, h, hk, dh, page, npg, num_pages,
                                     cap, lens, seed=seed)
    o1 = paged_decode_attention(q, kp, vp, tbl, sp)
    o2 = paged_decode_attention_ref(q, kp, vp, tbl, sp)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
