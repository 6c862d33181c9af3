"""Chip smoke run: the served TweakLLM path at Qwen2.5-3B width on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the row-sharded bank on four chips

One chip: builds the Qwen2.5-3B stack (36 layers, d2048, bf16, seeded
random weights shared by the MISS and the TWEAK model) through
``repro.launch.serve.build_stack`` with a 262,144-row cache bank, replays
a seeded 48-query trace through the scheduler, compares the served
prefill logits with a float32 reference forward of the same weights, and
runs every Pallas kernel, compiled, against its ``ref.py`` at real widths.

Four chips (and nothing else): one seeded trace through
``build_replica_group(4, cache_shards=4)`` over a 1,048,576-row bank
row-sharded across the chips, and through the same group with a local
bank.  Routes, top-1 slots and hit counts must agree.

Everything runs in this one process, on the chip; no child process is
started.  Off a TPU the script exits non-zero and prints no result.  Any
failing phase raises.  The last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.data import WorkloadGenerator  # noqa: E402
from repro.kernels.cosine_topk.ops import cosine_topk, cosine_topk_gather  # noqa: E402
from repro.kernels.cosine_topk.ref import (cosine_topk_gather_ref,  # noqa: E402
                                           cosine_topk_ref)
from repro.kernels.decode_attention.ops import (decode_attention,  # noqa: E402
                                                decode_attention_block)
from repro.kernels.decode_attention.ref import (  # noqa: E402
    decode_attention_block_ref, decode_attention_ref)
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa: E402
from repro.kernels.paged_attention import (  # noqa: E402
    paged_decode_attention, paged_decode_attention_block,
    paged_decode_attention_block_ref, paged_decode_attention_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (TRACE_SEED, build_engine,  # noqa: E402
                                build_replica_group)
from repro.models.reference import last_logits_f32  # noqa: E402
from repro.serving import (ReplicaScheduler, Scheduler,  # noqa: E402
                           SchedulerConfig, SimClock, poisson_trace,
                           replay_trace)
from repro.serving.batcher import pad_to_buckets  # noqa: E402

ARCH = "qwen2.5-3b"
BANK_ROWS = 262_144            # ~0.74 GB of cache bank on one chip
FOUR_CHIP_BANK_ROWS = 1_048_576  # ~0.73 GB per chip, row-sharded over 4
QUERIES = 48
BATCH = 8
MAX_NEW_TOKENS = 16
ARRIVAL_RATE = 200.0           # requests per simulated second
SEED = 0                       # weights, bank, kernel inputs
EMBED_DIM = 64                 # the served embedder's width
PAGE = 16                      # KV pool page, as GenerateConfig's default
SPEC_K = 4                     # draft tokens verified per speculative step

# Served bf16 prefill vs the float32 reference of the same bf16 weights:
# the largest |logit difference| over the batch, in units of the
# reference logits' standard deviation.  Only activation rounding
# separates the two (2^-9 relative per bf16 round, compounded over 36
# layers); a wrong weight, layout or mask moves logits by about one
# standard deviation.
LOGIT_MAX_ERR_TOL = 0.5
# Pallas cosine scores vs the float32 reference, both at float32 matmul
# precision.  A single bf16 MXU pass would be off by up to ~2 * 2^-9,
# enough to push a verbatim resend under the router's 0.9999 EXACT
# threshold, so the tolerance sits well below that.
KERNEL_SCORE_ATOL = 1e-4
# Pallas attention vs the float32 reference, bf16 inputs and output.
KERNEL_ATTN_TOL = 2e-2
# Sharded vs local bank: the same float32 dot products summed per shard
# instead of over the whole bank.
SHARDED_SCORE_ATOL = 1e-5


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    print(f"== {name}", flush=True)
    yield
    times[name] = time.perf_counter() - t0
    print(f"== {name}: {times[name]:.1f} s", flush=True)


def trace(n: int = QUERIES):
    texts = [q.text for q in WorkloadGenerator(profile="lmsys",
                                               seed=TRACE_SEED).sample(n)]
    return poisson_trace(texts, ARRIVAL_RATE, seed=TRACE_SEED)


def sched_cfg() -> SchedulerConfig:
    return SchedulerConfig(max_batch=BATCH, max_new_tokens=MAX_NEW_TOKENS)


def serve(eng, n: int = QUERIES) -> dict:
    """Replay the seeded trace through the scheduler; every request must
    be answered, and MISS, TWEAK and EXACT must each fire."""
    sched = Scheduler(eng, sched_cfg(), clock=SimClock())
    done = replay_trace(sched, trace(n))
    require(len(done) == n and sched.stats.rejected == 0,
            f"{len(done)} of {n} answered, {sched.stats.rejected} rejected")
    empty = [r.rid for r in done if not r.response]
    require(not empty, f"requests with empty responses: {empty}")
    s = eng.stats
    out = {"miss": s.miss, "tweak": s.tweak, "exact": s.exact,
           "joined": sched.stats.joined, "batches": sched.stats.batches,
           "big_tokens": s.big_tokens, "small_tokens": s.small_tokens}
    print(f"routes: miss={s.miss} tweak={s.tweak} exact={s.exact} "
          f"(+{sched.stats.joined} joined in flight), "
          f"{sched.stats.batches} batches")
    print(f"tokens: big={s.big_tokens} small={s.small_tokens}")
    require(min(s.miss, s.tweak, s.exact) > 0,
            f"a route never fired: {out}")
    return out


def numerics(eng) -> dict:
    """Served prefill logits vs a float32 forward of the same weights."""
    gen = eng.big
    cfg = gen.model.cfg
    texts = [q.text for q in WorkloadGenerator(profile="wildchat",
                                               seed=SEED).sample(BATCH)]
    toks, mask = eng.tok.encode_batch(texts, eng.max_query_len)
    toks = jnp.asarray(pad_to_buckets(toks, mask)[0])
    # the generator's own jitted prefill: the program every MISS runs
    served, _ = gen._prefill(gen.params, {"tokens": toks},
                             capacity=toks.shape[1] + 1)
    served = np.asarray(served[:, :cfg.vocab_size], np.float64)
    ref = jax.jit(last_logits_f32, static_argnums=2)(gen.params, toks, cfg)
    ref = np.asarray(ref, np.float64)
    err = np.abs(served - ref)
    scale = float(ref.std())
    out = {"max_abs_err": float(err.max()), "ref_std": scale,
           "max_err_over_std": float(err.max()) / scale,
           "rel_rms_err": float(np.sqrt((err ** 2).mean() / (ref ** 2).mean())),
           "argmax_agree": float((served.argmax(1) == ref.argmax(1)).mean()),
           "tol": LOGIT_MAX_ERR_TOL}
    print(f"float32 reference ({cfg.dtype} served, batch {toks.shape}): "
          f"max|err|={out['max_abs_err']:.4g} = "
          f"{out['max_err_over_std']:.4g} x ref std {scale:.4g} "
          f"(tol {LOGIT_MAX_ERR_TOL}), rel rms err {out['rel_rms_err']:.4g}, "
          f"argmax agree {out['argmax_agree']:.3f}")
    require(np.isfinite(served).all(), "non-finite served logits")
    require(out["max_err_over_std"] <= LOGIT_MAX_ERR_TOL,
            f"served logits off the float32 reference: {out}")
    return out


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def kernels(cfg, bank_rows: int, *, seq: int = 512, cache_len: int = 2048,
            compiled: bool = True) -> dict:
    """Each Pallas kernel once against its ref.py at the served widths:
    the bank and the embedder's width for the lookups, the model's
    attention heads in bf16 for the attention kernels.  ``compiled``
    demands the kernel in the lowered program (a ``tpu_custom_call``)."""
    h, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dim, batch, page, spec_k = EMBED_DIM, BATCH, PAGE, SPEC_K
    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(ks), shape).astype(dt)
    out = {}

    def check(name, fn, args, kwargs, ref, compare):
        text = fn.lower(*args, **kwargs).as_text()
        require(not compiled or "tpu_custom_call" in text,
                f"{name}: no Pallas kernel in the lowered program")
        got = fn(*args, **kwargs)
        with jax.default_matmul_precision("highest"):
            want = ref()
        err = compare(got, want)
        out[name] = err
        print(f"kernel {name}: max err {err:.3g}")

    def scores_close(got, want):
        (gs, gi), (ws, wi) = got, want
        np.testing.assert_array_equal(np.asarray(gi)[:, 0],
                                      np.asarray(wi)[:, 0])
        np.testing.assert_allclose(np.asarray(gs), np.asarray(ws),
                                   atol=KERNEL_SCORE_ATOL)
        return float(np.abs(np.asarray(gs) - np.asarray(ws)).max())

    def attn_close(got, want):
        g = np.asarray(got, np.float32)
        w = np.asarray(want, np.float32)
        np.testing.assert_allclose(g, w, atol=KERNEL_ATTN_TOL,
                                   rtol=KERNEL_ATTN_TOL)
        return float(np.abs(g - w).max())

    # cosine top-k: queries near valid stored rows, so top-1 is unambiguous
    db = _unit(normal((bank_rows, dim)))
    valid = jnp.arange(bank_rows) % 7 != 3
    near = 7 * jnp.arange(batch)
    q = _unit(db[near] + 0.05 * normal((batch, dim)))
    check("cosine_topk", cosine_topk, (q, db, valid),
          dict(k=4, impl="pallas", block_n=1024),
          lambda: cosine_topk_ref(q, db, 4, valid), scores_close)
    # shortlist (IVF probe) variant: 2,048 candidates per query
    m = 2048
    cand = jax.random.randint(next(ks), (batch, m), 0, bank_rows)
    cand = cand.at[:, 0].set(near)
    cvalid = jnp.arange(m)[None, :] < m - 100
    check("cosine_topk_gather", cosine_topk_gather, (q, db, cand, cvalid),
          dict(k=4, impl="pallas", block_m=256),
          lambda: cosine_topk_gather_ref(
              q, jnp.take(db, cand, axis=0), cand, cvalid, 4),
          scores_close)

    # decode attention over a dense KV cache
    qd = normal((batch, h, dh), bf)
    k = normal((batch, cache_len, hk, dh), bf)
    v = normal((batch, cache_len, hk, dh), bf)
    lens = jax.random.randint(next(ks), (batch,), 1, cache_len + 1)
    check("decode_attention", decode_attention, (qd, k, v, lens),
          dict(block_t=1024, impl="pallas"),
          lambda: decode_attention_ref(qd, k, v, lens), attn_close)
    qb = normal((batch, spec_k, h, dh), bf)
    blens = jnp.minimum(lens, cache_len - spec_k)
    check("decode_attention_block", decode_attention_block,
          (qb, k, v, blens), dict(block_t=1024, impl="pallas"),
          lambda: decode_attention_block_ref(qb, k, v, blens), attn_close)

    # paged decode attention: the same rows scattered over pool pages
    npg = cache_len // page
    tbl = jax.random.permutation(next(ks), batch * npg).reshape(batch, npg)
    kp = jnp.zeros((batch * npg + 1, page, hk, dh), bf).at[
        tbl.reshape(-1)].set(k.reshape(batch * npg, page, hk, dh))
    vp = jnp.zeros_like(kp).at[tbl.reshape(-1)].set(
        v.reshape(batch * npg, page, hk, dh))
    slots = jnp.arange(cache_len)[None, :]
    slot_pos = jnp.where(slots < lens[:, None], slots, -1).astype(jnp.int32)
    check("paged_attention", paged_decode_attention,
          (qd, kp, vp, tbl, slot_pos), dict(impl="pallas"),
          lambda: paged_decode_attention_ref(qd, kp, vp, tbl, slot_pos),
          attn_close)
    bslot = jnp.where(slots < (blens + spec_k)[:, None], slots,
                      -1).astype(jnp.int32)
    check("paged_attention_block", paged_decode_attention_block,
          (qb, kp, vp, tbl, bslot, blens), dict(impl="pallas"),
          lambda: paged_decode_attention_block_ref(qb, kp, vp, tbl, bslot,
                                                   blens),
          attn_close)

    # flash attention: a causal prefill
    qf = normal((batch, seq, h, dh), bf)
    kf = normal((batch, seq, hk, dh), bf)
    vf = normal((batch, seq, hk, dh), bf)
    check("flash_attention", flash_attention, (qf, kf, vf),
          dict(causal=True, impl="pallas"),
          lambda: flash_attention_ref(qf, kf, vf, causal=True), attn_close)
    return out


def _record_top1(bank) -> list:
    """Wrap the bank's stage-1 route so every call's top-1 slots are kept."""
    calls = []
    route = bank.route_batch

    def recorded(q_embs, cost=None):
        res = route(q_embs, cost)
        calls.append(res[1][:, 0])
        return res

    bank.route_batch = recorded
    return calls


def _serve_group(name: str, shards: int, bank_rows: int, n: int) -> dict:
    """The seeded trace through four replicas over one shared bank,
    row-sharded over ``shards`` chips or (0) local to device 0."""
    group = build_replica_group(4, cache_shards=shards, arch=ARCH,
                                capacity=bank_rows, seed=SEED)
    bank = group.bank
    top1 = _record_top1(bank)
    sched = ReplicaScheduler(group.engines, sched_cfg(), clock=SimClock())
    done = sorted(replay_trace(sched, trace(n)), key=lambda r: r.rid)
    require(len(done) == n, f"{name}: {len(done)} of {n} answered")
    emb = bank.state["emb"]
    print(f"{name}: bank emb {emb.shape} over "
          f"{len(emb.sharding.device_set)} device(s), "
          f"routes miss={group.stats.miss} tweak={group.stats.tweak} "
          f"exact={group.stats.exact}")
    for rid, e in enumerate(group.engines):
        dev = sorted({d.id for leaf in jax.tree.leaves(
            (e.big.params, e.small.params)) for d in leaf.devices()})
        print(f"{name}: replica {rid} model weights on device(s) {dev}")
    return {"decisions": [r.meta["decision"] for r in done],
            "sims": np.asarray([r.meta["sim"] for r in done]),
            "top1": np.concatenate(jax.device_get(top1)),
            "hits": np.asarray(jax.device_get(bank.state["hits"])),
            "routes": (group.stats.miss, group.stats.tweak,
                       group.stats.exact)}


def four_chips(bank_rows: int = FOUR_CHIP_BANK_ROWS,
               n: int = QUERIES) -> dict:
    """One trace through a row-sharded and through a local shared bank."""
    runs = {}
    for name, shards in (("sharded", 4), ("local", 0)):
        runs[name] = _serve_group(name, shards, bank_rows, n)
        # the top-1 recorder closes a reference cycle through the bank:
        # collect it, so this group's weights leave device 0 before the
        # next group builds its own
        gc.collect()
        in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        print(f"{name}: released; bytes_in_use on device 0: {in_use}")
    sh, lo = runs["sharded"], runs["local"]
    require(sh["decisions"] == lo["decisions"], "route decisions differ")
    np.testing.assert_array_equal(sh["top1"], lo["top1"])
    np.testing.assert_array_equal(sh["hits"], lo["hits"])
    np.testing.assert_allclose(sh["sims"], lo["sims"],
                               atol=SHARDED_SCORE_ATOL)
    require(sh["routes"] == lo["routes"],
            f"route counts differ: {sh['routes']} vs {lo['routes']}")
    live = np.isfinite(lo["sims"])       # -inf: lookups into an empty bank
    diff = float(np.abs(sh["sims"][live] - lo["sims"][live]).max())
    print(f"sharded == local: {n} decisions, {sh['top1'].size} top-1 slots, "
          f"{int(sh['hits'].sum())} hits; max |score diff| {diff:.3g} "
          f"(tol {SHARDED_SCORE_ATOL})")
    return {"routes": sh["routes"], "max_score_diff": diff}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded bank across four chips "
                         "against a local bank")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's backend is {backend!r}, not 'tpu'; this "
              f"run is only meaningful on the chip", file=sys.stderr)
        return 1
    devices = jax.devices()
    dev = devices[0]
    print(f"device_kind: {dev.device_kind}, devices: {len(devices)}")
    print(f"jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}")
    print(f"compile cache: {enable_compile_cache()}")
    times: dict = {}
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        with phase("four_chips", times):
            four_chips()
    else:
        with phase("build_stack", times):
            eng = build_engine(arch=ARCH, capacity=BANK_ROWS, seed=SEED)
            n_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.big.params))
            print(f"{ARCH}: {n_bytes / 1e9:.2f} GB of "
                  f"{eng.big.model.cfg.dtype} weights, shared by MISS and "
                  f"TWEAK: {eng.big.params is eng.small.params}")
        with phase("serve", times):
            serve(eng)
        with phase("numerics", times):
            numerics(eng)
        with phase("kernels", times):
            kernels(eng.big.model.cfg, BANK_ROWS)
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
              f"(device {d.id} of {len(devices)})")
    print("phase wall times (s): " + ", ".join(
        f"{k}={v:.1f}" for k, v in times.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
