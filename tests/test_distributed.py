"""Distributed-cache and sharding tests.

These need a fresh device count, so they spawn a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N (the main test process
must keep seeing 1 device by default — smoke tests rely on it; the
tier1-multidevice lane additionally runs the in-process suites under 8
forced devices, see tests/test_replicas.py).
"""
import json
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")

_PREAMBLE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
""")


def run_device_script(body: str, *, n_dev: int = 8, timeout: int = 600):
    """Run ``body`` in a fresh interpreter with ``n_dev`` forced host devices.

    The body inherits the preamble's ``os/json/jax/jnp/np`` imports and
    must ``print(json.dumps({...}))`` as its LAST stdout line; the parsed
    dict is returned.  Failures raise with the subprocess stderr in the
    assertion message (a bare returncode check used to surface as a JSON
    decode error on empty stdout).
    """
    script = _PREAMBLE.format(n_dev=n_dev) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # forced host devices are CPU devices: the child must never reach for
    # an accelerator, which a parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (
        f"device-script subprocess failed (rc={out.returncode}), stderr:\n"
        f"{out.stderr[-4000:]}")
    lines = out.stdout.strip().splitlines()
    assert lines, f"no stdout from device script; stderr:\n{out.stderr[-4000:]}"
    return json.loads(lines[-1])


def test_distributed_lookup_matches_single_device():
    res = run_device_script("""
        from repro.core import cache as cache_lib
        from repro.core.distributed import (make_distributed_insert_batch,
                                            make_distributed_lookup,
                                            shard_cache_state)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = cache_lib.CacheConfig(capacity=64, dim=16, topk=4)
        state = cache_lib.init_cache(cfg)
        key = jax.random.PRNGKey(0)
        for i in range(40):
            e = jax.random.normal(jax.random.fold_in(key, i), (cfg.dim,))
            z = jnp.zeros((cfg.max_query_tokens,), jnp.int32)
            m = jnp.ones((cfg.max_query_tokens,), jnp.float32)
            z2 = jnp.zeros((cfg.max_response_tokens,), jnp.int32)
            m2 = jnp.ones((cfg.max_response_tokens,), jnp.float32)
            state = cache_lib.insert(state, cfg, e, z, m, z2, m2)
        q = jax.random.normal(jax.random.PRNGKey(7), (5, cfg.dim))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        # single-device reference
        ref_s, ref_i = cache_lib.lookup(state, cfg, q)
        # sharded lookup
        sstate = shard_cache_state(state, mesh)
        lookup = make_distributed_lookup(mesh, cfg)
        ds, di = lookup(sstate, q)
        ok_scores = bool(np.allclose(np.asarray(ds), np.asarray(ref_s),
                                     atol=1e-5))
        ok_idx = bool(np.array_equal(np.sort(np.asarray(di)),
                                     np.sort(np.asarray(ref_i))))
        # sharded insert_batch vs single-device insert_batch (40 real rows)
        B = 48
        embs = jax.random.normal(jax.random.PRNGKey(3), (B, cfg.dim))
        qt = jnp.ones((B, cfg.max_query_tokens), jnp.int32)
        qm = jnp.ones((B, cfg.max_query_tokens), jnp.float32)
        rt = jnp.ones((B, cfg.max_response_tokens), jnp.int32)
        rm = jnp.ones((B, cfg.max_response_tokens), jnp.float32)
        ref_state, ref_slots = cache_lib.insert_batch(
            cache_lib.init_cache(cfg), cfg, embs, qt, qm, rt, rm, 40)
        dib = make_distributed_insert_batch(mesh, cfg)
        dstate, dslots = dib(
            shard_cache_state(cache_lib.init_cache(cfg), mesh),
            embs, qt, qm, rt, rm, 40)
        ok_ins = all(np.allclose(np.asarray(ref_state[k]),
                                 np.asarray(dstate[k]), atol=1e-6)
                     for k in ref_state)
        ok_slots = bool(np.array_equal(np.asarray(ref_slots),
                                       np.asarray(dslots)))
        print(json.dumps({"ok_scores": ok_scores, "ok_idx": ok_idx,
                          "ok_ins": ok_ins, "ok_slots": ok_slots,
                          "n_dev": len(jax.devices())}))
    """)
    assert res["n_dev"] == 8
    assert res["ok_scores"], res
    assert res["ok_idx"], res
    assert res["ok_ins"], res
    assert res["ok_slots"], res


def test_distributed_ivf_matches_flat():
    res = run_device_script("""
        from repro.core import cache as cache_lib
        from repro.core import index as index_lib
        from repro.core.distributed import (make_distributed_insert_batch,
                                            make_distributed_ivf_lookup,
                                            shard_ivf_cache_state)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        flat_cfg = cache_lib.CacheConfig(capacity=64, dim=16, topk=4)
        # nprobe == nclusters -> must be score/decision-identical to flat
        cfg = cache_lib.CacheConfig(capacity=64, dim=16, topk=4, index="ivf",
                                    nclusters=8, nprobe=8)
        B = 80  # 70 real rows laps capacity 64 -> overwrite/stale churn
        embs = jax.random.normal(jax.random.PRNGKey(0), (B, cfg.dim))
        qt = jnp.zeros((B, cfg.max_query_tokens), jnp.int32)
        qm = jnp.ones((B, cfg.max_query_tokens), jnp.float32)
        rt = jnp.zeros((B, cfg.max_response_tokens), jnp.int32)
        rm = jnp.ones((B, cfg.max_response_tokens), jnp.float32)
        state, _ = cache_lib.insert_batch(cache_lib.init_cache(cfg), cfg,
                                          embs, qt, qm, rt, rm, 70)
        q = embs[40:60] / jnp.linalg.norm(embs[40:60], axis=-1, keepdims=True)
        ref_s, ref_i = cache_lib.lookup(state, flat_cfg, q)
        # rebuilt index, sharded layout, distributed two-stage lookup
        sstate = shard_ivf_cache_state(
            index_lib.build_index(state, cfg, seed=0), mesh, cfg)
        dl = make_distributed_ivf_lookup(mesh, cfg)
        ds, di = dl(sstate, q)
        ok_scores = bool(np.allclose(np.asarray(ds), np.asarray(ref_s),
                                     atol=1e-5))
        ok_idx = bool(np.array_equal(np.asarray(di), np.asarray(ref_i)))
        # sharded IVF insert path from empty must agree with the flat oracle
        dib = make_distributed_insert_batch(mesh, cfg)
        s1, slots = dib(
            shard_ivf_cache_state(cache_lib.init_cache(cfg), mesh, cfg),
            embs, qt, qm, rt, rm, 70)
        ref_state, ref_slots = cache_lib.insert_batch(
            cache_lib.init_cache(cfg), cfg, embs, qt, qm, rt, rm, 70)
        ds2, di2 = dl(s1, q)
        ok_ins = (bool(np.array_equal(np.asarray(slots),
                                      np.asarray(ref_slots)))
                  and int(s1["ivf_pending"]) == int(ref_state["ivf_pending"])
                  and bool(np.allclose(np.asarray(ds2), np.asarray(ref_s),
                                       atol=1e-5))
                  and bool(np.array_equal(np.asarray(di2),
                                          np.asarray(ref_i))))
        print(json.dumps({"ok_scores": ok_scores, "ok_idx": ok_idx,
                          "ok_ins": ok_ins, "n_dev": len(jax.devices())}))
    """)
    assert res["n_dev"] == 8
    assert res["ok_scores"], res
    assert res["ok_idx"], res
    assert res["ok_ins"], res


def test_distributed_lookup_and_touch_matches_local():
    """The fused sharded lookup+route+touch (DESIGN.md §12) must reproduce
    cache.lookup_and_touch exactly: scores, decisions, AND the recency
    scatter on the row-sharded arrays — for both flat and IVF banks."""
    res = run_device_script("""
        import functools
        from repro.core import cache as cache_lib
        from repro.core import index as index_lib
        from repro.core import router as router_lib
        from repro.core.distributed import (
            make_distributed_lookup_and_touch, shard_cache_state,
            shard_ivf_cache_state)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rcfg = router_lib.RouterConfig()
        out = {"n_dev": len(jax.devices())}
        for name, cfg in [
            ("flat", cache_lib.CacheConfig(capacity=64, dim=16, topk=4)),
            ("ivf", cache_lib.CacheConfig(capacity=64, dim=16, topk=4,
                                          index="ivf", nclusters=8,
                                          nprobe=8)),
        ]:
            B = 48
            embs = jax.random.normal(jax.random.PRNGKey(0), (B, cfg.dim))
            qt = jnp.zeros((B, cfg.max_query_tokens), jnp.int32)
            qm = jnp.ones((B, cfg.max_query_tokens), jnp.float32)
            rt = jnp.zeros((B, cfg.max_response_tokens), jnp.int32)
            rm = jnp.ones((B, cfg.max_response_tokens), jnp.float32)
            state, _ = cache_lib.insert_batch(cache_lib.init_cache(cfg), cfg,
                                              embs, qt, qm, rt, rm, 40)
            if cfg.index == "ivf":
                state = index_lib.build_index(state, cfg, seed=0)
            # queries straddling the EXACT/TWEAK/MISS bands: 8 cached rows
            # (EXACT), 8 fresh gaussians (mostly MISS/TWEAK)
            q = jnp.concatenate([
                state["emb"][:8],
                jax.random.normal(jax.random.PRNGKey(5), (8, cfg.dim))])
            q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
            lt_local = jax.jit(functools.partial(
                cache_lib.lookup_and_touch, cfg=cfg, router_cfg=rcfg))
            ref_state, ref_s, ref_i, ref_d = lt_local(dict(state), q_embs=q)
            cost = jnp.full((q.shape[0],), rcfg.default_cost, jnp.float32)
            (_, _, _, nref_d, nref_tau, nref_cl, nref_ad) = \\
                cache_lib.lookup_route_touch(dict(state), cfg, rcfg, q, cost)
            sstate = (shard_ivf_cache_state(state, mesh, cfg)
                      if cfg.index == "ivf"
                      else shard_cache_state(state, mesh))
            lt = make_distributed_lookup_and_touch(mesh, cfg, rcfg)
            new, ds, di, dd, dtau, dcl, dad = lt(sstate, q, cost)
            out[name] = {
                "scores": bool(np.allclose(np.asarray(ds),
                                           np.asarray(ref_s), atol=1e-5)),
                "idx": bool(np.array_equal(np.asarray(di)[:, 0],
                                           np.asarray(ref_i)[:, 0])),
                "decisions": bool(np.array_equal(np.asarray(dd),
                                                 np.asarray(ref_d))),
                "last_used": bool(np.array_equal(
                    np.asarray(new["last_used"]),
                    np.asarray(ref_state["last_used"]))),
                "hits": bool(np.array_equal(np.asarray(new["hits"]),
                                            np.asarray(ref_state["hits"]))),
                "clock": int(new["clock"]) == int(ref_state["clock"]),
                # band=0 at the default cost: the new cascade path must
                # reproduce the legacy decisions bit-for-bit, and the
                # sharded cascade outputs must match the local ones
                "new_path_legacy": bool(np.array_equal(np.asarray(nref_d),
                                                       np.asarray(ref_d))),
                "tau": bool(np.allclose(np.asarray(dtau),
                                        np.asarray(nref_tau), atol=1e-6)),
                "cluster": bool(np.array_equal(np.asarray(dcl),
                                               np.asarray(nref_cl))),
                "admit": bool(np.array_equal(np.asarray(dad),
                                             np.asarray(nref_ad))),
            }
        print(json.dumps(out))
    """)
    assert res["n_dev"] == 8
    for name in ("flat", "ivf"):
        assert all(res[name].values()), (name, res[name])


def test_distributed_cascade_matches_local():
    """Sharded stage-1 cascade routing (uncertainty band > 0, varying
    per-request cost) must be decision-identical to the local
    lookup_route_touch — the cascade runs AFTER the all_gather top-k
    merge, so both paths score the same merged shortlist — and the
    replicated admission EMA must evolve identically (DESIGN.md §13)."""
    res = run_device_script("""
        from repro.core import cache as cache_lib
        from repro.core import index as index_lib
        from repro.core import router as router_lib
        from repro.core.distributed import (
            make_distributed_lookup_and_touch, shard_ivf_cache_state)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rcfg = router_lib.RouterConfig(band=0.2, admit_floor=0.4,
                                       admit_min=1)
        cfg = cache_lib.CacheConfig(capacity=64, dim=16, topk=4,
                                    index="ivf", nclusters=8, nprobe=8)
        B = 48
        embs = jax.random.normal(jax.random.PRNGKey(0), (B, cfg.dim))
        qt = jnp.zeros((B, cfg.max_query_tokens), jnp.int32)
        qm = jnp.ones((B, cfg.max_query_tokens), jnp.float32)
        rt = jnp.zeros((B, cfg.max_response_tokens), jnp.int32)
        rm = jnp.ones((B, cfg.max_response_tokens), jnp.float32)
        state, _ = cache_lib.insert_batch(cache_lib.init_cache(cfg), cfg,
                                          embs, qt, qm, rt, rm, 40)
        state = index_lib.build_index(state, cfg, seed=0)
        # exact hits, band-straddling perturbations, and cold misses
        q = jnp.concatenate([
            state["emb"][:8],
            0.9 * state["emb"][8:16]
            + 0.45 * jax.random.normal(jax.random.PRNGKey(5), (8, cfg.dim)),
            jax.random.normal(jax.random.PRNGKey(6), (8, cfg.dim))])
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        cost = jnp.linspace(0.0, 1.0, q.shape[0]).astype(jnp.float32)
        (ref_state, _, _, ref_d, ref_tau, ref_cl, ref_ad) = \\
            cache_lib.lookup_route_touch(dict(state), cfg, rcfg, q, cost)
        lt = make_distributed_lookup_and_touch(mesh, cfg, rcfg)
        new, ds, di, dd, dtau, dcl, dad = lt(
            shard_ivf_cache_state(state, mesh, cfg), q, cost)
        print(json.dumps({
            "n_dev": len(jax.devices()),
            "n_uncertain": int((np.asarray(ref_d)
                                == router_lib.UNCERTAIN).sum()),
            "decisions": bool(np.array_equal(np.asarray(dd),
                                             np.asarray(ref_d))),
            "tau": bool(np.allclose(np.asarray(dtau), np.asarray(ref_tau),
                                    atol=1e-6)),
            "cluster": bool(np.array_equal(np.asarray(dcl),
                                           np.asarray(ref_cl))),
            "admit": bool(np.array_equal(np.asarray(dad),
                                         np.asarray(ref_ad))),
            "adm_ema": bool(np.allclose(np.asarray(new["adm_ema"]),
                                        np.asarray(ref_state["adm_ema"]),
                                        atol=1e-6)),
            "adm_count": bool(np.array_equal(
                np.asarray(new["adm_count"]),
                np.asarray(ref_state["adm_count"]))),
        }))
    """)
    assert res["n_dev"] == 8
    assert res["n_uncertain"] > 0, res       # the band is actually exercised
    for k in ("decisions", "tau", "cluster", "admit", "adm_ema",
              "adm_count"):
        assert res[k], (k, res)


def test_sharded_bank_cross_replica_visibility():
    """Two engines on one SHARDED bank: replica 0's miss-commit must be an
    EXACT hit for replica 1 on its very next lookup (DESIGN.md §12)."""
    res = run_device_script("""
        from repro.core import CacheConfig, ReplicaGroup, RouterConfig
        from repro.core.engine import SharedCacheBank, TweakLLMEngine
        from repro.launch.mesh import make_cache_mesh
        from repro.launch.serve import build_stack

        stack = build_stack(capacity=64, train_embedder_steps=0,
                            threshold=1.1)  # EXACT-or-MISS routing
        cache_cfg = stack.pop("cache_cfg")
        router_cfg = stack.pop("router_cfg")
        mesh = make_cache_mesh(4)
        group = ReplicaGroup.build(2, cache_cfg=cache_cfg,
                                   router_cfg=router_cfg, mesh=mesh, **stack)
        r0, r1 = group.engines
        text = "what is the airspeed of an unladen swallow"
        a = r0.handle_batch([text], max_new_tokens=4)
        b = r1.handle_batch([text], max_new_tokens=4)
        print(json.dumps({
            "n_dev": len(jax.devices()),
            "sharded": group.bank.sharded,
            "same_response": a == b,
            "r0": [r0.stats.miss, r0.stats.exact],
            "r1": [r1.stats.miss, r1.stats.exact],
            "agg": [group.stats.miss, group.stats.exact, group.stats.total],
        }))
    """, timeout=900)
    assert res["n_dev"] == 8
    assert res["sharded"]
    assert res["same_response"], res
    assert res["r0"] == [1, 0], res       # replica 0 took the miss
    assert res["r1"] == [0, 1], res       # replica 1 hit replica 0's write
    assert res["agg"] == [1, 1, 2], res


def test_production_mesh_shapes():
    res = run_device_script("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print(json.dumps({
            "single": [list(m1.axis_names),
                       [int(m1.shape[a]) for a in m1.axis_names]],
            "multi": [list(m2.axis_names),
                      [int(m2.shape[a]) for a in m2.axis_names]],
        }))
    """, n_dev=512)
    assert res["single"] == [["data", "model"], [16, 16]]
    assert res["multi"] == [["pod", "data", "model"], [2, 16, 16]]


def test_sharding_specs_divisibility():
    """Every generated spec must divide the production mesh axes."""
    import jax
    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.launch import sharding as shd
    from repro.launch.shapes import abstract_params

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
        axis_names = ("pod", "data", "model")

    mesh = FakeMesh()
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        params = abstract_params(cfg)
        specs = shd.param_specs(mesh, params)
        from jax.sharding import PartitionSpec
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, PartitionSpec))
        import numpy as np
        for p, s in zip(flat_p, flat_s):
            for dim, ax in zip(p.shape, tuple(s)):
                if ax is None:
                    continue
                names = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([mesh.shape[n] for n in names]))
                assert dim % size == 0, (arch, p.shape, tuple(s))
