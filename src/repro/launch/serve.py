"""Serving launcher: a TweakLLM deployment on synthetic chat traffic.

Builds the full stack (embedder + big + small + sharded-capable cache +
router), replays a Zipfian arrival trace through the continuous-batching
scheduler (DESIGN.md §6: queue -> coalesce -> dedup -> dispatch), and
reports the paper's §5.2.3 economics — hit-rate split, token volumes,
cost vs all-Big baseline — plus the scheduler's coalescing stats.

  PYTHONPATH=src python -m repro.launch.serve --queries 200 --profile lmsys
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --queries 48
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.configs import get_config
from repro.core import (CacheConfig, ReplicaGroup, RouterConfig,
                        TweakLLMEngine)
from repro.data import WorkloadGenerator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_cache_mesh
from repro.models import ModelConfig, build_model
from repro.models.embedder import tiny_embedder_config, init_embedder
from repro.models.reranker import tiny_reranker_config, init_reranker
from repro.serving import (GenerateConfig, Generator, ReplicaScheduler,
                           SamplerConfig, Scheduler, SchedulerConfig,
                           SimClock, poisson_trace, replay_trace)
from repro.tokenizer import HashWordTokenizer
from repro.training.embedder_train import train_embedder
from repro.training.reranker_train import train_reranker

# The lmsys sample of this seed holds an exact resend (request 13 repeats
# request 0, two batches later), so a 48-query trace fires EXACT as well.
TRACE_SEED = 4


def build_stack(*, arch: Optional[str] = None, vocab: int = 8192,
                capacity: int = 4096,
                train_embedder_steps: int = 60, policy: str = "fifo",
                lookup_impl: str = "xla", index: str = "flat",
                nclusters: int = 0, nprobe: int = 8, threshold: float = 0.7,
                band: float = 0.0, train_reranker_steps: int = 120,
                admit_floor: float = 0.0, seed: int = 0):
    """Shared model stack + configs for one engine or a replica group.

    ``arch`` names a config in ``repro.configs`` (``--arch``): the MISS
    and the TWEAK generator then both serve that model at its published
    widths and dtype, from ONE set of seeded weights (one copy on the
    device), and the tokenizer, embedder and sampler take its
    vocabulary (``vocab`` is ignored).  Without ``arch`` a toy float32
    pair serves: a 4-layer d128 MISS model and a 2-layer d64 TWEAK
    model, sized for CPU tests.

    ``band > 0`` turns on the router cascade (DESIGN.md §13): the stack
    then also builds + trains the cross-encoder reranker the second
    stage scores shortlists with, returned under the ``reranker`` key
    that ``TweakLLMEngine`` / ``ReplicaGroup.build`` accept.
    """
    if arch is None:
        big_cfg = ModelConfig(name="big", num_layers=4, d_model=128,
                              num_heads=8, num_kv_heads=4, d_ff=256,
                              vocab_size=vocab, max_seq_len=1024,
                              dtype="float32")
        # The small (tweak) model uses fixed-block flash attention so the
        # engine's shared-prefix KV reuse applies on every TWEAK hit
        # (DESIGN.md §9) — naive/auto softmax would disqualify it from the
        # byte-identical prefix-prefill contract.
        small_cfg = big_cfg.replace(name="small", num_layers=2, d_model=64,
                                    num_heads=4, num_kv_heads=2, d_ff=128,
                                    attention_impl="xla_flash",
                                    flash_block_q=32, flash_block_k=32)
        big_m, small_m = build_model(big_cfg), build_model(small_cfg)
        big_params = big_m.init(jax.random.PRNGKey(1))  # seed: ok demo CLI, fixed init for reproducibility
        small_params = small_m.init(jax.random.PRNGKey(2))  # seed: ok demo CLI, fixed init for reproducibility
    else:
        big_cfg = get_config(arch)
        vocab = big_cfg.vocab_size
        # TWEAK keeps the fixed-block flash attention prefix reuse needs.
        # Queries tile 32 rows (suffix buckets start at 16); keys tile 128,
        # one lane width, which covers the 45-token instruction prefix
        # plus a typical suffix in one block.
        small_cfg = big_cfg.replace(attention_impl="xla_flash",
                                    flash_block_q=32, flash_block_k=128)
        big_m, small_m = build_model(big_cfg), build_model(small_cfg)
        big_params = small_params = jax.jit(big_m.init)(
            jax.random.PRNGKey(1))  # seed: ok demo CLI, fixed init for reproducibility
    tok = HashWordTokenizer(vocab)
    ecfg = tiny_embedder_config(vocab)
    eparams = init_embedder(jax.random.PRNGKey(seed), ecfg)
    if train_embedder_steps:
        eparams, _ = train_embedder(eparams, ecfg, tok,
                                    steps=train_embedder_steps, batch=16)
    gen_cfg = GenerateConfig(max_new_tokens=16,
                             sampler=SamplerConfig(vocab_size=vocab))
    big = Generator(big_m, big_params, gen_cfg)
    small = Generator(small_m, small_params, gen_cfg)
    cache_cfg = CacheConfig(capacity=capacity, dim=ecfg.d_model,
                            policy=policy, lookup_impl=lookup_impl,
                            index=index, nclusters=nclusters, nprobe=nprobe)
    stack = dict(tokenizer=tok, embedder_params=eparams, embedder_cfg=ecfg,
                 big=big, small=small, cache_cfg=cache_cfg,
                 router_cfg=RouterConfig(tweak_threshold=threshold,
                                         band=band, admit_floor=admit_floor))
    if band > 0.0:
        rr_cfg = tiny_reranker_config(vocab)
        rr_params = init_reranker(jax.random.PRNGKey(seed + 3), rr_cfg)
        if train_reranker_steps:
            rr_params, _ = train_reranker(rr_params, rr_cfg, tok,
                                          steps=train_reranker_steps)
        stack["reranker"] = (rr_params, rr_cfg)
    return stack


def build_engine(**kw):
    return TweakLLMEngine(**build_stack(**kw))


def build_replica_group(n: int, *, shared: bool = True,
                        cache_shards: int = 0, **kw) -> ReplicaGroup:
    """``n`` replicas over one shared bank (model weights replicated —
    the Generators are shared handles, so compiled functions are too).
    ``cache_shards > 1`` row-shards the bank over that many devices."""
    stack = build_stack(**kw)
    mesh = make_cache_mesh(cache_shards) if cache_shards > 1 else None
    return ReplicaGroup.build(n, shared=shared, mesh=mesh, **stack)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="serve this config from repro.configs at its "
                         "published widths (e.g. qwen2.5-3b); default: a "
                         "toy float32 pair")
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8,
                    help="scheduler max_batch (unique queries per dispatch)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="simulated arrival rate (requests/s)")
    ap.add_argument("--max-wait", type=float, default=0.05,
                    help="scheduler coalescing deadline (simulated s)")
    ap.add_argument("--profile", default="lmsys", choices=["lmsys", "wildchat"])
    ap.add_argument("--threshold", type=float, default=0.7)
    ap.add_argument("--cost-threshold", type=float, default=None,
                    help="routing operating point in [0,1] applied to every "
                         "request (DESIGN.md §13); default: the router's "
                         "calibrated default cost")
    ap.add_argument("--band", type=float, default=0.0,
                    help="uncertainty band width around the TWEAK/MISS "
                         "boundary; > 0 enables the reranker second stage")
    ap.add_argument("--reranker-steps", type=int, default=120,
                    help="training steps for the cascade reranker "
                         "(only used when --band > 0)")
    ap.add_argument("--admit-floor", type=float, default=0.0,
                    help="suppress cache inserts for IVF clusters whose "
                         "hit EMA falls below this (0 = admit everything)")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "lru", "lfu"])
    ap.add_argument("--index", default="flat", choices=["flat", "ivf"],
                    help="cache lookup index (ivf = clustered, DESIGN.md §7)")
    ap.add_argument("--embedder-steps", type=int, default=60)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas over ONE shared cache bank "
                         "(DESIGN.md §12)")
    ap.add_argument("--cache-shards", type=int, default=0,
                    help="row-shard the shared bank over this many devices "
                         "(needs forced host devices on CPU; 0 = local)")
    ap.add_argument("--private-caches", action="store_true",
                    help="give each replica a private bank (the degraded "
                         "baseline the replica bench compares against)")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache {cache_dir}")
    print("building TweakLLM stack (training embedder contrastively)...")
    kw = dict(arch=args.arch, threshold=args.threshold, policy=args.policy,
              index=args.index,
              train_embedder_steps=args.embedder_steps, band=args.band,
              train_reranker_steps=args.reranker_steps,
              admit_floor=args.admit_floor)
    scfg = SchedulerConfig(max_wait=args.max_wait, max_batch=args.batch,
                           max_new_tokens=8,
                           cost_threshold=args.cost_threshold)
    if args.replicas > 1 or args.cache_shards > 1:
        group = build_replica_group(args.replicas,
                                    shared=not args.private_caches,
                                    cache_shards=args.cache_shards, **kw)
        sched = ReplicaScheduler(group.engines, scfg, clock=SimClock())
        stats_src = group
    else:
        eng = build_engine(**kw)
        sched = Scheduler(eng, scfg, clock=SimClock())
        stats_src = eng
    wl = WorkloadGenerator(profile=args.profile, seed=TRACE_SEED)
    texts = [q.text for q in wl.sample(args.queries)]
    trace = poisson_trace(texts, args.rate, seed=TRACE_SEED)
    t0 = time.time()
    done = replay_trace(sched, trace)
    dt = time.time() - t0
    # shedding (QueueFull) is a designed outcome under overload, not a bug
    assert len(done) == len(texts) - sched.stats.rejected

    s, ss = stats_src.stats, sched.stats
    print(f"\n== TweakLLM serving report ({args.profile} profile) ==")
    print(f"requests: {ss.completed}  ({dt/max(ss.completed,1)*1e3:.1f} "
          f"ms/request wall on {dev.platform} {dev.device_kind}, "
          f"compiles included)")
    print(f"scheduler: batches={ss.batches} mean_batch={ss.mean_batch:.1f} "
          f"dedup_joined={ss.joined} rejected={ss.rejected}")
    if args.replicas > 1:
        lanes = " ".join(
            f"r{i}:{lane.dispatched}d/{lane.batches}b+{lane.stolen_in}st"
            for i, lane in enumerate(sched.lanes))
        print(f"replicas: {args.replicas} "
              f"({'shared' if not args.private_caches else 'private'} bank, "
              f"shards={max(args.cache_shards, 1)}) {lanes} "
              f"stolen={ss.stolen}")
    print(f"routing: miss={s.miss} tweak={s.tweak} exact={s.exact} "
          f"hit_rate={s.hit_rate:.2%} (+{ss.joined} joined in flight)")
    if args.band > 0 or args.admit_floor > 0:
        print(f"cascade: uncertain={s.uncertain} recovered={s.recovered} "
              f"suppressed_inserts={s.suppressed_inserts} "
              f"(band={args.band} cost="
              f"{args.cost_threshold if args.cost_threshold is not None else 'default'})")
    print(f"tokens:  big={s.big_tokens} small={s.small_tokens}")
    print(f"cost:    {s.cost:,.0f} vs all-big {s.baseline_cost:,.0f} "
          f"-> {s.cost/max(s.baseline_cost,1):.2%} of baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
