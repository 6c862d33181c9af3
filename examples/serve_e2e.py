"""End-to-end serving driver: a TweakLLM deployment with REAL generation.

Pretrains tiny Big/Small LMs on the synthetic corpus (big deeper than
small), trains the embedder contrastively, then replays a Zipfian arrival
trace through the continuous-batching scheduler (DESIGN.md §6) over the
full router: misses generate with the Big LM and populate the cache,
paraphrase hits run the Appendix-A tweak prompt through the Small LM,
exact repeats return verbatim, and identical in-flight requests join one
dispatch.

  PYTHONPATH=src python examples/serve_e2e.py [--queries 120]
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import CacheConfig, RouterConfig, TweakLLMEngine
from repro.data import WorkloadGenerator, token_stream_batches
from repro.models import ModelConfig, build_model
from repro.models.embedder import init_embedder, tiny_embedder_config
from repro.serving import (GenerateConfig, Generator, SamplerConfig,
                           Scheduler, SchedulerConfig, SimClock,
                           poisson_trace, replay_trace)
from repro.tokenizer import HashWordTokenizer
from repro.training import AdamWConfig, init_opt_state, make_train_step
from repro.training.embedder_train import train_embedder

VOCAB = 8192


def pretrain_lm(cfg, steps, seed, tok):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(model, AdamWConfig(lr=3e-3),
                                   total_steps=steps))
    opt = init_opt_state(params)
    stream = token_stream_batches(tok, 8, 64, seed=seed)
    first = last = None
    for _ in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
        params, opt, m = step(params, opt, batch)
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    print(f"  {cfg.name}: loss {first:.2f} -> {last:.2f} over {steps} steps")
    return model, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=80)
    args = ap.parse_args()

    tok = HashWordTokenizer(VOCAB)
    print("pretraining Big and Small LMs on the synthetic corpus...")
    big_cfg = ModelConfig(name="big-lm", num_layers=4, d_model=128,
                          num_heads=8, num_kv_heads=4, d_ff=256,
                          vocab_size=VOCAB, max_seq_len=1024, dtype="float32")
    # fixed-block flash attention qualifies the small model for the
    # engine's shared-prefix KV reuse on TWEAK hits (DESIGN.md §9)
    small_cfg = big_cfg.replace(name="small-lm", num_layers=2, d_model=96,
                                num_heads=4, num_kv_heads=2, d_ff=192,
                                attention_impl="xla_flash",
                                flash_block_q=32, flash_block_k=32)
    big_m, big_p = pretrain_lm(big_cfg, args.steps, 1, tok)
    small_m, small_p = pretrain_lm(small_cfg, args.steps, 2, tok)

    print("training embedder contrastively...")
    ecfg = tiny_embedder_config(VOCAB)
    eparams = init_embedder(jax.random.PRNGKey(0), ecfg)
    eparams, losses = train_embedder(eparams, ecfg, tok, steps=60, batch=16)
    print(f"  InfoNCE {losses[0]:.2f} -> {losses[-1]:.2f}")

    gen_cfg = GenerateConfig(max_new_tokens=12,
                             sampler=SamplerConfig(vocab_size=VOCAB))
    eng = TweakLLMEngine(
        tokenizer=tok, embedder_params=eparams, embedder_cfg=ecfg,
        big=Generator(big_m, big_p, gen_cfg),
        small=Generator(small_m, small_p, gen_cfg),
        cache_cfg=CacheConfig(capacity=1024, dim=ecfg.d_model),
        router_cfg=RouterConfig(tweak_threshold=0.7))

    wl = WorkloadGenerator(profile="lmsys", seed=0)
    texts = [q.text for q in wl.sample(args.queries)]
    trace = poisson_trace(texts, rate=100.0, seed=0)
    sched = Scheduler(
        eng, SchedulerConfig(max_wait=0.1, max_batch=args.batch,
                             max_new_tokens=12),
        clock=SimClock())
    print(f"replaying {args.queries} arrivals through the scheduler "
          f"(max_batch={args.batch})...")
    t0 = time.time()
    done = replay_trace(sched, trace)
    dt = time.time() - t0
    assert len(done) == len(texts) - sched.stats.rejected

    s, ss = eng.stats, sched.stats
    print(f"\n== serving report ==")
    dev = jax.devices()[0]
    print(f"requests {ss.completed} in {dt:.1f}s "
          f"({dt/max(ss.completed,1)*1e3:.0f} ms/req wall on "
          f"{dev.platform} {dev.device_kind})")
    print(f"scheduler: batches={ss.batches} mean_batch={ss.mean_batch:.1f} "
          f"dedup_joined={ss.joined}")
    print(f"routing: miss={s.miss} tweak={s.tweak} exact={s.exact} "
          f"(hit rate {s.hit_rate:.1%})")
    print(f"generated tokens: big={s.big_tokens} small={s.small_tokens}")
    print(f"cost: {s.cost:,.0f} vs all-big {s.baseline_cost:,.0f} "
          f"= {s.cost/max(s.baseline_cost,1):.1%} of baseline "
          f"(paper: 35% on LMSYS)")


if __name__ == "__main__":
    main()
