"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig2_*      precision/recall of GPTCache-style caching   (paper Fig 2)
  frontier_*  router cost-quality frontier, 1-stage vs cascade (DESIGN.md §13)
  fig3_*      satisfaction per similarity band             (paper Fig 3)
  fig5/6/7_*  LLM-debate verdicts per band + control       (paper Figs 5-7)
  fig89_*     cache-hit distribution + cost analysis       (paper Figs 8-9)
  microbench  per-component latencies                      (paper Table 1)
  roofline_*  dry-run roofline terms per (arch x shape)    (§Roofline)
  scheduler   coalesced-vs-per-request + latency sweeps    (DESIGN.md §6)
  replicas    multi-replica scaling + shared-bank hits     (DESIGN.md §12)
  index       clustered (IVF) vs flat cache lookup         (DESIGN.md §7)
  generate    fused on-device vs host-loop decode          (DESIGN.md §8)
  prefill     prefix-KV reuse + suffix buckets vs full     (DESIGN.md §9)
  speculative cached-response draft verify vs plain decode (DESIGN.md §14)

  PYTHONPATH=src python -m benchmarks.run [--only fig2,...] \
      [--smoke] [--json BENCH_ci.json]

``--smoke`` runs the scaled-down CI subset
(index/scheduler/microbench/generate)
— the perf-gate job in .github/workflows/ci.yml.  ``--json`` dumps every
emitted metric in the repo-standard BENCH_*.json format that
``benchmarks.check_regression`` compares against a checked-in baseline.
"""
from __future__ import annotations

import argparse
import inspect
import json
import platform
import sys
import time
import traceback

SUITES = ("fig2", "frontier", "fig34567", "fig89", "microbench", "roofline",
          "scheduler", "replicas", "index", "generate", "prefill",
          "speculative")
SMOKE_SUITES = ("microbench", "index", "scheduler", "replicas", "generate",
                "prefill", "frontier", "speculative")
SCHEMA = "tweakllm-bench/v1"


def write_json(path: str, suites, smoke: bool) -> None:
    import jax
    from .common import RESULTS
    doc = {
        "schema": SCHEMA,
        "created_unix": int(time.time()),
        "smoke": smoke,
        "suites": list(suites),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "metrics": RESULTS,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {len(RESULTS)} metrics to {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down CI subset (index/scheduler/microbench)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump all emitted metrics as BENCH json")
    args, _ = ap.parse_known_args()
    default = SMOKE_SUITES if args.smoke else SUITES
    only = tuple(args.only.split(",")) if args.only else default

    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    from . import (bench_frontier, bench_generate, bench_index,
                   bench_prefill, bench_replicas, bench_scheduler,
                   bench_speculative, fig2_precision_recall,
                   fig34567_quality, fig89_cost_analysis, microbench,
                   roofline)
    mods = {
        "fig2": fig2_precision_recall,
        "frontier": bench_frontier,
        "fig34567": fig34567_quality,
        "fig89": fig89_cost_analysis,
        "microbench": microbench,
        "roofline": roofline,
        "scheduler": bench_scheduler,
        "replicas": bench_replicas,
        "index": bench_index,
        "generate": bench_generate,
        "prefill": bench_prefill,
        "speculative": bench_speculative,
    }
    print("name,us_per_call,derived")
    failures = 0
    for name in SUITES:
        if name not in only:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            fn = mods[name].main
            if "smoke" in inspect.signature(fn).parameters:
                fn(smoke=args.smoke)
            else:
                fn()
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0.0,{traceback.format_exc(limit=2)!r}")
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    if args.json:
        write_json(args.json, only, args.smoke)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
