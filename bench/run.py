"""Run one cell of the TweakLLM chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment through the program's ``build_stack``, with
weights and traffic drawn from ``--seed``; prefills the bank, warms every
program the window runs, then drives the scheduler on the wall clock for
``--seconds`` and drains it.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from spans, counters and a
profiler window inside the measured one; the program's own tracer
(``repro.serving.trace``) records its spans through the window of a
traced run, and stays off in an untraced one.  After the window the
deployment's state is freed and what it served is checked against the
plain references (``check.py``).

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared, with its limit); everything else
goes to stderr, whose last lines are the same numbers and limits.  Off a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  ``--control 1`` judges, in the same comparison, what
the plain reference one precision step down would have served in the
program's place, and so has to come out not correct (it shows the check
can fail; it is not part of a benchmark run).

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, with no size limit, so that only a cell's first run
there compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, cell=None, require_tpu: bool = True,
         sabotage=None) -> int:
    """One run.  Tests pass a ``cell`` of their own, ``require_tpu=False``
    and a ``sabotage(engine)`` that breaks the timed path underneath."""
    args = parse(argv)
    import jax

    from bench import check, harness, program_spans, tracing
    from bench.harness import log, now

    if cell is None:
        cell = harness.load_cell(args.workload)
    backend = jax.default_backend()
    devices = jax.devices()
    if require_tpu and backend != "tpu":
        log(f"bench: JAX's backend is {backend!r}, not 'tpu'; no result")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, found "
            f"{len(devices)}; no result")
        return 2
    kind = devices[0].device_kind
    if require_tpu:
        harness.peaks(kind)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Scheduler, SchedulerConfig, WallClock
    from repro.serving import trace as program_trace
    log(f"device {devices[0].platform} {kind} x{len(devices)}, "
        f"compile cache {enable_compile_cache()}")
    # every program cached, however quick to compile or small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    rec = harness.Recorder(annotate=bool(args.trace))
    jax.monitoring.register_event_duration_secs_listener(rec.on_event)
    jax.monitoring.register_event_listener(rec.on_event)
    sc = cell.config["scheduler"]
    t = now()
    engine, stack, weights = harness.build(cell, args.seed)
    log(f"build {now() - t:.2f} s")
    if cell.traffic["arrivals"] == "poisson":
        n_live = int(cell.traffic["rate_per_s"] * args.seconds * 2) + 256
    else:
        n_live = int(cell.traffic["clients"] * (1 + 64 * args.seconds))
    t = now()
    pairs, live, warm = harness.traffic_streams(
        cell, args.seed, n_live, sc["max_batch"] * (sc["max_batch"] + 64))
    harness.populate(engine, pairs)
    jax.block_until_ready(engine.bank.state)
    log(f"bank prefill {len(pairs)} pairs {now() - t:.2f} s")
    if sabotage is not None:
        sabotage(engine)
    harness.instrument(engine, rec)
    sched = Scheduler(engine, SchedulerConfig(
        max_batch=sc["max_batch"], max_wait=sc["max_wait"],
        max_new_tokens=sc["max_new_tokens"],
        cost_threshold=sc["cost_threshold"]), clock=WallClock())
    t = now()
    warmed = harness.warm_up(engine, cell, warm, rec)
    log(f"warm-up {now() - t:.2f} s: {warmed}")
    stats0 = dict(vars(engine.stats))
    tracer = (harness.Tracer(0.6 * args.seconds,
                             min(3.0, 0.3 * args.seconds), rec)
              if args.trace else None)
    setup_s = now() - T_START
    w = harness.Window(sched, live, cell.traffic, args.seconds, args.seed,
                       rec, tracer)
    rec.phase = "window"
    drive = harness.load_module("arrivals", cell.traffic["arrivals"]).drive
    program_records = None
    if args.trace:
        program_trace.start()
        try:
            drive(w)
        finally:
            program_records = program_trace.stop()
    else:
        drive(w)
    rec.phase = "after"
    t_drained = now()
    stats = {k: v - stats0[k] for k, v in vars(engine.stats).items()
             if isinstance(v, int)}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])

    from bench.reference import route as ref_route
    vocab = cell.config["router_models"]["vocab_size"]
    for r in w.reqs:
        if r.meta is None:
            continue
        if r.decision == ref_route.EXACT:
            r.tokens = len(ref_route.word_ids(r.response, vocab, bos=False))
        else:
            r.tokens = int(r.meta["gen_tokens"])
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      reqs=w.reqs, t0=w.t0, rec=rec, setup_s=setup_s,
                      peak=peak, kind=kind, t_close=w.t_close,
                      program_records=program_records)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if tracer is not None:
        events = tracing.load(tracer.dir)
        tracer.cleanup()
        log(f"trace: {tracing.summary(events)}")
        red = tracing.reduce(events)
        if red is not None:
            run.trace, run.trace_events = red, events
            run.trace_offset = tracer.t_start - red["lo"] / 1e9
            run.trace_start = tracer.t_start
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            idle = program_spans.idle_by_span(events, red["planes"][0],
                                              red["lo"], red["hi"])
            log(f"idle by span: {idle}")
    metrics = harness.read_metrics(
        run, cell.per_layer if args.trace else cell.end_to_end)

    measured = run.measured()
    routes = {k: sum(r.decision == v for r in measured)
              for k, v in (("miss", 0), ("tweak", 1), ("exact", 2))}
    lateness = sorted(r.sent - r.due for r in w.reqs)
    log(f"window {args.seconds} s, drained {t_drained - w.end:.2f} s after; "
        f"requests {len(w.reqs)} attempted, {len(measured)} measured, "
        f"{w.rejected} rejected; routes {routes}; engine {stats}")
    log(f"dispatches {len(run.window_dispatches())}, generate calls "
        f"miss {len(run.window_gens('miss'))} tweak "
        f"{len(run.window_gens('tweak'))}; generator lateness p50 "
        f"{1e3 * lateness[len(lateness) // 2]:.2f} ms max "
        f"{1e3 * lateness[-1]:.2f} ms; programs {rec.compiles}; "
        f"peak HBM {peak}; compile cache {harness.dir_size(CACHE_DIR)}")

    # free the deployment before the references run
    gens, dispatch_n = rec.gens, len(rec.dispatches)
    emb_params = stack["embedder_params"]
    rr = stack.get("reranker")
    model = cell.config["model"]
    del sched, engine, stack, w.sched
    gc.collect()
    t = now()
    control = bool(args.control)
    numbers = {"unanswered": sum(r.failed or r.response is None
                                 for r in run.reqs),
               # a program first run inside the window: it compiled, or
               # was loaded, where the window is timed
               "window_programs": rec.programs("window")}
    info = {}
    info.update(check.exact_check(run, pairs))
    numbers["exact_mismatch"] = info.pop("exact_mismatch")
    info.update(check.route_check(run, pairs, emb_params, rr,
                                  control=control))
    numbers["route_mismatch"] = info.pop("route_mismatch")
    rows = check.sample_rows(check.generated_rows(gens), args.seed)
    if rows:
        info.update(check.logit_gaps(weights, rows, model,
                                     cell.config["family"], control=control))
        numbers["gen_logit_gap"] = info.pop("gen_logit_gap")
    limits = {k: v for k, v in cell.config["limits"].items()
              if k in numbers}
    correct = check.verdict(numbers, limits)
    log(f"check {now() - t:.2f} s over {dispatch_n} dispatches, "
        f"{len(rows)} sampled rows{' (control)' if control else ''}: {info}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k, v in checks.items():
        log(f"{k} {v['value']} limit {v['limit']}")
    result = {"correct": bool(correct), "attempted": len(run.reqs),
              "failed": int(numbers["unanswered"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # read by JAX when it is first imported, in main()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.exit(main())
