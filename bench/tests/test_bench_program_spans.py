"""The readers of the program's own spans and of the lookup program's
device time: on a traced tiny-cell run, in which ``bench/run.py`` turns the
program's tracer on for the window, and on synthetic records and traces."""
import json
import types

import pytest

import tiny_cell
from bench import harness, program_spans
from bench.tracing import WINDOW_SPAN, Event

PROGRAM_METRICS = {"sched_queue_wait_p90_ms": "ms", "sched_batch_fill": "ratio",
                   "engine_route_ms": "ms", "engine_host_ms": "ms"}
DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def _tiny_run(monkeypatch, capsys, trace: int):
    """A tiny-cell run; its result line and the ``Run`` its metrics read."""
    from bench import run
    got = {}
    read_metrics = harness.read_metrics

    def read_metrics_(r, names):
        got["run"] = r
        return read_metrics(r, names)

    monkeypatch.setattr(harness, "read_metrics", read_metrics_)
    tiny_cell.patch_program(monkeypatch)
    rc = run.main(["--workload", tiny_cell.STAND_IN, "--seed",
                   str(tiny_cell.SEED), "--seconds", "2", "--trace",
                   str(trace)], cell=tiny_cell.cell(), require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1]), got["run"]


def test_traced_tiny_run_reports_the_program_span_metrics(monkeypatch,
                                                          capsys):
    res, run = _tiny_run(monkeypatch, capsys, trace=1)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] is True
    assert set(PROGRAM_METRICS) <= set(m)
    assert {k: res["metrics"][k]["unit"] for k in PROGRAM_METRICS} == \
        PROGRAM_METRICS
    assert "lookup_device_ms" not in m          # no device trace off the chip
    assert m["sched_batch_fill"] == pytest.approx(m["batch_fill"], abs=1e-12)
    assert m["engine_route_ms"] <= m["route_ms"]
    assert 0 < m["engine_host_ms"] <= m["engine_route_ms"]
    assert m["sched_queue_wait_p90_ms"] > 0
    assert run.program_records.spans and run.program_records.requests


def test_untraced_run_starts_no_program_tracer(monkeypatch, capsys):
    from repro.serving import trace
    starts = []
    start = trace.start
    monkeypatch.setattr(trace, "start",
                        lambda: starts.append(1) or start())
    res, run = _tiny_run(monkeypatch, capsys, trace=0)
    assert res["correct"] is True
    assert starts == [] and not trace.active()
    assert run.program_records is None
    assert not set(PROGRAM_METRICS) & set(res["metrics"])


def test_program_span_metrics_are_in_the_benchmark():
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name, unit in PROGRAM_METRICS.items():
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["unit"] == unit
        assert per_layer[name]["workloads"] == [tiny_cell.STAND_IN]


def _span(i, name, s, e, parent=None, dispatch=0, **attrs):
    from repro.serving.trace import Span
    return Span(i, parent, name, int(s * MS), int(e * MS), dispatch, attrs)


def _run(spans, requests=()):
    from repro.serving.trace import Records
    cell = types.SimpleNamespace(config={"scheduler": {"max_batch": 4}})
    return types.SimpleNamespace(
        program_records=Records(list(spans), list(requests)),
        t0=0.0, host_end=1.0, cell=cell)


def test_engine_readers_on_synthetic_records():
    engine_host_ms, engine_route_ms = (harness.load_module("metrics", n)
                                       for n in ("engine_host_ms",
                                                 "engine_route_ms"))
    spans = [
        # dispatch 0, 10 ms: sync 2-5, a MISS 6-9 with its insert 8-9.5
        _span(0, "engine.dispatch", 0, 10),
        _span(1, "engine.route_sync", 2, 5, parent=0),
        _span(2, "engine.miss", 6, 9, parent=0),
        _span(3, "engine.insert", 8, 9.5, parent=2),
        # dispatch 1, 4 ms, EXACT only: sync 1 ms
        _span(4, "engine.dispatch", 20, 24, dispatch=1),
        _span(5, "engine.route_sync", 21, 22, parent=4, dispatch=1),
        # after the host stretch: not read
        _span(6, "engine.dispatch", 1001, 1002, dispatch=2),
    ]
    run = _run(spans)
    # route: 6 ms to the MISS, then the whole 4 ms
    assert engine_route_ms.read(run) == pytest.approx(5.0)
    # self time: 10 - (3 + 3.5) = 3.5, 4 - 1 = 3
    assert engine_host_ms.read(run) == pytest.approx(3.25)
    assert engine_host_ms.read(types.SimpleNamespace()) is None


def test_scheduler_readers_on_synthetic_records():
    sched_batch_fill, sched_queue_wait_p90_ms = (
        harness.load_module("metrics", n)
        for n in ("sched_batch_fill", "sched_queue_wait_p90_ms"))
    from repro.serving.trace import RequestRecord
    spans = [_span(0, "scheduler.dispatch", 0, 10, rows=4, requests=5),
             _span(1, "scheduler.dispatch", 10, 20, rows=2, requests=2)]
    reqs = [RequestRecord(i, 0.001 * i, 0.001 * i + 0.010 * (i + 1), 0.5, 0)
            for i in range(10)]
    run = _run(spans, reqs)
    assert sched_batch_fill.read(run) == pytest.approx(0.75)
    # waits 10, 20, ..., 100 ms: the 90th percentile lies at 91 ms
    assert sched_queue_wait_p90_ms.read(run) == pytest.approx(91.0)


def _trace():
    return [
        Event(HOST, "python", WINDOW_SPAN, 0, 100 * MS),
        Event(HOST, "python", "bench.dispatch", 10 * MS, 60 * MS),
        Event(HOST, "python", "engine.dispatch", 11 * MS, 58 * MS),
        Event(HOST, "python", "engine.route_sync", 20 * MS, 30 * MS),
        Event(HOST, "python", "host.gc", 60 * MS, 5 * MS),
        Event(HOST, "python", "bench.wait", 75 * MS, 20 * MS),
        Event(HOST, "python", "some_python_function", 0, 100 * MS),
        Event(DEV, "XLA Ops", "fusion.1", 12 * MS, 3 * MS),
        Event(DEV, "XLA Ops", "sort.1", 22 * MS, 26 * MS),
        Event(DEV, "XLA Modules", "jit_lookup_route_touch(3)", 21 * MS,
              28 * MS),
        Event(DEV, "XLA Modules", "jit_lookup_route_touch(3)", 90 * MS,
              2 * MS),
        Event(DEV, "XLA Modules", "jit_embed_encode(2)", 12 * MS, 3 * MS),
        Event(DEV, "XLA Ops", "sort.1", 90 * MS, 2 * MS),
    ]


def test_idle_by_span_names_the_innermost_span():
    got = program_spans.idle_by_span(_trace(), DEV, 0, 100 * MS)
    want = {
        # [0, 10], [70, 75], [95, 100]
        "outside any span": 0.010 + 0.005 + 0.005,
        "bench.dispatch": 0.001 + 0.001,            # [10, 11], [69, 70]
        # [11, 12], [15, 20], [50, 60], [65, 69]: inside the engine's
        # dispatch, which is inside the harness's
        "engine.dispatch": 0.001 + 0.005 + 0.010 + 0.004,
        "engine.route_sync": 0.002 + 0.002,         # [20, 22], [48, 50]
        "host.gc": 0.005,                           # [60, 65]
        "bench.wait": 0.015 + 0.003,                # [75, 90], [92, 95]
    }
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v, abs=1e-12), k
    # every idle instant is counted once: 100 ms less 31 ms busy
    assert sum(got.values()) == pytest.approx(0.069, abs=1e-12)


def test_lookup_device_ms_reads_the_named_program():
    from bench import tracing
    lookup_device_ms = harness.load_module("metrics", "lookup_device_ms")
    ev = _trace()
    red = tracing.reduce(ev)
    run = types.SimpleNamespace(trace=red, trace_events=ev)
    assert lookup_device_ms.read(run) == pytest.approx(15.0)
    anonymous = [e._replace(name="jit__lambda(3)")
                 if e.name.startswith("jit_lookup") else e for e in ev]
    run = types.SimpleNamespace(trace=red, trace_events=anonymous)
    assert lookup_device_ms.read(run) is None
    assert lookup_device_ms.read(types.SimpleNamespace(trace=None)) is None
