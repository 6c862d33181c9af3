"""Program spans and request records (``repro.serving.trace``): nothing is
recorded or annotated while off; while on, the scheduler's and engine's
spans nest per dispatch, request records point at their dispatch, a
collection is a ``host.gc`` span, and spans share the profiler's clock.
The served programs carry stable names."""
import gc
import glob
import os

import jax
import pytest

from repro.core import CacheConfig, RouterConfig, TweakLLMEngine
from repro.models import ModelConfig, build_model
from repro.models.embedder import init_embedder, tiny_embedder_config
from repro.serving import (GenerateConfig, Generator, ReplicaScheduler,
                           SamplerConfig, Scheduler, SchedulerConfig,
                           SimClock, trace)
from repro.tokenizer import HashWordTokenizer

VOCAB = 4096
# two copies of one text join one dispatch row; the repeats after the
# first dispatch are served EXACT, the near-repeats TWEAK or MISS
TEXTS = ["how do i learn python setup", "how do i learn python setup",
         "why is keto diet good", "best way to cook rice",
         "how do i learn python setup", "what makes keto diet worthwhile",
         "best way to cook rice", "tips for running a marathon"]


@pytest.fixture(scope="module")
def stack():
    tok = HashWordTokenizer(VOCAB)
    ecfg = tiny_embedder_config(VOCAB)
    eparams = init_embedder(jax.random.PRNGKey(0), ecfg)
    lm = ModelConfig(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                     d_ff=64, vocab_size=VOCAB, max_seq_len=512,
                     dtype="float32")
    gcfg = GenerateConfig(max_new_tokens=4,
                          sampler=SamplerConfig(vocab_size=VOCAB))
    m = build_model(lm)
    big = Generator(m, m.init(jax.random.PRNGKey(1)), gcfg)
    small = Generator(m, m.init(jax.random.PRNGKey(2)), gcfg)
    return tok, ecfg, eparams, big, small


def _engine(stack):
    tok, ecfg, eparams, big, small = stack
    return TweakLLMEngine(
        tokenizer=tok, embedder_params=eparams, embedder_cfg=ecfg,
        big=big, small=small,
        cache_cfg=CacheConfig(capacity=128, dim=ecfg.d_model, topk=4),
        router_cfg=RouterConfig(tweak_threshold=0.3))


def _serve(stack):
    """A few dispatches of at most 4 rows on a SimClock."""
    clock = SimClock()
    sched = Scheduler(_engine(stack), SchedulerConfig(
        max_batch=4, max_wait=0.01, max_new_tokens=4), clock=clock)
    done = []
    for chunk in (TEXTS[:5], TEXTS[5:]):
        for t in chunk:
            clock.advance(0.001)
            sched.submit(t)
        clock.advance(0.05)
        done += sched.poll()
    done += sched.flush()
    return sched, done


@pytest.fixture(autouse=True)
def _tracer_off():
    trace.stop()
    yield
    trace.stop()


class _CountingAnnotation:
    entered = 0

    def __init__(self, name, **kw):
        pass

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_off_records_nothing_and_enters_no_annotation(stack, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    assert trace.span("engine.dispatch") is trace.span("other", rows=3)
    assert trace.next_dispatch() is None
    sched, done = _serve(stack)
    gc.collect()
    assert len(done) == len(TEXTS) and sched.stats.batches >= 2
    assert _CountingAnnotation.entered == 0
    assert trace.stop() == trace.Records([], [])


def test_on_spans_nest_per_dispatch_and_requests_point_at_them(stack):
    trace.start()
    sched, done = _serve(stack)
    rec = trace.stop()
    by_id = {s.id: s for s in rec.spans}
    disp = [s for s in rec.spans if s.name == "scheduler.dispatch"]
    assert len(disp) == sched.stats.batches
    assert sum(s.attrs["rows"] for s in disp) == sched.stats.dispatched
    assert sum(s.attrs["requests"] for s in disp) == len(TEXTS)
    assert any(s.attrs["requests"] > s.attrs["rows"] for s in disp)
    assert len({s.dispatch for s in disp}) == len(disp)
    for d in disp:
        kids = {s.name: s for s in rec.spans if s.parent == d.id}
        assert set(kids) == {"engine.dispatch", "scheduler.complete"}
        e = kids["engine.dispatch"]
        assert e.attrs["rows"] == d.attrs["rows"]
        assert d.start_ns <= e.start_ns <= e.end_ns <= d.end_ns
    for s in rec.spans:
        if not s.name.startswith("engine.") or s.name == "engine.dispatch":
            continue
        p = s
        while p.name != "engine.dispatch":
            p = by_id[p.parent]
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
        assert s.dispatch == p.dispatch is not None
    names = {s.name for s in rec.spans}
    assert {"engine.tokenize", "engine.route", "engine.route_sync",
            "engine.exact", "engine.miss", "engine.insert"} <= names
    assert "engine.stage2" not in names     # no cascade configured
    ids = {s.dispatch for s in disp}
    assert sorted(r.rid for r in rec.requests) == list(range(len(TEXTS)))
    for r in rec.requests:
        assert r.dispatch in ids
        assert r.arrival <= r.dispatched <= r.finish
    req = {r.rid: r for r in rec.requests}
    assert all(req[h.rid].finish == h.finish for h in done)


def test_replica_dispatches_carry_their_lane(stack):
    clock = SimClock()
    sched = ReplicaScheduler([_engine(stack), _engine(stack)],
                             SchedulerConfig(max_batch=2, max_wait=0.01,
                                             max_new_tokens=4), clock=clock)
    trace.start()
    for t in TEXTS:
        clock.advance(0.001)
        sched.submit(t)
    clock.advance(0.05)
    done = sched.poll() + sched.flush()
    rec = trace.stop()
    disp = [s for s in rec.spans if s.name == "scheduler.dispatch"]
    assert {s.attrs["lane"] for s in disp} == {0, 1}
    assert [lane.batches for lane in sched.lanes] == [
        sum(s.attrs["lane"] == i for s in disp) for i in (0, 1)]
    assert len(rec.requests) == len(done) == len(TEXTS)
    assert {r.dispatch for r in rec.requests} <= {s.dispatch for s in disp}


def test_forced_collection_is_a_gc_span():
    trace.start()
    with trace.span("outer", dispatch=7):
        gc.collect()
    rec = trace.stop()
    g = [s for s in rec.spans if s.name == "host.gc"]
    outer, = [s for s in rec.spans if s.name == "outer"]
    assert g and g[-1].attrs["generation"] == 2
    assert g[-1].parent == outer.id and g[-1].dispatch == 7
    assert outer.start_ns <= g[-1].start_ns <= g[-1].end_ns <= outer.end_ns


def test_spans_share_the_profiler_clock(tmp_path):
    import time

    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    trace.start()
    for k in range(6):
        with trace.span(f"test.clock.{k}"):
            time.sleep(0.002)
        time.sleep(0.003)
    rec = trace.stop()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    starts = {ev.name: ev.start_ns for pl in data.planes for ln in pl.lines
              for ev in ln.events if ev.name.startswith("test.clock.")}
    offsets = [starts[s.name] - s.start_ns for s in rec.spans
               if s.name.startswith("test.clock.")]
    assert len(offsets) == 6
    assert max(offsets) - min(offsets) < 50_000


def test_served_programs_have_stable_names(stack):
    eng = _engine(stack)
    assert eng.bank._lookup_touch.__name__ == "lookup_route_touch"
    assert eng._embed.__name__ == "embed_encode"
    assert eng.bank._insert.__name__ == "insert_batch"
    q = eng.embed_texts(["a query"])
    hlo = eng.bank._lookup_touch.lower(
        eng.bank.state, q, eng.bank.default_cost(1)).as_text()
    assert "jit_lookup_route_touch" in hlo
