"""One run of one cell: build the deployment, warm it, drive the window,
check what it served, and report.

Everything that belongs to one configuration, traffic mix, arrival
process or metric is a file the harness finds by the name
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment (``build_stack`` arguments,
  scheduler settings, the model's published shapes, the router it runs);
- ``traffic/<mix>.json``: the mix (query profile, bank size, arrival
  process and its parameters);
- ``arrivals/<kind>.py``: ``drive(window)``, the arrival process;
- ``metrics/<metric>.py``: ``read(run)``, one metric from the run's
  records, or None where the run holds nothing to read;
- ``reference/<family>.py``: what the benchmark knows of a model
  family's language model: its plain reference and control, the fields
  the program's configuration must hold, its weight rules (the module's
  docstring of ``reference/dense.py`` lists what one exports);
- ``counts/<family>.py``: operations and bytes from a model's shapes;
- ``peaks.json``: the chip's peaks by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
now = time.monotonic


# ------------------------------------------------------------- the files
def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(family: str):
    """The reference module of a model family, ``reference/<family>.py``."""
    try:
        return load_module("reference", family)
    except FileNotFoundError:
        raise FileNotFoundError(
            f"model family {family!r} has no reference module: the benchmark "
            f"needs bench/reference/{family}.py (KEYS, served_gaps, "
            f"program_fields, FAN_IN) to build, check and draw weights for "
            f"it") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT, configs[w["config"]]["file"])
    traffic = load_json(BENCH, "traffic", f"{w['traffic']}.json")
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        end_to_end=[m["name"] for m in spec["end_to_end"]
                    if _applies(m, name)],
        per_layer=[m["name"] for m in spec["per_layer"] if _applies(m, name)],
        units={m["name"]: m["unit"]
               for m in spec["end_to_end"] + spec["per_layer"]})


def peaks(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


def stream_seed(seed: int, stream: str) -> int:
    """A 32-bit seed for one named stream of a run seed of any size."""
    return int(np.random.SeedSequence(
        [seed, int.from_bytes(stream.encode(), "little")]).generate_state(1)[0])


# --------------------------------------------------------------- records
@dataclasses.dataclass
class Req:
    text: str
    due: float
    sent: float = 0.0
    done: Optional[float] = None
    response: Optional[str] = None
    meta: Optional[dict] = None
    dispatch: Optional[int] = None
    tokens: int = 0
    failed: bool = False

    @property
    def decision(self):
        return None if self.meta is None else self.meta["decision"]


class Recorder:
    """Spans the harness takes around its calls into the program."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.dispatches: List[dict] = []
        self.gens: List[dict] = []
        self.last_dispatch: Dict[str, int] = {}
        self.phase = "setup"
        self.compiles: Dict[str, Dict[str, int]] = {}

    def span(self, name: str):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation(f"bench.{name}")
        return contextlib.nullcontext()

    def on_event(self, event: str, *args, **kw):
        """Counts compilations, traces and compile-cache hits and misses,
        per phase of the run (set-up, window)."""
        for key, suffix in (("compile", "backend_compile_duration"),
                            ("trace", "jaxpr_trace_duration"),
                            ("cache_hit", "cache_hits"),
                            ("cache_miss", "cache_misses")):
            if event.endswith(suffix):
                c = self.compiles.setdefault(self.phase, {})
                c[key] = c.get(key, 0) + 1

    def programs(self, phase: str) -> int:
        """Programs compiled, or loaded from the persistent cache, in a
        phase: JAX times either as a backend compile.  A retrace that finds
        its executable in memory is neither."""
        return self.compiles.get(phase, {}).get("compile", 0)


def instrument(engine, rec: Recorder) -> None:
    """Wrap the engine's dispatch entry, both generators and the insert
    with spans, and record what each dispatch served; outputs pass
    through untouched."""
    handle = engine.handle_batch_result

    def handle_batch_result(queries, **kw):
        d = {"t0": now(), "texts": list(queries), "phase": rec.phase}
        rec.dispatches.append(d)
        with rec.span("dispatch"):
            res = handle(queries, **kw)
        d["t1"] = now()
        d["decisions"] = [m["decision"] for m in res.meta]
        d["responses"] = list(res.responses)
        for t in queries:
            rec.last_dispatch[t] = len(rec.dispatches) - 1
        return res

    engine.handle_batch_result = handle_batch_result
    for gen, kind in ((engine.big, "miss"), (engine.small, "tweak")):
        def generate_with_lengths(batch, *, _orig=gen.generate_with_lengths,
                                  _kind=kind, **kw):
            t0 = now()
            with rec.span(f"generate.{_kind}"):
                out = _orig(batch, **kw)
            pc = kw.get("prefix_cache")
            rec.gens.append({
                "kind": _kind, "t0": t0, "t1": now(),
                "tokens": batch["tokens"],
                "prefix": tuple(pc.token_ids) if pc is not None else (),
                "out": out[0], "lengths": out[1], "phase": rec.phase,
                "dispatch": len(rec.dispatches) - 1})
            return out
        gen.generate_with_lengths = generate_with_lengths
    insert = engine._insert_entries

    def _insert_entries(*args, **kw):
        with rec.span("insert"):
            return insert(*args, **kw)

    engine._insert_entries = _insert_entries


# ---------------------------------------------------------------- window
class Window:
    """What an arrival process drives: the scheduler, the query stream and
    the clock.  ``submit`` at a due time, ``poll``, ``sleep_until``."""

    def __init__(self, sched, queries: List[str], traffic: dict,
                 seconds: float, seed: int, rec: Recorder, tracer=None):
        self.sched = sched
        self.queries = queries
        self.traffic = traffic
        self.seconds = seconds
        self.rng = np.random.default_rng(stream_seed(seed, "arrivals"))
        self.rec = rec
        self.tracer = tracer
        self.reqs: List[Req] = []
        self._live: Dict[int, tuple] = {}
        self._next = 0
        self.t0: Optional[float] = None
        self.rejected = 0
        self.t_close: Optional[float] = None

    def begin(self) -> float:
        self.t0 = now()
        return self.t0

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def next_query(self) -> str:
        q = self.queries[self._next % len(self.queries)]
        self._next += 1
        return q

    def submit(self, text: str, due: float) -> Req:
        from repro.serving import QueueFull
        r = Req(text=text, due=due, sent=now())
        self.reqs.append(r)
        try:
            handle = self.sched.submit(text)
        except QueueFull:
            r.failed = True
            self.rejected += 1
            return r
        self._live[id(handle)] = (handle, r)
        return r

    def poll(self) -> List[Req]:
        if self.tracer is not None:
            self.tracer.step(now() - self.t0)
        done = self.sched.poll()
        t = now()
        out = []
        for handle in done:
            _, r = self._live.pop(id(handle))
            r.done = t
            r.response = handle.response
            r.meta = handle.meta
            r.dispatch = self.rec.last_dispatch.get(handle.text)
            if not handle.response:
                r.failed = True
            out.append(r)
        return out

    @property
    def outstanding(self) -> int:
        return len(self._live)

    def sleep_until(self, t: float) -> None:
        dt = t - now()
        if dt > 0:
            with self.rec.span("wait"):
                time.sleep(dt)

    def wakeup(self) -> Optional[float]:
        """Monotonic time at which the scheduler next wants a poll."""
        return self.sched.next_wakeup()

    def drain(self) -> None:
        """Serve every request still queued, honouring the scheduler's
        deadlines, as a client that stopped sending would see it."""
        while self.outstanding:
            w = self.wakeup()
            if w is not None:
                self.sleep_until(w)
            self.poll()
        if self.tracer is not None:
            self.tracer.stop()


class Tracer:
    """Starts the profiler between two polls ``start_s`` into the window
    and stops it between two polls ``length_s`` later."""

    def __init__(self, start_s: float, length_s: float, rec: Recorder):
        self.start_s, self.length_s = start_s, length_s
        self.rec = rec
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "idle"
        self.t_start = None
        self._span = None

    def step(self, t: float) -> None:
        import jax
        if self.state == "idle" and t >= self.start_s:
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation("bench.trace_window")
            self._span.__enter__()
            self.t_start = now()
            self.state = "on"
        elif self.state == "on" and t >= self.start_s + self.length_s:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state != "on":
            return
        self._span.__exit__(None, None, None)
        self.t_stop = now()
        jax.profiler.stop_trace()
        self.state = "done"

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


# ----------------------------------------------------------------- build
def build(cell: Cell, seed: int):
    """The deployment, built through the program's own ``build_stack``,
    with the benchmark's seeded weights in place of the program's: the
    language model's, the embedder's and the reranker's, installed before
    the engine is made."""
    import jax
    from repro.core import TweakLLMEngine
    from repro.launch.serve import build_stack
    from repro.models.embedder import init_embedder
    from repro.models.reranker import init_reranker

    from bench import weights as weights_lib

    cfg = cell.config
    family = family_module(cfg["family"])
    stack = build_stack(**cfg["build_stack"])
    big, small = stack["big"], stack["small"]
    check_config(cfg, big.model.cfg, stack["router_cfg"], family)
    big.params = small.params = None
    gc.collect()
    w = weights_lib.make(big.model.init, seed, rules=family.FAN_IN)
    big.params = small.params = w
    ecfg = stack["embedder_cfg"]
    stack["embedder_params"] = weights_lib.make(
        lambda k: init_embedder(k, ecfg), seed, "embedder")
    if "reranker" in stack:
        rcfg = stack["reranker"][1]
        stack["reranker"] = (weights_lib.make(
            lambda k: init_reranker(k, rcfg), seed, "reranker"), rcfg)
    engine = TweakLLMEngine(**stack)
    jax.block_until_ready(w)
    return engine, stack, w


def check_config(cfg: dict, mc, rc, family) -> None:
    """The program must serve what the configuration file states: the
    model's fields as the ``family`` module reads them, and the router's."""
    want = family.program_fields(cfg["model"])
    got = {k: getattr(mc, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    r = cfg["router"]
    for k in ("tweak_threshold", "exact_threshold", "default_cost",
              "cal_span", "band", "probe_temp", "w_agree", "w_rerank",
              "commit_at"):
        if getattr(rc, k) != r[k]:
            bad[f"router.{k}"] = (getattr(rc, k), r[k])
    if bad:
        raise ValueError(f"program config differs from the file "
                         f"(program, file): {bad}")


def traffic_streams(cell: Cell, seed: int, n_window: int, n_warm: int):
    """The bank's (query, answer) pairs, the window's queries and the
    warm-up's, from one seeded stream of the mix: the live traffic repeats
    the bank's history as its profile does, and a share ``resend_share``
    of it (0 where the mix names none) resends a bank query verbatim."""
    from bench.traffic import chat
    gen = chat.WorkloadGenerator(cell.traffic["profile"],
                                 seed=stream_seed(seed, "traffic"))
    hist = gen.sample(cell.traffic["bank_pairs"])
    pairs = [(q.text, chat.synthesize_response(q.text, q.topic, q.intent))
             for q in hist]
    share = cell.traffic.get("resend_share", 0.0)
    rng = np.random.default_rng(stream_seed(seed, "resend"))

    def live(n):
        out = [q.text for q in gen.sample(n)]
        resend = rng.random(n) < share
        pick = rng.integers(0, len(pairs), n)
        return [pairs[j][0] if r else t for t, r, j in zip(out, resend, pick)]

    return pairs, live(n_window), live(n_warm)


def populate(engine, pairs, chunk: int = 4096) -> None:
    for i in range(0, len(pairs), chunk):
        part = pairs[i:i + chunk]
        engine.populate([q for q, _ in part], [r for _, r in part])


# ---------------------------------------------------------------- warm-up
def warm_up(engine, cell: Cell, queries: List[str], rec: Recorder,
            quiet: int = 3, most: int = 64) -> dict:
    """Run every program the window reaches, through the engine's public
    dispatch entry and the cell's own mix (``queries``, a stream disjoint
    from the window's): one dispatch of each size up to ``max_batch``, then
    full dispatches until ``quiet`` in a row bring no new program, or
    ``most`` dispatches in all."""
    import jax
    sc = cell.config["scheduler"]
    mnt, max_batch, cost = (sc["max_new_tokens"], sc["max_batch"],
                            sc["cost_threshold"])
    uniq = list(dict.fromkeys(queries))
    at = 0

    def dispatch(n: int) -> int:
        nonlocal at
        batch = [uniq[(at + j) % len(uniq)] for j in range(n)]
        at += n
        kw = {} if cost is None else {"cost_thresholds": [cost] * n}
        before = rec.programs(rec.phase)
        engine.handle_batch_result(batch, max_new_tokens=mnt, **kw)
        return rec.programs(rec.phase) - before

    for n in range(1, max_batch + 1):
        dispatch(n)
    calls, still = max_batch, 0
    while still < quiet and calls < most:
        still = 0 if dispatch(max_batch) else still + 1
        calls += 1
    jax.block_until_ready(engine.bank.state)
    return {"dispatches": calls, "programs": rec.programs(rec.phase),
            "quiet": still}


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    reqs: List[Req]
    t0: float
    rec: Recorder
    setup_s: float
    peak: int
    kind: str
    trace: Optional[dict] = None
    trace_events: Optional[list] = None
    trace_offset: float = 0.0
    trace_start: Optional[float] = None
    t_close: Optional[float] = None
    # the program's spans and request records (repro.serving.trace), in a
    # traced run
    program_records: Optional[object] = None

    @property
    def open_loop(self) -> bool:
        return self.cell.traffic["arrivals"] == "poisson"

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def measured(self) -> List[Req]:
        """Requests the end-to-end metrics cover: in an open loop every
        request due in the window, in a closed loop every request answered
        by the window's close."""
        if self.open_loop:
            return [r for r in self.reqs if r.due < self.t_end]
        return [r for r in self.reqs
                if r.done is not None and r.done <= self.t_close]

    @property
    def host_end(self) -> float:
        """End of the stretch the host spans are read over: the window's
        end, or in a traced run the profiler's start, since starting and
        stopping it stalls the host for seconds."""
        return self.t_end if self.trace_start is None else self.trace_start

    def window_dispatches(self) -> List[dict]:
        return [d for d in self.rec.dispatches
                if self.t0 <= d["t0"] < self.host_end]

    def window_gens(self, kind=None) -> List[dict]:
        return [g for g in self.rec.gens
                if self.t0 <= g["t0"] and g["t1"] <= self.host_end
                and (kind is None or g["kind"] == kind)]

    def span_reqs(self) -> List[Req]:
        """Requests due before ``host_end`` whose dispatch began before it."""
        d = self.rec.dispatches
        return [r for r in self.reqs if r.due < self.host_end
                and r.dispatch is not None
                and d[r.dispatch]["t0"] < self.host_end]

    def traced_gens(self) -> List[dict]:
        """Generate calls run wholly inside the profiler's window."""
        if self.trace is None:
            return []
        lo = self.trace["lo"] / 1e9 + self.trace_offset
        hi = self.trace["hi"] / 1e9 + self.trace_offset
        return [g for g in self.rec.gens if lo <= g["t0"] and g["t1"] <= hi]

    def model(self) -> dict:
        return self.cell.config["model"]

    def counts(self):
        return load_module("counts", self.cell.config["family"])


def read_metrics(run: Run, names: List[str]) -> dict:
    out = {}
    for name in names:
        v = load_module("metrics", name).read(run)
        if v is not None:
            out[name] = {"value": float(v), "unit": run.cell.units[name]}
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics; +inf for a
    request that never completed."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def latency_ms(reqs: List[Req]) -> List[float]:
    return [np.inf if (r.failed or r.done is None) else (r.done - r.due) * 1e3
            for r in reqs]


def dir_size(path: str) -> str:
    """Files and bytes under a directory, for the log."""
    n = size = 0
    for root, _, files in os.walk(path):
        for f in files:
            n += 1
            size += os.path.getsize(os.path.join(root, f))
    return f"{n} files {size} B"


def log(*a):
    print(*a, file=sys.stderr, flush=True)
