"""The program's own spans and request records (``repro.serving.trace``),
as the per-layer metrics read them, and device idle time attributed to
the host spans of a profiler trace.

A run holds the program's records as ``run.program_records`` where its
runner turned the program's tracer on for the window (``trace.start()``
before it, ``trace.stop()`` after the drain).  Elsewhere, and on a
program without the tracer, the readers find nothing and return None.
Like the harness's host spans, records are read from the window's start
up to ``run.host_end``, the profiler's start in a traced run.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional

from bench import tracing

# names of the program's spans in a profiler trace; the harness's are bench.*
PROGRAM_PREFIXES = ("scheduler.", "engine.", "host.")
OUTSIDE = "outside any span"


def records(run):
    return getattr(run, "program_records", None)


def window_spans(run, name: str) -> Optional[list]:
    """Spans called ``name`` that start in the window's host stretch;
    None where the run holds no program records."""
    rec = records(run)
    if rec is None:
        return None
    lo, hi = run.t0 * 1e9, run.host_end * 1e9
    return [s for s in rec.spans if s.name == name and lo <= s.start_ns < hi]


def window_requests(run) -> Optional[list]:
    """Request records of the window's host stretch: arrived and
    dispatched before ``run.host_end``."""
    rec = records(run)
    if rec is None:
        return None
    return [r for r in rec.requests if run.t0 <= r.arrival
            and r.arrival < run.host_end and r.dispatched < run.host_end]


def children(run, parents: list, names) -> Dict[int, list]:
    """The spans called one of ``names`` below each of ``parents`` (at
    any depth), by parent id."""
    rec = records(run)
    by_id = {s.id: s for s in rec.spans}
    want = {p.id for p in parents}
    out: Dict[int, list] = {p.id: [] for p in parents}
    for s in rec.spans:
        if s.name not in names:
            continue
        p = by_id.get(s.parent)
        while p is not None and p.id not in want:
            p = by_id.get(p.parent)
        if p is not None:
            out[p.id].append(s)
    return out


def covered_ns(lo: int, hi: int, spans) -> int:
    """Nanoseconds of [lo, hi] that the union of ``spans`` covers."""
    cover = tracing.union((max(s.start_ns, lo), min(s.end_ns, hi))
                          for s in spans if s.end_ns > lo and s.start_ns < hi)
    return sum(e - s for s, e in cover)


# --------------------------------------------------- idle time by span
def host_spans(events) -> List[tracing.Event]:
    """Host events that are spans of the program or of the harness."""
    devices = set(tracing.device_planes(events))
    return [e for e in events
            if e.name != tracing.WINDOW_SPAN and e.plane not in devices
            and e.name.startswith(("bench.",) + PROGRAM_PREFIXES)]


def idle_by_span(events, plane: str, lo: float,
                 hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi] in which ``plane`` ran no operation, summed by
    the innermost host span (program or ``bench.*``) covering each
    instant; ``outside any span`` where none does."""
    spans = sorted(host_spans(events), key=lambda e: e.start_ns)
    out: Dict[str, float] = collections.defaultdict(float)
    # the gaps come in order and do not overlap: sweep the spans once,
    # keeping those begun before the gap's end and not ended by its start
    live, nxt = [], 0
    for gs, ge in tracing.gaps(events, plane, lo, hi):
        while nxt < len(spans) and spans[nxt].start_ns < ge:
            live.append(spans[nxt])
            nxt += 1
        live = [e for e in live if e.end_ns > gs]
        cuts = sorted({gs, ge} | {t for e in live
                                  for t in (e.start_ns, e.end_ns)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            cover = [e for e in live if e.start_ns <= a and e.end_ns >= b]
            name = min(cover, key=lambda e: e.dur_ns).name if cover \
                else OUTSIDE
            out[name] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
