"""Mean self time of the engine's ``engine.dispatch`` spans on the host:
each span's duration less the union of its ``engine.route_sync``
(waiting for the device), ``engine.stage2``, ``engine.tweak``,
``engine.miss`` and ``engine.insert`` spans, ms."""
from bench import program_spans

AWAY = ("engine.route_sync", "engine.stage2", "engine.tweak", "engine.miss",
        "engine.insert")


def read(run):
    ds = program_spans.window_spans(run, "engine.dispatch")
    if not ds:
        return None
    kids = program_spans.children(run, ds, AWAY)
    times = [(d.end_ns - d.start_ns
              - program_spans.covered_ns(d.start_ns, d.end_ns, kids[d.id]))
             / 1e6 for d in ds]
    return sum(times) / len(times)
