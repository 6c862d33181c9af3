"""Mean over the engine's ``engine.dispatch`` spans of the time from the
span's start to its first ``engine.tweak`` or ``engine.miss`` child (or
to its end when it generates nothing): tokenize, embed, lookup, route,
stage 2 and EXACT answers, ms."""
from bench import program_spans

GENERATE = ("engine.tweak", "engine.miss")


def read(run):
    ds = program_spans.window_spans(run, "engine.dispatch")
    if not ds:
        return None
    gens = program_spans.children(run, ds, GENERATE)
    times = [(min([g.start_ns for g in gens[d.id]], default=d.end_ns)
              - d.start_ns) / 1e6 for d in ds]
    return sum(times) / len(times)
