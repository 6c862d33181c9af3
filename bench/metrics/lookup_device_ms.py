"""Mean device time of one execution of the served lookup program
(``jit_lookup_route_touch``: cosine scores over the bank, top-k, route
and hit accounting) started inside the profiler's window, ms."""
from bench import tracing

PROGRAM = r"jit_lookup_route_touch"


def read(run):
    if run.trace is None:
        return None
    dev, n = tracing.module_ns(run.trace_events, run.trace["planes"][0],
                               PROGRAM, run.trace["lo"], run.trace["hi"])
    return dev / n / 1e6 if n else None
