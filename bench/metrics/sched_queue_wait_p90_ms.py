"""90th percentile of (dispatched - arrival) over the scheduler's request
records, both on its own clock: the queueing and coalescing wait, ms."""
from bench import harness, program_spans


def read(run):
    reqs = program_spans.window_requests(run)
    if not reqs:
        return None
    return harness.percentile([(r.dispatched - r.arrival) * 1e3
                               for r in reqs], 90)
