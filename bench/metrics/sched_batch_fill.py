"""Mean ``rows`` (unique queries) of the scheduler's ``scheduler.dispatch``
spans over the scheduler's max_batch."""
from bench import program_spans


def read(run):
    ds = program_spans.window_spans(run, "scheduler.dispatch")
    if not ds:
        return None
    mb = run.cell.config["scheduler"]["max_batch"]
    return sum(s.attrs["rows"] for s in ds) / len(ds) / mb
