"""Device idle share of the traced window: 1 - (union of the intervals in
which a device operation ran / window), averaged over the cell's chips,
in % (``bench/trace_reduce.py``)."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_pct
