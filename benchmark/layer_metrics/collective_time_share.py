"""Time in collective operations over the traced window."""
from benchmark.readers import trace_share


def read(record):
    return trace_share(record, "collective_s", "window_s")
