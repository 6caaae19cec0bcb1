"""The part of the collectives' time during which no other operation ran on
that device, over the traced window."""
from benchmark.readers import trace_share


def read(record):
    return trace_share(record, "collective_exposed_s", "window_s")
