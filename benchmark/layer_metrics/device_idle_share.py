"""1 minus the union of the device operations' intervals over the traced
window (first operation's start to the last one's end), averaged over the
cell's devices."""
from benchmark.readers import trace_share


def read(record):
    busy = trace_share(record, "busy_s", "window_s")
    return None if busy is None else 100.0 - busy
