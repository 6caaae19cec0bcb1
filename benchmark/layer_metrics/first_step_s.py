"""Host seconds of the first run of the training program: lowering, trace,
compile or cache load, one step."""
from benchmark.readers import span_seconds


def read(record):
    return span_seconds(record, "first_step")
