"""Host seconds in the model builder and optimizer.minimize: layers/,
core/framework.py, core/backward.py."""
from benchmark.readers import span_seconds


def read(record):
    return span_seconds(record, "build")
