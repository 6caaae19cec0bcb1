"""Device milliseconds a step in operations XLA generated: all but Mosaic
custom calls and collectives."""
from benchmark.readers import category_ms_per_step


def read(record):
    return category_ms_per_step(record, "xla")
