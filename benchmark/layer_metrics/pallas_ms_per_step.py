"""Device milliseconds a step in Mosaic custom calls, the Pallas kernels."""
from benchmark.readers import category_ms_per_step


def read(record):
    return category_ms_per_step(record, "pallas")
