"""A per-layer metric a test adds from outside benchmark/layer_metrics: the
steps the window ran."""


def read(record):
    return record["window"]["attempted"]
