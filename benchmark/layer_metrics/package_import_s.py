"""Seconds `import paddle_tpu` took, first line of its `__init__` to the
last (`ptpu_import_seconds{module="paddle_tpu"}`). The benchmark imports
jax before it, so jax's own import is not in it; both are in the set-up
line's `imports`."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_import_seconds", module="paddle_tpu")
