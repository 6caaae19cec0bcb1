"""Device milliseconds a step in operations under no fluid op's scope and in
no named kernel: `copy`, `copy-done`, `slice-done`, whatever XLA adds under
no op name (benchmark/op_ms.py)."""
from benchmark.op_ms import unnamed_ms_per_step


def read(record):
    return unnamed_ms_per_step(record)
