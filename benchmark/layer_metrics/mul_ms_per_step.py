"""Device milliseconds a step under the fluid op `mul`: the forward matmuls
of every projection and head (benchmark/op_ms.py)."""
from benchmark.op_ms import op_ms_per_step


def read(record):
    return op_ms_per_step(record, types=("mul",))
