"""Device milliseconds a step under the fluid op `mul_grad`: the matmuls'
backward, two products a forward one (benchmark/op_ms.py)."""
from benchmark.op_ms import op_ms_per_step


def read(record):
    return op_ms_per_step(record, types=("mul_grad",))
