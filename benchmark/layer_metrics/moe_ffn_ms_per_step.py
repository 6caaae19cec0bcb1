"""Device milliseconds a step under the fluid ops `moe_ffn` and
`moe_ffn_grad`, kernels and XLA's own operations alike: the router, the
sorts, the rows' moves and the grouped matmuls (benchmark/op_ms.py)."""
from benchmark.op_ms import op_ms_per_step

TYPES = ("moe_ffn", "moe_ffn_grad")


def read(record):
    return op_ms_per_step(record, types=TYPES)
