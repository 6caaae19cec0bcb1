"""Device milliseconds a step under `moe_ffn` and `moe_ffn_grad` LESS the
grouped expert matmuls, by exactly the operations that
`expert_matmul_ms_per_step` counts (its `ragged-dot*` instructions and the
kernels of the program's EXPERT_MATMUL_KERNELS): the routing around the
experts, every one of which lies under the two ops."""
from benchmark.kernel_ms import _is_kernel
from benchmark.layer_metrics.expert_matmul_ms_per_step import (INSTRUCTION,
                                                              kernels)
from benchmark.layer_metrics.moe_ffn_ms_per_step import TYPES
from benchmark.op_ms import op_ms_per_step


def read(record):
    names = kernels()
    return op_ms_per_step(
        record, types=TYPES,
        less=lambda op: op.split(" ")[0].startswith(INSTRUCTION)
        or any(_is_kernel(op, k) for k in names))
