"""Device milliseconds a step under the update ops the program's optimizers
append (`paddle_tpu.optimizer.UPDATE_OP_TYPES`: `adam`,
`adam_beta_pow_update`, `momentum`, `sgd`, ...; not the generic `scale` /
`elementwise_*` ops of clipping and the schedule). benchmark/op_ms.py."""
from benchmark.op_ms import op_ms_per_step


def read(record):
    from paddle_tpu import optimizer
    types = getattr(optimizer, "UPDATE_OP_TYPES", None)
    return None if types is None else op_ms_per_step(record, types=types)
