"""Device milliseconds a step in the multi-token-prediction module: every
row by instance whose instance carries the role `mtp.`
(lowering.ROLE_ATTR, models/causal_lm.py). benchmark/op_ms.py."""
from benchmark.op_ms import op_ms_per_step


def read(record):
    return op_ms_per_step(record, role="mtp")
