"""Device milliseconds a step in the Pallas kernel that writes the
embedding's dense gradient (`paddle_tpu/ops/embedding_grad.py`, PR 41: on
one TPU, for rows of 2048 and wider): Mosaic calls named
`ptpu_embedding_grad`. XLA's sort of the ids and its gather of the rows
into their order run around the kernel under no name of their own and are
not in this time (0.39 ms a step beside the kernel's 0.95 in the
SmallThinker cell, PR 41's builder). None without a trace, or where the
lookup took XLA's scatter (a narrower row, a mesh) and no such call ran."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_embedding_grad"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
