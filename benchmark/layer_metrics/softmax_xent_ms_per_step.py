"""Device milliseconds a step in the softmax cross-entropy forward kernel, the
Mosaic calls named `ptpu_softmax_xent_fwd`."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_softmax_xent_fwd"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
