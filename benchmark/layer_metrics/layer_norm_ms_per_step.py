"""Device milliseconds a step in the layer_norm forward kernel (the forward
op's calls and the ones its gradient op runs again), the Mosaic calls named
`ptpu_layer_norm_fwd`."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_layer_norm_fwd"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
