"""Device milliseconds a step in the flash attention backward kernel for dQ,
the Mosaic calls named `ptpu_flash_bwd_dq`."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_flash_bwd_dq"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
