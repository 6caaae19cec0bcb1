"""Device milliseconds a step in the flash attention backward kernel for dK
and dV, the Mosaic calls named `ptpu_flash_bwd_dkdv`."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_flash_bwd_dkdv"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
