"""Device milliseconds a step in the flash attention forward kernel, the
Mosaic calls named `ptpu_flash_fwd`."""
from benchmark.kernel_ms import kernel_ms_per_step

KERNEL = "ptpu_flash_fwd"


def read(record):
    return kernel_ms_per_step(record, KERNEL)
