"""Device milliseconds a step in the selective scan's two kernels, the
Mosaic calls named `ptpu_selective_scan_fwd` (the recurrence over a
sequence's chunks, which also writes the state that enters every chunk) and
`ptpu_selective_scan_bwd` (its reverse, which replays a chunk's states in
VMEM): the kernels the configuration module names in
`SELECTIVE_SCAN_KERNELS`. The projections, the convolution and the gate
around the scan run outside them and are not in this number. None without a
trace, for a configuration that names none, or where one of them did not
run under its name (a program without the op, or with the kernels off)."""
from benchmark.kernel_ms import kernel_ms_per_step


def kernel_ms(record):
    """{kernel: ms a step} of the kernels the configuration names, or None
    where there is nothing to read."""
    kernels = getattr(record["cell"].config_module, "SELECTIVE_SCAN_KERNELS",
                      None)
    if kernels is None:
        return None
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in kernels}
    return None if None in ms.values() else ms


def read(record):
    ms = kernel_ms(record)
    return None if ms is None else sum(ms.values())
