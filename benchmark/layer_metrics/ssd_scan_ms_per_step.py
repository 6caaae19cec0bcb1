"""Device milliseconds a step in the state-space-dual scan's two kernels,
the Mosaic calls named `ptpu_ssd_fwd` (the pass over a sequence's chunks;
the backward pass runs it once more, for the state that enters every chunk)
and `ptpu_ssd_bwd` (its reverse): the kernels the configuration module names
in `SSD_KERNELS`. The projections, the convolution, the gate and the norm
around the scan, and what XLA prepares for the kernels and sums behind them
(the running sums of Delta A, the decay's gradient), run outside them and
are not in this number. None without a trace, for a configuration that names
none, or where one of them did not run under its name (a program without the
op, or with the kernels off)."""
from benchmark.kernel_ms import kernel_ms_per_step


def kernel_ms(record):
    """{kernel: ms a step} of the kernels the configuration names, or None
    where there is nothing to read."""
    kernels = getattr(record["cell"].config_module, "SSD_KERNELS", None)
    if kernels is None:
        return None
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in kernels}
    return None if None in ms.values() else ms


def read(record):
    ms = kernel_ms(record)
    return None if ms is None else sum(ms.values())
