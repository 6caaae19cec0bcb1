"""Device milliseconds a step in the convolutions of the short_conv mixers:
the Mosaic calls named `ptpu_causal_conv1d_fwd` and `ptpu_causal_conv1d_bwd`,
the kernels the configuration module names in `SHORT_CONV_KERNELS`. The two
gate multiplies around a convolution run in XLA outside them and are not in
this number. None without a trace, for a configuration that names none, or
where one of them did not run under its name (a program without the mixer,
or with the kernels off)."""
from benchmark.kernel_ms import kernel_ms_per_step


def kernel_ms(record):
    """{kernel: ms a step} of the kernels the configuration names, or None
    where there is nothing to read."""
    kernels = getattr(record["cell"].config_module, "SHORT_CONV_KERNELS",
                      None)
    if kernels is None:
        return None
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in kernels}
    return None if None in ms.values() else ms


def read(record):
    ms = kernel_ms(record)
    return None if ms is None else sum(ms.values())
