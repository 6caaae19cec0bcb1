"""Device milliseconds a step in the hyper-connections' Pallas kernels: the
Mosaic calls named in the configuration module's `MHC_KERNELS`, the six
passes over the residual streams (`ptpu_mhc_pre_fwd`, `ptpu_mhc_pre_bwd`,
`ptpu_mhc_post_fwd`, `ptpu_mhc_post_bwd`, `ptpu_mhc_expand`,
`ptpu_mhc_reduce`) and the two kernels of a token's 24 coefficients
(`ptpu_mhc_coeffs_fwd`, `ptpu_mhc_coeffs_bwd`: sigmoids, the Sinkhorn steps
and their replayed backward, which the VPU bounds and not the bytes). What
XLA runs between them (the [T, n D] x [n D, 24] projection, its gradient,
the transposes around the coefficient kernels) is not in this number. None
without a trace, for a configuration that names none, or where one of them
did not run under its name (a program without the ops, or with the kernels
off)."""
from benchmark.kernel_ms import kernel_ms_per_step


def kernel_ms(record):
    """{kernel: ms a step} of the kernels the configuration names, or None
    where there is nothing to read."""
    kernels = getattr(record["cell"].config_module, "MHC_KERNELS", None)
    if kernels is None:
        return None
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in kernels}
    return None if None in ms.values() else ms


def read(record):
    ms = kernel_ms(record)
    return None if ms is None else sum(ms.values())
