"""Device milliseconds a step in the two kernels of the delta rule whose
decay is a key channel's (Kimi Delta Attention), the Mosaic calls named
`ptpu_kda_fwd` (the pass over chunks; it runs once in the forward pass and
once more, for the states, in the backward) and `ptpu_kda_bwd` (its
reverse): the kernels the configuration module names in `KDA_KERNELS`. What
prepares the chunks (the decayed products a channel, (I + L)^-1) runs in XLA
outside them and is not in this number. None without a trace, for a
configuration that names none, or where one of them did not run under its
name (a program without the op, or with the kernels off)."""
from benchmark.kernel_ms import kernel_ms_per_step


def kernel_ms(record):
    """{kernel: ms a step} of the kernels the configuration names, or None
    where there is nothing to read."""
    kernels = getattr(record["cell"].config_module, "KDA_KERNELS", None)
    if kernels is None:
        return None
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in kernels}
    return None if None in ms.values() else ms


def read(record):
    ms = kernel_ms(record)
    return None if ms is None else sum(ms.values())
