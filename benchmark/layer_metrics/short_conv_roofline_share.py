"""The short convolutions' kernels' share of the HBM rate: the time the
chip needs at least to move what the two kernels read from and write to HBM
a step (the configuration module's `short_conv_kernel_bytes`, over the HBM
rate of benchmark/peaks.json; 2 K multiply-adds an element make bytes the
only roof there is a peak for), over the same kernels' traced seconds. The
module counts the operands that the compiled step holds in HBM, not every
operand: XLA feeds a kernel from VMEM where it can, and a count that takes
such an operand for an HBM read passes 100 % (the module's docstring has
the reading). None wherever short_conv_ms_per_step is, or without the
chip's peaks."""
import os

from benchmark import manifest

_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "short_conv_ms_per_step.py"))


def read(record):
    ms = _ms.kernel_ms(record)
    if ms is None or not record["peak"]:
        return None
    cell = record["cell"]
    nbytes = cell.config_module.short_conv_kernel_bytes(cell.config,
                                                        cell.traffic)
    least = sum(nbytes[kernel] for kernel in ms) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * sum(ms.values()))
