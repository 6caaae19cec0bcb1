"""The hyper-connections' kernels' share of the HBM rate: the time the chip
needs at least to move what the kernels read from and write to HBM a step
(the configuration module's `mhc_kernel_bytes`: each stream array read or
written once a kernel, as the compiled step's layouts hold them, over the
HBM rate of benchmark/peaks.json; a dozen multiply-adds an element make
bytes the only roof there is a peak for), over the same kernels' traced
seconds. The two coefficient kernels are among them, with their whole time
and the few bytes of their [24, T] arrays: the VPU bounds them, so they can
only lower the share (by 0.13 ms of 17.9 in the Xing4.0 cell, PERF.md
section 3). None wherever mhc_ms_per_step is, or without the chip's
peaks."""
import os

from benchmark import manifest

_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "mhc_ms_per_step.py"))


def read(record):
    ms = _ms.kernel_ms(record)
    if ms is None or not record["peak"]:
        return None
    cell = record["cell"]
    nbytes = cell.config_module.mhc_kernel_bytes(cell.config, cell.traffic)
    least = sum(nbytes[kernel] for kernel in ms) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * sum(ms.values()))
