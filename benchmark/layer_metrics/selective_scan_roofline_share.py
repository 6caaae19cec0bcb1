"""The selective scan's kernels' share of the HBM rate: the time the chip
needs at least to move what the two kernels read from and write to HBM a
step (the configuration module's `selective_scan_kernel_bytes`, over the HBM
rate of benchmark/peaks.json) over the same kernels' traced seconds. The
recurrence has no matmul and peaks.json no vector peak, so bytes are the
only roof there is a peak for; 16 states a channel are 16 exponentials and
about a hundred vector operations a token and register of channels, so where
the VPU binds this reads low (the module's docstring). The kernels move at
least these bytes, so the share cannot pass 100 %. None wherever
selective_scan_ms_per_step is, or without the chip's peaks."""
import os

from benchmark import manifest

_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "selective_scan_ms_per_step.py"))


def read(record):
    ms = _ms.kernel_ms(record)
    if ms is None or not record["peak"]:
        return None
    cell = record["cell"]
    nbytes = cell.config_module.selective_scan_kernel_bytes(cell.config,
                                                            cell.traffic)
    least = sum(nbytes[kernel] for kernel in ms) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * sum(ms.values()))
