"""The three flash kernels' share of the chip's bf16 peak: the matmul
operations of the pairs inside the mask (the configuration module's
`flash_kernel_ops`: 4, 8 and 6 x D a pair and query head for the forward,
dK/dV and dQ kernels, causal and windowed layers each by the pairs they see)
over the kernels' traced seconds times the peak of benchmark/peaks.json.
Masked pairs that edge blocks compute are not counted, so the share cannot
pass 100 %. None without a trace, where a kernel did not run under its name,
or for a configuration whose module does not count its flash operations."""
from benchmark.kernel_ms import kernel_ms_per_step


def read(record):
    cell = record["cell"]
    count = getattr(cell.config_module, "flash_kernel_ops", None)
    if count is None or not record["peak"]:
        return None
    ops = count(cell.config, cell.traffic)
    ms = {kernel: kernel_ms_per_step(record, kernel) for kernel in ops}
    if None in ms.values():
        return None
    return 100.0 * sum(ops.values()) / (
        1e-3 * sum(ms.values()) * record["peak"]["bf16_flops_per_s"])
