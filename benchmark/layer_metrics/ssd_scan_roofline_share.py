"""The state-space-dual scan's kernels' share of their own roofline: the
least time the chip could take for what the chunked form needs, a call the
larger of its products' operations over the bf16 peak and its operands' and
results' bytes over the HBM rate of benchmark/peaks.json (the configuration
module's `ssd_kernel_ops`, at the chunk the program says it lowered the op
at: the label `chunk` of its counter `ptpu_ssd_scan_layers_total`), over the
same kernels' traced seconds. The count is of the LEAST the form needs (C
B^T once a chunk and not once a head, a chunk's products under L by the
pairs a token sees, every operand once, in two bytes), so the share cannot
pass 100 % whatever the kernels do inside. Expect it low: a head's result is
P = 64 columns, half of the v5e's 128 x 128 MXU, and the kernels compute a
lane tile of two heads for each of its heads, so against the bf16 peak they
stand under 50 % by construction; the float32 copies the backward pass's two
kernels write (the states, Y and dX without rounding) are bytes the count
leaves out. None wherever ssd_scan_ms_per_step is, or where the program's
counter does not say one chunk on the kernel path."""
import os

from benchmark import manifest

COUNTER = "ptpu_ssd_scan_layers_total"
_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ssd_scan_ms_per_step.py"))


def lowered_chunk():
    """The one chunk the program lowered its scans at on the kernel path, or
    None."""
    try:
        from paddle_tpu.observability.registry import REGISTRY
    except ImportError:
        return None
    family = REGISTRY.snapshot().get(COUNTER, {"samples": []})
    chunks = {labels.get("chunk") for labels, count in family["samples"]
              if count and labels.get("path") == "kernel"}
    only = chunks.pop() if len(chunks) == 1 else None
    return int(only) if only and only.isdigit() else None


def read(record):
    ms, chunk = _ms.kernel_ms(record), lowered_chunk()
    if ms is None or chunk is None or not record["peak"]:
        return None
    cell, peak = record["cell"], record["peak"]
    calls = cell.config_module.ssd_kernel_ops(cell.config, cell.traffic,
                                              chunk)
    least = sum(max(ops / peak["bf16_flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
                for kernel in calls for ops, nbytes in calls[kernel])
    return 100.0 * least / (1e-3 * sum(ms.values()))
