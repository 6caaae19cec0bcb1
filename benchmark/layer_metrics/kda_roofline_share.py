"""The KDA kernels' share of their own roofline: the least time the chip
could take for what the two kernels of the pass over chunks are given to do,
a call the larger of its matmuls' operations over the bf16 peak and its
operands' and results' bytes over the HBM rate of benchmark/peaks.json (the
configuration module's `kda_kernel_ops`, at the chunk the program says it
lowered the op at: the label `chunk` of the `kind="kda"` samples of its
counter `ptpu_linear_attention_layers_total`), over the same kernels' traced
seconds. Numerator and denominator are the kernels' alone: what prepares the
chunks in XLA is in neither. A call moves each operand it is counted for at
least once and computes each product, so the share cannot pass 100 % while
the kernels are given these operands (layer_metrics/
gated_delta_roofline_share.py argues the same for its own); a program that
gives them others needs another count. None wherever kda_ms_per_step is, or
where the program's counter does not say one chunk."""
import os

from benchmark import manifest

COUNTER = "ptpu_linear_attention_layers_total"
_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "kda_ms_per_step.py"))


def lowered_chunk():
    """The one chunk the program lowered its KDA rules at on the kernel
    path, or None."""
    try:
        from paddle_tpu.observability.registry import REGISTRY
    except ImportError:
        return None
    family = REGISTRY.snapshot().get(COUNTER, {"samples": []})
    chunks = {labels.get("chunk") for labels, count in family["samples"]
              if count and labels.get("kind") == "kda"
              and labels.get("path") == "kernel"}
    only = chunks.pop() if len(chunks) == 1 else None
    return int(only) if only and only.isdigit() else None


def read(record):
    ms, chunk = _ms.kernel_ms(record), lowered_chunk()
    if ms is None or chunk is None or not record["peak"]:
        return None
    cell, peak = record["cell"], record["peak"]
    calls = cell.config_module.kda_kernel_ops(cell.config, cell.traffic,
                                              chunk)
    least = sum(max(ops / peak["bf16_flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
                for kernel in calls for ops, nbytes in calls[kernel])
    return 100.0 * least / (1e-3 * sum(ms.values()))
