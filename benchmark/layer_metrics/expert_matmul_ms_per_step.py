"""Device milliseconds a step in the grouped expert matmuls of `moe_ffn`
(`paddle_tpu/parallel/moe.py _grouped_matmul`), by who they are and not by
what one implementation is called. Two kinds of device operation are theirs:

  * XLA's own: `jax.lax.ragged_dot` compiles to instructions named
    `ragged-dot*` (`ragged-dot-none.<n>` on the v5e), which the trace keeps;
  * the program's: the Mosaic calls of every kernel that the program names
    in `EXPERT_MATMUL_KERNELS` of `paddle_tpu/ops/pallas_kernels.py`, a
    tuple of names that are in its `KERNEL_NAMES` too. A PR that brings a
    grouped matmul of the repo's own appends the tuple at the END of that
    module (no line above a kernel moves: a Mosaic payload carries file and
    line) and this metric follows it with no edit here. A program that has
    no such tuple (every tree up to PR 47) names no kernel, and the metric
    is the `ragged-dot*` instructions' alone, as it was.

A Mosaic call that the program does not name so is not counted. Self time a
step, divided as kernel_ms_per_step divides; None without a trace or where
neither kind ran."""
from benchmark.kernel_ms import kernel_ms_per_step

INSTRUCTION = "ragged-dot"
KERNELS = "EXPERT_MATMUL_KERNELS"


def kernels():
    """The program's names of its own grouped-matmul kernels; () where it
    has none."""
    from paddle_tpu.ops import pallas_kernels
    return tuple(getattr(pallas_kernels, KERNELS, ()))


def read(record):
    trace, steps = record["trace"], record["window"]["attempted"]
    if not trace or not trace["busy_s"] > 0 or not steps:
        return None
    seconds = [s for op, s in trace["top_ops"]
               if op.split(" ")[0].startswith(INSTRUCTION)]
    parts = [1e3 * sum(seconds) / steps] if seconds else []
    parts += [ms for ms in (kernel_ms_per_step(record, k) for k in kernels())
              if ms is not None]
    return sum(parts) if parts else None
