"""Device milliseconds a step in the grouped expert matmuls of `moe_ffn`:
`jax.lax.ragged_dot` compiles to instructions named `ragged-dot*`
(`ragged-dot-none.<n>` on the v5e), which the trace keeps. Self time a step,
divided as kernel_ms_per_step divides; None without a trace or where no such
instruction ran."""
INSTRUCTION = "ragged-dot"


def read(record):
    trace, steps = record["trace"], record["window"]["attempted"]
    if not trace or not trace["busy_s"] > 0 or not steps:
        return None
    seconds = [s for op, s in trace["top_ops"]
               if op.split(" ")[0].startswith(INSTRUCTION)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / steps
