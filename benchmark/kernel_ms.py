"""What the per-kernel readers share: device milliseconds a step in the
Mosaic custom calls of one named Pallas kernel.

A `pl.pallas_call(..., name=<kernel>)` compiles to an HLO instruction named
`<kernel>` or `<kernel>.<n>`, and trace_reduce keeps the instruction's name:
`record["trace"]["top_ops"]` lists every device operation as
["<instruction> <opcode> <target>", seconds a plane over the traced window].
The kernels' names are the program's (`paddle_tpu/ops/pallas_kernels.py`,
KERNEL_NAMES). Where the program names no kernel so (the parent of the PR
that named them), every reader finds nothing and gives None."""
MOSAIC = ("custom-call", "tpu_custom_call")


def kernel_ms_per_step(record, kernel):
    """Self time a step, in ms, of the Mosaic calls named `kernel` or
    `kernel.<n>`; None without a trace, or where no such call ran. Divided
    as pallas_ms_per_step divides: a traced run's window is the traced
    window, so its steps are the trace's."""
    trace, steps = record["trace"], record["window"]["attempted"]
    if not trace or not trace["busy_s"] > 0 or not steps:
        return None
    seconds = [s for op, s in trace["top_ops"] if _is_kernel(op, kernel)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / steps


def _is_kernel(op, kernel):
    words = op.split(" ")
    if tuple(words[1:3]) != MOSAIC:
        return False
    name, dot, n = words[0].partition(".")
    return name == kernel and (not dot or n.isdigit())
