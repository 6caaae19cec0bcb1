"""What the per-kernel readers share: device milliseconds a step in the
Mosaic custom calls of one named Pallas kernel.

A `pl.pallas_call(..., name=<kernel>)` compiles to an HLO instruction named
`<kernel>` or `<kernel>.<n>`, and trace_reduce keeps the instruction's name:
`record["trace"]["top_ops"]` lists every device operation as
["<instruction> <opcode> <target>", seconds a plane over the traced window].
Where the program names no kernel so (the parent of the PR that named
them), every reader finds nothing and gives None.

A reader finds a kernel in one of two ways, and both are the program's to
say (`paddle_tpu/ops/pallas_kernels.py`), never a list kept here:

  by name        a reader that is one kernel's (`flash_fwd_ms_per_step`,
                 `embedding_grad_ms_per_step`) sums the calls of one entry
                 of KERNEL_NAMES; the kernel keeps its name through a
                 rewrite, and the metric follows it;
  by membership  a reader of some WORK that more than one implementation
                 may do (`expert_matmul_ms_per_step`: the grouped expert
                 matmuls, XLA's `ragged-dot*` today) sums, beside XLA's
                 instructions, every kernel of a tuple in which the program
                 lists who does that work (EXPERT_MATMUL_KERNELS, each name
                 in KERNEL_NAMES too; absent or empty while no kernel does).
                 A PR that brings such a kernel appends its name to the
                 tuple, at the end of the module, and edits nothing here.

The counts that a roofline share divides by the time are of the work (a
configuration module's `*_ops` and `*_bytes`), so a share reads the same
work whoever does it."""
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
