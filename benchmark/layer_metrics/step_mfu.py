"""The whole step's share of the chip's bf16 peak, on the device's clock:
the operations the traced steps needed (the configuration module's
ops_per_sample x samples a step x the steps of the traced window, what the
end-to-end `mfu` counts) over the traced window's seconds (first device
operation's start to the last one's end, idle gaps and all) times the chips
times the peak of benchmark/peaks.json. It stands beside the kernels'
roofline shares and moves what they move: a change that takes a kernel off
the path leaves that kernel's share silent, and this one still bounds what
the step can have gained. Recomputed operations do not count, so it cannot
pass 100 %. None without a trace or the chip's peaks."""


def read(record):
    trace, steps = record["trace"], record["window"]["attempted"]
    if not trace or not trace["window_s"] > 0 or not steps \
            or not record["peak"]:
        return None
    ops = record["ops_per_sample"] * record["samples_per_step"] * steps
    return 100.0 * ops / (trace["window_s"] * record["chips"]
                          * record["peak"]["bf16_flops_per_s"])
