"""The grouped expert matmuls' share of the chip's bf16 peak: the matmul
operations of the assignments that the experts held here computed (the
configuration module's `expert_matmul_ops`: 3 passes x 3 matrices x 2 x
hidden_size x the expert's width an assignment, the assignments read from
the run's own `expert_load` fetches) over expert_matmul_ms_per_step's
seconds times the peak of benchmark/peaks.json. The count is of the work, whatever
implements it: rows that a tile or a group pads, and rows of experts held
elsewhere that an implementation walks over, are not counted, so the share
cannot pass 100 %.

The load is the traced steps' own: every step fetches `expert_load`, the
window keeps it (`record["window"]["fetches"]`, [steps, E]), and the count
is the mean over those steps, the steps the time is of. The first step's
load would not do. Where every expert is held (OLMoE) the count is tokens x
top_k x layers whatever the router does. Where a share is held, the rows
the held experts get grow as the router trains on the one repeated batch
(only the experts that are there move the loss): over the 25 to 33 steps of
a traced window by 4.5 to 7.0 % in the SmallThinker cell, 3.1 to 3.3 % in
LFM2's, 1.5 to 13 % in Xing4.0's and 0.7 to 1.1 % in Qwen3-Next's, over the
69 steps of SmallThinker's 10 s window by 26 % (my chip runs, PR 47).
Between seeds they differ too, and the count follows them.

None wherever expert_matmul_ms_per_step is, or for a configuration whose
module does not count its experts' operations or fetches no load."""
import os

from benchmark import manifest

_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "expert_matmul_ms_per_step.py"))


def read(record):
    cell = record["cell"]
    count = getattr(cell.config_module, "expert_matmul_ops", None)
    load = record["window"]["fetches"].get("expert_load")
    ms = _ms.read(record)
    if count is None or load is None or ms is None or not record["peak"]:
        return None
    ops_a_step = count(cell.config, cell.traffic, load) / len(load)
    return 100.0 * ops_a_step \
        / (1e-3 * ms * record["peak"]["bf16_flops_per_s"])
