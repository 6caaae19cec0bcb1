"""The embedding gradient kernel's share of the HBM rate: the time the chip
needs at least to move what the gradient has to move a step (the
configuration module's `embedding_grad_bytes`: the [V, D] table written
once and the [tokens, D] rows read once, 4 bytes an element, over the HBM
rate of benchmark/peaks.json; a row costs one add an element, so bytes are
the only roof there is a peak for) over embedding_grad_ms_per_step's
seconds. Counted from the work, whatever implements it; XLA's sort and
gather around the kernel are in neither the bytes nor the time. An
implementation moves at least these bytes, so the share cannot pass 100 %.
None wherever embedding_grad_ms_per_step is, or for a configuration whose
module does not count the gradient's bytes."""
import os

from benchmark import manifest

_ms = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "embedding_grad_ms_per_step.py"))


def read(record):
    cell = record["cell"]
    count = getattr(cell.config_module, "embedding_grad_bytes", None)
    ms = _ms.read(record)
    if count is None or ms is None or not record["peak"]:
        return None
    least = count(cell.config, cell.traffic) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * ms)
