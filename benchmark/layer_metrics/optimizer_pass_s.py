"""Host seconds in what `Optimizer.minimize` appends behind the backward
pass: gradient clipping, regularization, and the accumulators and update
ops (`ptpu_build_seconds_total`, phases `clip` + `regularize` +
`optimize_pass`; spans `build/clip`, `build/regularize`,
`build/optimize_pass`). It lies inside `program_build_s`."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_build_seconds_total",
                      phase=("clip", "regularize", "optimize_pass"))
