"""Host seconds in `append_backward`: the op path to the loss and one
`grad_of` op a forward op on it
(`ptpu_build_seconds_total{phase="append_backward"}`, span
`build/append_backward`). It lies inside `program_build_s`."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_build_seconds_total", phase="append_backward")
