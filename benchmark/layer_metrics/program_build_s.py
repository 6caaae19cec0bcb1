"""Host seconds inside the outermost `program_guard`: the model builder,
`append_backward` and the optimizer's pass, timed by the program itself
(`ptpu_build_seconds_total{phase="program"}`, span `build/program`). It is
`build_s` seen from inside; the two differ by the benchmark's own `with`
statements."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_build_seconds_total", phase="program")
