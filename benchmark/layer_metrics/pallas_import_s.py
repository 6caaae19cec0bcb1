"""Seconds the import of `jax.experimental.pallas` took
(`ptpu_import_seconds{module="jax.experimental.pallas"}`, set by
paddle_tpu/ops/pallas_import.py, the one place it is imported). It is paid
inside whatever built or lowered the first op that needs a kernel, so it
lies inside `build_s` or `first_step_s`, never in `imports`. 0.0 where the
program has the gauge and never imported it (a cell that runs no kernel)."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_import_seconds",
                      module="jax.experimental.pallas")
