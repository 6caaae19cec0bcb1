"""Seconds of set-up in jax's trace of the executors' jitted steps: the
program's lowering rules run under it
(`ptpu_compile_phase_seconds_total{phase="trace"}`)."""
from benchmark.program_reads import compile_phase_seconds


def read(record):
    return compile_phase_seconds("trace")
