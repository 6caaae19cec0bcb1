"""Seconds of set-up the executors' dispatches waited for an executable: the
XLA compile, or the persistent cache's read and load
(`ptpu_compile_phase_seconds_total{phase="compile_or_load"}`)."""
from benchmark.program_reads import compile_phase_seconds


def read(record):
    return compile_phase_seconds("compile_or_load")
