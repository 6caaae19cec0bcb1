"""Seconds of set-up in jax's lowering of the traced steps, jaxpr to
StableHLO (`ptpu_compile_phase_seconds_total{phase="lower"}`)."""
from benchmark.program_reads import compile_phase_seconds


def read(record):
    return compile_phase_seconds("lower")
