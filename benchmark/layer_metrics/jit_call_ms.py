"""Median host milliseconds in the call of the jitted step alone
(`exec/jit_call`), over the window's steps in the flight recorder's ring."""
from benchmark.program_reads import median_ms, window_steps


def read(record):
    steps = window_steps(record)
    return median_ms([jit for _, jit in steps]) if steps else None
