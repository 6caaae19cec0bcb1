"""Median host milliseconds of an executor's `run` outside the jitted call
(`exec/step` less its `exec/jit_call`): the executor's own Python, over the
window's steps in the flight recorder's ring."""
from benchmark.program_reads import median_ms, window_steps


def read(record):
    steps = window_steps(record)
    return median_ms([step - jit for step, jit in steps]) if steps else None
