"""Median host milliseconds inside one run call in the window."""
import statistics


def read(record):
    calls = record["spans"].seconds("run_call")
    return 1e3 * statistics.median(calls) if calls else None
