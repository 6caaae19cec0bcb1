"""Peak device memory in GiB after the window, the largest over the cell's
devices: the arrays alive at once plus the scratch space the runtime
reserved for the programs' temporaries (cell._memory_peak)."""


def read(record):
    peak = record["memory_peak_bytes"]
    return None if peak is None else peak / 2.0 ** 30
