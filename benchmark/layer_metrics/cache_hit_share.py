"""Share of set-up's compile requests the persistent cache answered."""


def read(record):
    c = record["counters"]
    if not c["compile_requests"]:
        return None
    return 100.0 * c["cache_hits"] / c["compile_requests"]
