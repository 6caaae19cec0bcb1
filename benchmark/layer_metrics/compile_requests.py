"""XLA compile requests during set-up, counted from jax.monitoring."""


def read(record):
    return record["counters"]["compile_requests"]
