"""What the metric readers share. A reader is one file with one function,
`read(record)`, which returns the metric's value in the unit BENCHMARK.json
gives it, or None where there is nothing to read; the harness then leaves
the metric out of the line. `record` is the dict cell.run builds: the host
spans, the set-up counters, the window's blocks (with every step's small
fetches, [steps, elements] a name, under "window" / "fetches"), the memory
peak and, in a traced run, trace_reduce's summary under "trace"."""
import statistics


def samples_per_s_per_chip(record):
    """Median over the window's blocks of samples trained a second, over
    the chips of the cell."""
    if not record["block_rates"]:
        return None
    return statistics.median(record["block_rates"]) / record["chips"]


def span_seconds(record, name):
    """Total seconds of the benchmark's host span `name`, or None."""
    got = record["spans"].seconds(name)
    return sum(got) if got else None


def trace_share(record, seconds_key, over_key):
    """100 x one of the trace summary's seconds over another (its window_s
    or busy_s), or None without a trace."""
    trace = record["trace"]
    if not trace or not trace[over_key] > 0:
        return None
    return 100.0 * trace[seconds_key] / trace[over_key]


def category_ms_per_step(record, kind):
    """Device milliseconds a step in operations of `kind` (self time, a
    chip): a traced run's window is the traced window, so its steps are the
    trace's."""
    trace, steps = record["trace"], record["window"]["attempted"]
    if not trace or not trace["busy_s"] > 0 or not steps:
        return None
    return 1e3 * trace["category_s"].get(kind, 0.0) / steps
