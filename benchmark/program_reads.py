"""What the program says of itself, for the metric readers that read it and
not the benchmark's own spans: the registry's counters and the flight
recorder's ring (paddle_tpu/observability). Each function returns None where
the program has no such counter or span, as a commit from before it has
not; the harness then leaves the metric out of the line."""
import statistics


def compile_phase_seconds(phase):
    """Seconds the executors' dispatches waited for jax in `phase` (trace,
    lower, compile_or_load) since the process began. The window compiles
    nothing (`no_compile_in_window`), so after it this is set-up's total."""
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_compile_phase_seconds_total")
    for labels, value in family["samples"] if family else ():
        if labels.get("phase") == phase:
            return value
    return None


def window_steps(record):
    """[(seconds in exec/step, seconds in its exec/jit_call)] of the ring's
    steps that began after the window opened, or None. The ring's `ts` count
    from `epoch_perf`, a time.perf_counter reading as `t_open` is."""
    from paddle_tpu.observability import trace
    data = trace.dump(include_open=False)
    if "epoch_perf" not in data:
        return None
    t_open = 1e6 * (record["window"]["t_open"] - data["epoch_perf"])
    steps, dispatch_step, jit = {}, {}, {}
    for ev in data["events"]:
        if ev["name"] == "exec/step" and ev["ts"] >= t_open:
            steps[ev["span"]] = ev["dur"]
        elif ev["name"] == "exec/dispatch":
            dispatch_step[ev["span"]] = ev["parent"]
    for ev in data["events"]:
        if ev["name"] == "exec/jit_call":
            step = dispatch_step.get(ev["parent"])
            if step in steps:
                jit[step] = jit.get(step, 0.0) + ev["dur"]
    return [(steps[s] / 1e6, j / 1e6) for s, j in jit.items()] or None


def median_ms(values):
    return 1e3 * statistics.median(values)
