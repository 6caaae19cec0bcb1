"""What the readers by fluid op share: device milliseconds a step under the
program's own names, with no `.xplane.pb` at hand.

`record["trace"]["top_ops"]` lists EVERY device operation of the traced
window as ["<HLO instruction> <opcode> <detail>", self seconds a plane]
(trace_reduce.reduce_planes). The program says which fluid op each
instruction of its compiled step is (`paddle_tpu.profiler.step_op_names`:
the step's `compiled.as_text()`, read once, after the window, when a reader
first asks) and reduces instruction seconds to `profiler.device_op_table`'s
table: a row a fluid op type, a Mosaic kernel or a collective in a row of
its own beside the fluid op it ran for, an instruction under no fluid scope
under its own name with `scoped` false (`profiler.device_seconds_by_op`).
The rows add up to all of `top_ops`.

None without a trace; None where the program has no such reduction (the
parent of the PR that brought it) or no held step has the trace's
instructions: the harness then leaves the metric out of the line. A number,
0.0 included, wherever a table exists: a Program without such an op ran none.

A later metric by op type is one file of five lines under layer_metrics/

    from benchmark.op_ms import op_ms_per_step
    def read(record):
        return op_ms_per_step(record, types=("gated_delta_rule",
                                             "gated_delta_rule_grad"))

and one entry of BENCHMARK.json; nothing here is edited for it."""
_tables = {}    # by -> (the trace summary it is of, table or None)
COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"


def table(record, by="type"):
    """The program's table of the traced window (`total_ms` over the whole
    window, a chip; `busy_self_ms` all of it, `scoped_ms` what lies under a
    fluid scope or in a named kernel), or None."""
    trace = record["trace"]
    if not trace or not trace["busy_s"] > 0:
        return None
    if by not in _tables or _tables[by][0] is not trace:
        reduce = _reduce_and_say if not _tables else _reduce
        _tables[by] = (trace, reduce(trace["top_ops"], by))
    return _tables[by][1]


def _reduce(top_ops, by):
    from paddle_tpu import profiler
    if not hasattr(profiler, "device_seconds_by_op"):
        return None
    seconds = {}
    for op, s in top_ops:
        name = op.split(" ")[0]
        seconds[name] = seconds.get(name, 0.0) + s
    return profiler.device_seconds_by_op(seconds, by=by)


def _reduce_and_say(top_ops, by):
    """The first reduction of a process makes the step's map: say which step
    answered and what reading it cost (or why none answered), what is left
    unnamed by row (seconds over the traced window), and that nothing
    compiled meanwhile."""
    import jax
    from paddle_tpu import profiler
    from benchmark.registry_reads import family_sum
    requests = []
    jax.monitoring.register_event_listener(
        lambda event, **_: event == COMPILE_REQUEST
        and requests.append(event))
    booked = family_sum("ptpu_compile_phase_events_total")
    found = _reduce(top_ops, by)
    if found is not None:
        print("bench: step map: %s; unnamed rows, ms over the window: %s" % (
            {k: len(v) if k == "op_names" else v
             for k, v in found["step"].items()},
            ", ".join("%s %.2f" % (r["name"], r["total_ms"])
                      for r in found["rows"]
                      if not r["scoped"] and not r["kernel"]
                      and r["total_ms"] >= 0.1)), flush=True)
    elif hasattr(profiler, "step_op_names"):
        print("bench: step map: no held step has the trace's operations: %s"
              % [{k: len(v) if k == "op_names" else v for k, v in st.items()}
                 for st in profiler.step_op_names()], flush=True)
    print("bench: step map: compile requests while it was made %d, "
          "ptpu_compile_phase_events_total %s -> %s"
          % (len(requests), booked,
             family_sum("ptpu_compile_phase_events_total")), flush=True)
    return found


def op_ms_per_step(record, types=None, role=None, less=None):
    """Device milliseconds a step in the rows of the program's table that
    are picked by

      types  row names by fluid op type, kernels and XLA's own alike
             (("mul_grad",), ("moe_ffn", "moe_ffn_grad"))
      role   rows by instance whose instance starts with "<role>."
             (lowering.ROLE_ATTR: "mtp" for the module's "mtp.0.<var>")
      less   a function of a `top_ops` name: the operations it answers True
             for are left out BEFORE the reduction (the grouped matmuls,
             for the routing around them)

    over `record["window"]["attempted"]`, as kernel_ms_per_step divides."""
    steps = record["window"]["attempted"]
    found = table(record, "instance" if role else "type")
    if found is None or not steps:
        return None
    if less is not None:
        found = _reduce([(op, s) for op, s in record["trace"]["top_ops"]
                         if not less(op)], "type")
        if found is None:
            return None
    return sum(r["total_ms"] for r in found["rows"] if r["scoped"] and (
        types is None or r["name"] in types) and (
        role is None or r["name"].partition("/")[2].startswith(role + "."))
    ) / steps


def named_share(record):
    """100 x the self time under a fluid scope or in a named kernel over all
    self time: whether the table by op is whole."""
    found = table(record)
    if found is None or not found["busy_self_ms"] > 0:
        return None
    return 100.0 * found["scoped_ms"] / found["busy_self_ms"]


def unnamed_ms_per_step(record):
    """Device milliseconds a step under no fluid scope and in no named
    kernel: the copies, `copy-done`, `slice-done`, whatever XLA adds under
    no op name."""
    steps = record["window"]["attempted"]
    found = table(record)
    if found is None or not steps:
        return None
    return (found["busy_self_ms"] - found["scoped_ms"]) / steps
