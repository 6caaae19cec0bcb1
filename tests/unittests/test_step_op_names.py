"""The compiled step says which fluid op each of its instructions is
(profiler.step_op_names, PR 69): an executor describes, once a compile, the
arguments its jitted step was compiled for; asked later, jax hands the same
executable back for them without lowering or compiling, and the module's
text carries every instruction's op_name.

A tiny program with a loop op (two trips of a recomputing StaticRNN) and
grad_of ops, through Executor, Executor.run(steps=2) and ParallelExecutor:
the map is made after a step with no lowering and no backend compile event,
there is none before a step, every scope in it parses to an op of the
Program, and `device_seconds_by_op` reduces instruction seconds by it."""
import gc
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache, lowering
from paddle_tpu.observability.registry import REGISTRY

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
# "op:<t>_grad/<var>/transpose(jvp(op:<t>/<var>))/<primitive>" and nothing
# below: a grad op calling the linearization its forward op kept
KEPT_LINEARIZATION = re.compile(
    r"op:([^/()]+)_grad/[^/()]+/transpose\(jvp\(op:\1/[^/()]+\)\)/[^/()]+$")
_seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **_: _seen.append(event))


def _program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        rnn = fluid.layers.StaticRNN(steps=2, recompute=True)
        with rnn.step():
            state = rnn.memory(init=h)
            new = fluid.layers.fc(input=state, size=8, act="relu")
            rnn.update_memory(state, new)
            rnn.output(new)
        h = fluid.layers.reduce_sum(rnn(), dim=1)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(batch=8):
    rng = np.random.RandomState(3)
    return {"x": rng.rand(batch, 13).astype("float32"),
            "y": rng.rand(batch, 1).astype("float32")}


def _runner(kind, main, startup, loss):
    """(run(), the executor that holds the step): the startup program has
    run, no step of `main` yet."""
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if kind == "pexe":
        pexe = fluid.ParallelExecutor(main_program=main, loss_name=loss.name)
        return (lambda: pexe.run([loss.name], feed=_feed())), pexe
    if kind == "exe_committed":     # a reader's feeds: committed to the place
        feed = {n: jax.device_put(v, jax.devices()[0])
                for n, v in _feed().items()}
        return (lambda: exe.run(main, feed=feed, fetch_list=[loss])), exe
    kw = {"steps": 2} if kind == "exe_steps2" else {}
    return (lambda: exe.run(main, feed=_feed(), fetch_list=[loss], **kw)), exe


def _phase_events():
    return sum(v for _, v in REGISTRY.counter(
        "ptpu_compile_phase_events_total").samples())


KINDS = ["exe", "exe_steps2", "exe_committed", "pexe"]


@pytest.fixture
def fresh():
    """No step of an earlier test: the registry is weak, the executors of a
    finished test are garbage."""
    gc.collect()
    profiler._steps.clear()
    compile_cache.watch_compile_phases()
    yield


@pytest.mark.parametrize("kind", KINDS)
def test_the_map_is_made_after_a_step_and_compiles_nothing(fresh, kind):
    main, startup, loss = _program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        run, holder = _runner(kind, main, startup, loss)
        profiler._steps.clear()     # the startup program's step
        del _seen[:]
        assert profiler.step_op_names() == []       # before a step: nothing
        assert not _seen
        run()
        run()
        del _seen[:]
        booked = _phase_events()
        steps = profiler.step_op_names()
        assert LOWER not in _seen and COMPILE not in _seen, _seen
        assert _phase_events() == booked
        assert [s["label"] for s in steps] == [kind.split("_")[0]]
        step, = steps
        assert "left_out" not in step and step["module"].startswith("jit_")
        assert len(step["op_names"]) > 50 and step["seconds"] < 5.0
        # kept: a second question reads nothing again
        assert profiler.step_op_names()[0] is step
        run()                       # and the step still runs, warm
        assert LOWER not in _seen and COMPILE not in _seen, _seen
    del run, holder
    gc.collect()
    assert profiler.step_op_names() == []   # the executor went, its step too


@pytest.mark.parametrize("kind", KINDS)
def test_every_scope_of_the_map_is_an_op_of_the_program(fresh, kind):
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        run, holder = _runner(kind, main, startup, loss)
        profiler._steps.clear()
        run()
        step, = profiler.step_op_names()
    ops = {}
    for block in main.blocks:
        for op in block.ops:
            ops[profiler.parse_op_scope(lowering.op_scope(op))] = op
    found, transposed = set(), set()
    for instruction, (op_name, mosaic) in step["op_names"].items():
        assert not mosaic
        scope = profiler.parse_op_scope(op_name)
        if scope is None:
            continue
        assert scope in ops, (instruction, op_name)
        found.add(scope[0])
        kept = KEPT_LINEARIZATION.search(op_name)
        if kept:        # a forward op's equations transposed: the grad op's
            assert scope[0] == kept.group(1) + "_grad", op_name
            transposed.add(scope[0])
    # the loop op, its body's ops (the `while`'s children in a trace) and
    # the grad ops are all there, and the optimizer
    assert {"rnn_scan", "rnn_scan_grad", "mul", "mul_grad", "adam"} <= found
    assert transposed, sorted(found)
    in_loop = [n for n, _ in step["op_names"].values()
               if re.match(r"jit\(\w+\)/op:rnn_scan/", n) and "/op:mul/" in n]
    assert in_loop and all(
        profiler.scope_path(n).startswith("rnn_scan/") and
        "/mul/" in profiler.scope_path(n) for n in in_loop)


def test_a_step_that_misses_jaxs_caches_is_left_out_not_compiled(fresh):
    """Described as committed where the call's arguments were not, jax would
    lower and compile anew: the map says so and loads nothing."""
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        run, exe = _runner("exe", main, startup, loss)
        profiler._steps.clear()
        run()
        (executable, step), = profiler._steps.items()
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        step.args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here),
            step.args)
        del _seen[:]
        found, = profiler.step_op_names()
        assert "op_names" not in found and "anew" in found["left_out"]
        assert COMPILE not in _seen and executable is not None
        assert profiler.device_seconds_by_op({"fusion.1": 1.0}) is None


def test_the_reduction_reads_the_step_whose_instructions_are_the_traces(
        fresh):
    main, startup, loss = _program()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        startup_step, main_step = profiler.step_op_names()
        assert "adam" not in str(startup_step["op_names"].values())
        names = main_step["op_names"]
        seconds = {i: 1e-3 * (k + 1) for k, i in enumerate(sorted(names))}
        table = profiler.device_seconds_by_op(seconds)
        assert table["step"] is main_step
        assert table["busy_self_ms"] == pytest.approx(
            1e3 * sum(seconds.values()))
        assert sum(r["total_ms"] for r in table["rows"]) == pytest.approx(
            table["busy_self_ms"])
        by_name = {}
        for r in table["rows"]:
            by_name[r["name"]] = by_name.get(r["name"], 0.0) + r["total_ms"]
        want = sum(1e3 * s for i, s in seconds.items()
                   if (profiler.parse_op_scope(names[i][0]) or ("",))[0]
                   == "mul_grad")
        assert by_name["mul_grad"] == pytest.approx(want) and want > 0
        # by instance and by scope: the same seconds under other names
        for by in ("instance", "scope"):
            other = profiler.device_seconds_by_op(seconds, by=by)
            assert other["busy_self_ms"] == pytest.approx(
                table["busy_self_ms"])
            assert other["scoped_ms"] == pytest.approx(table["scoped_ms"])
        assert any(r["name"].startswith("rnn_scan/") and "/mul/" in r["name"]
                   for r in other["rows"])
        # an instruction of no held step: no table, never a merged one
        assert profiler.device_seconds_by_op(
            dict(seconds, **{"no_such_fusion.7": 1.0})) is None
        with pytest.raises(ValueError):
            profiler.device_seconds_by_op(seconds, by="layer")
