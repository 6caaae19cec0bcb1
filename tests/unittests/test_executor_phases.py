"""The executors time their own phases (PR 35): exec/step is covered by its
children in both executors, a first run books what it waited for (jax's
trace, lowering, compile-or-load) under the span that was open and into the
registry, the trace phase is split by fluid op type, the spans reach the
profiler's trace as `ptpu/...` annotations, and the benchmark's five readers
read all of it."""
import json
import os
import statistics
import sys
import threading
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.observability import trace
from paddle_tpu.observability.registry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEADY = ["exec/prepare", "exec/host_io", "exec/lookup", "exec/dispatch",
          "exec/writeback", "exec/d2h"]
PHASES = ("trace", "lower", "compile_or_load")


def _program(width=256):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=width, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(width=256, batch=256):
    rng = np.random.RandomState(3)
    return {"x": rng.rand(batch, width).astype("float32"),
            "y": rng.rand(batch, 1).astype("float32")}


def _runner(kind, main, startup, loss):
    """run(feed) -> fetches through Executor or ParallelExecutor, the
    startup program already run."""
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if kind == "exe":
        return lambda feed: exe.run(main, feed=feed, fetch_list=[loss])
    pexe = fluid.ParallelExecutor(main_program=main, loss_name=loss.name)
    return lambda feed: pexe.run([loss.name], feed=feed)


def _steps(events):
    """[(exec/step event, its direct children, every descendant)] in the
    ring's order."""
    kids = {}
    for ev in events:
        kids.setdefault(ev["parent"], []).append(ev)

    def below(span):
        out = []
        for ev in kids.get(span, []):
            out += [ev] + below(ev["span"])
        return out
    return [(ev, sorted(kids.get(ev["span"], []), key=lambda e: e["ts"]),
             below(ev["span"]))
            for ev in events if ev["name"] == "exec/step"]


def _phase_seconds():
    return {dict(key)["phase"]: v for key, v in REGISTRY.counter(
        "ptpu_compile_phase_seconds_total").samples()}


@pytest.mark.parametrize("kind", ["exe", "pexe"])
def test_a_step_is_covered_by_its_children(kind):
    main, startup, loss = _program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        run = _runner(kind, main, startup, loss)
        trace.configure(capacity=4096)
        feed = _feed()
        for _ in range(8):
            run(feed)
    steps = _steps(trace.dump()["events"])
    assert len(steps) == 8
    assert all(s["args"]["executor"] == kind for s, _, _ in steps)
    # the first run says what it waited for, under exec/dispatch (the lazy
    # path: the jitted function compiles when it is first called)
    first, kids, below = steps[0]
    dispatch = next(k for k in kids if k["name"] == "exec/dispatch")
    assert dispatch["args"] == {"compiled": True, "aot_hit": False}
    jit_call = next(e for e in below if e["name"] == "exec/jit_call")
    assert jit_call["parent"] == dispatch["span"]
    waited = [e for e in below if e["name"].startswith("jax/")]
    assert {e["name"] for e in waited} == {
        "jax/trace", "jax/lower", "jax/compile_or_load"}
    # each under the exec/* span that was innermost: the step's own under
    # exec/jit_call, once a phase; ParallelExecutor's placement of the
    # state compiles a small program of its own before the call
    assert {e["parent"] for e in waited} <= {jit_call["span"],
                                             dispatch["span"]}
    in_call = [e for e in waited if e["parent"] == jit_call["span"]]
    assert sorted(e["name"] for e in in_call) == [
        "jax/compile_or_load", "jax/lower", "jax/trace"]
    for e in in_call:
        assert jit_call["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= jit_call["ts"] + jit_call["dur"] + 1.0
    assert all("cache_hit" in e["args"] for e in waited
               if e["name"] == "jax/compile_or_load")
    assert sum(e["dur"] for e in in_call) <= jit_call["dur"]
    assert sum(e["dur"] for e in waited) <= dispatch["dur"]
    # a steady run: exactly the named children, one jit_call inside
    # exec/dispatch and nothing else, and together they are the step.
    # Sums are compared, over the steady steps, never a wall-clock limit.
    covered, whole = 0.0, 0.0
    for step, kids, below in steps[1:]:
        assert [k["name"] for k in kids] == STEADY
        assert [e["name"] for e in below if e not in kids] == [
            "exec/jit_call"]
        assert below[[e["name"] for e in below].index(
            "exec/jit_call")]["parent"] == kids[3]["span"]
        for a, b in zip(kids, kids[1:]):     # each opens where one ends
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1e-3)
        assert kids[0]["ts"] >= step["ts"]
        assert kids[-1]["ts"] + kids[-1]["dur"] <= step["ts"] + step["dur"]
        covered += sum(k["dur"] for k in kids)
        whole += step["dur"]
    assert 0.95 * whole <= covered <= whole


def test_return_numpy_false_and_watchdog_children():
    main, startup, loss = _program(32)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _feed(32, 8)
        exe.run(main, feed=feed, fetch_list=[loss])
        trace.configure(capacity=4096)
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        exe.run(main, feed=feed, fetch_list=[loss], timeout=60.0)
    (_, lazy, _), (_, watched, _) = _steps(trace.dump()["events"])
    assert [k["name"] for k in lazy] == STEADY[:-1]
    assert [k["name"] for k in watched] == STEADY[:4] + [
        "exec/watchdog_sync", "exec/writeback", "exec/d2h"]
    assert watched[0]["tid"] == "ptpu-watchdog"


def test_compile_phases_are_booked_for_a_first_run_only():
    main, startup, loss = _program(48)
    with fluid.scope_guard(fluid.Scope()):
        run = _runner("exe", main, startup, loss)
        feed = _feed(48, 16)
        before = _phase_seconds()
        events = REGISTRY.counter("ptpu_compile_phase_events_total")
        n_before = events.value(phase="trace")
        run(feed)
        first = _phase_seconds()
        assert all(first[p] > before.get(p, 0.0) for p in PHASES)
        # one jitted step: one outermost event a phase
        assert events.value(phase="trace") == n_before + 1
        run(feed)
        assert _phase_seconds() == first
        # a jit of the test's own, outside run: not the program's
        jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        assert _phase_seconds() == first


def test_an_inner_jit_counts_once():
    """A jit under the step's trace reports a trace of its own inside the
    step's: the outermost is booked. `outermost_phases` on intervals, then a
    real step that holds one."""
    outer = ("trace", 10.0, 20.0, None)
    got, reads = compile_cache.outermost_phases([
        ("trace", 12.0, 13.0, None),            # an inner jit's trace
        ("lower", 13.0, 13.5, None),            # an eager op inside it
        ("compile_or_load", 13.5, 14.0, None),
        ("cache_read", 13.6, 13.9, None),       # ... and its cache read
        outer,
        ("lower", 20.0, 21.0, None),
        ("cache_read", 21.2, 21.8, None),
        ("compile_or_load", 21.0, 22.0, None)])
    assert got == [outer, ("lower", 20.0, 21.0, None),
                   ("compile_or_load", 21.0, 22.0, None)]
    assert reads == [("cache_read", 21.2, 21.8, None)]

    from paddle_tpu.core import registry as op_registry
    inner = jax.jit(lambda v: jnp.tanh(v) * 2.0)
    od = op_registry.get("relu")
    plain = od.lower
    od.lower = lambda ctx, ins, attrs: {"Out": [inner(ins["X"][0])]}
    try:
        main, startup, loss = _program(40)
        with fluid.scope_guard(fluid.Scope()):
            run = _runner("exe", main, startup, loss)
            trace.configure(capacity=4096)
            events = REGISTRY.counter("ptpu_compile_phase_events_total")
            n = events.value(phase="trace")
            before = _phase_seconds().get("trace", 0.0)
            run(_feed(40, 16))
    finally:
        od.lower = plain
    (step, kids, below), = _steps(trace.dump()["events"])
    dispatch = next(k for k in kids if k["name"] == "exec/dispatch")
    booked = _phase_seconds()["trace"] - before
    assert events.value(phase="trace") == n + 1
    assert 0 < booked <= dispatch["dur"] / 1e6
    assert [e["name"] for e in below].count("jax/trace") == 1


def test_lowering_seconds_by_op_type():
    def rows():
        return {dict(key)["op"]: v for key, v in REGISTRY.counter(
            "ptpu_lowering_seconds_total").samples()}
    main, startup, loss = _program(24)
    before = rows()
    with fluid.scope_guard(fluid.Scope()):
        run = _runner("exe", main, startup, loss)
        run(_feed(24, 8))
        after = rows()
        for op in ("mul", "mul_grad", "relu", "relu_grad", "sgd"):
            assert after[op] > before.get(op, 0.0), op
        run(_feed(24, 8))
        assert rows() == after          # trace time only
    # the report's last block, wherever it has an entry to report on
    profiler.reset_profiler()
    assert "Lowering(s)" not in profiler.profile_report()
    profiler.record_event("test/entry")
    text = profiler.profile_report()
    profiler.reset_profiler()
    assert "Lowering(s) by op type" in text
    listed = text[text.index("Lowering(s) by op type"):
                  text.index("Build(s) by phase")].splitlines()[1:]
    assert len(listed) <= 11 and any(ln.split()[0] == "mul_grad" or
                                     "more op types" in ln for ln in listed)


def test_a_while_ops_row_does_not_hold_its_bodys():
    """The body's ops go through lower_op inside the `while` op's own call:
    their seconds are theirs. A slow rule in the body shows which row got
    them."""
    from paddle_tpu.core import registry as op_registry
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        n = fluid.layers.fill_constant(shape=[1], dtype="int64", value=3)
        acc = fluid.layers.fill_constant(shape=[4], dtype="float32",
                                         value=1.0)
        cond = fluid.layers.less_than(x=i, y=n)
        loop = fluid.layers.While(cond=cond)
        with loop.block():
            fluid.layers.assign(fluid.layers.scale(acc, scale=2.0), acc)
            fluid.layers.increment(x=i, in_place=True)
            fluid.layers.less_than(x=i, y=n, cond=cond)
    od = op_registry.get("scale")
    plain = od.lower

    def slow(ctx, ins, attrs):
        time.sleep(0.2)
        return plain(ctx, ins, attrs)
    counter = REGISTRY.counter("ptpu_lowering_seconds_total")
    was = {op: counter.value(op=op) for op in ("while", "scale")}
    od.lower = slow
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            out, = exe.run(main, fetch_list=[acc])
    finally:
        od.lower = plain
    assert np.allclose(out, 8.0)
    body = counter.value(op="scale") - was["scale"]
    own = counter.value(op="while") - was["while"]
    assert body >= 0.2
    assert 0 < own < 0.2 <= own + body


def _host_events(trace_dir, prefix=trace.ANNOTATION_PREFIX):
    path = profiler.find_xplane(str(trace_dir))
    assert path is not None
    return [e.name
            for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith(prefix)]


def test_spans_reach_the_profilers_host_plane(tmp_path):
    main, startup, loss = _program(32)
    with fluid.scope_guard(fluid.Scope()):
        run = _runner("exe", main, startup, loss)
        feed = _feed(32, 8)
        run(feed)
        crossed = []
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                run(feed)
            # a span ended on another thread than began it, as the serving
            # window's are: no annotation, and nothing raised
            sp = trace.span("serving/crossed")
            t = threading.Thread(target=lambda: crossed.append(sp.end()))
            t.start()
            t.join(30)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
    assert crossed == [sp]
    trace.span("after/the_session").end()       # lets the held one go
    names = _host_events(tmp_path)
    assert names.count("ptpu/exec/jit_call") == 2
    assert names.count("ptpu/exec/step") == 2
    for child in STEADY:
        assert names.count("ptpu/" + child) == 2
    assert "ptpu/serving/crossed" not in names
    assert trace.recorder().dump()["events"][-2]["name"] == "serving/crossed"
    # and with no session a span carries no annotation at all
    assert trace.span("no/session")._ann is None


def test_dump_gives_the_epoch_on_perf_counter():
    trace.configure(capacity=64)
    t0 = time.perf_counter()
    with trace.span("one"):
        pass
    t1 = time.perf_counter()
    data = trace.dump()
    began = data["epoch_perf"] + data["events"][-1]["ts"] / 1e6
    assert t0 <= began <= t1


def test_the_environment_knobs_are_gone(monkeypatch):
    monkeypatch.setenv("PTPU_TRACE_RING", "64")
    monkeypatch.setenv("PTPU_TRACE_OPEN_CAP", "64")
    assert trace.FlightRecorder().capacity == 4096
    assert trace.configure(capacity=128).capacity == 128
    trace.configure(capacity=4096)


def test_idle_gaps_go_to_the_innermost_program_span():
    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)

    def plane(name, **lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=n.replace("_", " "), events=evs)
            for n, evs in lines.items()])
    host = plane("/host:CPU", python=[
        ev("bench/run_call", 0, 1000),
        ev("ptpu/exec/step", 100, 800),
        ev("ptpu/exec/dispatch", 300, 400),
        ev("ptpu/exec/jit_call", 350, 300),
        ev("ptpu/exec/prefetch_stage", 2000, 500)])
    device = plane("/device:TPU:0", XLA_Ops=[
        ev("%a", 0, 320),       # gap 320..400: dispatch 30, jit_call 50
        ev("%b", 400, 300),     # gap 700..950: dispatch 0, step 200, none 50
        ev("%c", 950, 50),      # gap 1000..2100: none 1000, prefetch 100
        ev("%d", 2100, 10)])
    got = profiler.idle_gaps_by_span([host, device])
    assert got["planes"] == 1 and got["gaps"] == 3
    rows = {name: (ms, led) for name, ms, led in got["by_span"]}
    assert rows["ptpu/exec/jit_call"] == (pytest.approx(50e-6), 1)
    assert rows["ptpu/exec/dispatch"] == (pytest.approx(30e-6), 0)
    assert rows["ptpu/exec/step"] == (pytest.approx(200e-6), 1)
    assert rows["ptpu/exec/prefetch_stage"] == (pytest.approx(100e-6), 0)
    assert rows["none"] == (pytest.approx(1050e-6), 1)
    assert got["idle_ms"] == pytest.approx(1430e-6)
    assert got["named_ms"] == pytest.approx(380e-6)
    assert got["longest"][0] == ["none", pytest.approx(1100e-6)]
    text = profiler.render_idle_gaps(got)
    assert "ptpu/exec/jit_call" in text and "26.57% under a program" in text
    # the benchmark's own annotations through the same rule
    bench = profiler.idle_gaps_by_span([host, device], prefix="bench/")
    assert dict((n, led) for n, _, led in bench["by_span"]) == {
        "bench/run_call": 2, "none": 1}
    # no device plane: nothing, and text that says so
    empty = profiler.idle_gaps_by_span([host])
    assert empty["by_span"] == [] and empty["idle_ms"] == 0.0
    assert "no gap" in profiler.render_idle_gaps(empty)


READERS = ("jaxpr_trace_s", "mlir_lower_s", "compile_or_load_s",
           "jit_call_ms", "executor_host_ms")


def test_the_five_readers_on_a_real_run(monkeypatch):
    """Loaded by path, as the manifest loads them, on a record made from a
    real CPU run; on a cleared registry and ring every one gives None, as on
    a parent commit."""
    monkeypatch.syspath_prepend(ROOT)
    from benchmark import manifest
    readers = {name: manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
        for name in READERS}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert [entries[n]["source"] for n in READERS] == \
        ["program_counter"] * 3 + ["program_span"] * 2
    assert all("workloads" not in entries[n] for n in READERS)

    main, startup, loss = _program(64)
    with fluid.scope_guard(fluid.Scope()):
        run = _runner("exe", main, startup, loss)
        trace.configure(capacity=4096)
        feed = _feed(64, 32)
        run(feed)                       # set-up: compiles
        run(feed)                       # before the window: not read
        record = {"window": {"t_open": time.perf_counter()}}
        for _ in range(5):
            run(feed)
    got = {name: readers[name].read(record) for name in READERS}
    assert all(isinstance(got[n], float) and got[n] > 0 for n in READERS)
    steps = [s for s, _, _ in _steps(trace.dump()["events"])][-5:]
    jit = [e["dur"] for e in trace.dump()["events"]
           if e["name"] == "exec/jit_call"][-5:]
    assert got["jit_call_ms"] == pytest.approx(
        statistics.median(jit) / 1e3)
    assert got["executor_host_ms"] == pytest.approx(statistics.median(
        s["dur"] - j for s, j in zip(steps, jit)) / 1e3)
    # a window that opens after the last step holds none
    late = {"window": {"t_open": time.perf_counter()}}
    assert readers["jit_call_ms"].read(late) is None
    # a program without the counter and without the spans: the parent
    trace.clear()
    monkeypatch.setattr(REGISTRY, "_metrics", {})
    assert {name: readers[name].read(record) for name in READERS} == \
        dict.fromkeys(READERS)
    assert "benchmark.program_reads" in sys.modules
