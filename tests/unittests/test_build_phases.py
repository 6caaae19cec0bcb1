"""Set-up accounts for itself from inside the program (PR 51): building a
Program is spans `build/*` of one trace under the outermost program_guard
and counters from the same clock readings, shape inference is booked by op
type where every append_op passes, the two imports are gauges, the report
prints all of it and the benchmark's six readers read it."""
import json
import os
import re
import subprocess
import sys
import threading

import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import framework
from paddle_tpu.observability import trace
from paddle_tpu.observability.registry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARTS = ["append_backward", "clip", "regularize", "optimize_pass"]
PHASES = ["program", "minimize"] + PARTS
READERS = ["program_build_s", "infer_shape_s", "append_backward_s",
           "optimizer_pass_s", "package_import_s", "pallas_import_s"]
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_build_phases",
                    "manifest.json")


def _family(name):
    return {tuple(v for _, v in key): value
            for key, value in REGISTRY.counter(name).samples()}


def _seconds():
    return {k[0]: v for k, v in _family("ptpu_build_seconds_total").items()}


def _ops():
    return {k[0]: v for k, v in _family("ptpu_build_ops_total").items()}


def _gained(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _op_count(*programs):
    return sum(len(b.ops) for p in programs for b in p.blocks)


def _model(minimize=True, clip=False, decay=False):
    """A small regression in the current guard; returns its loss."""
    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=8, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(
        x=fluid.layers.square_error_cost(input=pred, label=y))
    if clip:
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(clip_norm=1.0))
    if minimize:
        fluid.optimizer.Adam(
            learning_rate=0.01,
            regularization=fluid.regularizer.L2Decay(1e-4) if decay
            else None).minimize(loss)
    return loss


def _build(**kw):
    """(main, startup, the build/* events of the build in the ring's
    order, seconds gained by phase, ops gained by phase)."""
    trace.configure(capacity=4096)
    sec0, ops0 = _seconds(), _ops()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        _model(**kw)
    events = [e for e in trace.dump()["events"]
              if e["name"].startswith("build/")]
    return main, startup, events, _gained(_seconds(), sec0), \
        _gained(_ops(), ops0)


# ------------------------------------------------------------- the spans --
def test_the_spans_nest_as_listed_under_one_trace():
    _, _, events, _, _ = _build(clip=True, decay=True)
    by_name = {e["name"]: e for e in events}
    assert sorted(by_name) == sorted("build/" + p for p in PHASES)
    assert len(events) == len(PHASES)       # each once: no nested guard's
    assert {e["cat"] for e in events} == {"build"}
    assert len({e["trace"] for e in events}) == 1
    assert events[0]["trace"] is not None
    program, minimize = by_name["build/program"], by_name["build/minimize"]
    assert program["parent"] is None
    assert minimize["parent"] == program["span"]
    for part in PARTS:
        assert by_name["build/" + part]["parent"] == minimize["span"], part
    # a child lies inside its parent on the one clock
    for child, parent in [(minimize, program)] + [
            (by_name["build/" + p], minimize) for p in PARTS]:
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] \
            + 1e-3


@pytest.mark.parametrize("phase", PHASES)
def test_a_counter_holds_its_spans_own_seconds_and_ops(phase):
    """Span and counters are booked from the same two clock readings."""
    _, _, events, seconds, ops = _build(clip=True, decay=True)
    ev = next(e for e in events if e["name"] == "build/" + phase)
    assert seconds[phase] == pytest.approx(ev["dur"] / 1e6, abs=1e-9)
    assert ops[phase] == ev["args"]["ops"]


def test_the_parts_add_up_to_no_more_than_the_whole():
    _, _, _, seconds, _ = _build(clip=True, decay=True)
    assert all(seconds[p] > 0 for p in PHASES)
    assert sum(seconds[p] for p in PARTS) <= seconds["minimize"]
    assert seconds["minimize"] <= seconds["program"]


def test_ops_total_equals_the_ops_the_programs_gained():
    main, startup, _, _, ops = _build(clip=True, decay=True)
    assert ops["program"] == _op_count(main, startup) > 0
    assert sum(ops[p] for p in PARTS) == ops["minimize"]
    assert ops["clip"] > 0 and ops["regularize"] > 0
    # the backward pass is in the main program alone; the optimizer's
    # accumulators are initialised in the startup program
    assert ops["append_backward"] == sum(
        op.type in ("grad_of", "fill_constant") and any(
            "@GRAD" in n for n in op.all_output_vars())
        for op in main.global_block().ops)
    assert ops["optimize_pass"] > sum(
        op.type == "adam" for op in main.global_block().ops)


def test_a_nested_guard_adds_neither_a_span_nor_seconds():
    trace.configure(capacity=4096)
    main, startup, other = fluid.Program(), fluid.Program(), fluid.Program()
    sec0, ops0 = _seconds(), _ops()
    with fluid.program_guard(main, startup):
        assert framework.in_build_phase()
        with fluid.program_guard(main, startup):
            fluid.layers.data(name="a", shape=[4], dtype="float32")
        with fluid.program_guard(other):        # another program's, too
            b = fluid.layers.data(name="b", shape=[4], dtype="float32")
            fluid.layers.scale(b, scale=2.0)
    assert not framework.in_build_phase()
    events = [e for e in trace.dump()["events"]
              if e["name"].startswith("build/")]
    assert [e["name"] for e in events] == ["build/program"]
    gained = {k: v for k, v in _gained(_seconds(), sec0).items() if v}
    assert list(gained) == ["program"]
    assert gained["program"] == pytest.approx(events[0]["dur"] / 1e6)
    # ops are those of the guard's own programs: `other` is not its work
    assert _gained(_ops(), ops0)["program"] == _op_count(main, startup)


def test_append_backward_alone_is_booked():
    trace.configure(capacity=4096)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss = _model(minimize=False)
    before, ops0, n = _seconds(), _ops(), _op_count(main)
    pairs = fluid.append_backward(loss)        # outside any guard
    assert pairs
    gained = {k: v for k, v in _gained(_seconds(), before).items() if v}
    assert list(gained) == ["append_backward"]
    assert _gained(_ops(), ops0)["append_backward"] == _op_count(main) - n
    ev = trace.dump()["events"][-1]
    assert (ev["name"], ev["parent"], ev["cat"]) == \
        ("build/append_backward", None, "build")
    assert ev["dur"] / 1e6 == pytest.approx(gained["append_backward"])


def test_minimize_outside_a_guard_is_its_own_root():
    """The legacy style: layers on the default programs, no guard."""
    trace.configure(capacity=4096)
    main, startup = fluid.Program(), fluid.Program()
    old = (fluid.switch_main_program(main),
           fluid.switch_startup_program(startup))
    try:
        with fluid.unique_name.guard():
            _model()
    finally:
        fluid.switch_main_program(old[0])
        fluid.switch_startup_program(old[1])
    events = {e["name"]: e for e in trace.dump()["events"]
              if e["name"].startswith("build/")}
    assert "build/program" not in events    # minimize's own guard is nested
    assert events["build/minimize"]["parent"] is None
    assert events["build/optimize_pass"]["parent"] == \
        events["build/minimize"]["span"]


def test_a_build_that_raises_closes_its_phase():
    trace.configure(capacity=4096)
    before = _seconds().get("program", 0.0)
    with pytest.raises(ZeroDivisionError):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            1 / 0
    assert not framework.in_build_phase()
    ev = trace.dump()["events"][-1]
    assert ev["name"] == "build/program"
    assert ev["args"]["error"] == "ZeroDivisionError"
    assert _seconds()["program"] > before
    assert trace.dump()["open"] == []


def test_each_thread_has_its_own_outermost_guard():
    trace.configure(capacity=4096)
    inside = threading.Event()
    release = threading.Event()
    seen = {}

    def other():
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            inside.set()
            assert release.wait(timeout=60)
        seen["done"] = True

    worker = threading.Thread(target=other)
    worker.start()
    assert inside.wait(timeout=60)
    try:
        assert not framework.in_build_phase()   # the worker's, not ours
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            pass
    finally:
        release.set()
        worker.join(timeout=60)
    assert not worker.is_alive() and seen.get("done")
    events = [e for e in trace.dump()["events"]
              if e["name"] == "build/program"]
    assert len(events) == 2
    assert events[0]["trace"] != events[1]["trace"]
    assert {e["parent"] for e in events} == {None}


def test_recorder_off_keeps_the_counters():
    """`trace.set_enabled(False)` is the recorder's on/off switch (what an
    overhead reading compares): no event, the same counters."""
    trace.configure(capacity=4096)
    trace.set_enabled(False)
    try:
        sec0 = _seconds()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            _model()
        assert trace.dump()["events"] == []
    finally:
        trace.set_enabled(True)
    gained = _gained(_seconds(), sec0)
    assert all(gained[p] > 0 for p in ("program", "minimize",
                                       "append_backward", "optimize_pass"))


# ------------------------------------------------------- shape inference --
def _infer(name="ptpu_infer_shape_calls_total"):
    return {(dict(k)["op"], dict(k)["how"]): v
            for k, v in REGISTRY.counter(name).samples()}


def test_infer_shape_rows_by_op_type_and_how():
    calls0, sec0 = _infer(), _infer("ptpu_infer_shape_seconds_total")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6, 16], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, num_flatten_dims=2)
        fluid.layers.moe_ffn(h, num_experts=4, d_expert=8, top_k=2)
        block = main.global_block()
        out = block.create_var(name="not_inferred", dtype="float32")
        block.append_op(type="tanh", inputs={"X": [h]},
                        outputs={"Out": [out]}, infer_shape=False)
        pre = block.create_var(name="prepended", dtype="float32")
        block.prepend_op(type="sigmoid", inputs={"X": [x]},
                         outputs={"Out": [pre]})
    calls = _gained(_infer(), calls0)
    seconds = _gained(_infer("ptpu_infer_shape_seconds_total"), sec0)
    assert calls[("mul", "eval_shape")] == 1
    assert calls[("moe_ffn", "custom")] == 1
    assert calls[("sigmoid", "eval_shape")] == 1    # prepend_op passes too
    # gained, not seen: the counters are the process's, and a test file that
    # ran before this one in the same worker may have built a tanh
    assert not any(n for (op, _), n in calls.items() if op == "tanh")
    assert out.shape is None and tuple(pre.shape) == (-1, 6, 16)
    assert set(calls) == set(seconds)
    assert all(seconds[k] > 0 for k in calls if calls[k])
    # every registered op appended with infer_shape=True, and none else
    assert sum(calls.values()) == sum(
        op.type in ("mul", "elementwise_add", "moe_ffn", "sigmoid")
        for op in main.global_block().ops)


def test_infer_shape_seconds_lie_inside_the_program_phase():
    _, _, _, seconds, _ = _build()
    sec0 = sum(_infer("ptpu_infer_shape_seconds_total").values())
    _, _, _, seconds, _ = _build()
    gained = sum(_infer("ptpu_infer_shape_seconds_total").values()) - sec0
    assert 0 < gained <= seconds["program"]


def test_jax_reports_eval_shapes_trace_inside_infer_shape():
    """What jax.monitoring raises while a Program is built: one
    `jaxpr_trace_duration` an eval_shape (and one more, nested, a jitted
    function the rule calls), all under infer_and_set_shapes, so
    `ptpu_infer_shape_seconds_total` holds them and nothing books them a
    second time (compile_cache drops an event with no exec/step open)."""
    import jax
    seen = []

    def on_duration(event, seconds, **_):
        frame, inside = sys._getframe(1), False
        while frame is not None and not inside:
            inside = frame.f_code.co_name == "infer_and_set_shapes"
            frame = frame.f_back
        seen.append((event, inside))

    phases0 = _family("ptpu_compile_phase_seconds_total")
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _build()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert seen and {e for e, _ in seen} == \
        {"/jax/core/compile/jaxpr_trace_duration"}
    assert all(inside for _, inside in seen)
    assert _family("ptpu_compile_phase_seconds_total") == phases0


# ----------------------------------------------------------- the imports --
def _child(code):
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_import_gauges_in_a_child_process():
    got = _child(
        "import sys, json, time\n"
        "import jax\n"
        "t0 = time.perf_counter()\n"
        "import paddle_tpu\n"
        "whole = time.perf_counter() - t0\n"
        "from paddle_tpu.observability.registry import REGISTRY\n"
        "g = REGISTRY.gauge('ptpu_import_seconds')\n"
        "first = [g.value(module='paddle_tpu'),\n"
        "         g.value(module='jax.experimental.pallas'),\n"
        "         sorted(m for m in sys.modules if 'pallas' in m)]\n"
        "import paddle_tpu.ops.embedding_grad\n"
        "print(json.dumps(dict(whole=whole, first=first,\n"
        "    package=g.value(module='paddle_tpu'),\n"
        "    pallas=g.value(module='jax.experimental.pallas'),\n"
        "    help=REGISTRY.snapshot()['ptpu_import_seconds']['help'],\n"
        "    loaded='jax.experimental.pallas' in sys.modules)))\n")
    package, pallas, modules = got["first"]
    assert 0 < package <= got["whole"]
    assert pallas is None and modules == []     # PR 50's guard, from inside
    assert got["package"] == package            # set once
    assert got["pallas"] > 0 and got["loaded"]
    assert "jax" in got["help"] and "pallas" in got["help"]


@pytest.mark.parametrize("module", [
    "pallas_kernels", "expert_gmm", "mhc_kernels", "causal_conv_kernels",
    "gated_delta_kernels", "embedding_grad", "selective_scan_kernels",
    "ssd_kernels", "rotary_kernels"])
def test_a_kernel_module_takes_pallas_from_the_one_place(module):
    """The nine kernel modules import pallas through ops/pallas_import.py,
    in one line with `kernel_entry`, the form their entries take (PR 60),
    and nowhere else does the package import it."""
    src = open(os.path.join(ROOT, "paddle_tpu", "ops", module + ".py")).read()
    assert "from .pallas_import import kernel_entry, pl, pltpu\n" in src
    assert "from jax.experimental import pallas" not in src
    assert "from jax.experimental.pallas import" not in src


def test_nothing_else_under_paddle_tpu_imports_pallas():
    hits = []
    for root, _, names in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            for line in open(path):
                text = line.strip()
                if text.startswith(("import ", "from ")) and \
                        "jax.experimental" in text and "pallas" in text:
                    hits.append(os.path.relpath(path, ROOT))
    assert sorted(set(hits)) == ["paddle_tpu/ops/pallas_import.py"]


# ------------------------------------------------------------ the report --
def test_profile_report_ends_with_the_builds_tables():
    _build(clip=True)
    profiler.reset_profiler()
    assert "Build(s)" not in profiler.profile_report()
    profiler.record_event("test/entry")
    text = profiler.profile_report()
    profiler.reset_profiler()
    build = text[text.index("Build(s) by phase"):
                 text.index("Shape inference(s) by op type")].splitlines()
    assert [ln.split()[0] for ln in build[1:]] == PHASES
    assert all(float(ln.split()[1]) >= 0 and int(ln.split()[3]) >= 0
               for ln in build[1:])
    infer = text[text.index("Shape inference(s) by op type"):].splitlines()
    assert 2 <= len(infer) <= 12                # a heading, ten rows, a rest
    assert any(ln.split()[0] == "mul/eval_shape" or "more op types" in ln
               for ln in infer[1:])
    seconds = [float(ln.split()[-2]) for ln in infer[1:]
               if "more op types" not in ln]
    assert seconds == sorted(seconds, reverse=True)


# ----------------------------------------------------------- the readers --
@pytest.fixture
def readers(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    from benchmark import manifest
    return {name: manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
        for name in READERS}


def test_the_manifest_lists_the_six_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter",
            "layer": "program build and lowering", "moves": "setup_s"}
    assert "build_s" in entries         # timed from outside, it stays


def test_the_readers_read_the_programs_own_counters(readers, monkeypatch):
    _build(clip=True, decay=True)
    got = {name: readers[name].read({}) for name in READERS}
    seconds = _seconds()
    assert got["program_build_s"] == seconds["program"]
    assert got["append_backward_s"] == seconds["append_backward"]
    assert got["optimizer_pass_s"] == pytest.approx(
        seconds["clip"] + seconds["regularize"] + seconds["optimize_pass"])
    assert got["infer_shape_s"] == pytest.approx(
        sum(_infer("ptpu_infer_shape_seconds_total").values()))
    gauge = REGISTRY.gauge("ptpu_import_seconds")
    assert got["package_import_s"] == gauge.value(module="paddle_tpu") > 0
    assert got["pallas_import_s"] == (
        gauge.value(module="jax.experimental.pallas") or 0.0)
    # the package's gauge and no pallas: a cell that runs no kernel reads 0.0
    monkeypatch.setattr(gauge, "_values", {
        k: v for k, v in gauge._values.items()
        if dict(k)["module"] == "paddle_tpu"})
    assert readers["pallas_import_s"].read({}) == 0.0
    # a program without the counters: the parent
    monkeypatch.setattr(REGISTRY, "_metrics", {})
    assert {name: readers[name].read({}) for name in READERS} == \
        dict.fromkeys(READERS)


@pytest.mark.parametrize("workload,pallas", [("tiny_t16", True),
                                             ("tiny_hostu8", False)])
def test_the_six_print_on_a_real_rehearsal(workload, pallas, tmp_path):
    """Through the driver's command, traced, on the CPU: a `program_counter`
    metric prints there. The tiny transformer's `layer_norm` rule holds a
    kernel, so building it imports pallas; the tiny ResNet never does."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", TINY, "--workload", workload, "--rehearse",
         "--seed", "3000000019", "--seconds", "1", "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    got = {n: m["value"] for n, m in out["metrics"].items()}
    assert set(READERS) <= set(got)
    assert all(out["metrics"][n]["unit"] == "s" for n in READERS)
    line = next(ln for ln in proc.stdout.splitlines()
                if "bench: set-up" in ln)
    spans = {k: float(v) for k, v in re.findall(
        r"(\w+) (\d+\.\d+)", line.split("start: ")[1])}
    # the line rounds each span to 10 ms
    assert -0.01 < spans["build"] - got["program_build_s"] < 0.05 + 0.01
    assert got["infer_shape_s"] + got["append_backward_s"] \
        + got["optimizer_pass_s"] <= got["program_build_s"]
    assert 0 < got["package_import_s"] < spans["imports"] + 0.01
    assert (got["pallas_import_s"] > 0) == pallas
    assert got["package_import_s"] + got["pallas_import_s"] < \
        spans["imports"] + spans["build"] + spans["first_step"]
