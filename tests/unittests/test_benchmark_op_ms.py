"""The readers by fluid op (benchmark/op_ms.py, PR 69): `top_ops` through the
program's reduction over the compiled step's map. On a synthetic record with
a hand-made map: the rows add up to `top_ops`, the routing is `moe_ffn` less
exactly what `expert_matmul_ms_per_step` reads, two modules that share
`fusion.1` are never merged, None without a trace / a map / the program's
function (the parent), 0.0 where a table exists and no such op ran. On the
recorded four-chip trace: the profiler's table from the trace's own op_names
and the reduction from a step map are one table. And every new entry of
BENCHMARK.json loads through benchmark/manifest.py in every cell it lists."""
import importlib
import json
import os
import sys
import weakref

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, op_ms, trace_reduce     # noqa: E402
from paddle_tpu import profiler                          # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RECORDED = os.path.join(REPO, "benchmark", "tests", "recorded",
                        "dp4_boundary.xplane.pb")
NEW = ("named_device_share", "unnamed_op_ms_per_step", "mul_ms_per_step",
       "mul_grad_ms_per_step", "moe_ffn_ms_per_step",
       "moe_routing_ms_per_step", "optimizer_ms_per_step", "mtp_ms_per_step")
MOSAIC = "custom-call tpu_custom_call"


def _reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


# instruction -> (op_name, is a Mosaic call, "<opcode> <detail>", seconds)
MAIN = {
    "fusion.1": ("jit(fn)/op:mul/fc_0.tmp_0/dot_general", False,
                 "fusion kOutput", 0.010),
    "fusion.2": ("jit(fn)/op:mul_grad/fc_0.w_0~GRAD/transpose(jvp("
                 "op:mul/fc_0.tmp_0))/dot_general", False,
                 "fusion kOutput", 0.020),
    "ragged-dot-none.3": ("jit(fn)/op:moe_ffn/moe_0.out/ragged_dot", False,
                          "ragged-dot", 0.004),
    "ptpu_expert_gmm_fwd.2": ("jit(fn)/op:moe_ffn/moe_0.out/jit(_gmm)/"
                              "pallas_call", True, MOSAIC, 0.006),
    "ptpu_expert_gmm_dweights": (
        "jit(fn)/op:moe_ffn_grad/moe_0.w~GRAD/transpose(jvp(op:moe_ffn/"
        "moe_0.out))/jit(_gmm_dw)/pallas_call", True, MOSAIC, 0.008),
    "ptpu_flash_fwd.4": ("jit(fn)/op:fused_attention/attn_0.out/"
                         "jit(_flash_fwd_call)/pallas_call", True, MOSAIC,
                         0.003),
    "sort.7": ("jit(fn)/op:moe_ffn/moe_0.out/jit(_sorted_by)/sort", False,
               "sort", 0.005),
    "fusion.9": ("jit(fn)/op:moe_ffn_grad/moe_0.w~GRAD/transpose(jvp("
                 "op:moe_ffn/moe_0.out))/mul", False, "fusion kLoop", 0.007),
    "fusion.11": ("jit(fn)/op:adam/fc_0.w_0/sub", False, "fusion kLoop",
                  0.030),
    "fusion.12": ("jit(fn)/op:adam_beta_pow_update/beta1_pow_acc/mul", False,
                  "fusion kLoop", 0.001),
    "fusion.13": ("jit(fn)/op:mul/mtp.0.fc_9.tmp_0/dot_general", False,
                  "fusion kOutput", 0.002),
    "fusion.14": ("jit(fn)/op:scale/clip_0.tmp/mul", False, "fusion kLoop",
                  0.0005),
    "copy.5": ("", False, "copy", 0.0015),
    "copy-done.1": ("", False, "copy-done", 0.0025),
    "all-reduce.3": ("jit(fn)/op:mul_grad/fc_0.w_0~GRAD/psum", False,
                     "all-reduce", 0.0009),
}
STARTUP = {"fusion.1": ("jit(fn)/op:uniform_random/fc_0.w_0/mul", False,
                        "fusion kLoop", 0.0),
           "fusion.2": ("jit(fn)/op:fill_constant/b/broadcast", False,
                        "fusion kLoop", 0.0),
           "fusion.5": ("jit(fn)/op:fill_constant/c/broadcast", False,
                        "fusion kLoop", 0.001)}
STEPS = 2
TOTAL_MS = 1e3 * sum(v[3] for v in MAIN.values())


def _record(ops=MAIN, traced=True):
    top = [["%s %s" % (name, v[2]), v[3]] for name, v in ops.items()]
    trace = {"busy_s": sum(s for _, s in top), "top_ops": top} \
        if traced else None
    return {"trace": trace, "window": {"attempted": STEPS}}


def _steps_of(*modules):
    return [{"label": "exe", "module": "jit_fn_%d" % k, "seconds": 0.0,
             "op_names": {n: v[:2] for n, v in ops.items()}}
            for k, ops in enumerate(modules)]


class _Executable(object):
    """Stands for a step's executable in the profiler's weak registry."""


def _hold(monkeypatch, entries):
    """The profiler holds steps that say `entries` of themselves (oldest
    first), and nothing else."""
    steps, alive = weakref.WeakKeyDictionary(), []
    for entry in entries:
        step = profiler._Step(entry["label"], None, None)
        step.found = entry
        alive.append(_Executable())
        steps[alive[-1]] = step
    monkeypatch.setattr(profiler, "_steps", steps)
    monkeypatch.setattr(profiler, "_test_keeps_alive", alive, raising=False)


@pytest.fixture
def program(monkeypatch):
    """The program's step maps, hand-made: the startup program's module
    first, then the step's."""
    op_ms._tables.clear()
    _hold(monkeypatch, _steps_of(STARTUP, MAIN))
    monkeypatch.setattr(
        "paddle_tpu.ops.pallas_kernels.EXPERT_MATMUL_KERNELS",
        ("ptpu_expert_gmm_fwd", "ptpu_expert_gmm_dweights"), raising=False)
    yield
    op_ms._tables.clear()


def test_the_rows_add_up_to_top_ops(program):
    record = _record()
    for by in ("type", "instance"):
        found = op_ms.table(record, by)
        assert found["step"]["module"] == "jit_fn_1"
        assert sum(r["total_ms"] for r in found["rows"]) == pytest.approx(
            TOTAL_MS)
        assert found["busy_self_ms"] == pytest.approx(TOTAL_MS)
    unnamed = 1e3 * (0.0015 + 0.0025) / STEPS
    assert _reader("unnamed_op_ms_per_step").read(record) == pytest.approx(
        unnamed)
    assert _reader("named_device_share").read(record) == pytest.approx(
        100.0 * (1 - unnamed * STEPS / TOTAL_MS))
    # every op type's rows and the unnamed rest are the whole step
    types = {r["name"] for r in op_ms.table(record)["rows"] if r["scoped"]}
    assert sum(op_ms.op_ms_per_step(record, types=(t,)) for t in types) \
        + unnamed == pytest.approx(TOTAL_MS / STEPS)


def test_each_reader_reads_its_rows(program):
    record = _record()
    ms = lambda *seconds: 1e3 * sum(seconds) / STEPS    # noqa: E731
    assert _reader("mul_ms_per_step").read(record) == pytest.approx(
        ms(0.010, 0.002))
    # the grad op's matmul and the collective that ran for it
    assert _reader("mul_grad_ms_per_step").read(record) == pytest.approx(
        ms(0.020, 0.0009))
    assert _reader("moe_ffn_ms_per_step").read(record) == pytest.approx(
        ms(0.004, 0.006, 0.008, 0.005, 0.007))
    assert _reader("optimizer_ms_per_step").read(record) == pytest.approx(
        ms(0.030, 0.001))        # adam and its beta powers, not `scale`
    assert _reader("mtp_ms_per_step").read(record) == pytest.approx(
        ms(0.002))


def test_the_routing_is_moe_ffn_less_what_expert_matmul_reads(program):
    record = _record()
    whole = _reader("moe_ffn_ms_per_step").read(record)
    routing = _reader("moe_routing_ms_per_step").read(record)
    matmuls = _reader("expert_matmul_ms_per_step").read(record)
    assert matmuls == pytest.approx(1e3 * (0.004 + 0.006 + 0.008) / STEPS)
    assert whole - routing == pytest.approx(matmuls, abs=1e-9)
    assert routing == pytest.approx(1e3 * (0.005 + 0.007) / STEPS)


def test_two_modules_that_share_an_instruction_are_never_merged(program):
    """`fusion.1` and `fusion.2` are in both maps and `fusion.5` in the
    startup program's alone: the newest step that has EVERY name answers,
    and a trace with an instruction of each module alone, or of neither,
    has no table."""
    shared = {n: MAIN[n] for n in ("fusion.1", "fusion.2")}
    found = op_ms.table(_record(shared))
    assert found["step"]["module"] == "jit_fn_1"
    assert {r["name"] for r in found["rows"]} == {"mul", "mul_grad"}
    found = op_ms.table(_record(dict(shared, **{"fusion.5": STARTUP[
        "fusion.5"]})))
    assert found["step"]["module"] == "jit_fn_0"
    assert {r["name"] for r in found["rows"]} == {"uniform_random",
                                                 "fill_constant"}
    assert op_ms.table(_record())["step"]["module"] == "jit_fn_1"
    of_each = {"fusion.5": STARTUP["fusion.5"], "sort.7": MAIN["sort.7"]}
    assert op_ms.table(_record(of_each)) is None
    stranger = dict(MAIN, **{"fusion.77": ("", False, "fusion kLoop", 0.1)})
    assert op_ms.table(_record(stranger)) is None
    for name in NEW:
        assert _reader(name).read(_record(stranger)) is None


def test_none_without_a_trace_and_without_a_map(program, monkeypatch):
    for name in NEW:
        assert _reader(name).read(_record(traced=False)) is None
    _hold(monkeypatch, [])
    for name in NEW:
        op_ms._tables.clear()
        assert _reader(name).read(_record()) is None
    _hold(monkeypatch, [{"label": "exe", "left_out": "jax lowered it anew"}])
    op_ms._tables.clear()
    assert _reader("mul_ms_per_step").read(_record()) is None


def test_zero_where_a_table_exists_and_no_such_op_ran(program, monkeypatch):
    plain = {n: MAIN[n] for n in ("fusion.1", "fusion.2", "fusion.11",
                                  "copy.5", "fusion.14")}
    record = _record(plain)
    for name in ("moe_ffn_ms_per_step", "moe_routing_ms_per_step",
                 "mtp_ms_per_step"):
        value = _reader(name).read(record)
        assert value == 0.0 and isinstance(value, float)
    sgd_only = {"fusion.3": ("jit(fn)/op:sgd/w/sub", False, "fusion kLoop",
                             0.001), "copy.9": ("", False, "copy", 0.001)}
    _hold(monkeypatch, _steps_of(sgd_only))
    op_ms._tables.clear()
    record = _record(sgd_only)
    assert _reader("mul_grad_ms_per_step").read(record) == 0.0
    assert _reader("optimizer_ms_per_step").read(record) == pytest.approx(
        0.5)


def test_the_parents_profiler_reads_none_and_raises_nothing(program,
                                                           monkeypatch):
    """The parent of PR 69: no `step_op_names`, no `device_seconds_by_op`,
    no `optimizer.UPDATE_OP_TYPES`."""
    from paddle_tpu import optimizer
    monkeypatch.delattr(profiler, "step_op_names")
    monkeypatch.delattr(profiler, "device_seconds_by_op")
    monkeypatch.delattr(optimizer, "UPDATE_OP_TYPES")
    for name in NEW:
        op_ms._tables.clear()
        assert _reader(name).read(_record()) is None


# --- one table from two sources ----------------------------------------------
def _op_name_of(instruction):
    """A fluid scope for some instructions of the recorded trace (it was
    cut down to what trace_reduce reads and keeps no `tf_op`), none for the
    copies and the small ones."""
    base = instruction.partition(".")[0]
    if base in ("copy", "copy-done", "slice-done", "broadcast"):
        return ""
    op = {"convert_reduce_fusion": "batch_norm_grad", "fusion": "conv2d",
          "all-reduce": "conv2d_grad"}.get(base, "elementwise_add")
    return "jit(fn)/op:%s/v_%d/x" % (op, len(instruction) % 3)


@pytest.mark.parametrize("by", ["type", "instance", "scope"])
def test_the_traces_table_and_the_step_maps_are_one_table(monkeypatch, by):
    with open(RECORDED, "rb") as f:
        space = f.read()
    import jax
    planes = list(
        jax.profiler.ProfileData.from_serialized_xspace(space).planes)
    texts = {e.name for p in planes if p.name.startswith("/device:TPU:")
             for ln in p.lines if ln.name == "XLA Ops" for e in ln.events}
    by_text = {t: _op_name_of(trace_reduce.parse_op(t)[0]) for t in texts}
    from_trace = profiler.device_op_table(planes, by_text, by)
    assert from_trace["planes"] == 4 and from_trace["scoped_ms"] > 0

    step_map = {trace_reduce.parse_op(t)[0]: (by_text[t], profiler._MOSAIC
                                              in t) for t in texts}
    _hold(monkeypatch, [{"label": "pexe", "module": "jit_fn", "seconds": 0.0,
                         "op_names": step_map}])
    top_ops = trace_reduce.reduce_planes(planes)["top_ops"]
    op_ms._tables.clear()
    from_map = op_ms.table({"trace": {"busy_s": 1.0, "top_ops": top_ops},
                            "window": {"attempted": 1}}, by)
    op_ms._tables.clear()
    key = lambda r: (r["name"], r["kernel"], r["scoped"])   # noqa: E731
    want = {key(r): r["total_ms"] for r in from_trace["rows"]}
    got = {key(r): r["total_ms"] for r in from_map["rows"]}
    assert set(got) == set(want) and len(want) > 5
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k
    for total in ("busy_self_ms", "scoped_ms"):
        assert from_map[total] == pytest.approx(from_trace[total])


# --- the manifest -------------------------------------------------------------
def _new_entries():
    with open(MANIFEST) as f:
        m = json.load(f)
    cells = [w["name"] for w in m["workloads"]]
    return m, [(e["name"], c) for e in m["per_layer"] if e["name"] in NEW
               for c in e.get("workloads", cells)]


def test_the_new_entries_end_the_manifest_on_their_lists():
    m, _ = _new_entries()
    # PR 69's eight ended the list; PR 71's four stand behind them
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(NEW[0])
    assert tuple(names[first:first + len(NEW)]) == NEW
    assert names[first + len(NEW):] == [
        "kda_ms_per_step", "kda_roofline_share", "kda_layer_share",
        "group_limited_router_layer_share"]
    by_name = {e["name"]: e for e in m["per_layer"]}
    experts = by_name["expert_matmul_ms_per_step"]["workloads"]
    assert len(experts) == 10       # PR 71's cell behind PR 69's nine
    for name in NEW:
        e = by_name[name]
        assert e["source"] == "device_trace" and e["layer"] == "op lowerings"
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["moves"] == ("tokens_per_s_per_chip" if "workloads" in e
                              else "mfu")
    assert by_name["moe_ffn_ms_per_step"]["workloads"] == experts
    assert by_name["moe_routing_ms_per_step"]["workloads"] == experts
    assert by_name["mtp_ms_per_step"]["workloads"] == [
        "glm_4_7_flash_train_t4096"]


@pytest.mark.parametrize("name,cell", _new_entries()[1])
def test_a_new_entrys_reader_loads_in_every_cell_on_its_list(name, cell):
    loaded = manifest.load_cell(MANIFEST, cell)
    readers = {e["name"]: r for e, r in loaded.metrics["per_layer"]}
    assert callable(readers[name].read)
    assert readers[name].read(
        {"trace": None, "window": {"attempted": 3}}) is None
