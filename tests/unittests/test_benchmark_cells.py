"""The cells of BENCHMARK.json, as far as a CPU can see them with no array.

The driver judges every PR by `python benchmark/run.py` in each cell, on the
chip. Two things a program change can break there are cheap to see here:

  * a cell's builder at its published widths (a renamed layer argument, an
    op the analyzer refuses): every cell's whole training Program (forward,
    backward, optimizer) is built and analyzed, nothing is run;
  * a name the benchmark's readers spell: a Pallas kernel's, a counter
    family's, a span's. A rename in the program reads as `null` under
    `per_layer` on the chip; here it fails by name.

The rehearsals of the driver's command are test_benchmark_rehearsal.py.
"""
import functools
import glob
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
sys.path.insert(0, REPO)


def _read(path):
    with open(path) as f:
        return f.read()


def _sources(*tops):
    files = []
    for top in tops:
        path = os.path.join(REPO, top)
        if os.path.isfile(path):
            files.append(path)
        for root, _, names in os.walk(path):
            files += [os.path.join(root, n) for n in names
                      if n.endswith(".py")]
    return sorted(files)


# ------------------------------------------------------- (a) the builders --
CELLS = [w["name"] for w in json.loads(_read(MANIFEST))["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_builds_its_training_program_at_published_widths(name):
    import paddle_tpu as fluid
    from paddle_tpu.analysis import analyze
    from benchmark import manifest

    cell = manifest.load_cell(MANIFEST, name)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fetches = cell.config_module.build(fluid, cell.config, cell.traffic)
    ops = {op.type for op in main.global_block().ops}
    assert "grad_of" in ops, "no backward pass"
    assert ops & {"adam", "momentum"}, "no optimizer"
    assert main.global_block().all_parameters()
    assert fetches
    result = analyze(main)
    assert not result.errors, [str(d) for d in result.errors]
    assert cell.config_module.ops_per_sample(cell.config, cell.traffic) > 0
    assert cell.config_module.samples_per_step(cell.config, cell.traffic) > 0


@functools.lru_cache(maxsize=None)
def _expert_layers(name):
    """(attrs, {slot: [the input's shape and dtype]}) of every forward
    `moe_ffn` op of the cell's Program, and whether it computes under AMP:
    what the op's own count (`registry.get("moe_ffn").counts`) reads of a
    layer, with no array."""
    import types

    import paddle_tpu as fluid
    from benchmark import manifest

    cell = manifest.load_cell(MANIFEST, name)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    block = main.global_block()
    layers = []
    for op in block.ops:
        if op.type == "moe_ffn":
            layers.append((op.attrs, {
                slot: [types.SimpleNamespace(
                    shape=tuple(block.var(n).shape), dtype=block.var(n).dtype)
                    for n in names] for slot, names in op.inputs.items()}))
    return layers, bool(main._amp)


EXPERT_CELLS = [
    "olmoe_1b_7b_train_t4096", "smallthinker_21b_a3b_train_t8192",
    "qwen3_next_80b_a3b_train_t4096", "lfm2_8b_a1b_train_t8192",
    "xing4_0_29b_a4b_train_1seq", "glm_4_7_flash_train_t4096",
    "nemotron_3_super_120b_a12b_train_t4096", "laguna_s_2_1_train_t4096"]


@pytest.mark.parametrize("route", ["expert_gmm", "ragged_dot"])
@pytest.mark.parametrize("name", EXPERT_CELLS)
def test_an_expert_layer_says_where_its_unit_runs(name, route, monkeypatch):
    """ptpu_moe_layers_total, asked through the seam the lowering calls
    (`registry.get("moe_ffn").counts`), counts every `moe_ffn` layer of an
    expert cell's Program once under `unit="kernel"` where the step is one
    TPU's (the kernels' route at the cell's published widths: the unit is
    the gate/up kernel's epilogue, PR 65), and under no `unit` at all on
    `ragged_dot`'s (here, with no TPU)."""
    import types

    from paddle_tpu.core import registry
    from paddle_tpu.observability.registry import REGISTRY
    from paddle_tpu.ops import kernel_config

    assert set(EXPERT_CELLS) <= set(CELLS)
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    if route == "expert_gmm":
        monkeypatch.setattr(kernel_config, "dispatch_platform",
                            lambda: "tpu")
    layers, amp = _expert_layers(name)
    assert layers and amp

    def samples():
        return {tuple(sorted(labels.items())): value for labels, value
                in REGISTRY.snapshot().get(
                    "ptpu_moe_layers_total", {"samples": []})["samples"]}

    before = samples()
    ctx = types.SimpleNamespace(amp=amp, mesh=None)
    for attrs, ins in layers:
        registry.get("moe_ffn").counts(ctx, attrs, ins)
    moved = {labels: value - before.get(labels, 0)
             for labels, value in samples().items()
             if value != before.get(labels, 0)}
    assert sum(moved.values()) == len(layers)
    for labels in map(dict, moved):
        assert labels["path"] == route
        assert labels.get("unit") == ("kernel" if route == "expert_gmm"
                                      else None)


# {cell: {(path, heads, head_dim): norms}}: the norms over a head a cell's
# Program holds, as ptpu_rms_norm_calls_total books them where the step is
# one TPU's (PR 72: ops/rms_norm_kernels.py's one pass, the norm's
# transpose, enters five cells; a gated norm, a head of 64 and every block
# norm keep jax's own transpose, and the other cells have no 4-D norm at all)
HEAD_NORMS = {
    "sdar_30b_a3b_chat_train_t4096": {("kernel", 32, 128): 4,
                                      ("kernel", 4, 128): 4},
    "laguna_s_2_1_train_t4096": {("kernel", 12, 128): 2,
                                 ("kernel", 18, 128): 3,
                                 ("kernel", 2, 128): 5},
    "qwen3_next_80b_a3b_train_t4096": {("kernel", 16, 256): 1,
                                       ("kernel", 2, 256): 1,
                                       ("xla", 32, 128): 3},
    "ling_3_0_flash_train_t4096": {("kernel", 8, 128): 6},
    "phi4_mini_flash_train_t8192": {("kernel", 20, 128): 3},
    "lfm2_8b_a1b_train_t8192": {("xla", 32, 64): 1, ("xla", 8, 64): 1},
}


@pytest.mark.parametrize("name", [c for c in CELLS if "resnet" not in c])
def test_a_cell_says_which_of_its_norms_a_head_take_the_kernel(
        name, monkeypatch):
    """Every forward `rms_norm` op of the cell's Program through the op's
    own count (`registry.get("rms_norm").counts`, the seam the lowering
    calls) with no array, the step described as one
    TPU's: the counter gains HEAD_NORMS' samples and no other (SDAR: 8
    under path="kernel"); a cell that is not in the table has no norm over
    a 4-D x, and its step does not change with the kernel."""
    import types

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from benchmark import manifest
    from paddle_tpu.core import registry
    from paddle_tpu.observability.registry import REGISTRY
    from paddle_tpu.ops import kernel_config

    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    cell = manifest.load_cell(MANIFEST, name)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    assert main._amp

    def samples():
        return {tuple(sorted(labels.items())): value for labels, value
                in REGISTRY.snapshot().get(
                    "ptpu_rms_norm_calls_total", {"samples": []})["samples"]}

    def described(block, names, dtype):
        # the batch axis is -1 in a Program: a cell feeds one sequence
        return [jax.ShapeDtypeStruct(
            tuple(abs(d) for d in block.var_recursive(n).shape), dtype)
            for n in names]

    before = samples()
    ctx = types.SimpleNamespace(amp=True, mesh=None)
    for block in main.blocks:
        for op in block.ops:
            if op.type == "rms_norm":
                ins = {slot: described(block, names, jnp.bfloat16)
                       for slot, names in op.inputs.items()}
                registry.get("rms_norm").counts(ctx, op.attrs, ins)
    moved = {}
    for labels, value in samples().items():
        if value != before.get(labels, 0):
            labels = dict(labels)
            moved[labels["path"], int(labels["heads"]),
                  int(labels["head_dim"])] = value - before.get(
                      tuple(sorted(labels.items())), 0)
    assert moved == HEAD_NORMS.get(name, {})


# ------------------------------------------ (b) the names the readers spell --
READERS = sorted(
    glob.glob(os.path.join(REPO, "benchmark", "layer_metrics", "*.py"))
    + glob.glob(os.path.join(REPO, "benchmark", "configs", "*.py"))
    + [os.path.join(REPO, "benchmark", "program_reads.py"),
       os.path.join(REPO, "benchmark", "registry_reads.py"),
       os.path.join(REPO, "benchmark", "kernel_ms.py")])
# families that are no counter (the others end in _total): PR 51's gauge
GAUGES = ("ptpu_import_seconds",)


def _spelled():
    names = set()
    for path in READERS:
        names.update(re.findall(r"\bptpu_[a-z0-9_]+\b", _read(path)))
    names.update(re.findall(
        r'"(exec/[a-z_]+)"',
        _read(os.path.join(REPO, "benchmark", "program_reads.py"))))
    return sorted(names)


@functools.lru_cache(maxsize=None)
def _declared():
    """Counter families and spans a module under paddle_tpu/ declares: the
    first argument of a registry family's constructor, or of a span's."""
    families, spans = set(), set()
    for path in _sources("paddle_tpu"):
        src = _read(path)
        families.update(re.findall(
            r'\b(?:counter|gauge|histogram)\(\s*"(ptpu_[a-z0-9_]+)"', src))
        spans.update(re.findall(
            r'\b(?:span|child|enter)\(\s*"([a-z_]+/[a-z_]+)"', src))
    return families, spans


@pytest.mark.parametrize("name", _spelled())
def test_a_name_the_benchmark_reads_is_one_the_program_defines(name):
    from paddle_tpu.ops.pallas_kernels import KERNEL_NAMES
    families, spans = _declared()
    if name.startswith("exec/"):
        assert name in spans, "no span %r under paddle_tpu/" % name
    elif name.endswith("_total") or name in GAUGES:
        assert name in families, "no counter or gauge family %r" % name
    else:
        assert name in KERNEL_NAMES, "no Pallas kernel named %r" % name


def test_the_readers_spell_some_names():
    """The scan above finds what it is for: kernels, counters and spans."""
    names = _spelled()
    assert any(n.startswith("exec/") for n in names)
    assert any(n.endswith("_total") for n in names)
    assert len(names) >= 10
    # what set-up's six readers spell (PR 51)
    assert {"ptpu_build_seconds_total", "ptpu_infer_shape_seconds_total",
            "ptpu_import_seconds"} <= set(names)


@pytest.mark.parametrize("label,value", [
    ("phase", "program"), ("phase", "append_backward"), ("phase", "clip"),
    ("phase", "regularize"), ("phase", "optimize_pass"),
    ("module", "paddle_tpu"), ("module", "jax.experimental.pallas")])
def test_a_label_value_the_build_readers_spell_is_one_the_program_books(
        label, value):
    """A reader that asks for `phase="optimise_pass"` reads 0.0 for ever."""
    spelled = "".join(_read(p) for p in READERS
                      if os.path.basename(p) in (
                          "program_build_s.py", "append_backward_s.py",
                          "optimizer_pass_s.py", "package_import_s.py",
                          "pallas_import_s.py"))
    assert '"%s"' % value in spelled
    booked = "".join(_read(os.path.join(REPO, "paddle_tpu", p)) for p in (
        "core/framework.py", "core/backward.py", "optimizer.py",
        "__init__.py", "ops/pallas_import.py"))
    assert re.search(r'build_phase\(\s*"%s"' % value, booked) \
        if label == "phase" else '"%s"' % value in booked


# -------------------------------------------------- (c) one way to measure --
def test_no_program_file_reads_a_bench_environment_name():
    """Source guard: what selects a measurement is the benchmark's manifest
    and its command's arguments, never an environment name of the deleted
    bench script's family."""
    prefix = "BENCH" + "_"
    hits = [os.path.relpath(path, REPO)
            for path in _sources("paddle_tpu", "tools", "chip_smoke.py")
            if re.search(r"\b%s[A-Z0-9_]*" % prefix, _read(path))]
    assert hits == []
