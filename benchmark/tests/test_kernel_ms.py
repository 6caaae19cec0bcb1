"""The per-kernel readers (benchmark/kernel_ms.py and the five files that
name a kernel each) on a hand-made record.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import ast
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import kernel_ms, manifest  # noqa: E402

READERS = {
    "flash_fwd_ms_per_step": "ptpu_flash_fwd",
    "flash_bwd_dkdv_ms_per_step": "ptpu_flash_bwd_dkdv",
    "flash_bwd_dq_ms_per_step": "ptpu_flash_bwd_dq",
    "layer_norm_ms_per_step": "ptpu_layer_norm_fwd",
    "softmax_xent_ms_per_step": "ptpu_softmax_xent_fwd"}
MOSAIC = " custom-call tpu_custom_call"
TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ptpu_flash_bwd_dkdv.3" + MOSAIC, 0.16],
    ["ptpu_flash_bwd_dkdv.12" + MOSAIC, 0.04],
    ["ptpu_flash_bwd_dq.7" + MOSAIC, 0.1],
    ["ptpu_flash_fwd" + MOSAIC, 0.03],
    ["ptpu_flash_fwd.1" + MOSAIC, 0.03],
    ["ptpu_layer_norm_fwd.60" + MOSAIC, 0.02],
    ["ptpu_softmax_xent_fwd.1" + MOSAIC, 0.01],
    # not this kernel's: another name stack, another opcode, another target
    ["jvp_ptpu_layer_norm_fwd_.51" + MOSAIC, 0.25],
    ["ptpu_layer_norm_fwd.x9" + MOSAIC, 0.25],
    ["ptpu_flash_fwd.4 fusion kLoop", 0.25],
    ["ptpu_flash_fwd.5 custom-call TopK", 0.25]]


def _record(top_ops=TOP_OPS, steps=8, busy_s=4.0):
    trace = None if top_ops is None else {
        "busy_s": busy_s, "top_ops": top_ops,
        "category_s": {"xla": 1.25, "pallas": 0.89}}
    return {"trace": trace, "window": {"attempted": steps}}


def _reader(name):
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name,want_ms", [
    ("flash_fwd_ms_per_step", 7.5), ("flash_bwd_dkdv_ms_per_step", 25.0),
    ("flash_bwd_dq_ms_per_step", 12.5), ("layer_norm_ms_per_step", 2.5),
    ("softmax_xent_ms_per_step", 1.25)])
def test_a_reader_sums_its_kernel_and_no_other(name, want_ms):
    reader = _reader(name)
    assert reader.KERNEL == READERS[name]
    assert reader.read(_record()) == pytest.approx(want_ms)
    assert reader.read(_record(top_ops=None)) is None       # --trace 0
    assert reader.read(_record(busy_s=0.0)) is None         # a CPU rehearsal
    assert reader.read(_record(steps=0)) is None
    # a program that names no kernel so (the parent): nothing, not zero
    assert reader.read(_record(top_ops=TOP_OPS[:1] + TOP_OPS[8:])) is None


def test_the_five_add_up_to_the_pallas_category():
    """As pallas_ms_per_step divides: every named Mosaic call of TOP_OPS
    is one of the five kernels', so their sum is the category's time less
    the stray `jvp_...` and `.x9` calls, which are a defect to show."""
    record = _record()
    named = sum(_reader(n).read(record) for n in READERS)
    assert named == pytest.approx(1e3 * 0.39 / 8)
    pallas = _reader("pallas_ms_per_step").read(record)
    assert pallas - named == pytest.approx(1e3 * 0.5 / 8)


def test_every_reader_names_a_kernel_of_the_program():
    """The literal each reader sums is one of pallas_kernels.KERNEL_NAMES,
    read from the program's source: importing it would import jax."""
    path = os.path.join(ROOT, "paddle_tpu", "ops", "pallas_kernels.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = next(ast.literal_eval(node.value) for node in tree.body
                 if isinstance(node, ast.Assign)
                 and node.targets[0].id == "KERNEL_NAMES")
    assert set(READERS.values()) <= set(names)
    for kernel in READERS.values():     # `<kernel>.<n>` is no other kernel
        assert not any(other != kernel and other.startswith(kernel + ".")
                       for other in names)
    assert kernel_ms._is_kernel("ptpu_flash_bwd_dq.7" + MOSAIC,
                                "ptpu_flash_bwd_dq")
    assert not kernel_ms._is_kernel("ptpu_flash_bwd_dq.7" + MOSAIC,
                                    "ptpu_flash_bwd_dkdv")
    assert not kernel_ms._is_kernel("ptpu_flash_bwd_dkdv.7" + MOSAIC,
                                    "ptpu_flash_bwd_d")


def test_the_manifest_lists_the_five_for_the_transformer_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    both = ["transformer_base_train_t256", "transformer_base_train_t2048"]
    for name in READERS:
        entry = entries[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "ms", "lower", "device_trace", "kernels",
            "tokens_per_s_per_chip")
        # the decoder cells that run a kernel came behind the two
        listed = entry["workloads"]
        assert listed[:2] == both if name in (
            "layer_norm_ms_per_step", "softmax_xent_ms_per_step") \
            else listed[0] == both[1] and both[0] not in listed
    cell = manifest.load_cell(os.path.join(ROOT, "BENCHMARK.json"), both[1])
    assert set(READERS) <= {m["name"] for m, _ in cell.metrics["per_layer"]}
