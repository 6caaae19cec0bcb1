"""The readers that measure WORK whoever does it (PR 47), on hand-made
records as test_kernel_ms.py makes them, and the counts they divide by, at
the tiny presets and at the published sizes against counts worked by hand:

  expert_matmul_ms_per_step      XLA's `ragged-dot*` and the kernels the
                                 program names in EXPERT_MATMUL_KERNELS
  expert_matmul_roofline_share   `expert_matmul_ops` over that time
  embedding_grad_ms_per_step     Mosaic calls named ptpu_embedding_grad
  embedding_grad_roofline_share  `embedding_grad_bytes` over that time
  flash_roofline_share           now also in the two oldest flash cells
  step_mfu                       the whole step on the device's clock

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_work_readers.py -q -p no:cacheprovider
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
MOSAIC = " custom-call tpu_custom_call"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RAGGED = [["ragged-dot-none.3 ragged-dot ", 0.16],
          ["ragged-dot-none.11 ragged-dot ", 0.08]]
OTHERS = [["fusion.85 fusion kOutput", 0.5],
          ["ptpu_flash_fwd.1" + MOSAIC, 0.03]]
GMM = [["ptpu_expert_gmm.2" + MOSAIC, 0.06],
       ["ptpu_expert_gmm_dw" + MOSAIC, 0.02]]
# a Mosaic call the program does not list as an expert matmul
STRAY = [["ptpu_other_gmm.4" + MOSAIC, 0.25]]
NAMED = ("ptpu_expert_gmm", "ptpu_expert_gmm_dw")
FIVE = ["olmoe_1b_7b_train_t4096", "smallthinker_21b_a3b_train_t8192",
        "qwen3_next_80b_a3b_train_t4096", "lfm2_8b_a1b_train_t8192",
        "xing4_0_29b_a4b_train_1seq"]
SIX = FIVE[:3] + ["ouro_2_6b_train_t4096"] + FIVE[3:]


def _reader(name):
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def _cell(name, path=BENCHMARK):
    return manifest.load_cell(path, name)


def _tiny(directory, name):
    return _cell(name, os.path.join(HERE, directory, "manifest.json"))


def _record(top_ops, steps=8, cell=None, fetches=None, **more):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "window_s": 5.0, "top_ops": top_ops, "category_s": {}}
    window = {"attempted": steps, "fetches": fetches or {}}
    return dict({"trace": trace, "window": window, "cell": cell,
                 "peak": PEAK}, **more)


# ---- expert_matmul_ms_per_step ---------------------------------------------

@pytest.mark.parametrize("top_ops,named,want_ms", [
    (OTHERS + RAGGED, (), 30.0),                    # today: XLA's alone
    (OTHERS + RAGGED, NAMED, 30.0),
    (OTHERS + GMM, NAMED, 10.0),                    # a kernel of the repo's
    (OTHERS + RAGGED + GMM, NAMED, 40.0),           # both
    (OTHERS + RAGGED + GMM + STRAY, NAMED, 40.0),   # not named: not counted
    (OTHERS + GMM, NAMED[:1], 7.5),
    (OTHERS + GMM, (), None),                       # named by nobody
    (OTHERS + STRAY, NAMED, None),                  # neither
    (None, NAMED, None)],                           # --trace 0
    ids=["ragged", "ragged_tuple_set", "kernel", "both", "stray", "one_name",
         "unnamed", "neither", "untraced"])
def test_expert_matmuls_are_found_by_who_they_are(monkeypatch, top_ops,
                                                  named, want_ms):
    reader = _reader("expert_matmul_ms_per_step")
    monkeypatch.setattr(reader, "kernels", lambda: named)
    got = reader.read(_record(top_ops))
    assert got is None if want_ms is None else got == pytest.approx(want_ms)


def test_expert_matmul_reader_on_a_cpu_rehearsal_and_an_empty_window():
    reader = _reader("expert_matmul_ms_per_step")
    assert reader.read(_record(RAGGED, steps=0)) is None
    record = _record(RAGGED)
    record["trace"]["busy_s"] = 0.0
    assert reader.read(record) is None


def test_every_expert_matmul_kernel_is_a_named_kernel_of_the_program():
    """Every name the reader takes from the program is one the trace can
    show (KERNEL_NAMES). The program lists them in EXPERT_MATMUL_KERNELS;
    where it has no such tuple the reader names no kernel."""
    from paddle_tpu.ops import pallas_kernels
    reader = _reader("expert_matmul_ms_per_step")
    assert reader.kernels() == tuple(
        getattr(pallas_kernels, reader.KERNELS, ()))
    assert set(reader.kernels()) <= set(pallas_kernels.KERNEL_NAMES)
    assert "ptpu_embedding_grad" in pallas_kernels.KERNEL_NAMES


def test_a_tuple_in_the_program_is_followed(monkeypatch):
    from paddle_tpu.ops import pallas_kernels
    reader = _reader("expert_matmul_ms_per_step")
    monkeypatch.setattr(pallas_kernels, reader.KERNELS, NAMED, raising=False)
    assert reader.kernels() == NAMED
    assert reader.read(_record(OTHERS + RAGGED + GMM + STRAY)) \
        == pytest.approx(40.0)


# ---- the counts -------------------------------------------------------------

def test_expert_operations_against_the_hand_count():
    """3 passes x 3 matrices x 2 x hidden x width an assignment of a held
    expert. OLMoE: 2048 x 1024, every expert held, so any load that sums to
    16384 x 8 gives 131072 x 37.75e6. SmallThinker: 2560 x 768, experts 0-15
    of 64. LFM2: 2048 x 1792, 0-7 of 32. Xing4.0: 3584 x 1024, 0-7 of 64.
    Qwen3-Next: 2048 x 512, 0-31 of 512."""
    per = {"olmoe_1b_7b_train_t4096": (18 * 2048 * 1024, 64, 64),
           "smallthinker_21b_a3b_train_t8192": (18 * 2560 * 768, 64, 16),
           "qwen3_next_80b_a3b_train_t4096": (18 * 2048 * 512, 512, 32),
           "lfm2_8b_a1b_train_t8192": (18 * 2048 * 1792, 32, 8),
           "xing4_0_29b_a4b_train_1seq": (18 * 3584 * 1024, 64, 8)}
    assert sorted(per) == sorted(FIVE)
    rng = np.random.RandomState(0)
    for name, (an_assignment, routed, held) in per.items():
        cell = _cell(name)
        count = cell.config_module.expert_matmul_ops
        load = rng.randint(0, 5000, size=routed)
        assert count(cell.config, cell.traffic, load) \
            == an_assignment * int(load[:held].sum())
        # a row a layer reads as the layers' sum does
        stacked = np.stack([load, load[::-1]])
        assert count(cell.config, cell.traffic, stacked) \
            == an_assignment * int(load[:held].sum() + load[::-1][:held].sum())
    olmoe = _cell("olmoe_1b_7b_train_t4096")
    even = np.full(64, 16384 * 8 // 64)
    assert olmoe.config_module.expert_matmul_ops(
        olmoe.config, olmoe.traffic, even) == 131072 * 37748736
    assert round(per["lfm2_8b_a1b_train_t8192"][0] / 1e6, 2) == 66.06
    assert round(per["smallthinker_21b_a3b_train_t8192"][0] / 1e6, 2) == 35.39
    assert round(per["qwen3_next_80b_a3b_train_t4096"][0] / 1e6, 2) == 18.87


def test_expert_operations_at_the_tiny_presets():
    """tiny_olmoe: hidden 64, width 32, 8 experts all held: 18 x 64 x 32 =
    36864 an assignment. tiny_smallthinker: hidden 64, width 32, chip 1 of
    2 holds experts 8-15 of 16."""
    cell = _tiny("tiny_olmoe", "tiny_olmoe_t32")
    load = np.arange(8)
    assert cell.config_module.expert_matmul_ops(
        cell.config, cell.traffic, load) == 36864 * 28
    cell = _tiny("tiny_smallthinker", "tiny_smallthinker_t48")
    load = np.arange(16)
    assert cell.config_module.expert_matmul_ops(
        cell.config, cell.traffic, load) == 36864 * sum(range(8, 16))


def test_embedding_gradient_bytes_against_the_hand_count():
    """4 bytes x D x (V held + tokens a step)."""
    want = {"olmoe_1b_7b_train_t4096": 4 * 2048 * (50304 + 16384),
            "smallthinker_21b_a3b_train_t8192": 4 * 2560 * (37984 + 8192),
            "qwen3_next_80b_a3b_train_t4096": 4 * 2048 * (18992 + 4096),
            "ouro_2_6b_train_t4096": 4 * 2048 * (49152 + 4096),
            "lfm2_8b_a1b_train_t8192": 4 * 2048 * (16384 + 8192),
            "xing4_0_29b_a4b_train_1seq": 4 * 3584 * (16384 + 4096)}
    assert sorted(want) == sorted(SIX)
    for name, nbytes in want.items():
        cell = _cell(name)
        assert cell.config_module.embedding_grad_bytes(
            cell.config, cell.traffic) == nbytes
    # SmallThinker: 389.0 MB written, 83.9 MB read (PR 41's reckoning)
    assert 4 * 2560 * 37984 == 388956160 and 4 * 2560 * 8192 == 83886080
    cell = _tiny("tiny_olmoe", "tiny_olmoe_t32")
    assert cell.config_module.embedding_grad_bytes(
        cell.config, cell.traffic) == 4 * 64 * (160 + 2 * 32)
    for name in ("transformer_base_train_t2048", "resnet50_train_b256"):
        assert not hasattr(_cell(name).config_module, "embedding_grad_bytes")


def test_flash_operations_of_the_two_oldest_flash_cells():
    """OLMoE: 1 layer, causal, 4 x 16 heads of 128 at T=4096: 64 x 8390656
    pairs. transformer_base at T=2048, batch 8, 8 heads of 64, six layers of
    encoder (T^2), causal decoder (T (T + 1) / 2) and cross (T^2)."""
    cell = _cell("olmoe_1b_7b_train_t4096")
    pairs = 4 * 16 * (4096 * 4097 // 2)
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 512 * pairs, "ptpu_flash_bwd_dkdv": 1024 * pairs,
        "ptpu_flash_bwd_dq": 768 * pairs}
    cell = _cell("transformer_base_train_t2048")
    pairs = 6 * 8 * 8 * (2048 * 2048 + 2048 * 2049 // 2 + 2048 * 2048)
    assert pairs == 4026925056
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 256 * pairs, "ptpu_flash_bwd_dkdv": 512 * pairs,
        "ptpu_flash_bwd_dq": 384 * pairs}
    # the tiny presets: one layer of 2 heads of 16 at T=16, batch 4; two
    # layers of 4 heads of 16 at T=32, batch 2
    cell = _tiny("tiny", "tiny_t16")
    pairs = 1 * 4 * 2 * (256 + 136 + 256)
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 64 * pairs, "ptpu_flash_bwd_dkdv": 128 * pairs,
        "ptpu_flash_bwd_dq": 96 * pairs}
    cell = _tiny("tiny_olmoe", "tiny_olmoe_t32")
    pairs = 2 * 2 * 4 * (32 * 33 // 2)
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 64 * pairs, "ptpu_flash_bwd_dkdv": 128 * pairs,
        "ptpu_flash_bwd_dq": 96 * pairs}


def test_flash_operations_where_key_and_value_widths_differ():
    """q k^T and its two transposes go over d_key, the products with v and
    dO over d_value."""
    cell = _tiny("tiny", "tiny_t16")
    cfg = dict(cell.config, d_key=8, d_value=32)
    pairs = 1 * 4 * 2 * (256 + 136 + 256)
    assert cell.config_module.flash_kernel_ops(cfg, cell.traffic) == {
        "ptpu_flash_fwd": (2 * 8 + 2 * 32) * pairs,
        "ptpu_flash_bwd_dkdv": (4 * 8 + 4 * 32) * pairs,
        "ptpu_flash_bwd_dq": (4 * 8 + 2 * 32) * pairs}


# ---- what the window keeps of every step -----------------------------------

@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_the_window_keeps_every_steps_small_fetches(steps_per_call):
    """The counts a step fetches (the experts' load) are kept for every step
    of the window, [steps, elements]; a crop of the logits is not."""
    import argparse
    import contextlib
    import types

    import jax
    import jax.numpy as jnp
    from benchmark import cell as runner

    class Loop(object):
        names = ["loss", "logits", "expert_load"]
        calls = 0

        def __init__(self):
            self.steps_per_call = steps_per_call

        def step(self):
            k = () if steps_per_call == 1 else (steps_per_call,)
            first = self.calls * steps_per_call
            self.calls += 1
            return {"loss": jnp.ones(k),
                    "logits": jnp.zeros(k + (2, 64, 128)),
                    "expert_load": jnp.broadcast_to(
                        first + jnp.arange(steps_per_call)[:, None],
                        (steps_per_call, 8)).reshape(k + (8,))}

    fluid = types.SimpleNamespace(
        scope_guard=lambda scope: contextlib.nullcontext())
    cell = types.SimpleNamespace(traffic={"steps_per_block": 4})
    args = argparse.Namespace(seconds=0.05, trace=0, keep_trace=None)
    win = runner._window(jax, fluid, Loop(), None, cell, args,
                         runner.Spans(), {"compile_requests": 0})
    steps = win["attempted"]
    assert steps >= 4 and steps % 4 == 0 and len(win["scalars"]) == steps
    assert sorted(win["fetches"]) == ["expert_load"]
    load = win["fetches"]["expert_load"]
    assert load.shape == (steps, 8)
    assert (load[:, 0] == np.arange(steps)).all()       # in the steps' order


# ---- the shares -------------------------------------------------------------

def test_expert_roofline_divides_the_counted_work_by_the_peak(monkeypatch):
    """The work is the traced steps' own: the mean over the window's steps
    of the rows the held experts computed, which grow as the router
    trains."""
    reader = _reader("expert_matmul_roofline_share")
    cell = _cell("smallthinker_21b_a3b_train_t8192")
    load = np.zeros((8, 64), np.int32)
    load[:, :16] = 768 * 4          # the expected rows of four layers
    load[4:, :16] += 768            # ... and more from the fifth step on
    load[:, 16:] = 1000             # held elsewhere: not this chip's work
    fetches = {"expert_load": load}
    ops = 18 * 2560 * 768 * (49152 + 6144)
    want = 100 * ops / (0.030 * 197e12)
    assert reader.read(_record(OTHERS + RAGGED, cell=cell, fetches=fetches)) \
        == pytest.approx(want)
    assert 31 < want < 34
    # the same work in a kernel of the program's that takes half the time
    # reads twice the share, with no edit to reader or count
    monkeypatch.setattr(reader._ms, "kernels", lambda: NAMED[:1])
    half = OTHERS + [["ptpu_expert_gmm.2" + MOSAIC, 0.12]]
    assert reader.read(_record(half, cell=cell, fetches=fetches)) \
        == pytest.approx(2 * want)
    # nothing to read: no trace, no matmul in it, steps that fetch no load,
    # a configuration with no experts
    for record in (_record(None, cell=cell, fetches=fetches),
                   _record(OTHERS, cell=cell, fetches=fetches),
                   _record(OTHERS + RAGGED, cell=cell),
                   _record(OTHERS + RAGGED, fetches=fetches,
                           cell=_cell("ouro_2_6b_train_t4096"))):
        assert reader.read(record) is None


EMB = [["ptpu_embedding_grad.1" + MOSAIC, 0.0076],
       # XLA's sort and gather around the kernel, and another kernel
       ["sort.3 sort ", 0.002], ["gather.9 gather ", 0.001],
       ["ptpu_embedding_gradx.1" + MOSAIC, 0.5]]


def test_embedding_gradient_readers():
    ms, share = (_reader("embedding_grad_ms_per_step"),
                 _reader("embedding_grad_roofline_share"))
    assert ms.KERNEL == "ptpu_embedding_grad"
    cell = _cell("smallthinker_21b_a3b_train_t8192")
    record = _record(OTHERS + EMB, cell=cell)
    assert ms.read(record) == pytest.approx(0.95)
    least = 4 * 2560 * (37984 + 8192) / 819e9
    assert share.read(record) == pytest.approx(100 * least / 0.95e-3)
    assert 60 < share.read(record) < 61
    for record in (_record(None, cell=cell), _record(OTHERS, cell=cell),
                   _record(OTHERS + EMB[1:], cell=cell)):
        assert ms.read(record) is None and share.read(record) is None
    # a configuration whose module counts no such bytes
    other = _record(OTHERS + EMB, cell=_cell("transformer_base_train_t2048"))
    assert ms.read(other) == pytest.approx(0.95)
    assert share.read(other) is None
    assert share.read(dict(record, peak=None)) is None


FLASH = [["ptpu_flash_fwd.1" + MOSAIC, 0.02],
         ["ptpu_flash_bwd_dkdv.3" + MOSAIC, 0.035],
         ["ptpu_flash_bwd_dq.7" + MOSAIC, 0.025]]


@pytest.mark.parametrize("name,ops", [
    ("olmoe_1b_7b_train_t4096", 18 * 128 * 64 * (4096 * 4097 // 2)),
    ("transformer_base_train_t2048", 18 * 64 * 4026925056)])
def test_flash_roofline_reads_the_two_oldest_flash_cells(name, ops):
    reader = _reader("flash_roofline_share")
    cell = _cell(name)
    assert reader.read(_record(FLASH, cell=cell)) == pytest.approx(
        100 * ops / (0.010 * 197e12))
    assert reader.read(_record(FLASH[:2], cell=cell)) is None
    assert reader.read(_record(None, cell=cell)) is None


def test_step_mfu_is_the_counted_operations_over_the_traced_window():
    reader = _reader("step_mfu")
    record = _record(OTHERS, steps=8, ops_per_sample=1e9,
                     samples_per_step=16384, chips=1)
    assert reader.read(record) == pytest.approx(
        100 * 8 * 16384e9 / (5.0 * 197e12))
    assert reader.read(dict(record, chips=4)) == pytest.approx(
        100 * 8 * 16384e9 / (5.0 * 4 * 197e12))
    assert reader.read(dict(record, peak=None)) is None
    assert reader.read(dict(record, trace=None)) is None
    assert reader.read(dict(record, window={"attempted": 0})) is None


# ---- the manifest -----------------------------------------------------------

def test_the_manifest_lists_the_new_readers_where_they_find_something():
    with open(BENCHMARK) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "tokens_per_s_per_chip")["workloads"]
    want = {"expert_matmul_ms_per_step": ("ms", "lower", "kernels", FIVE),
            "expert_matmul_roofline_share": ("%", "higher", "kernels", FIVE),
            "embedding_grad_ms_per_step": ("ms", "lower", "kernels", SIX),
            "embedding_grad_roofline_share": ("%", "higher", "kernels", SIX),
            "flash_roofline_share": (
                "%", "higher", "kernels",
                ["transformer_base_train_t2048"] + SIX),
            "step_mfu": ("%", "higher", "device", tokens)}
    for name, (unit, better, layer, workloads) in want.items():
        entry = entries[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"], entry["workloads"]) == (
            unit, better, "device_trace", layer, "tokens_per_s_per_chip",
            workloads), name
        assert set(workloads) <= set(tokens)
    # every cell that times the three flash kernels now bounds them
    assert entries["flash_roofline_share"]["workloads"] \
        == entries["flash_fwd_ms_per_step"]["workloads"]
    # a cell's module counts what the manifest says the cell reports
    for workload in bench["workloads"]:
        cell = _cell(workload["name"])
        listed = {m["name"] for m, _ in cell.metrics["per_layer"]}
        for metric, count in (
                ("expert_matmul_roofline_share", "expert_matmul_ops"),
                ("embedding_grad_roofline_share", "embedding_grad_bytes"),
                ("flash_roofline_share", "flash_kernel_ops")):
            assert (metric in listed) == hasattr(cell.config_module, count) \
                or (metric == "flash_roofline_share"
                    and workload["name"] == "transformer_base_train_t256"), \
                (workload["name"], metric)
    # ten cells, one of them on four chips, as before
    assert len(bench["workloads"]) == 10
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
