"""benchmark/tests/test_work_readers.py (PR 47: the readers that measure WORK
whoever does it, and the counts they divide by) run from tier-1, which
collects `tests/` alone, with the GLM-4.7-Flash configuration's counts as
further cases against hand counts.

One test of that file is replaced here and not run as it stands:
`test_the_manifest_lists_the_new_readers_where_they_find_something` pins
BENCHMARK.json at ten cells and at the lists PR 47 left, and a PR that adds a
cell may not edit a file the benchmark has; `test_the_manifest_lists_the_
work_readers_in_eleven_cells` below is that test with the eleventh cell on
the lists, and `test_the_pinned_manifest_test_is_red_for_the_eleventh_cell_
alone` holds the pinned one to its one known cause, so that a second
breakage in it is not hidden behind the first. The next `benchmark` PR turns
the pinned test into the prefix check and drops both (ROADMAP.md)."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

_spec = importlib.util.spec_from_file_location(
    "_bench_tests_work_readers",
    os.path.join(REPO, "benchmark", "tests", "test_work_readers.py"))
readers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(readers)

PINNED = "test_the_manifest_lists_the_new_readers_where_they_find_something"
globals().update({name: test for name, test in vars(readers).items()
                  if name.startswith("test_") and name != PINNED})

GLM = "glm_4_7_flash_train_t4096"
PHI = "phi4_mini_flash_train_t8192"
# the three metrics PR 54 added with its cell
PHI_METRICS = ("selective_scan_ms_per_step", "selective_scan_roofline_share",
               "shared_state_layer_share")
GRANITE = "granite_4_0_h_micro_train_t2048"
# the three metrics PR 57 added with its cell
GRANITE_METRICS = ("ssd_scan_ms_per_step", "ssd_scan_roofline_share",
                   "ssd_layer_share")
NEMOTRON = "nemotron_3_super_120b_a12b_train_t4096"
# the two metrics PR 61 added with its cell
NEMOTRON_METRICS = ("latent_moe_layer_share", "single_branch_layer_share")
LAGUNA = "laguna_s_2_1_train_t4096"
# the two metrics PR 64 added with its cell
LAGUNA_METRICS = ("windowed_layer_share", "per_head_gate_layer_share")
SDAR = "sdar_30b_a3b_chat_train_t4096"
# the two metrics PR 66 added with its cell
SDAR_METRICS = ("block_diffusion_layer_share", "ffn_rows_per_token")
LING = "ling_3_0_flash_train_t4096"
# the four metrics PR 71 added with its cell
LING_METRICS = ("kda_ms_per_step", "kda_roofline_share", "kda_layer_share",
                "group_limited_router_layer_share")


def test_glm_flash_operations_against_the_hand_count():
    """Six cores (five trunk layers and the module's), causal, 20 heads at
    T=4096: 6 x 20 x 8,390,656 pairs a sequence; the whole head of 192 + 64
    = 256 on a value of 256: 2 x (256 + 256), 2 x 4 x 256 and 2 x 3 x 256 a
    pair and head, whatever form the core takes."""
    cell = readers._cell(GLM)
    pairs = 6 * 20 * (4096 * 4097 // 2) * cell.traffic["batch"]
    assert pairs == 1006878720
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 1024 * pairs, "ptpu_flash_bwd_dkdv": 2048 * pairs,
        "ptpu_flash_bwd_dq": 1536 * pairs}
    # the tiny preset: three cores of 4 heads at T=64, batch 2, 48 + 16 on 64
    cell = readers._tiny("tiny_glm_4_7_flash", "tiny_glm_4_7_flash_t64")
    pairs = 3 * 4 * 2 * (64 * 65 // 2)
    assert cell.config_module.flash_kernel_ops(cell.config, cell.traffic) == {
        "ptpu_flash_fwd": 256 * pairs, "ptpu_flash_bwd_dkdv": 512 * pairs,
        "ptpu_flash_bwd_dq": 384 * pairs}


def test_glm_expert_operations_against_the_hand_count():
    """3 passes x 3 matrices x 2 x 2048 x 1536 = 56.62e6 an assignment of a
    held expert, experts 0-7 of 64; the load is the five expert layers' sum
    (four of the trunk and the module's)."""
    cell = readers._cell(GLM)
    count = cell.config_module.expert_matmul_ops
    load = np.random.RandomState(0).randint(0, 5000, size=64)
    an_assignment = 18 * 2048 * 1536
    assert round(an_assignment / 1e6, 2) == 56.62
    assert count(cell.config, cell.traffic, load) \
        == an_assignment * int(load[:8].sum())
    stacked = np.stack([load, load[::-1]])
    assert count(cell.config, cell.traffic, stacked) \
        == an_assignment * int(load[:8].sum() + load[::-1][:8].sum())
    # expected: 5 layers x 4096 tokens x 4 / 8 chips = 10240 rows a step
    even = np.full(64, 5 * 4096 * 4 // 64)
    assert count(cell.config, cell.traffic, even) == 10240 * an_assignment
    cell = readers._tiny("tiny_glm_4_7_flash", "tiny_glm_4_7_flash_t64")
    assert cell.config_module.expert_matmul_ops(
        cell.config, cell.traffic, np.arange(16)) \
        == 18 * 128 * 64 * sum(range(4, 8))         # chip 1 of 4 holds 4-7


def test_glm_embedding_gradient_bytes_count_two_lookups():
    """The table written once, the rows of BOTH lookups read: 4 bytes x
    2048 x (19360 + 2 x 4096); the base count takes one lookup a step."""
    cell = readers._cell(GLM)
    mod = cell.config_module
    assert mod.embedding_grad_bytes(cell.config, cell.traffic) \
        == 4 * 2048 * (19360 + 2 * 4096) == 225705984
    assert mod.embedding_grad_bytes is not mod.base.embedding_grad_bytes
    assert mod.base.embedding_grad_bytes(cell.config, cell.traffic) \
        == 4 * 2048 * (19360 + 4096)
    cell = readers._tiny("tiny_glm_4_7_flash", "tiny_glm_4_7_flash_t64")
    assert cell.config_module.embedding_grad_bytes(
        cell.config, cell.traffic) == 4 * 128 * (160 + 2 * 128)


def test_the_pinned_manifest_test_is_red_for_the_eleventh_cell_alone(
        tmp_path, monkeypatch):
    """PR 47's test as it stands fails on this manifest at the first list it
    pins, and passes on the same manifest with GLM-4.7-Flash's cell, its
    configuration and its metric taken off again, and with them the
    twelfth cell (Phi-4-mini-flash's), its configuration and its three
    metrics, and the thirteenth (granite-4.0-h-micro's), its configuration,
    its traffic's cell and its three metrics, and the fourteenth
    (Nemotron-3-Super's), its configuration and its two metrics, and the
    fifteenth (Laguna-S-2.1's), its configuration and its two metrics, and
    the sixteenth (SDAR-30B-A3B-Chat's), its configuration and its two
    metrics, and the seventeenth (Ling-3.0-flash's), its configuration and
    its four metrics: nothing else it holds has moved."""
    pinned = getattr(readers, PINNED)
    with pytest.raises(AssertionError, match="^expert_matmul_ms_per_step$"):
        pinned()
    with open(readers.BENCHMARK) as f:
        bench = json.load(f)
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in (GLM, PHI, GRANITE, NEMOTRON,
                                               LAGUNA, SDAR, LING)]
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] not in ("glm_4_7_flash",
                                             "phi4_mini_flash",
                                             "granite_4_0_h_micro",
                                             "nemotron_3_super_120b_a12b",
                                             "laguna_s_2_1",
                                             "sdar_30b_a3b_chat",
                                             "ling_3_0_flash")]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in ("mtp_layer_share",)
                          + PHI_METRICS + GRANITE_METRICS
                          + NEMOTRON_METRICS + LAGUNA_METRICS
                          + SDAR_METRICS + LING_METRICS]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        for cell in (GLM, PHI, GRANITE, NEMOTRON, LAGUNA, SDAR, LING):
            if cell in metric.get("workloads", ()):
                metric["workloads"].remove(cell)
    without = tmp_path / "BENCHMARK.json"
    without.write_text(json.dumps(bench))
    monkeypatch.setattr(readers, "BENCHMARK", str(without))
    pinned()


def test_the_manifest_lists_the_work_readers_in_eleven_cells():
    """PR 47's manifest test with the eleventh cell: the lists it pinned,
    each with GLM-4.7-Flash's cell appended and nothing else changed."""
    with open(readers.BENCHMARK) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "tokens_per_s_per_chip")["workloads"]
    five, six = readers.FIVE + [GLM], readers.SIX + [GLM]
    want = {"expert_matmul_ms_per_step": ("ms", "lower", "kernels", five),
            "expert_matmul_roofline_share": ("%", "higher", "kernels", five),
            "embedding_grad_ms_per_step": ("ms", "lower", "kernels", six),
            "embedding_grad_roofline_share": ("%", "higher", "kernels", six),
            "flash_roofline_share": (
                "%", "higher", "kernels",
                ["transformer_base_train_t2048"] + six),
            "step_mfu": ("%", "higher", "device", tokens)}
    for name, (unit, better, layer, workloads) in want.items():
        entry = entries[name]
        # a prefix check: a later PR appends its cells behind these
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"],
                entry["workloads"][:len(workloads)]) == (
            unit, better, "device_trace", layer, "tokens_per_s_per_chip",
            workloads), name
        assert set(entry["workloads"]) <= set(tokens)
    assert tokens[8] == GLM
    assert entries["flash_roofline_share"]["workloads"] \
        == entries["flash_fwd_ms_per_step"]["workloads"]
    # a cell's module counts what the manifest says the cell reports
    for workload in bench["workloads"]:
        cell = readers._cell(workload["name"])
        listed = {m["name"] for m, _ in cell.metrics["per_layer"]}
        for metric, count in (
                ("expert_matmul_roofline_share", "expert_matmul_ops"),
                ("embedding_grad_roofline_share", "embedding_grad_bytes"),
                ("flash_roofline_share", "flash_kernel_ops")):
            assert (metric in listed) == hasattr(cell.config_module, count) \
                or (metric == "flash_roofline_share"
                    and workload["name"] == "transformer_base_train_t256"), \
                (workload["name"], metric)
    # the twelfth cell behind them on the lists whose reader finds something
    # to read there, with its module's hand counts: 40 heads of 128 over the
    # pairs inside three masks (one of 512 keys, two causal) and the table of
    # 25008 words written (its module's own count: the compiled step holds
    # the 8192 rows in VMEM)
    for name in ("flash_roofline_share", "embedding_grad_ms_per_step",
                 "embedding_grad_roofline_share", "step_mfu"):
        assert entries[name]["workloads"][-6:] == [PHI, GRANITE, NEMOTRON,
                                                   LAGUNA, SDAR, LING], name
    phi = readers._cell(PHI)
    pairs = (512 * 513 // 2 + (8192 - 512) * 512) + 2 * (8192 * 8193 // 2)
    assert phi.config_module.flash_kernel_ops(phi.config, phi.traffic) == {
        "ptpu_flash_fwd": 4 * 128 * 40 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * 40 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * 40 * pairs}
    assert phi.config_module.embedding_grad_bytes(phi.config, phi.traffic) \
        == 4 * 2560 * 25008 == 256081920
    assert phi.config_module.embedding_grad_bytes \
        is not phi.config_module.base.embedding_grad_bytes
    # the thirteenth cell behind that one, with its module's hand counts:
    # one core of 32 heads of 64, causal at T = 2048; the table of 12544
    # words of 2048 written; the scan's two kernels at chunks of 128, nine
    # layers, the forward pass's twice (a call: operations, bytes)
    granite = readers._cell(GRANITE)
    mod = granite.config_module
    pairs = 2048 * 2049 // 2
    assert mod.flash_kernel_ops(granite.config, granite.traffic) == {
        "ptpu_flash_fwd": 4 * 64 * 32 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 64 * 32 * pairs,
        "ptpu_flash_bwd_dq": 6 * 64 * 32 * pairs}
    assert mod.embedding_grad_bytes(granite.config, granite.traffic) \
        == 4 * 2048 * 12544 == 102760448
    calls = mod.ssd_kernel_ops(granite.config, granite.traffic, 128)
    assert {k: len(v) for k, v in calls.items()} == {"ptpu_ssd_fwd": 18,
                                                     "ptpu_ssd_bwd": 9}
    assert calls["ptpu_ssd_fwd"][0] == (
        2 * 2048 * (64.5 * 128 + 64 * (64.5 * 64 + 2 * 128 * 64)),
        2048 * (2 * 2 * 4096 + 2 * 2 * 128 + 4 * 64))
    assert calls["ptpu_ssd_bwd"][0] == (
        2 * 2048 * (2 * 64.5 * 128 + 64 * (2 * 64.5 * 64 + 4 * 128 * 64)),
        2048 * (3 * 2 * 4096 + 4 * 2 * 128 + 2 * 4 * 64))
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in GRANITE_METRICS] == [[GRANITE, NEMOTRON]] * 3
    # the fourteenth cell behind that one on the lists whose reader finds
    # something in its trace (the scan's, the flash kernels', the experts',
    # the embedding gradient's, the loss's), with its module's hand counts:
    # one core of 4 query heads of 128 on 1, causal at T = 4096; the table
    # of 16384 words of 4096 written; the scan's two kernels at 16 heads and
    # one group, five layers; and TWO matmuls an expert of [1024 x 2688]
    # (configs/causal_lm.py's three would read 1.5 times the work)
    for name in ("expert_matmul_ms_per_step", "expert_matmul_roofline_share"):
        assert entries[name]["workloads"][-5:] == [GLM, NEMOTRON, LAGUNA,
                                                   SDAR, LING], name
    for name in ("pallas_ms_per_step", "softmax_xent_ms_per_step",
                 "flash_fwd_ms_per_step", "flash_bwd_dkdv_ms_per_step",
                 "flash_bwd_dq_ms_per_step"):
        assert entries[name]["workloads"][-5:] == [GRANITE, NEMOTRON,
                                                   LAGUNA, SDAR, LING], name
    nemotron = readers._cell(NEMOTRON)
    mod = nemotron.config_module
    pairs = 4096 * 4097 // 2
    assert mod.flash_kernel_ops(nemotron.config, nemotron.traffic) == {
        "ptpu_flash_fwd": 4 * 128 * 4 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * 4 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * 4 * pairs}
    assert mod.embedding_grad_bytes(nemotron.config, nemotron.traffic) \
        == 4 * 4096 * 16384 == 268435456
    calls = mod.ssd_kernel_ops(nemotron.config, nemotron.traffic, 128)
    assert {k: len(v) for k, v in calls.items()} == {"ptpu_ssd_fwd": 10,
                                                     "ptpu_ssd_bwd": 5}
    assert calls["ptpu_ssd_bwd"][0] == (
        2 * 4096 * (2 * 64.5 * 128 + 16 * (2 * 64.5 * 64 + 4 * 128 * 64)),
        4096 * (3 * 2 * 1024 + 4 * 2 * 128 + 2 * 4 * 16))
    assert mod.EXPERT_MATMULS == 2 and mod.base.EXPERT_MATMULS == 3
    even = np.zeros(512, np.int64)
    even[:8] = 5 * 176              # 22 x 4096 / 512 rows a held expert
    assert mod.expert_matmul_ops(nemotron.config, nemotron.traffic, even) \
        == 3 * 2 * 2 * 1024 * 2688 * 7040
    assert mod.base.expert_matmul_ops is not mod.expert_matmul_ops
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in NEMOTRON_METRICS] == [[NEMOTRON]] * 2
    assert sum(mod.forward_macs(nemotron.config, nemotron.traffic)
               .values()) == pytest.approx(426.3e6, rel=1e-3)
    # the fifteenth cell behind that one on the lists whose reader finds
    # something in its trace (the flash kernels', the experts', the
    # embedding gradient's, the loss's), with its module's hand counts: a
    # LAYER's heads over a LAYER's pairs (12 of 128 over all causal pairs on
    # layers 0 and 4, 18 over the visible pairs of a window of 512 on layers
    # 1-3); the table of 25088 words of 3072 written; three matmuls an
    # expert of [3072 x 1024]
    laguna = readers._cell(LAGUNA)
    mod = laguna.config_module
    windowed = 512 * 513 // 2 + (4096 - 512) * 512
    pairs = 2 * 12 * (4096 * 4097 // 2) + 3 * 18 * windowed
    assert windowed == 1966336 and pairs == 307557888
    assert mod.flash_kernel_ops(laguna.config, laguna.traffic) == {
        "ptpu_flash_fwd": 4 * 128 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * pairs}
    assert mod.embedding_grad_bytes(laguna.config, laguna.traffic) \
        == 4 * 3072 * 25088 == 308281344
    assert mod.embedding_grad_bytes is not mod.base.embedding_grad_bytes
    even = np.zeros(256, np.int64)
    even[:8] = 4 * 160              # 10 x 4096 / 256 rows a held expert
    assert mod.expert_matmul_ops(laguna.config, laguna.traffic, even) \
        == 3 * 3 * 2 * 3072 * 1024 * 5120
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in LAGUNA_METRICS] == [[LAGUNA]] * 2
    assert sum(mod.forward_macs(laguna.config, laguna.traffic)
               .values()) == pytest.approx(331.6e6, rel=1e-3)
    # the sixteenth cell behind that one on the same lists, with its
    # module's hand counts: every layer's 32 heads of 128 over the visible
    # pairs of BOTH copies under the block-diffusion mask (T^2 + 4 T a head
    # at blocks of 4); the table of 18992 words of 2048 written and the
    # 8192 rows of both copies' ONE lookup read; three
    # matmuls an expert of [2048 x 768]; two rows a token in the trunk, one
    # behind the last layer's core
    sdar = readers._cell(SDAR)
    mod = sdar.config_module
    pairs = 4 * 32 * (4096 * 4096 + 4 * 4096)
    assert pairs == 2149580800
    assert mod.flash_kernel_ops(sdar.config, sdar.traffic) == {
        "ptpu_flash_fwd": 4 * 128 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * pairs}
    assert mod.embedding_grad_bytes(sdar.config, sdar.traffic) \
        == 4 * 2048 * (18992 + 8192) == 222691328
    assert mod.embedding_grad_bytes is not mod.base.embedding_grad_bytes
    even = np.zeros(128, np.int64)
    even[:16] = 3 * 512 + 256       # 8 x 8192 / 128 rows a held expert
    assert mod.expert_matmul_ops(sdar.config, sdar.traffic, even) \
        == 3 * 3 * 2 * 2048 * 768 * 28672
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in SDAR_METRICS] == [[SDAR]] * 2
    assert sum(mod.forward_macs(sdar.config, sdar.traffic)
               .values()) == pytest.approx(333.9e6, rel=1e-3)
    assert mod.samples_per_step(sdar.config, sdar.traffic) == 4096
    # the seventeenth cell behind that one on the same lists (not on
    # SDAR's own two), with its module's hand counts: ONE latent core of 8
    # heads, 192 wide on the scores and 128 on the values, over the causal
    # pairs; the table of 39296 words of 2560 written and 4096 rows read;
    # three matmuls an expert of [2560 x 768]; the two KDA kernels' tiles
    sdar_lists = {m["name"] for m in bench["per_layer"]
                  if SDAR in m.get("workloads", ())}
    ling_lists = {m["name"] for m in bench["per_layer"]
                  if LING in m.get("workloads", ())}
    assert ling_lists == (sdar_lists - set(SDAR_METRICS)) | set(LING_METRICS)
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in LING_METRICS] == [[LING]] * 4
    ling = readers._cell(LING)
    mod = ling.config_module
    pairs = 8 * (4096 * 4097 // 2)
    assert mod.flash_kernel_ops(ling.config, ling.traffic) == {
        "ptpu_flash_fwd": 2 * 320 * pairs,
        "ptpu_flash_bwd_dkdv": 2 * 640 * pairs,
        "ptpu_flash_bwd_dq": 2 * 512 * pairs}
    assert mod.embedding_grad_bytes(ling.config, ling.traffic) \
        == 4 * 2560 * (39296 + 4096) == 444334080
    even = np.zeros(512, np.int64)
    even[:8] = 6 * 64               # 8 x 4096 / 512 rows a held expert
    assert mod.expert_matmul_ops(ling.config, ling.traffic, even) \
        == 3 * 3 * 2 * 2560 * 768 * 3072
    assert sum(mod.forward_macs(ling.config, ling.traffic)
               .values()) == pytest.approx(306.7e6, rel=1e-3)
    assert mod.samples_per_step(ling.config, ling.traffic) == 4096
    # seventeen cells, one of them on four chips: floor(17 x 0.25) = 4
    assert [w["name"] for w in bench["workloads"]][16] == LING
    assert [w["chips"] for w in bench["workloads"]][:17].count(4) == 1
    # sixteen cells, one of them on four chips: floor(16 x 0.25) = 4
    assert [w["name"] for w in bench["workloads"]][15] == SDAR
    assert [w["chips"] for w in bench["workloads"]][:16].count(4) == 1
    # fifteen cells, one of them on four chips: floor(15 x 0.25) = 3
    assert [w["name"] for w in bench["workloads"]][14] == LAGUNA
    assert [w["chips"] for w in bench["workloads"]][:15].count(4) == 1
    # fourteen cells, one of them on four chips: floor(14 x 0.25) = 3
    assert [w["name"] for w in bench["workloads"]][13] == NEMOTRON
    assert [w["chips"] for w in bench["workloads"]][:14].count(4) == 1
    # thirteen cells, one of them on four chips: floor(13 x 0.25) = 3
    assert [w["name"] for w in bench["workloads"]][12] == GRANITE
    assert [w["chips"] for w in bench["workloads"]][:13].count(4) == 1
    # eleven cells (PR 49), one of them on four chips: floor(11 x 0.25) = 2
    assert len(bench["workloads"]) >= 11
    assert [w["name"] for w in bench["workloads"]][10] == GLM
    assert [w["chips"] for w in bench["workloads"]][:11].count(4) == 1
