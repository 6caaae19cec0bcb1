"""The configuration smallthinker_21b_a3b and the cell
smallthinker_21b_a3b_train_t8192, on the CPU: the cell's path rehearsed on a
tiny SmallThinker-shaped configuration of this directory's own
(tests/tiny_smallthinker: chip 1 of 2, T three windows long), every mutant
of tests/mutant_smallthinker.py refused, the operations count at the
published sizes against a hand count, the blocked reference against the
plain one, the two new readers on a recorded `top_ops`, and what the
manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_smallthinker.py -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_smallthinker", "manifest.json")
TINY_CELL = "tiny_smallthinker_t48"
CELL = "smallthinker_21b_a3b_train_t8192"
LAYOUT = [0, 1, 1, 1] * 13
# architectures.jsonl of the model-configs guide, `config` of
# SmallThinker-21BA3B-Instruct: every key of it is in the configuration's
# file, and only the four counts of the cut differ (the heads are two keys)
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
HELD = {"num_hidden_layers": 4, "num_attention_heads": 7,
        "num_key_value_heads": 1, "moe_num_primary_experts": 16,
        "vocab_size": 37984}
MOSAIC = " custom-call tpu_custom_call"


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if "bench: correct:" in ln)
    return (json.loads(proc.stdout.strip().splitlines()[-1]),
            json.loads(line.rpartition("verdicts ")[2]), line)


def _cell(manifest_path=None, name=CELL):
    from benchmark import manifest
    return manifest.load_cell(
        manifest_path or os.path.join(ROOT, "BENCHMARK.json"), name)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_cells_path(trace):
    """A large seed, as the driver's are; every verdict of the cell."""
    out, verdicts, line = _run(("run.py",), "--trace", str(trace),
                               seed=3000000019)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    assert set(verdicts) == {"reference", "loss_fell", "dropless", "finite",
                             "no_compile_in_window", "placement"}
    assert "1152 of 1152 assignments counted" in line   # 4 x 3 x 96
    assert "the 8 held experts computed" in line
    # a CPU run reports counts and no device number
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share"} if trace else set())


@pytest.mark.parametrize("mutant", [
    "window_off", "rope_on_global", "silu_for_relu",
    "router_after_attention", "wrong_kv_head"])
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_smallthinker.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False and all(verdicts.values())


def test_top5_is_not_dropless():
    """Top-2 for top-3 here: a third of the assignments is not counted,
    which `dropless` sees whatever the logits' tolerance lets through."""
    out, verdicts, line = _run(("tests", "mutant_smallthinker.py"), "top5")
    assert out["correct"] is False and verdicts["dropless"] is False
    assert "768 of 1152 assignments counted" in line


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_smallthinker.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_operations_against_the_hand_count():
    """Four layers at the published widths, T=8192, a token's forward
    multiply-adds: projections 4 x 2560 x 128 x (2 x 7 + 2 x 1) = 20.97e6;
    router 4 x 2560 x 64 = 0.66e6; experts 4 x 1.5 x 3 x 2560 x 768 =
    35.39e6; head 2560 x 37984 = 97.24e6; attention (33,558,528 + 3 x
    25,167,872) pairs / 8192 x 2 x 128 x 7 = 23.86e6. Twice that, three
    passes: 1068.7e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    assert mod.visible_pairs(8192, None) == 8192 * 8193 // 2 == 33558528
    assert mod.visible_pairs(8192, 4096) == 25167872 \
        == sum(min(i + 1, 4096) for i in range(8192))
    assert mod.visible_pairs(4096, 4096) == mod.visible_pairs(4096, None)
    assert mod.layer_pairs(cfg, traffic) == [33558528] + [25167872] * 3
    macs = mod.forward_macs(cfg, traffic)
    hand = {"projections": 20.97e6, "router": 0.655e6, "experts": 35.39e6,
            "attention": 23.86e6, "head": 97.24e6}
    assert {k: round(v / 1e6, 1) for k, v in macs.items()} \
        == {k: round(v / 1e6, 1) for k, v in hand.items()}
    ops = mod.ops_per_sample(cfg, traffic)
    assert abs(ops - 1068.7e6) < 0.1e6
    share = {k: round(100 * v / sum(macs.values()), 1)
             for k, v in macs.items()}
    assert share == {"head": 54.6, "experts": 19.9, "attention": 13.4,
                     "projections": 11.8, "router": 0.4}
    assert mod.samples_per_step(cfg, traffic) == 8192
    # the flash kernels: 4 / 8 / 6 x 128 a pair and query head, 7 heads
    pairs = 7 * (33558528 + 3 * 25167872)
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 512 * pairs, "ptpu_flash_bwd_dkdv": 1024 * pairs,
        "ptpu_flash_bwd_dq": 768 * pairs}


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (4, 0)
    assert all(CATALOG[k] == 4 * HELD[k] for k in HELD
               if k != "num_hidden_layers")
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts"}
    assert set(cfg["assumed"]) >= {
        "adam", "learning_rate", "clip_norm", "auxiliary_losses",
        "initialisation", "precision", "window", "hidden_act",
        "router_input", "biases", "data"}
    assert set(cfg["reference"]["tolerance"]) == {"loss", "logits"}
    assert cell.traffic["seq_len"] == 2 * cfg["sliding_window_size"] == 8192
    assert cell.traffic["batch"] == 1 and cell.chips == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (64, 16, 0)
    assert c["window_layers"] == [None, 4096, 4096, 4096]
    assert c["rope_layers"] == [False, True, True, True]


def test_manifest_holds_the_configuration_and_its_cell():
    """Later PRs add configurations, cells and readers behind these: the
    test holds what the manifest promises of this cell, not its length."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(c for c in m["configs"]
                 if c["name"] == "smallthinker_21b_a3b")
    assert entry["reduced"] == _cell().config["reduced"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="smallthinker_21b_a3b",
                        traffic="train_t8192", chips=1)
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert len(cell["why"]) <= 200
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "expert_matmul_roofline_share",
        "embedding_grad_ms_per_step", "embedding_grad_roofline_share",
        "step_mfu"}
    assert "layer_norm_ms_per_step" not in reports     # it has no layer_norm
    entries = {e["name"]: e for e in m["per_layer"]}
    assert CELL in entries["flash_roofline_share"]["workloads"]
    assert entries["expert_matmul_ms_per_step"]["workloads"][:2] \
        == ["olmoe_1b_7b_train_t4096", CELL]


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/smallthinker.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (a sequence and a
    query head, an expert, rows of the head) and changes none of it."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm_reference as plain
    from benchmark import checks
    cell = _cell(TINY, TINY_CELL)
    cfg, traffic, mod = cell.config, cell.traffic, cell.config_module
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        mod.build(fluid, cfg, traffic)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.global_block().all_parameters()]
    batch = mod.make_batch(cfg, traffic, jax.random.key(11))
    assert int(batch["ids"].max()) < cfg["vocab_size"]      # from the slice
    mod.HEAD_ROWS = 32          # three blocks of the 96 rows
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    loss, (logits, load) = plain.loss_fn(cfg, params, batch["ids"],
                                         batch["pos"], batch["labels"])
    assert checks.normalised_error(got["loss"], loss) < 1e-6
    assert checks.normalised_error(
        got["logits"], logits[:, :, :mod.PROBE_COLUMNS]) < 1e-5
    np.testing.assert_array_equal(got["expert_load"], load)
    assert got["expert_load"].shape == (16,)
    margin = np.asarray(got["router_margin"])
    assert margin.shape == (traffic["batch"], traffic["seq_len"])
    assert (margin >= 0).all() and np.isfinite(margin).any()


def test_check_leaves_out_only_undecided_tokens():
    """A token under the router margin may differ; one over it may not; and
    rows the held experts lost are not dropless."""
    cell = _cell()
    mod = cell.config_module
    cfg = dict(cell.config, num_hidden_layers=1,
               moe_num_active_primary_experts=2,
               reference={"tolerance": {"loss": 1e-4, "logits": 2e-2},
                          "router_margin": 0.01})
    logits = np.ones((1, 4, 8), np.float32)
    load = np.zeros(64, np.int64)
    load[[0, 40]] = 4
    want = {"loss": np.float32(1.0), "logits": logits, "expert_load": load,
            "router_margin": np.array([[0.5, 0.001, 0.5, np.inf]])}
    moved = load.copy()
    moved[[0, 1]] = 3, 1        # one assignment went to the next held expert
    first = {"loss": np.float32(1.0), "logits": logits.copy(),
             "expert_load": moved}
    first["logits"][0, 1] += 0.5        # the undecided token
    verdicts, found = mod.check(cfg, first, want, [1.0, 0.5])
    assert verdicts == {"reference": True, "loss_fell": True,
                        "dropless": True}
    assert "logits of 3 of 4 tokens compared" in found
    assert "the 16 held experts computed 4 rows (reference 4" in found
    first["logits"][0, 2] += 0.5        # a decided one
    assert mod.check(cfg, first, want, [1.0, 0.5])[0]["reference"] is False
    first["expert_load"] = load.copy()
    first["expert_load"][0] = 3         # an assignment nobody counted
    assert mod.check(cfg, first, want, [1.0, 0.5])[0]["dropless"] is False


def test_margin_counts_only_trades_that_move_a_held_expert():
    import jax.numpy as jnp
    mod = _cell().config_module
    c = {"num_experts_per_tok": 2, "first_expert": 0, "experts_held": 2}
    logits = jnp.log(jnp.asarray([
        [0.4, 0.3, 0.29, 0.01],       # held e1 is 2nd, 0.29 may push it out
        [0.4, 0.01, 0.3, 0.29],       # 2nd and 3rd absent: only e0 counts
        [0.01, 0.29, 0.4, 0.3],       # held e1 is 3rd, it may come in
        [0.25, 0.005, 0.375, 0.37],   # no held expert near the cut
        [0.32, 0.02, 0.33, 0.325]]))  # three level at the cut, e0 the third
    margin = np.asarray(mod._router_margin(logits, c))
    np.testing.assert_allclose(
        margin, [0.01 / 0.3, 0.11 / 0.4, 0.01 / 0.3, 0.12 / 0.37,
                 0.005 / 0.325], rtol=1e-4)
    # every expert held: the plain (p_k - p_(k+1)) / p_k
    everyone = dict(c, experts_held=4)
    np.testing.assert_allclose(
        np.asarray(mod._router_margin(logits, everyone))[:3],
        [0.01 / 0.3, 0.01 / 0.3, 0.01 / 0.3], rtol=1e-4)
    # no expert of this chip in sight: nothing to decide
    nobody = dict(c, first_expert=8, experts_held=2)
    assert np.isinf(np.asarray(mod._router_margin(logits, nobody))).all()


# --- the two new readers on a recorded top_ops --------------------------------

TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ragged-dot-none.3" + MOSAIC, 0.16],
    ["ragged-dot-none.12" + MOSAIC, 0.04],
    ["ragged-dot.1 ragged-dot", 0.04],
    ["ptpu_flash_bwd_dkdv.3" + MOSAIC, 0.02],
    ["ptpu_flash_bwd_dq.7" + MOSAIC, 0.015],
    ["ptpu_flash_fwd" + MOSAIC, 0.005],
    ["ptpu_flash_fwd.1" + MOSAIC, 0.005],
    # not the experts': another instruction that only mentions them
    ["fusion.ragged-dot.4 fusion kLoop", 0.25]]


def _record(cell, top_ops=TOP_OPS, steps=8):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "top_ops": top_ops, "category_s": {}}
    return {"trace": trace, "window": {"attempted": steps}, "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12}}


def _reader(name):
    from benchmark import manifest
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_expert_matmul_reader_sums_the_ragged_dots():
    reader = _reader("expert_matmul_ms_per_step")
    cell = _cell()
    assert reader.read(_record(cell)) == pytest.approx(1e3 * 0.24 / 8)
    assert reader.read(_record(cell, top_ops=None)) is None
    assert reader.read(_record(cell, top_ops=TOP_OPS[:1])) is None
    assert reader.read(_record(cell, steps=0)) is None


def test_flash_roofline_reader_divides_counted_operations_by_the_peak():
    reader = _reader("flash_roofline_share")
    cell = _cell()
    ops = sum(cell.config_module.flash_kernel_ops(cell.config,
                                                  cell.traffic).values())
    seconds_a_step = (0.02 + 0.015 + 0.01) / 8
    want = 100 * ops / (seconds_a_step * 197e12)
    assert reader.read(_record(cell)) == pytest.approx(want)
    assert ops == pytest.approx(1.759e12, rel=1e-3)
    # nothing to read: no trace, a kernel that did not run under its name,
    # a configuration whose module counts no flash operations (ResNet's, or
    # any parent's): None, never an exception
    assert reader.read(_record(cell, top_ops=None)) is None
    assert reader.read(_record(cell, top_ops=TOP_OPS[:5])) is None
    assert reader.read(_record(_cell(name="resnet50_train_b256"))) is None
