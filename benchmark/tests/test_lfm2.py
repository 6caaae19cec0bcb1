"""The configuration lfm2_8b_a1b and the cell lfm2_8b_a1b_train_t8192, on the
CPU: the cell's path rehearsed on a tiny LFM2-shaped configuration of this
directory's own (tests/tiny_lfm2: chip 1 of 4), every mutant of
tests/mutant_lfm2.py refused, the operations count at the published sizes
against a hand count, the blocked reference against the plain one, the two
new readers on a recorded `top_ops`, and what the manifest promises of the
new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_lfm2", "manifest.json")
TINY_CELL = "tiny_lfm2_t48"
CELL = "lfm2_8b_a1b_train_t8192"
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# architectures.jsonl of the model-configs guide, `config` of LFM2-8B-A1B:
# every key of it is in the configuration's file, and only the five of the
# cut differ
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
HELD = {"num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": TYPES[1:6], "num_experts": 8, "vocab_size": 16384}
MOSAIC = " custom-call tpu_custom_call"
MUTANTS = ["bias_off", "bias_in_weights", "softmax_for_sigmoid", "no_renorm",
           "gates_swapped", "taps_reversed", "conv_silu",
           "dense_layer_routed", "untied_head", "qk_norm_off",
           "wrong_kv_head"]


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
    assert "the 4 held experts computed" in line
    # a CPU run reports counts and no device number
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share"} if trace else set())


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_lfm2.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_lfm2.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no short_conv mixer (the parent of
    the PR that added it) `build` raises before anything is built: the
    driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "short_conv")
    with pytest.raises(NotImplementedError, match="short_conv"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Five layers at the published widths, T=8192, a token's forward
    multiply-adds. A short_conv mixer: 2048 x 6144 + 2048 x 2048 = 16.78e6
    of projections and 3 x 2048 taps: four of them 67.11e6 + 0.02e6.
    Attention: 2048 x 64 x (2 x 32 + 2 x 8) = 10.49e6 and 33,558,528 pairs /
    8192 x 2 x 64 x 32 = 16.78e6. The dense FFN 3 x 2048 x 7168 = 44.04e6.
    Four routers 4 x 2048 x 32 = 0.26e6 and 4 x (4 x 8 / 32) experts of 3 x
    2048 x 1792 = 44.04e6. The tied head 2048 x 16384 = 33.55e6. Twice the
    sum, three passes: 1297.8e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"conv_projections": 67.11e6, "conv_taps": 0.02e6,
            "attention_projections": 10.49e6, "attention": 16.78e6,
            "dense_ffn": 44.04e6, "router": 0.26e6, "experts": 44.04e6,
            "head": 33.55e6}
    assert {k: round(v / 1e6, 2) for k, v in macs.items()} \
        == {k: round(v / 1e6, 2) for k, v in hand.items()}
    assert abs(mod.ops_per_sample(cfg, traffic) - 1297.8e6) < 0.1e6
    total = sum(macs.values())
    share = {k: round(100 * sum(v for n, v in macs.items()
                                if n.startswith(k)) / total, 1)
             for k in ("conv", "attention", "dense", "experts", "head")}
    assert share == {"conv": 31.0, "attention": 12.6, "dense": 20.4,
                     "experts": 20.4, "head": 15.5}
    assert mod.samples_per_step(cfg, traffic) == 8192
    # the flash kernels: 4 / 8 / 6 x 64 a pair and query head, 32 heads, one
    # layer
    pairs = 32 * (8192 * 8193 // 2) * traffic["batch"]
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 256 * pairs, "ptpu_flash_bwd_dkdv": 512 * pairs,
        "ptpu_flash_bwd_dq": 384 * pairs}
    # the convolution's kernels: [8192, 2048] bf16 arrays of 33,554,432
    # bytes, four layers, those that cross HBM in the compiled step: one
    # forward (the convolution out; v comes from VMEM), three backward (v
    # and the output's gradient in, v's gradient out) less the first
    # layer's gradient of v, which stays in VMEM
    assert mod.SHORT_CONV_KERNELS == ("ptpu_causal_conv1d_fwd",
                                      "ptpu_causal_conv1d_bwd")
    assert mod.short_conv_kernel_bytes(cfg, traffic) == {
        "ptpu_causal_conv1d_fwd": 4 * 33554432,
        "ptpu_causal_conv1d_bwd": (3 * 4 - 1) * 33554432}


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (4, 0)
    assert CATALOG["num_experts"] == 4 * HELD["num_experts"]
    assert CATALOG["vocab_size"] == 4 * HELD["vocab_size"]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "tie_word_embeddings", "expert_bias", "router_scoring", "qk_norm",
        "adam", "learning_rate", "clip_norm", "auxiliary_losses",
        "initialisation", "precision", "data"}
    assert set(cfg["reference"]["tolerance"]) == {
        "loss", "logits", "logits_mean", "queries_keys"}
    assert cell.traffic["seq_len"] == 8192 and cell.chips == 1
    # what the modeling file always does and config.json has no key for is
    # a key here, noted under `assumed`
    always = {"qk_norm": "head", "router_scoring": "sigmoid",
              "tie_word_embeddings": True}
    assert {k: cfg[k] for k in always} == always
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (32, 8, 0)
    assert c["mixer_layers"] == ["short_conv", "attention"] \
        + ["short_conv"] * 3
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert (c["head_dim"], c["rotary_dim"], c["intermediate_size"],
            c["dense_intermediate_size"]) == (64, 64, 1792, 7168)


def test_manifest_holds_the_new_entries():
    """A prefix check: the cell and its configuration are where this PR put
    them (ninth and seventh), whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["configs"]][6] == "lfm2_8b_a1b"
    assert m["configs"][6]["reduced"] == _cell().config["reduced"]
    assert m["configs"][6]["source"] == _cell().config["source"]
    assert m["workloads"][8] == dict(
        m["workloads"][8], name=CELL, config="lfm2_8b_a1b",
        traffic="train_t8192", chips=1)
    assert len(m["workloads"][8]["why"]) <= 200
    assert len(m["configs"][6]["why"]) <= 200
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "short_conv_ms_per_step",
        "short_conv_roofline_share"}
    assert not reports & {"layer_norm_ms_per_step", "gated_delta_ms_per_step",
                          "recomputed_forward_share"}
    new = {e["name"]: e for e in m["per_layer"]}
    for name, unit, better in (("short_conv_ms_per_step", "ms", "lower"),
                               ("short_conv_roofline_share", "%", "higher")):
        assert new[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]}


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/lfm2.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (a sequence and a
    query head, an expert, rows of the tied head) and changes none of it."""
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
    mod.HEAD_ROWS = 16          # six blocks of the 96 rows
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


def test_the_margin_is_the_held_sets_on_the_biased_scores():
    """configs/smallthinker.py's rule on s + b: with experts 4..7 of 16
    held and top-3, a token whose third choice is a held expert 2 % ahead
    of the fourth has a margin of 0.02; a trade among experts that are not
    held does not count; a score at or under zero is as far as can be."""
    import jax.numpy as jnp
    mod = _cell(TINY, TINY_CELL).config_module
    c = {"num_experts_per_tok": 3, "first_expert": 4, "experts_held": 4}
    scores = np.full((3, 16), 0.1, np.float32)
    scores[0, [0, 1, 5, 2]] = [0.9, 0.8, 0.5, 0.49]     # held 5 nearly out
    scores[1, [0, 1, 2, 3]] = [0.9, 0.8, 0.5, 0.499]    # a trade elsewhere
    scores[1, 4:8] = 0.05
    scores[2, [0, 1, 2]] = [0.9, 0.8, 0.5]
    scores[2, 4:8] = [-0.2, 0.0, -0.01, -0.5]           # held, and far
    margin = np.asarray(mod._router_margin(jnp.asarray(scores), c))
    assert margin[0] == pytest.approx(0.02, rel=1e-3)
    assert margin[1] == pytest.approx(0.9, rel=1e-3)    # 1 - 0.05 / 0.5
    assert margin[2] == pytest.approx(1.0, abs=1e-6)


# --- the two new readers on a recorded top_ops --------------------------------

TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ptpu_causal_conv1d_fwd.3" + MOSAIC, 0.004],
    ["ptpu_causal_conv1d_fwd.4" + MOSAIC, 0.004],
    ["ptpu_causal_conv1d_bwd.1" + MOSAIC, 0.016],
    ["ptpu_flash_fwd" + MOSAIC, 0.005],
    # not the kernels': a transform's wrapper, another instruction
    ["jvp_ptpu_causal_conv1d_fwd_.2" + MOSAIC, 0.25],
    ["ptpu_causal_conv1d_fwd.9 fusion kLoop", 0.25]]


def _record(cell, top_ops=TOP_OPS, steps=8):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "top_ops": top_ops, "category_s": {}}
    return {"trace": trace, "window": {"attempted": steps}, "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _reader(name):
    from benchmark import manifest
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_short_conv_readers_on_a_recorded_trace():
    ms, share = (_reader("short_conv_ms_per_step"),
                 _reader("short_conv_roofline_share"))
    cell = _cell()
    assert ms.read(_record(cell)) == pytest.approx(1e3 * 0.024 / 8)
    # the least time: the [8192, 2048] bf16 arrays that cross HBM, one a
    # layer forward and three backward less the one kept in VMEM
    least = (4 + 11) * 33554432 / 819e9
    assert share.read(_record(cell)) == pytest.approx(
        100 * least / (0.024 / 8))
    assert 0 < share.read(_record(cell)) < 100
    # nothing to read: no trace, a kernel that did not run under its name
    # (the jax.numpy passes, or a parent's program), no peak for the device,
    # a configuration whose module names no such kernel: None, never an
    # exception
    for reader in (ms, share):
        assert reader.read(_record(cell, top_ops=None)) is None
        assert reader.read(_record(cell, top_ops=TOP_OPS[:3])) is None
        assert reader.read(_record(cell, steps=0)) is None
        assert reader.read(_record(_cell(
            name="smallthinker_21b_a3b_train_t8192"))) is None
    assert share.read(dict(_record(cell), peak=None)) is None
