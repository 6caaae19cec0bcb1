"""The configuration xing4_0_29b_a4b and the cell xing4_0_29b_a4b_train_1seq,
on the CPU: the cell's path rehearsed on a tiny Xing4.0-shaped configuration
of this directory's own (tests/tiny_xing4_0: chip 1 of 4, the
hyper-connections' kernels in the interpreter), every mutant of
tests/mutant_xing4_0.py refused, the operations count at the published sizes
against a hand count, the blocked reference against the plain one, the two
new readers on a recorded `top_ops`, and what the manifest promises of the
new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_xing4_0.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_xing4_0", "manifest.json")
TINY_CELL = "tiny_xing4_0_t32"
CELL = "xing4_0_29b_a4b_train_1seq"
# architectures.jsonl of the model-configs guide, `config` of
# Xing4.0-29B-A4B: every key of it is in the configuration's file, and only
# the five of the cut differ
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
HELD = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "vocab_size": 16384,
        "num_nextn_predict_layers": 0}
MOSAIC = " custom-call tpu_custom_call"
MUTANTS = ["yarn_off", "mscale_off", "rope_on_nope", "rope_half_split",
           "k_rope_per_head", "kv_norm_off", "q_norm_off", "v_from_k_nope",
           "sinkhorn_off", "sinkhorn_rows_only", "h_res_transposed",
           "h_post_unscaled", "h_pre_softmax", "coeffs_static",
           "readout_mean", "shared_gated", "scale_1", "bias_in_weights",
           "softmax_for_sigmoid"]


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        # the hyper-connections' kernels in the interpreter: what a TPU runs
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS="mhc"),
        capture_output=True, text=True, timeout=900)
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
    assert "384 of 384 assignments counted" in line     # 3 x 2 x 64
    assert "the 4 held experts computed" in line
    assert "H_res rows and columns off 1 by" in line
    # a CPU run reports counts and no device number
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share"} if trace else set())


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_xing4_0.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_xing4_0.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no latent attention (the parent of
    the PR that added it) `build` raises before anything is built: the
    driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "latent_attention")
    with pytest.raises(NotImplementedError, match="latent attention"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Five layers at the published widths, T=4096, a token's forward
    multiply-adds. Latent attention's projections: 3584 x 768 + 768 x 6144 +
    3584 x 576 + 512 x 8192 + 4096 x 3584 = 28.41e6 a layer, five 142.05e6;
    its core 8,390,656 pairs / 4096 x 32 x (192 + 128) = 20.98e6 a layer,
    five 104.88e6. Ten hyper-connections: 14336 x 24 = 0.344e6 each of
    projection and 3584 x 24 = 0.086e6 of mixing. The dense FFN 3 x 3584 x
    9216 = 99.09e6. Four routers 4 x 3584 x 64 = 0.92e6, 4 x (4 x 8 / 64)
    experts of 3 x 3584 x 1024 = 22.02e6 and four shared experts 44.04e6.
    The head 3584 x 16384 = 58.72e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"attention_projections": 142.05e6, "attention": 104.88e6,
            "hyper_connection_projections": 3.44e6,
            "hyper_connection_mixing": 0.86e6, "dense_ffn": 99.09e6,
            "router": 0.92e6, "experts": 22.02e6, "shared_expert": 44.04e6,
            "head": 58.72e6}
    assert {k: round(v / 1e6, 2) for k, v in macs.items()} \
        == {k: round(v / 1e6, 2) for k, v in hand.items()}
    total = sum(macs.values())
    assert abs(mod.ops_per_sample(cfg, traffic) - 6 * total) < 1
    assert abs(6 * total - 2856.1e6) < 0.1e6
    share = {k: round(100 * sum(v for n, v in macs.items()
                                if n.startswith(k)) / total, 1)
             for k in ("attention", "hyper", "dense", "experts", "shared",
                       "head")}
    assert share == {"attention": 51.9, "hyper": 0.9, "dense": 20.8,
                     "experts": 4.6, "shared": 9.3, "head": 12.3}
    assert mod.samples_per_step(cfg, traffic) == 4096
    # the flash kernels at the mixed widths, 32 heads, five layers
    pairs = 5 * 32 * (4096 * 4097 // 2) * traffic["batch"]
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 2 * (192 + 128) * pairs,
        "ptpu_flash_bwd_dkdv": 2 * (192 + 128 + 128 + 192) * pairs,
        "ptpu_flash_bwd_dq": 2 * (192 + 128 + 192) * pairs}
    # the hyper-connections' kernels: a stream array [4096, 4 x 3584] bf16
    # is 117,440,512 bytes, a [4096, 3584] one a quarter, a [4096, 128]
    # float32 array of coefficients 2,097,152; ten sub-layers
    from paddle_tpu.ops import mhc_kernels
    assert set(mod.MHC_KERNELS) == set(mhc_kernels.KERNELS)
    stream, one, coef = 117440512, 29360128, 2097152
    small = 4096 * 24 * 4
    assert mod.mhc_kernel_bytes(cfg, traffic) == {
        "ptpu_mhc_coeffs_fwd": 10 * 2 * small,
        "ptpu_mhc_coeffs_bwd": 10 * 4 * small,
        "ptpu_mhc_pre_fwd": 10 * (stream + one + coef),
        "ptpu_mhc_post_fwd": 10 * (2 * stream + one + coef),
        "ptpu_mhc_post_bwd": 10 * (3 * stream + 2 * one + 2 * coef),
        "ptpu_mhc_pre_bwd": 10 * (3 * stream + one + 3 * coef),
        "ptpu_mhc_expand": 2 * (stream + one),
        "ptpu_mhc_reduce": 2 * (stream + one)}


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (8, 0)
    assert CATALOG["n_routed_experts"] == 8 * HELD["n_routed_experts"]
    assert CATALOG["vocab_size"] == 8 * HELD["vocab_size"]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "streams", "hc_eps", "sinkhorn", "hyper_connection_initialisation",
        "rotary", "expert_bias", "adam", "learning_rate", "clip_norm",
        "auxiliary_losses", "initialisation", "precision", "data",
        "multi_token_prediction"}
    assert set(cfg["reference"]["tolerance"]) == {
        "loss", "logits", "logits_mean", "queries_keys", "streams", "h_res",
        "h_res_sums", "readout", "experts", "streams_out"}
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (64, 8, 0)
    assert c["mixer_layers"] == ["attention"] * 5 and c["latent"]
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert (c["hc_mult"], c["rotary_dim"], c["intermediate_size"],
            c["dense_intermediate_size"],
            c["shared_expert_intermediate_size"]) \
        == (4, 64, 1024, 9216, 1024)
    assert c["attention_scale"] == pytest.approx(0.14468, abs=1e-5)


def test_manifest_holds_the_new_entries():
    """A prefix check: the cell and its configuration are where this PR put
    them (tenth and eighth), whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["configs"]][7] == "xing4_0_29b_a4b"
    assert m["configs"][7]["reduced"] == _cell().config["reduced"]
    assert m["configs"][7]["source"] == _cell().config["source"]
    assert m["workloads"][9] == dict(
        m["workloads"][9], name=CELL, config="xing4_0_29b_a4b",
        traffic="train_1seq_t4096", chips=1)
    assert len(m["workloads"][9]["why"]) <= 200
    assert len(m["configs"][7]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"][:10]) == 1
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "mhc_ms_per_step", "mhc_roofline_share"}
    assert not reports & {"layer_norm_ms_per_step", "gated_delta_ms_per_step",
                          "recomputed_forward_share",
                          "short_conv_ms_per_step"}
    new = {e["name"]: e for e in m["per_layer"]}
    for name, unit, better in (("mhc_ms_per_step", "ms", "lower"),
                               ("mhc_roofline_share", "%", "higher")):
        assert new[name] == dict(new[name], **{
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "tokens_per_s_per_chip"})
        assert new[name]["workloads"][0] == CELL


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/xing4_0.py:reference cuts the arithmetic of
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
        fetches = mod.build(fluid, cfg, traffic)
    assert set(fetches) == {"loss", "logits", "expert_load", "queries",
                            "keys", "streams", "coefficients", "readout",
                            "experts", "streams_out"}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.global_block().all_parameters()]
    batch = mod.make_batch(cfg, traffic, jax.random.key(11))
    assert int(batch["ids"].max()) < cfg["vocab_size"]      # from the slice
    mod.HEAD_ROWS = 16          # four blocks of the 64 rows
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    loss, (logits, load) = plain.loss_fn(cfg, params, batch["ids"],
                                         batch["pos"], batch["labels"])
    assert checks.normalised_error(got["loss"], loss) < 1e-6
    assert checks.normalised_error(
        got["logits"], logits[:, :, :mod.PROBE_COLUMNS]) < 1e-5
    np.testing.assert_array_equal(got["expert_load"], load)
    assert got["expert_load"].shape == (16,)
    assert got["queries"].shape == got["keys"].shape == (2, 32, 1, 192)
    assert got["streams"].shape == got["streams_out"].shape \
        == (2, 32, 4 * 128)
    assert checks.normalised_error(
        np.asarray(got["streams_out"]).reshape(2, 32, 4, 128).sum(2),
        got["readout"]) < 1e-6
    res = np.asarray(got["coefficients"]).reshape(2, 32, 4, 4)
    assert np.abs(res.sum(-1) - 1).max() < 1e-5
    margin = np.asarray(got["router_margin"])
    assert margin.shape == (traffic["batch"], traffic["seq_len"])
    assert (margin >= 0).all() and np.isfinite(margin).any()
    # the first expert layer's held experts alone, and its own margin
    first = np.asarray(got["experts_margin"])
    assert got["experts"].shape == (2, 32, mod.PROBE_COLUMNS)
    assert first.shape == margin.shape and (first >= 0).all()


# --- the two new readers on a recorded top_ops --------------------------------

TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ptpu_mhc_pre_fwd.3" + MOSAIC, 0.004],
    ["ptpu_mhc_pre_fwd.4" + MOSAIC, 0.004],
    ["ptpu_mhc_pre_bwd.1" + MOSAIC, 0.016],
    ["ptpu_mhc_post_fwd" + MOSAIC, 0.008],
    ["ptpu_mhc_post_bwd.2" + MOSAIC, 0.016],
    ["ptpu_mhc_expand.1" + MOSAIC, 0.002],
    ["ptpu_mhc_reduce.1" + MOSAIC, 0.002],
    ["ptpu_mhc_coeffs_fwd.7" + MOSAIC, 0.001],
    ["ptpu_mhc_coeffs_bwd.7" + MOSAIC, 0.003],
    ["ptpu_flash_fwd" + MOSAIC, 0.005],
    # not the kernels': a transform's wrapper, another instruction
    ["jvp_ptpu_mhc_pre_fwd_.2" + MOSAIC, 0.25],
    ["ptpu_mhc_pre_fwd.9 fusion kLoop", 0.25]]


def _record(cell, top_ops=TOP_OPS, steps=8):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "top_ops": top_ops, "category_s": {}}
    return {"trace": trace, "window": {"attempted": steps}, "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _reader(name):
    from benchmark import manifest
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_mhc_readers_on_a_recorded_trace():
    ms, share = _reader("mhc_ms_per_step"), _reader("mhc_roofline_share")
    cell = _cell()
    assert ms.read(_record(cell)) == pytest.approx(1e3 * 0.056 / 8)
    nbytes = cell.config_module.mhc_kernel_bytes(cell.config, cell.traffic)
    least = sum(nbytes.values()) / 819e9
    assert share.read(_record(cell)) == pytest.approx(
        100 * least / (0.056 / 8))
    # nothing to read: no trace, a kernel that did not run under its name
    # (the jax.numpy passes, or a parent's program), no peak for the device,
    # a configuration whose module names no such kernel: None, never an
    # exception
    for reader in (ms, share):
        assert reader.read(_record(cell, top_ops=None)) is None
        assert reader.read(_record(cell, top_ops=TOP_OPS[:3])) is None
        assert reader.read(_record(cell, steps=0)) is None
        assert reader.read(_record(_cell(
            name="lfm2_8b_a1b_train_t8192"))) is None
    assert share.read(dict(_record(cell), peak=None)) is None
