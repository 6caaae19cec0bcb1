"""The configuration glm_4_7_flash and the cell glm_4_7_flash_train_t4096, on
the CPU: the cell's path rehearsed on a tiny GLM-4.7-Flash-shaped
configuration of this directory's own (tests/tiny_glm_4_7_flash: chip 1 of
4, 48 + 16 on 64, the flash kernels in the interpreter), every mutant of
tests/mutant_glm_4_7_flash.py refused, the operations count at the published
sizes against a hand count, the blocked reference against the plain one, the
new reader on the program's counter, and what the manifest promises of the
new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_glm_4_7_flash.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_glm_4_7_flash", "manifest.json")
TINY_CELL = "tiny_glm_4_7_flash_t64"
CELL = "glm_4_7_flash_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of GLM-4.7-Flash:
# every key of it is in the configuration's file, and only the three of the
# cut differ
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
HELD = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}
MUTANTS = ["module_embeds_inputs", "second_labels_are_the_first",
           "hnorm_off", "concat_swapped", "lambda_1", "head_not_shared",
           "module_ffn_dense", "scale_1", "kvb_columns_256_192",
           "rope_on_nope", "module_head_gradient_dropped",
           "module_lookup_gradient_dropped"]


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        # the flash kernels in the interpreter at T=64: what a TPU runs
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS="attn",
                 FLAGS_flash_min_seq="32"),
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
    assert "768 of 768 assignments counted" in line     # 128 x 3 x 2 layers
    assert "the 4 held experts computed" in line
    for name in ("main_loss", "mtp_loss", "mtp_logits", "mtp_input",
                 "mtp_logits_mean", "queries_keys"):
        assert name + " off by" in line
    # a CPU run reports counts and no device number; one layer of three is
    # the module's
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "mtp_layer_share"}
        if trace else set())
    if trace:
        assert out["metrics"]["mtp_layer_share"]["value"] \
            == pytest.approx(100 / 3)


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_glm_4_7_flash.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_glm_4_7_flash.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm builds no module (the parent of the PR
    that added it) `build` raises before anything is built, by name: the
    driver sees the parent fail cleanly and soon. The parent's `resolve`
    refuses the key too, before any array exists."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delitem(causal_lm.DEFAULTS, "mtp_loss_weight")
    with pytest.raises(NotImplementedError,
                       match="multi-token-prediction module"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Five trunk layers and the module's at the published widths, T=4096,
    a token's forward multiply-adds. Latent attention's projections: 2048 x
    768 + 768 x 5120 + 2048 x 576 + 512 x 8960 + 5120 x 2048 = 21.76e6 a
    layer, six 130.55e6; its core 8,390,656 pairs / 4096 x 20 x (256 +
    256) = 20.98e6 a layer, six 125.86e6. The dense FFN 3 x 2048 x 10240 =
    62.91e6. Five routers 5 x 2048 x 64 = 0.66e6, 5 x (4 x 8 / 64) experts
    of 3 x 2048 x 1536 = 23.59e6 and five shared experts 47.19e6. eh_proj
    4096 x 2048 = 8.39e6. Two passes of the head 2 x 2048 x 19360 =
    79.30e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"attention_projections": 130.55e6, "attention": 125.86e6,
            "dense_ffn": 62.91e6, "router": 0.66e6, "experts": 23.59e6,
            "shared_expert": 47.19e6, "eh_proj": 8.39e6, "head": 79.30e6}
    assert {k: round(v / 1e6, 2) for k, v in macs.items()} \
        == {k: round(v / 1e6, 2) for k, v in hand.items()}
    total = sum(macs.values())
    assert abs(mod.ops_per_sample(cfg, traffic) - 6 * total) < 1
    assert abs(total - 478.5e6) < 0.06e6
    share = {k: round(100 * sum(v for n, v in macs.items()
                                if n.startswith(k)) / total, 1)
             for k in ("attention", "dense", "experts", "shared", "head",
                       "eh_proj")}
    assert share == {"attention": 53.6, "dense": 13.1, "experts": 4.9,
                     "shared": 9.9, "head": 16.6, "eh_proj": 1.8}
    # the module all in: its layer (a sixth of the attention, a fifth of
    # the experts, shared experts and routers), eh_proj, its pass of the head
    module = (macs["attention_projections"] + macs["attention"]) / 6 \
        + (macs["router"] + macs["experts"] + macs["shared_expert"]) / 5 \
        + macs["eh_proj"] + macs["head"] / 2
    assert round(module / 1e6, 1) == 105.1
    assert round(100 * module / total, 1) == 22.0
    assert mod.samples_per_step(cfg, traffic) == 4096 * traffic["batch"]


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
        "mtp_loss_weight", "mtp_input", "router", "rotary", "expert_bias",
        "norm_weights", "adam", "learning_rate", "clip_norm",
        "auxiliary_losses", "initialisation", "precision", "data"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    assert set(cfg["reference"]["tolerance"]) == {
        "loss", "main_loss", "mtp_loss", "logits", "logits_mean",
        "mtp_logits", "mtp_logits_mean", "queries_keys", "mtp_input",
        "head_grad_mean", "embedding_grad"}
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (64, 8, 0)
    assert c["mixer_layers"] == ["attention"] * 6 and c["latent"]
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 5
    assert (c["mtp_layers"], c["mtp_loss_weight"], c["rotary_dim"],
            c["intermediate_size"], c["dense_intermediate_size"],
            c["shared_expert_intermediate_size"], c["head_dim"]) \
        == (1, 0.3, 64, 1536, 10240, 1536, 256)
    assert c["attention_scale"] is None and c["routed_scaling_factor"] == 1.8


def test_the_program_counts_706_518_528_trained_parameters():
    """The issue's arithmetic against the program's own count, nothing run:
    the dense layer 84,677,888, four expert layers of 106,829,056, the
    module 115,223,808, embedding and head with the final norm
    79,300,608; 5 x 64 bias values held, not trained."""
    import paddle_tpu as fluid
    cell = _cell()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    params = main.global_block().all_parameters()

    def count(keep):
        return sum(int(np.prod(p.shape)) for p in params if keep(p))
    assert count(lambda p: p.trainable) == 706518528
    assert count(lambda p: not p.trainable) == 320
    assert count(lambda p: p.name.startswith("layer_0.")) == 84677888
    assert count(lambda p: p.name.startswith("layer_1.")
                 and p.trainable) == 106829056
    assert count(lambda p: p.name.startswith("layer_5.")
                 and p.trainable) == 115223808
    assert count(lambda p: "layer_" not in p.name) == 79300608
    assert [p.name for p in params].count("embedding") == 1
    assert [p.name for p in params].count("head") == 1


def test_manifest_holds_the_new_entries():
    """A prefix check: the cell and its configuration are where this PR put
    them (eleventh and ninth), whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["configs"]][8] == "glm_4_7_flash"
    assert m["configs"][8]["reduced"] == _cell().config["reduced"]
    assert m["configs"][8]["source"] == _cell().config["source"]
    assert m["workloads"][10] == dict(
        m["workloads"][10], name=CELL, config="glm_4_7_flash",
        traffic="train_1seq_t4096", chips=1)
    assert len(m["workloads"][10]["why"]) <= 200
    assert len(m["configs"][8]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"][:11]) == 1
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "step_mfu", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "expert_matmul_roofline_share",
        "embedding_grad_ms_per_step", "embedding_grad_roofline_share",
        "mtp_layer_share"}
    assert not reports & {"layer_norm_ms_per_step", "gated_delta_ms_per_step",
                          "recomputed_forward_share", "mhc_ms_per_step",
                          "short_conv_ms_per_step"}
    new = {e["name"]: e for e in m["per_layer"]}["mtp_layer_share"]
    assert new == {"name": "mtp_layer_share", "unit": "%",
                   "better": "higher", "source": "program_counter",
                   "layer": "program build and lowering",
                   "moves": "tokens_per_s_per_chip", "workloads": [CELL]}


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/glm_4_7_flash.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (a sequence and a
    query head, an expert, rows of the two passes of the head) and changes
    none of it."""
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
    assert list(fetches) == ["loss", "main_loss", "mtp_loss", "logits",
                             "mtp_logits", "expert_load", "queries", "keys",
                             "mtp_input", "head_grad", "embedding_grad",
                             "rows_grad"]
    # the module's hnorm starts off 1 (at 1 it is the identity), alone
    starts = {op.output("Out")[0]: op.type
              for op in startup.global_block().ops}
    assert starts["layer_2.hnorm"] == "gaussian_random"
    assert starts["layer_2.enorm"] == starts["final_norm"] == "fill_constant"
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.global_block().all_parameters()]
    batch = mod.make_batch(cfg, traffic, jax.random.key(11))
    assert int(batch["ids"].max()) < cfg["vocab_size"]      # from the slice
    # one draw of T + 2: each feed the one before it, moved on by a token
    np.testing.assert_array_equal(batch["ids"][:, 1:],
                                  batch["labels"][:, :-1, 0])
    np.testing.assert_array_equal(batch["labels"][:, 1:, 0],
                                  batch["labels_next"][:, :-1, 0])
    mod.HEAD_ROWS = 32          # four blocks of the 128 rows
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    found = {}
    loss, (logits, load) = plain.loss_fn(
        cfg, params, batch["ids"], batch["pos"], batch["labels"],
        labels_next=batch["labels_next"], found=found)
    for name, want in (("loss", loss), ("main_loss", found["main_loss"]),
                       ("mtp_loss", found["mtp_loss"])):
        assert checks.normalised_error(got[name], want) < 1e-6, name
    columns = mod.PROBE_COLUMNS
    assert checks.normalised_error(got["logits"],
                                   logits[:, :, :columns]) < 1e-5
    assert checks.normalised_error(
        got["mtp_logits"], found["mtp_logits"][:, :, :columns]) < 1e-5
    assert checks.normalised_error(
        got["mtp_input"], found["mtp_input"][:, :, :columns]) < 1e-5
    # the head's gradient in closed form is jax.grad's, both uses summed
    head = [p.name for p in main.global_block().all_parameters()].index(
        "head")
    grads = jax.grad(lambda p: plain.loss_fn(
        cfg, p, batch["ids"], batch["pos"], batch["labels"],
        labels_next=batch["labels_next"])[0])(
            [jax.numpy.asarray(p) for p in params])
    assert got["head_grad"].shape == (cfg["hidden_size"], columns)
    assert checks.normalised_error(got["head_grad"],
                                   grads[head][:, :columns]) < 1e-5
    np.testing.assert_array_equal(got["lookups"], np.concatenate(
        [batch["ids"], batch["labels"][..., 0]]))
    np.testing.assert_array_equal(got["expert_load"], load)
    assert got["expert_load"].shape == (16,)
    assert got["queries"].shape == got["keys"].shape == (2, 64, 1, 64)
    margin = np.asarray(got["router_margin"])
    assert margin.shape == (traffic["batch"], traffic["seq_len"])
    assert (margin >= 0).all() and np.isfinite(margin).any()


# --- the new reader on the program's counter ---------------------------------

def test_mtp_layer_share_reads_the_programs_counter(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    reader = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "mtp_layer_share.py"))
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)
    # no such counter (a program that built no decoder): nothing to read
    assert reader.read({}) is None
    layers = fresh.counter("ptpu_causal_lm_layers_total", "")
    # a program from before the module: the counter has no `module` label
    layers.inc(5, mixer="attention", ffn="experts")
    assert reader.read({}) is None
    # a program that builds no module counts its layers under `trunk`
    layers.inc(5, mixer="attention", ffn="experts", module="trunk")
    assert reader.read({}) is None
    layers.inc(1, mixer="attention", ffn="experts", module="mtp")
    assert reader.read({}) == pytest.approx(100 / 11)
