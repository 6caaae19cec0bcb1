"""The causal_lm configuration family and the cell olmoe_1b_7b_train_t4096,
on the CPU: the cell's path rehearsed on a tiny OLMoE-shaped configuration
of this directory's own (tests/tiny_olmoe), the `dropless` verdict, the
operations count at the published sizes, the blocked reference against the
plain one, and what the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_olmoe.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_olmoe", "manifest.json")
CELL = "olmoe_1b_7b_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of
# OLMoE-1B-7B-0125-Instruct: every number of it is in the configuration's
# file under the same key, and only the depth differs
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", "tiny_olmoe_t32",
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if "bench: correct:" in ln)
    return (json.loads(proc.stdout.strip().splitlines()[-1]),
            json.loads(line.rpartition("verdicts ")[2]), line)


def _cell():
    from benchmark import manifest
    return manifest.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_cells_path(trace):
    """A large seed, as the driver's are; every verdict of the cell."""
    out, verdicts, line = _run(("run.py",), "--trace", str(trace),
                               seed=3000000019)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    assert set(verdicts) == {"reference", "loss_fell", "dropless", "finite",
                             "no_compile_in_window", "placement"}
    assert "experts computed 256 of 256 assignments" in line
    # a CPU run reports counts and no device number
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share"} if trace else set())


def test_a_dropped_assignment_is_not_dropless():
    """tests/mutant_olmoe.py fewer_experts: top-1 for top-2. The experts
    computed half the assignments, which `dropless` sees whatever the
    logits' tolerance lets through."""
    out, verdicts, line = _run(("tests", "mutant_olmoe.py"), "fewer_experts")
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts["dropless"] is False
    assert "experts computed 128 of 256 assignments" in line


@pytest.mark.parametrize("mutant", ["no_qk_norm", "rotary_off"])
def test_broken_attention_inputs_are_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_olmoe.py"), mutant)
    assert out["correct"] is False
    assert verdicts.pop("reference") is False and all(verdicts.values())


def test_operations_against_the_hand_count():
    """One layer at the published widths, T=4096: 4 x 2048^2 attention
    projections + 2048 x 64 router + 8 x 3 x 2048 x 1024 active experts +
    2048 x 50304 head = 170.26e6 weights a token, six operations each, and
    3 x 2 x 4096 x 4096 x 0.5 = 50.33e6 for causal attention."""
    cell = _cell()
    mod, cfg = cell.config_module, cell.config
    assert abs(mod.active_weights(cfg) - 170.26e6) < 0.01e6
    assert abs(mod.ops_per_sample(cfg, cell.traffic) - 1072e6) < 1e6
    head = 6 * cfg["hidden_size"] * cfg["vocab_size"]
    assert round(100 * head / mod.ops_per_sample(cfg, cell.traffic)) == 58
    full = dict(cfg, num_hidden_layers=16)
    assert round(100 * head / mod.ops_per_sample(full, cell.traffic)) == 8
    assert mod.samples_per_step(cfg, cell.traffic) \
        == cell.traffic["batch"] * 4096


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 1
    assert set(cfg["assumed"]) >= {"adam", "learning_rate", "clip_norm",
                                   "weight_decay", "auxiliary_losses",
                                   "initialisation", "precision", "data"}
    assert set(cfg["reference"]["tolerance"]) == {"loss", "logits"}
    assert cell.traffic["seq_len"] == cfg["max_position_embeddings"]


def test_manifest_holds_the_configuration_and_its_cell():
    """Later PRs add configurations, cells and readers behind these: the
    test holds what the manifest promises of this cell, not its length."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert "olmoe_1b_7b" in [c["name"] for c in m["configs"]]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="olmoe_1b_7b", traffic="train_t4096",
                        chips=1)
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


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/causal_lm.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (a sequence and a
    head, an expert, a sequence of the head) and changes none of it."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm_reference as plain
    from benchmark import checks, manifest
    cell = manifest.load_cell(TINY, "tiny_olmoe_t32")
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
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    loss, (logits, load) = plain.loss_fn(cfg, params, batch["ids"],
                                         batch["pos"], batch["labels"])
    # the same products summed in the same precision, block by block
    assert checks.normalised_error(got["loss"], loss) < 1e-6
    assert checks.normalised_error(
        got["logits"], logits[:, :, :mod.PROBE_COLUMNS]) < 1e-5
    np.testing.assert_array_equal(got["expert_load"], load)
    margin = np.asarray(got["router_margin"])
    assert margin.shape == (traffic["batch"], traffic["seq_len"])
    assert (margin >= 0).all() and (margin < 1).all()


def test_check_leaves_out_only_undecided_tokens():
    """A token under the router margin may differ; one over it may not."""
    cell = _cell()
    mod = cell.config_module
    cfg = dict(cell.config, num_hidden_layers=1, num_experts_per_tok=2,
               reference={"tolerance": {"loss": 1e-4, "logits": 2e-2},
                          "router_margin": 0.01})
    logits = np.ones((1, 4, 8), np.float32)
    want = {"loss": np.float32(1.0), "logits": logits,
            "expert_load": np.array([4, 4]),
            "router_margin": np.array([[0.5, 0.001, 0.5, 0.5]])}
    first = {"loss": np.float32(1.0), "logits": logits.copy(),
             "expert_load": np.array([5, 3])}
    first["logits"][0, 1] += 0.5        # the undecided token
    verdicts, found = mod.check(cfg, first, want, [1.0, 0.5])
    assert verdicts == {"reference": True, "loss_fell": True,
                        "dropless": True}
    assert "logits of 3 of 4 tokens compared" in found
    first["logits"][0, 2] += 0.5        # a decided one
    assert mod.check(cfg, first, want, [1.0, 0.5])[0]["reference"] is False
    first["expert_load"] = np.array([4, 3])
    assert mod.check(cfg, first, want, [1.0, 0.5])[0]["dropless"] is False
