"""The configuration ouro_2_6b and the cell ouro_2_6b_train_t4096, on the
CPU: the cell's path rehearsed on a tiny Ouro-shaped configuration of this
directory's own (tests/tiny_ouro: 2 layers run 4 times), every mutant of
tests/mutant_ouro.py refused, the operations count at the published sizes
against a hand count, the blocked reference against the plain one, the new
reader, and what the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ouro.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_ouro", "manifest.json")
TINY_CELL = "tiny_ouro_t32"
CELL = "ouro_2_6b_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of Ouro-2.6B:
# every key of it is in the configuration's file, and only the depth differs
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
MUTANTS = ["three_passes", "pass_two_on_other_weights",
           "no_norm_between_passes", "sandwich_off", "last_pass_gated",
           "gate_off", "entropy_term_off", "rotary_off"]
LISTED = ["tokens_per_s_per_chip", "pallas_ms_per_step",
          "flash_fwd_ms_per_step", "flash_bwd_dkdv_ms_per_step",
          "flash_bwd_dq_ms_per_step", "softmax_xent_ms_per_step",
          "flash_roofline_share", "recomputed_forward_share"]


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
    """A large seed, as the driver's are; every verdict of the cell; every
    position compared."""
    out, verdicts, line = _run(("run.py",), "--trace", str(trace),
                               seed=3000000019)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    assert set(verdicts) == {"reference", "loss_fell", "finite",
                             "no_compile_in_window", "placement"}
    for name in ("exit_p off by", "logits off by", "loss off by",
                 "logits by pass off by"):
        assert name in line
    # a CPU run reports counts and no device number: the new metric is one
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "recomputed_forward_share"}
        if trace else set())
    if trace:
        share = out["metrics"]["recomputed_forward_share"]
        assert share["unit"] == "%" and 50.0 < share["value"] <= 100.0


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_ouro.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    verdicts.pop("loss_fell")
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_ouro.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no total_ut_steps (the parent of
    the PR that added it) `build` raises before anything is built: the
    driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delitem(causal_lm.DEFAULTS, "total_ut_steps")
    with pytest.raises(NotImplementedError, match="total_ut_steps"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Eight layers at the published widths run four times, T=4096, a
    token's forward multiply-adds. A layer application: 4 x 2048^2 =
    16.78e6 of projections, 3 x 2048 x 5632 = 34.60e6 of SwiGLU, 2 x 2048
    x 2048.5 = 8.39e6 of causal attention; 32 of them 1912.6e6. Four heads
    of 2048 x 49152 = 402.7e6. Twice the sum, three passes: 13.89e9 a
    token, 56.9e12 a step."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    assert {k: round(v / 1e6, 1) for k, v in macs.items()} == {
        "projections": 536.9, "feed_forward": 1107.3, "attention": 268.5,
        "heads": 402.7}
    ops = mod.ops_per_sample(cfg, traffic)
    assert abs(ops - 13.89e9) < 0.01e9
    assert mod.samples_per_step(cfg, traffic) == 4096
    assert abs(ops * 4096 - 56.9e12) < 0.05e12
    total = sum(macs.values())
    assert round(100 * macs["heads"] / total) == 17
    deep = mod.forward_macs(dict(cfg, num_hidden_layers=48), traffic)
    assert round(100 * deep["heads"] / sum(deep.values()), 1) == 3.4
    # parameters: a layer 51,388,416, eight of them, embedding and head,
    # the final norm, the gate
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51388416
    assert 8 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 612438017


def test_flash_operations_follow_the_programs_counters(monkeypatch):
    """4 / 8 / 6 x 128 a pair and query head, 16 heads, 32 layer
    applications; the forward kernel as often as the counters say it ran."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    pairs = 32 * 16 * (4096 * 4097 // 2)
    monkeypatch.setattr(mod, "flash_forward_runs", lambda: 1.0)
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 512 * pairs, "ptpu_flash_bwd_dkdv": 1024 * pairs,
        "ptpu_flash_bwd_dq": 768 * pairs}
    monkeypatch.setattr(mod, "flash_forward_runs", lambda: 2.0)
    assert mod.flash_kernel_ops(cfg, traffic)["ptpu_flash_fwd"] \
        == 1024 * pairs
    monkeypatch.undo()
    lowered = {("ptpu_remat_ops_total", "forward"): 8.0,
               ("ptpu_remat_ops_total", "replayed"): 8.0,
               ("ptpu_lowering_grad_ops_total", None): 0.0}
    monkeypatch.setattr(
        mod, "_lowered", lambda counter, **labels: lowered[
            counter, labels.get("kind")])
    assert mod.flash_forward_runs() == 2.0
    lowered["ptpu_remat_ops_total", "forward"] = None      # no such counter
    assert mod.flash_forward_runs() == 1.0


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] in (8, 6, 4)
    assert cfg["total_ut_steps"] == 4 and cfg["vocab_size"] == 49152
    assert cfg["sandwich_norm"] is True and cfg["exit_gate"] is True
    assert set(cfg["assumed"]) >= {
        "adam", "learning_rate", "clip_norm", "weight_decay",
        "initialisation", "exit_entropy_coef", "sandwich_norm",
        "norm_between_passes", "precision", "data"}
    assert set(cfg["reference"]["tolerance"]) == {"loss", "logits", "exit_p"}
    assert set(cfg["reduced_why"]) >= {"num_hidden_layers", "arithmetic",
                                       "measured", "distorts"}
    assert "six stages of 8 layers" in cfg["deployment"]
    assert cell.traffic == dict(
        cell.traffic, batch=1, seq_len=4096, executor="Executor",
        feed="device", steps_per_call=1, steps_per_block=2, chips=1)


def test_manifest_holds_the_new_entries():
    """A prefix check: the entries this configuration brought are there,
    whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    config = next(c for c in m["configs"] if c["name"] == "ouro_2_6b")
    assert config == dict(
        config, file="benchmark/configs/ouro_2_6b.json",
        reduced=["num_hidden_layers"],
        source="https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/"
               "config.json")
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="ouro_2_6b", traffic="train_t4096_b1",
                        chips=1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [w["name"] for w in m["workloads"]].index(CELL) == 7
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)
    listed = {e["name"] for key in ("end_to_end", "per_layer")
              for e in m[key] if CELL in e.get("workloads", [])}
    # PR 47's readers of work came behind them
    assert listed == set(LISTED) | {
        "step_mfu", "embedding_grad_ms_per_step",
        "embedding_grad_roofline_share"}
    share = next(e for e in m["per_layer"]
                 if e["name"] == "recomputed_forward_share")
    assert share == {
        "name": "recomputed_forward_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "op lowerings",
        "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= set(LISTED) | {
        "mfu", "peak_hbm_gib", "setup_s", "jaxpr_trace_s", "mlir_lower_s",
        "compile_or_load_s", "build_s", "first_step_s", "xla_op_ms_per_step",
        "device_idle_share", "host_dispatch_ms", "jit_call_ms",
        "executor_host_ms", "compile_requests", "cache_hit_share"}
    assert "layer_norm_ms_per_step" not in reports
    assert not {"expert_matmul_ms_per_step",
                "expert_matmul_roofline_share"} & reports


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/ouro.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (a sequence and a
    head, rows of the heads) and changes none of it."""
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
    assert list(fetches) == ["loss", "logits", "exit_p"]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.global_block().all_parameters()]
    batch = mod.make_batch(cfg, traffic, jax.random.key(11))
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    loss, (logits, p, _) = plain.loss_fn(
        cfg, params, batch["ids"], batch["pos"], batch["labels"],
        with_passes=True)
    assert checks.normalised_error(got["loss"], loss) < 1e-6
    assert checks.normalised_error(got["logits"], np.concatenate(
        [z[:, :, :mod.PROBE_COLUMNS] for z in logits], axis=1)) < 1e-5
    assert checks.normalised_error(got["exit_p"],
                                   np.moveaxis(np.asarray(p), 0, 1)) < 1e-5
    np.testing.assert_allclose(np.asarray(got["exit_p"]).sum(1), 1.0,
                               atol=1e-6)


@pytest.mark.parametrize("counts,want", [
    (None, None), ({"forward": 0.0}, None),
    ({"forward": 40.0}, 0.0), ({"forward": 40.0, "replayed": 30.0}, 75.0)])
def test_the_new_reader(monkeypatch, counts, want):
    """100 x replayed over forward of ptpu_remat_ops_total, summed over op
    types; None where the program has no such counter, as the parent has
    not."""
    from benchmark import manifest
    from paddle_tpu.observability import registry
    reader = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "recomputed_forward_share.py"))
    snap = {} if counts is None else {"ptpu_remat_ops_total": {"samples": [
        ({"kind": kind, "op": op}, value / 2)
        for kind, value in counts.items() for op in ("mul", "rms_norm")]}}
    monkeypatch.setattr(registry.REGISTRY, "snapshot", lambda: snap)
    assert reader.read({}) == want
