"""The configuration phi4_mini_flash and the cell phi4_mini_flash_train_t8192,
on the CPU: the cell's path rehearsed on a tiny Phi-4-mini-flash-shaped
configuration of this directory's own (tests/tiny_phi4_mini_flash: the six
kinds of layer at toy widths, the flash kernels in the interpreter), every
mutant of tests/mutant_phi4_mini_flash.py refused,
the operations count at the published sizes against a hand count, the
blocked reference against the plain one, the new readers on the program's
counters, and what the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_phi4_mini_flash.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_phi4_mini_flash", "manifest.json")
TINY_CELL = "tiny_phi4_mini_flash_t64"
CELL = "phi4_mini_flash_train_t8192"
# architectures.jsonl of the model-configs guide, `config` of
# Phi-4-mini-flash-reasoning: every key of it is in the configuration's
# file, and only the two of the cut differ
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
HELD = {"num_hidden_layers": 6, "vocab_size": 25008}
MUTANTS = ["window_off", "window_off_by_one", "lambda_init_at_cuts_index",
           "subln_off", "scale_off", "halves_as_chunks", "memory_after_gate",
           "cross_reads_layer_1", "d_dropped", "conv_bias_dropped",
           "dt_bias_dropped", "memory_readers_gradient_dropped",
           "kv_readers_gradient_dropped"]
COMPARED = ("loss", "logits", "logits_mean", "memory", "shared_k", "shared_v",
            "delta", "memory_grad_mean", "memory_grad_p999",
            "shared_k_grad_mean", "shared_k_grad_p999")


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
    assert set(verdicts) == {"reference", "loss_fell", "finite",
                             "no_compile_in_window", "placement"}
    for name in COMPARED:
        assert name + " off by" in line
    # a CPU run reports counts and no device number: two layers of six read
    # another's state
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "shared_state_layer_share"}
        if trace else set())
    if trace:
        assert out["metrics"]["shared_state_layer_share"]["value"] \
            == pytest.approx(100 / 3)


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_phi4_mini_flash.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_phi4_mini_flash.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no layer that reads another's state
    (the parent of the PR that added them) `build` raises before anything
    is built, by name: the driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "gated_memory_unit")
    with pytest.raises(NotImplementedError, match="another layer's state"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Published layers 0, 1, 16, 17, 18, 19 at the published widths,
    T=8192, a token's forward multiply-adds. Two Mamba mixers' projections:
    2560 x 10240 + 5120 x 192 + 160 x 5120 + 5120 x 2560 = 41.12e6 each,
    82.25e6. The memory unit 2 x 2560 x 5120 = 26.21e6. Attention's
    projections: the windowed and the full layer 2 x 2560 x 2560 + 2 x 2560
    x 1280 = 19.66e6 each, the cross layer 13.11e6: 52.43e6. The cores: 20
    pairs x 2 maps x (64 + 128) = 7,680 a visible key, over 4,063,488 /
    8192 = 496.03 keys under the window and 4096.5 in each of the two full
    layers: 66.73e6. Six MLPs of 3 x 2560 x 10240 = 471.86e6. The head 2560
    x 25008 = 64.02e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"scan_projections": 82.25e6, "memory_unit": 26.21e6,
            "attention_projections": 52.43e6, "attention": 66.73e6,
            "mlp": 471.86e6, "head": 64.02e6}
    assert {k: round(v / 1e6, 2) for k, v in macs.items()} \
        == {k: round(v / 1e6, 2) for k, v in hand.items()}
    total = sum(macs.values())
    assert abs(total - 763.5e6) < 0.06e6
    assert abs(mod.ops_per_sample(cfg, traffic) - 6 * total) < 1
    share = {k: round(100 * v / total, 1) for k, v in macs.items()}
    assert share == {"mlp": 61.8, "scan_projections": 10.8, "attention": 8.7,
                     "head": 8.4, "attention_projections": 6.9,
                     "memory_unit": 3.4}
    assert mod.samples_per_step(cfg, traffic) == 8192
    # a step: 37.5 TFLOP
    assert round(mod.ops_per_sample(cfg, traffic) * 8192 / 1e12, 1) == 37.5
    # what the kernels are given: 40 heads of 128 on values of 128 over the
    # pairs inside the three masks, 4 / 3 of the cores' arithmetic
    pairs = 4063488 + 2 * (8192 * 8193 // 2)
    given = mod.flash_kernel_ops(cfg, traffic)
    assert given == {"ptpu_flash_fwd": 4 * 128 * 40 * pairs,
                     "ptpu_flash_bwd_dkdv": 8 * 128 * 40 * pairs,
                     "ptpu_flash_bwd_dq": 6 * 128 * 40 * pairs}
    assert given["ptpu_flash_fwd"] == pytest.approx(
        4 / 3 * 2 * macs["attention"] * 8192)
    # the table's gradient through HBM: [25008, 2560] written (the 8192
    # rows read are the compiled step's to hold in VMEM)
    assert mod.embedding_grad_bytes(cfg, traffic) == 4 * 2560 * 25008
    # the scan: three (five) [8192, 5120] float32 arrays a layer, B and C
    # read (and their gradients written); no state between chunks
    array, small = 4 * 8192 * 5120, 4 * 8192 * 32
    assert mod.selective_scan_kernel_bytes(cfg, traffic) == {
        "ptpu_selective_scan_fwd": 2 * (3 * array + small),
        "ptpu_selective_scan_bwd": 2 * (5 * array + 2 * small)}


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (8, 0)
    assert CATALOG["vocab_size"] == 8 * HELD["vocab_size"]
    assert cfg["layer_indices"] == [0, 1, 16, 17, 18, 19]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "mamba", "differential_attention", "lambda_init", "biases",
        "positions", "window", "identities", "initialisation", "adam",
        "learning_rate", "clip_norm", "precision", "data", "recomputation"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    assert set(cfg["reference"]["tolerance"]) == set(COMPARED)
    assert cell.traffic["seq_len"] == 8192 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert c["mixer_layers"] == ["mamba", "attention", "mamba", "attention",
                                 "gmu", "attention"]
    assert c["window_layers"] == [None, 512, None, None, None, None]
    assert c["reads_layers"] == ["own"] * 4 + ["shared"] * 2
    assert (c["mamba_dt_rank"], c["mamba_d_state"], c["mamba_d_conv"],
            c["mamba_expand"], c["head_dim"], c["rope_theta"]) \
        == (160, 16, 4, 2, 64, None)
    assert [round(x, 4) for x in c["lambda_init_layers"] if x is not None] \
        == [round(0.8 - 0.6 * np.exp(-0.3 * i), 4) for i in (1, 17, 19)]


def test_the_program_counts_the_published_parameters():
    """697,094,272 trained parameters, by kind of layer as ISSUE 54 counts
    them, from the program's own variables at the published widths (no
    array is made)."""
    import paddle_tpu as fluid
    cell = _cell()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    by_layer = {}
    for p in main.global_block().all_parameters():
        key = p.name.split(".")[0]
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
    assert by_layer == {
        "embedding": 64020480, "layer_0": 119895040, "layer_1": 98322304,
        "layer_2": 119895040, "layer_3": 98322304, "layer_4": 104867840,
        "layer_5": 91766144, "final_norm": 5120}
    assert sum(by_layer.values()) == 697094272


def test_blocked_reference_is_the_plain_reference():
    """configs/phi4_mini_flash.py:reference against
    models/causal_lm_reference.py on random weights at the tiny sizes, in
    float32: the forward fetches."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm_reference as plain
    cell = _cell(TINY, TINY_CELL)
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        mod.build(fluid, cfg, traffic)
    rng = np.random.RandomState(3)
    params = [jnp.asarray(
        (np.ones(p.shape) if p.name.endswith(("_norm", ".subln", ".d"))
         else np.zeros(p.shape))
        + (0.1 if len(p.shape) == 1 else 0.06)
        * rng.standard_normal(p.shape), jnp.float32)
        for p in main.global_block().all_parameters()]
    # A_log and Delta's bias as a mixer has them, not around 0
    for i, p in enumerate(main.global_block().all_parameters()):
        if p.name.endswith(".a_log"):
            params[i] = jnp.log(1.0 + jnp.abs(params[i]) * 20)
        elif p.name.endswith(".dt_bias"):
            params[i] = params[i] - 3.0
    batch = mod.make_batch(cfg, traffic, jax.random.key(1))
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    found = {}
    loss, (logits, _) = plain.loss_fn(cfg, params, batch["ids"], batch["pos"],
                                      batch["labels"], found=found)
    want = {"loss": loss, "logits": logits[..., :128],
            "memory": found["memory"][..., :128],
            "shared_k": found["shared_k"][:, :, 0],
            "shared_v": found["shared_v"][:, :, :1],
            "delta": found["delta"][..., :128]}
    for name, ref in want.items():
        ref = np.asarray(ref)
        err = np.abs(np.asarray(got[name]).reshape(ref.shape) - ref).max() \
            / np.abs(ref).max()
        assert err < 2e-5, (name, err)
    assert np.abs(np.asarray(got["memory_grad"])).max() > 0
    assert np.abs(np.asarray(got["shared_k_grad"])).max() > 0


def test_the_new_readers_on_the_programs_counters(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)
    reader = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "shared_state_layer_share.py"))
    assert reader.read({}) is None          # no such counter
    layers = fresh.counter("ptpu_causal_lm_layers_total", "")
    layers.inc(5, mixer="attention", module="trunk")    # before the label
    assert reader.read({}) is None
    layers.inc(4, mixer="attention", reads="own")
    assert reader.read({}) is None          # every layer computes its own
    layers.inc(1, mixer="gmu", reads="shared")
    layers.inc(1, mixer="attention", reads="shared")
    assert reader.read({}) == pytest.approx(100 * 2 / 11)
    # the scan's readers: silent without a trace, and for a configuration
    # that names no such kernels
    ms = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "selective_scan_ms_per_step.py"))
    share = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "selective_scan_roofline_share.py"))
    cell = _cell()
    record = {"cell": cell, "trace": None, "window": {"attempted": 4},
              "peak": {"hbm_bytes_per_s": 819e9}}
    assert ms.read(record) is None and share.read(record) is None
    ops = [["ptpu_selective_scan_fwd.1 custom-call tpu_custom_call", 0.020],
           ["ptpu_selective_scan_bwd.2 custom-call tpu_custom_call", 0.060],
           ["fusion.1 fusion kOutput", 1.0]]
    record["trace"] = {"busy_s": 1.0, "top_ops": ops}
    assert ms.read(record) == pytest.approx(20.0)
    nbytes = cell.config_module.selective_scan_kernel_bytes(cell.config,
                                                            cell.traffic)
    assert share.read(record) == pytest.approx(
        100 * sum(nbytes.values()) / 819e9 / 0.020)
    record["trace"]["top_ops"] = ops[:1]    # one kernel did not run: silent
    assert ms.read(record) is None and share.read(record) is None


def test_the_manifest_promises_the_new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "phi4_mini_flash"
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 12
    new = [m["name"] for m in bench["per_layer"][-3:]]
    assert new == ["selective_scan_ms_per_step",
                   "selective_scan_roofline_share",
                   "shared_state_layer_share"]
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-3:])
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in bench[key] if CELL in m.get("workloads", ())}
    assert listed == set(new) | {
        "tokens_per_s_per_chip", "step_mfu", "pallas_ms_per_step",
        "flash_fwd_ms_per_step", "flash_bwd_dkdv_ms_per_step",
        "flash_bwd_dq_ms_per_step", "flash_roofline_share",
        "softmax_xent_ms_per_step", "layer_norm_ms_per_step",
        "embedding_grad_ms_per_step", "embedding_grad_roofline_share"}
