"""The configuration granite_4_0_h_micro and the cell
granite_4_0_h_micro_train_t2048, on the CPU: the cell's path rehearsed on a
tiny granite-4.0-h-shaped configuration of this directory's own
(tests/tiny_granite_4_0_h_micro: three Mamba-2 mixers and the attention layer
at toy widths, the scan's kernels and the flash kernels in the interpreter),
every mutant of tests/mutant_granite_4_0_h_micro.py refused, the operations
count at the published sizes against a hand count, the blocked reference
against the plain one, the new readers on the program's counters, and what
the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_granite_4_0_h_micro.py -q -p no:cacheprovider
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
sys.path.insert(0, HERE)
TINY = os.path.join(HERE, "tiny_granite_4_0_h_micro", "manifest.json")
TINY_CELL = "tiny_granite_4_0_h_micro_t64"
CELL = "granite_4_0_h_micro_train_t2048"
# architectures.jsonl of the model-configs guide, `config` of
# granite-4.0-h-micro: every key of it is in the configuration's file, and
# only the two of the cut differ
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
HELD = {"num_hidden_layers": 10, "vocab_size": 12544}
COMPARED = ("loss", "logits", "scan", "carried", "delta", "attention",
            "state", "a_log_grad", "dt_bias_grad", "d_grad", "conv_bias_grad",
            "gated_norm_grad", "a_log_grad_mean", "dt_bias_grad_mean",
            "attention_mean")


def _mutants():
    import mutant_granite_4_0_h_micro as mutants
    return [name for name in mutants.HAVE_TO_FAIL
            if not name.startswith("reference_")]


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        # the scan's and the flash kernels in the interpreter at T=64: what
        # a TPU runs
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PADDLE_TPU_PALLAS="attn,ssd", FLAGS_flash_min_seq="32"),
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
    # a CPU run reports counts and no device number: three layers of four
    # are Mamba-2 mixers
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "ssd_layer_share"}
        if trace else set())
    if trace:
        assert out["metrics"]["ssd_layer_share"]["value"] \
            == pytest.approx(75.0)


@pytest.mark.parametrize("mutant", _mutants())
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_granite_4_0_h_micro.py"),
                            mutant)
    assert out["correct"] is False
    assert verdicts.pop("reference") is False
    # a state that grows without bound (A not negated, Delta without its
    # softplus) is also not finite; every other verdict holds
    verdicts.pop("finite")
    verdicts.pop("loss_fell")
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_granite_4_0_h_micro.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no Mamba-2 mixer (the parent of the
    PR that added it) `build` raises before anything is built, by name: the
    driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "mamba2")
    with pytest.raises(NotImplementedError, match="Mamba-2 mixer"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Published layers 0-9 at the published widths, T=2048, a token's
    forward multiply-adds. Nine mixers' projections: 2048 x 8512 + 4096 x
    2048 = 25.82e6 each, 232.39e6. The recurrence: 64 heads x 2 x 128 x 64 =
    1.049e6 a mixer, 9.44e6. Attention's projections 2 x 2048 x (32 + 8) x
    64 = 10.49e6; its core 32 heads x 128 a visible key over 1024.5 keys:
    4.20e6. Ten MLPs of 3 x 2048 x 8192 = 503.32e6. The head 2048 x 12544 =
    25.69e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"scan_projections": 232.39e6, "scan": 9.44e6,
            "attention_projections": 10.49e6, "attention": 4.20e6,
            "mlp": 503.32e6, "head": 25.69e6}
    assert {k: round(v / 1e6, 2) for k, v in macs.items()} \
        == {k: round(v / 1e6, 2) for k, v in hand.items()}
    total = sum(macs.values())
    assert abs(total - 785.5e6) < 0.06e6
    assert abs(mod.ops_per_sample(cfg, traffic) - 6 * total) < 1
    share = {k: round(100 * v / total, 1) for k, v in macs.items()}
    assert share == {"mlp": 64.1, "scan_projections": 29.6, "head": 3.3,
                     "attention_projections": 1.3, "scan": 1.2,
                     "attention": 0.5}
    assert mod.samples_per_step(cfg, traffic) == 2048
    # a step: 9.7 TFLOP
    assert round(mod.ops_per_sample(cfg, traffic) * 2048 / 1e12, 1) == 9.7
    # one core, 32 heads of 64, the pairs inside the causal mask
    pairs = 2048 * 2049 // 2
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 4 * 64 * 32 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 64 * 32 * pairs,
        "ptpu_flash_bwd_dq": 6 * 64 * 32 * pairs}
    # the table's gradient through HBM: [12544, 2048] float32 written
    assert mod.embedding_grad_bytes(cfg, traffic) == 4 * 2048 * 12544
    # the scan at chunks of 128: 64.5 pairs a token
    calls = mod.ssd_kernel_ops(cfg, traffic, 128)
    forward = (2 * 2048 * (64.5 * 128 + 64 * (64.5 * 64 + 2 * 128 * 64)),
               2048 * (4 * 4096 + 4 * 128 + 4 * 64))
    reverse = (2 * 2048 * (2 * 64.5 * 128
                           + 64 * (2 * 64.5 * 64 + 4 * 128 * 64)),
               2048 * (6 * 4096 + 8 * 128 + 8 * 64))
    assert calls == {"ptpu_ssd_fwd": [forward, forward] * 9,
                     "ptpu_ssd_bwd": [reverse] * 9}
    # bytes bind every call at the v5e's peaks
    assert all(ops / 197e12 < nbytes / 819e9
               for kernel in calls for ops, nbytes in calls[kernel])


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
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "mamba2", "gate_before_norm", "chunk", "dt_bias", "a_log_and_d",
        "identities", "positions", "multipliers", "initialisation", "adam",
        "learning_rate", "clip_norm", "precision", "data", "recomputation"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    assert set(cfg["reference"]["tolerance"]) == set(COMPARED)
    assert cell.traffic["seq_len"] == 2048 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert c["mixer_layers"] == ["mamba2"] * 5 + ["attention"] \
        + ["mamba2"] * 4
    assert c["rope_theta"] is None and c["attention_scale"] == 1 / 64
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["head_dim"],
            c["dense_intermediate_size"]) == (64, 64, 128, 4, 64, 8192)


def test_the_program_counts_the_published_parameters():
    """772,160,448 trained parameters, by kind of layer as ISSUE 57 counts
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
    want = {"layer_%d" % i: 76182976 for i in range(10)}
    want.update(embedding=25690112, layer_5=60821504, final_norm=2048)
    assert by_layer == want
    assert sum(by_layer.values()) == 772160448
    types = [op.type for op in main.global_block().ops]
    # nine mixers' scans and the benchmark's own probe behind the step
    assert types.count("ssd_scan") == 9 + 1
    assert "rotary_embedding" not in types


def test_blocked_reference_is_the_plain_reference():
    """configs/granite_4_0_h_micro.py:reference against
    models/causal_lm_reference.py on random weights at the tiny sizes, in
    float32: the forward fetches, and the five gradients against jax.grad
    of the plain reference's whole loss (the same numbers: a parameter of
    the last layer reaches the loss through that layer alone)."""
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
    names = [p.name for p in main.global_block().all_parameters()]
    params = [jnp.asarray(
        (np.ones(p.shape) if p.name.endswith(("_norm", ".d"))
         else np.zeros(p.shape))
        + (0.1 if len(p.shape) == 1 else 0.06)
        * rng.standard_normal(p.shape), jnp.float32)
        for p in main.global_block().all_parameters()]
    # A_log and Delta's bias as a mixer has them, not around 0
    for i, name in enumerate(names):
        if name.endswith(".a_log"):
            params[i] = jnp.log(1.0 + jnp.abs(params[i]) * 20)
        elif name.endswith(".dt_bias"):
            params[i] = params[i] - 3.0
    batch = mod.make_batch(cfg, traffic, jax.random.key(1))
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    found = {}
    (loss, (logits, _)), grads = jax.value_and_grad(
        lambda p: plain.loss_fn(cfg, p, batch["ids"], batch["pos"],
                                batch["labels"]), has_aux=True)(params)
    plain.loss_fn(cfg, params, batch["ids"], batch["pos"], batch["labels"],
                  found=found)
    want = {"loss": loss, "logits": logits[..., :128],
            "scan": found["scan"][..., :128],
            "carried": found["carried"][..., :128],
            "delta": found["delta"],
            "attention": found["attention"][..., :128]}
    want.update((fetch, grads[names.index("layer_3." + role)])
                for fetch, role in mod.GRADIENTS.items())
    for name, ref in want.items():
        ref = np.asarray(ref)
        err = np.abs(np.asarray(got[name]).reshape(ref.shape) - ref).max() \
            / np.abs(ref).max()
        assert err < 2e-5, (name, err)
    assert got["state"].shape == (2, 64, 128)


def test_the_new_readers_on_the_programs_counters(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)

    def reader(name):
        return manifest.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))

    layers = reader("ssd_layer_share")
    assert layers.read({}) is None          # no such counter
    built = fresh.counter("ptpu_causal_lm_layers_total", "")
    built.inc(4, mixer="attention")
    assert layers.read({}) is None          # no such layer
    built.inc(9, mixer="mamba2")
    built.inc(1, mixer="attention")
    assert layers.read({}) == pytest.approx(100 * 9 / 14)
    # the scan's readers: silent without a trace, where one kernel did not
    # run, and where the program does not say one chunk on the kernel path
    ms, share = reader("ssd_scan_ms_per_step"), \
        reader("ssd_scan_roofline_share")
    cell = _cell()
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    record = {"cell": cell, "trace": None, "window": {"attempted": 4},
              "peak": peak}
    assert ms.read(record) is None and share.read(record) is None
    ops = [["ptpu_ssd_fwd.1 custom-call tpu_custom_call", 0.020],
           ["ptpu_ssd_fwd.7 custom-call tpu_custom_call", 0.020],
           ["ptpu_ssd_bwd.2 custom-call tpu_custom_call", 0.060],
           ["fusion.1 fusion kOutput", 1.0]]
    record["trace"] = {"busy_s": 1.0, "top_ops": ops}
    assert ms.read(record) == pytest.approx(25.0)
    assert share.read(record) is None       # the counter says no chunk
    scans = fresh.counter("ptpu_ssd_scan_layers_total", "")
    scans.inc(9, path="scan", chunk="128")
    assert share.read(record) is None       # not on the kernel path
    scans.inc(9, path="kernel", chunk="128")
    calls = cell.config_module.ssd_kernel_ops(cell.config, cell.traffic, 128)
    least = sum(max(o / 197e12, b / 819e9)
                for kernel in calls for o, b in calls[kernel])
    assert share.read(record) == pytest.approx(100 * least / 0.025)
    assert 0 < share.read(record) < 100
    record["trace"]["top_ops"] = ops[:2]    # one kernel did not run: silent
    assert ms.read(record) is None and share.read(record) is None


def test_the_manifest_promises_the_new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert "granite_4_0_h_micro" in [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 12          # the thirteenth
    assert sum(w["chips"] == 4 for w in bench["workloads"][:13]) == 1
    new = {"ssd_scan_ms_per_step", "ssd_scan_roofline_share",
           "ssd_layer_share"}
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"]
               if m["name"] in new)
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in bench[key] if CELL in m.get("workloads", ())}
    assert listed == new | {
        "tokens_per_s_per_chip", "step_mfu", "pallas_ms_per_step",
        "flash_fwd_ms_per_step", "flash_bwd_dkdv_ms_per_step",
        "flash_bwd_dq_ms_per_step", "flash_roofline_share",
        "softmax_xent_ms_per_step", "embedding_grad_ms_per_step",
        "embedding_grad_roofline_share"}
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           bench["workloads"][12]["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert {k: traffic[k] for k in ("chips", "batch", "seq_len", "executor",
                                    "feed", "steps_per_call",
                                    "steps_per_block")} == {
        "chips": 1, "batch": 1, "seq_len": 2048, "executor": "Executor",
        "feed": "device", "steps_per_call": 1, "steps_per_block": 8}
