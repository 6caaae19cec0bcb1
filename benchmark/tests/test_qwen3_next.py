"""The configuration qwen3_next_80b_a3b and the cell
qwen3_next_80b_a3b_train_t4096, on the CPU: the cell's path rehearsed on a
tiny Qwen3-Next-shaped configuration of this directory's own
(tests/tiny_qwen3_next: chip 1 of 4, T no multiple of the delta rule's
chunk), every mutant of tests/mutant_qwen3_next.py refused, the operations
count at the published sizes against a hand count, the blocked reference
against the plain one, the two new readers on a recorded `top_ops`, and what
the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_qwen3_next.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_qwen3_next", "manifest.json")
TINY_CELL = "tiny_qwen3_next_t40"
CELL = "qwen3_next_80b_a3b_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of
# Qwen3-Next-80B-A3B-Instruct: every key of it is in the configuration's
# file, and only the three counts of the cut differ
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
HELD = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
MOSAIC = " custom-call tpu_custom_call"
MUTANTS = ["no_decay", "beta_one", "no_l2norm", "conv_off",
           "rope_whole_head", "output_gate_off", "norm_not_zero_centred",
           "shared_gate_off", "wrong_key_head"]


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
    assert "960 of 960 assignments counted" in line     # 4 x 3 x 80
    assert "the 4 held experts computed" in line
    # a CPU run reports counts and no device number
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share"} if trace else set())


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_qwen3_next.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    verdicts.pop("loss_fell")   # a dead norm (w for 1 + w) learns nothing
    assert all(verdicts.values())


def test_top9_is_not_dropless():
    """Top-2 for top-3 here: a third of the assignments is not counted,
    which `dropless` sees whatever the logits' tolerance lets through."""
    out, verdicts, line = _run(("tests", "mutant_qwen3_next.py"), "top9")
    assert out["correct"] is False and verdicts["dropless"] is False
    assert "640 of 960 assignments counted" in line


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_qwen3_next.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program without fluid.layers.gated_delta_rule (the parent of
    the PR that added it) `build` raises before anything is built: the
    driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(fluid.layers, "gated_delta_rule")
    with pytest.raises(NotImplementedError, match="gated_delta_rule"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


@pytest.mark.parametrize("path", ["kernel", "scan"])
def test_no_carry_cuts_the_state_at_every_chunk(monkeypatch, path):
    """The rehearsal's 40 tokens are one chunk, so the mutant `no_carry` is
    shown here on the op alone: three chunks of 16 under a slow decay. The
    first chunk is untouched, the later ones are wrong, on both paths."""
    import jax.numpy as jnp
    from paddle_tpu.models import causal_lm_reference as plain
    from paddle_tpu.ops import gated_delta_kernels as gdk
    sys.path.insert(0, HERE)
    import mutant_qwen3_next
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 48, 2, 16), jnp.float32)
               for _ in range(3))
    g = -jnp.asarray(rng.rand(1, 48, 2) * 0.05, jnp.float32)
    beta = jnp.asarray(rng.rand(1, 48, 2), jnp.float32)
    want = plain.delta_rule(plain.l2norm(q) * 16 ** -0.5, plain.l2norm(k), v,
                            g, beta)

    def error(got, rows):
        return float(jnp.abs(got[:, rows] - want[:, rows]).max()
                     / jnp.abs(want).max())

    healthy = gdk.gated_delta_rule(q, k, v, g, beta, path=path, chunk=16)
    assert error(healthy, slice(None)) < 1e-5
    monkeypatch.setattr(gdk, "_prepare", gdk._prepare)      # restored after
    mutant_qwen3_next.no_carry(None, None, None)
    cut = gdk.gated_delta_rule(q, k, v, g, beta, path=path, chunk=16)
    assert error(cut, slice(0, 16)) < 1e-5
    assert error(cut, slice(16, None)) > 0.1


def test_operations_against_the_hand_count():
    """Four layers at the published widths, T=4096, a token's forward
    multiply-adds. A delta net: 2048 x (12288 + 64) + 4096 x 2048 =
    33.69e6 of projections, 4 x 8192 = 0.03e6 of convolution, 32 x 3 x 128
    x 128 = 1.57e6 of recurrence: three of them 105.87e6. Full attention:
    2048 x (8192 + 2 x 512) + 4096 x 2048 = 27.26e6 and 8,390,656 pairs /
    4096 x 2 x 256 x 16 = 16.78e6. A layer's router 1.05e6, held experts
    0.625 x 3 x 2048 x 512 = 1.97e6, shared expert 3.15e6: four of them
    24.65e6. Head 2048 x 18992 = 38.90e6. Twice the sum, three passes:
    1280.8e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"delta_projections": 101.07e6, "delta_convolution": 0.098e6,
            "delta_rule": 4.72e6, "attention_projections": 27.26e6,
            "attention": 16.78e6, "router": 4.19e6, "experts": 7.86e6,
            "shared_expert": 12.59e6, "head": 38.90e6}
    assert {k: round(v / 1e6, 1) for k, v in macs.items()} \
        == {k: round(v / 1e6, 1) for k, v in hand.items()}
    assert abs(mod.ops_per_sample(cfg, traffic) - 1280.8e6) < 0.1e6
    total = sum(macs.values())
    share = {k: round(100 * sum(v for n, v in macs.items()
                                if n.startswith(k)) / total)
             for k in ("delta", "attention", "head", "experts")}
    assert share == {"delta": 50, "attention": 21, "head": 18, "experts": 4}
    assert mod.samples_per_step(cfg, traffic) == 4096
    # the flash kernels: 4 / 8 / 6 x 256 a pair and query head, 16 heads,
    # one layer
    pairs = 16 * (4096 * 4097 // 2) * traffic["batch"]
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 1024 * pairs, "ptpu_flash_bwd_dkdv": 2048 * pairs,
        "ptpu_flash_bwd_dq": 1536 * pairs}
    # the delta kernels, by what they are given at chunks of 64: a tile is
    # a (sequence, value head, chunk) of three layers; operands qe, kd, w,
    # u [64, 128] and m [64, 64] bf16 and erow [128] float32 are 74,240
    # bytes, o and dO 16,384, a state 32,768; a [64, 128] x [128, 128]
    # product is 2,097,152 operations and a [64, 64] x [64, 128] 1,048,576
    tiles = 3 * traffic["batch"] * 32 * 64
    assert mod.GATED_DELTA_KERNELS == ("ptpu_gated_delta_fwd",
                                       "ptpu_gated_delta_bwd")
    assert mod.gated_delta_kernel_ops(cfg, traffic, 64) == {
        "ptpu_gated_delta_fwd": [
            (7340032 * tiles, (74240 + 16384) * tiles),
            (4194304 * tiles, (3 * 16384 + 512 + 32768) * tiles)],
        "ptpu_gated_delta_bwd": [
            (16777216 * tiles, (2 * 74240 + 32768 + 16384) * tiles)]}
    # more than the recurrence's least, which ops_per_sample counts
    assert (7340032 + 4194304 + 16777216) * tiles \
        > 18 * 128 * 128 * 32 * 3 * 4096 * traffic["batch"]


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (16, 0)
    assert CATALOG["num_experts"] == 16 * HELD["num_experts"]
    assert CATALOG["vocab_size"] == 8 * HELD["vocab_size"]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "adam", "learning_rate", "clip_norm", "auxiliary_losses",
        "initialisation", "precision", "multi_token_prediction", "decay",
        "biases", "data"}
    assert set(cfg["reference"]["tolerance"]) == {"loss", "logits"}
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    # what the modeling file always applies and config.json has no key for
    # is a key here, noted under `assumed` (as OLMoE's qk_norm is)
    always = {"qk_norm": "head", "norm_zero_centered": True,
              "attention_gate": True}
    assert {k: cfg[k] for k in always} == always
    assert set(cfg["assumed"]) >= set(always)
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (512, 32, 0)
    assert c["mixer_layers"] == ["gated_delta"] * 3 + ["attention"]
    assert (c["rotary_dim"], c["intermediate_size"]) == (64, 512)


def test_manifest_holds_the_new_entries():
    """A prefix check: the cell and its configuration are where this PR put
    them (seventh and fifth), whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["configs"]][4] == "qwen3_next_80b_a3b"
    assert m["configs"][4]["reduced"] == _cell().config["reduced"]
    assert m["workloads"][6] == dict(
        m["workloads"][6], name=CELL, config="qwen3_next_80b_a3b",
        traffic="train_t4096_b2", chips=1)
    assert len(m["workloads"][6]["why"]) <= 200
    assert len(m["configs"][4]["why"]) <= 200
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "gated_delta_ms_per_step",
        "gated_delta_roofline_share"}
    assert "layer_norm_ms_per_step" not in reports     # it has no layer_norm
    new = {e["name"]: e for e in m["per_layer"]}
    for name, unit, better in (("gated_delta_ms_per_step", "ms", "lower"),
                               ("gated_delta_roofline_share", "%",
                                "higher")):
        assert new[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]}


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/qwen3_next.py:reference cuts the arithmetic of
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
    mod.HEAD_ROWS = 16          # five blocks of the 80 rows
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


# --- the two new readers on a recorded top_ops --------------------------------

TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ptpu_gated_delta_fwd.3" + MOSAIC, 0.012],
    ["ptpu_gated_delta_fwd.4" + MOSAIC, 0.012],
    ["ptpu_gated_delta_bwd.1" + MOSAIC, 0.04],
    ["ptpu_flash_fwd" + MOSAIC, 0.005],
    # not the kernels': a transform's wrapper, another instruction
    ["jvp_ptpu_gated_delta_fwd_.2" + MOSAIC, 0.25],
    ["ptpu_gated_delta_fwd.9 fusion kLoop", 0.25]]


def _record(cell, top_ops=TOP_OPS, steps=8):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "top_ops": top_ops, "category_s": {}}
    return {"trace": trace, "window": {"attempted": steps}, "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _reader(name):
    from benchmark import manifest
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_gated_delta_readers_on_a_recorded_trace(monkeypatch):
    from paddle_tpu.observability.registry import REGISTRY
    ms, share = (_reader("gated_delta_ms_per_step"),
                 _reader("gated_delta_roofline_share"))
    cell = _cell()
    assert ms.read(_record(cell)) == pytest.approx(1e3 * 0.064 / 8)
    # the chunk is the program's to say, by its counter's label: none
    # lowered on the kernel path, no share; two chunks, none either
    counter = REGISTRY.counter(share.COUNTER, "")
    monkeypatch.setattr(counter, "_values", {})
    counter.inc(kind="gated_delta", chunk="32", path="scan")
    assert share.lowered_chunk() is None
    assert share.read(_record(cell)) is None
    counter.inc(3, kind="gated_delta", chunk="64", path="kernel")
    assert share.lowered_chunk() == 64
    # the least time: every call of both kernels is bound by its bytes
    tiles = 3 * cell.traffic["batch"] * 32 * 64
    assert 16777216 / 197e12 < 197632 / 819e9
    least = tiles * (90624 + 82432 + 197632) / 819e9
    assert share.read(_record(cell)) == pytest.approx(
        100 * least / (0.064 / 8))
    assert 0 < share.read(_record(cell)) < 100
    # nothing to read: no trace, a kernel that did not run under its name
    # (the scan path, or a parent's program), a configuration whose module
    # counts no such kernel: None, never an exception
    for reader in (ms, share):
        assert reader.read(_record(cell, top_ops=None)) is None
        assert reader.read(_record(cell, top_ops=TOP_OPS[:3])) is None
        assert reader.read(_record(cell, steps=0)) is None
        assert reader.read(_record(_cell(
            name="smallthinker_21b_a3b_train_t8192"))) is None
    counter.inc(kind="gated_delta", chunk="128", path="kernel")
    assert share.read(_record(cell)) is None
