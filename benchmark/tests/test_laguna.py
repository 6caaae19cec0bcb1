"""The configuration laguna_s_2_1 and the cell laguna_s_2_1_train_t4096, on
the CPU: the cell's path rehearsed on a tiny Laguna-shaped configuration of
this directory's own (tests/tiny_laguna: five layers at toy widths, 4 / 6
query heads on 2 by layer, a window of 16 at T = 64, 2 of 16 experts held
under top-3, so the row buffer is the cut one), every mutant of
tests/mutant_laguna.py refused, the operations count at the published sizes
against a hand count, the program's parameters against ISSUE 64's
arithmetic, the blocked reference against the plain one, the new readers on
the program's counters, and what the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_laguna.py -q -p no:cacheprovider
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
TINY = os.path.join(HERE, "tiny_laguna", "manifest.json")
TINY_CELL = "tiny_laguna_t64"
CELL = "laguna_s_2_1_train_t4096"
PERIOD = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention"]
# architectures.jsonl of the model-configs guide, `config` of Laguna-S-2.1:
# every key of it is in the configuration's file, and only the six of the
# cut differ
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12, "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
HELD = {"num_hidden_layers": 5, "num_attention_heads_per_layer":
        [12, 18, 18, 18] * 12, "num_attention_heads": 12,
        "num_key_value_heads": 2, "num_experts": 8, "vocab_size": 25088}
COMPARED = ("loss", "logits", "logits_mean", "attention_1_mean", "q_0",
            "k_0", "q_1", "k_1", "gate_1", "attention_1", "attention_4",
            "routed", "shared", "state", "wg_4_grad", "wq_4_grad",
            "wg_3_grad", "expert_gate_4_grad")


def _mutants():
    import mutant_laguna as mutants
    return [name for name in mutants.HAVE_TO_FAIL
            if not name.startswith("reference_")]


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
    for name in COMPARED:
        assert name + " off by" in line
    # a CPU run reports counts and no device number: three layers of five
    # sit behind a window, every one has a gate a head
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "windowed_layer_share",
         "per_head_gate_layer_share"} if trace else set())
    if trace:
        assert out["metrics"]["windowed_layer_share"]["value"] \
            == pytest.approx(60.0)
        assert out["metrics"]["per_head_gate_layer_share"]["value"] \
            == pytest.approx(100.0)


@pytest.mark.parametrize("mutant", _mutants())
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_laguna.py"), mutant)
    assert out["correct"] is False
    # a wrong count of assignments (top_k_9) is not dropless either
    if mutant == "top_k_9":
        assert verdicts["dropless"] is False
    else:
        assert verdicts["reference"] is False
    for name in ("reference", "dropless", "finite", "loss_fell"):
        verdicts.pop(name)
    assert all(verdicts.values())


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_laguna.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no geometry by layer (the parent of
    the PR that added it) `build` raises before anything is built, by name:
    the driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "_geometry_by_layer")
    with pytest.raises(NotImplementedError, match="geometry by layer"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """ISSUE 64's arithmetic at T = 4096: 332 M forward multiply-adds a
    token, layer 0's dense FFN 34 %, the head 23 %, attention's projections
    21 %, its core 6 % (35 % of that on the three sliding layers), the
    shared experts 11 %, the held routed experts 3.6 %, the router 1 %; the
    kernels' counts at what a LAYER holds."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    assert macs["dense_ffn"] == 3 * 3072 * 12288 == 113246208
    full = 3072 * (2 * 12 * 128 + 2 * 2 * 128 + 12)
    sliding = 3072 * (2 * 18 * 128 + 2 * 2 * 128 + 18)
    assert macs["attention_projections"] == 2 * full + 3 * sliding
    windowed = 512 * 513 // 2 + (4096 - 512) * 512
    assert windowed == 1966336 and mod.visible_pairs(4096, 512) == windowed
    assert macs["attention"] == pytest.approx(
        2 * 2048.5 * 12 * 256 + 3 * windowed / 4096.0 * 18 * 256)
    assert macs["router"] == 4 * 3072 * 256
    assert macs["shared_expert"] == 4 * (3 * 3072 * 1024 + 3072)
    assert macs["routed_experts"] == pytest.approx(
        4 * (10 * 8 / 256.0) * 3 * 3072 * 1024)
    assert macs["head"] == 3072 * 25088
    total = sum(macs.values())
    assert total == pytest.approx(331.6e6, rel=1e-3)
    share = {k: round(100.0 * v / total, 1) for k, v in macs.items()}
    assert share == {"dense_ffn": 34.1, "head": 23.2,
                     "attention_projections": 20.9, "attention": 5.8,
                     "shared_expert": 11.4, "routed_experts": 3.6,
                     "router": 0.9}
    on_sliding = 3 * windowed / 4096.0 * 18 * 256 / macs["attention"]
    assert round(100 * on_sliding) == 35
    assert mod.ops_per_sample(cfg, traffic) == pytest.approx(6 * total)
    assert mod.samples_per_step(cfg, traffic) == 4096
    # 8.15 TFLOP a step
    assert 6 * total * 4096 == pytest.approx(8.15e12, rel=2e-3)
    # three matmuls an expert of [3072 x 1024], three passes: a load of 160
    # rows on each of the 8 held experts in each of 4 layers
    load = np.zeros((256,), np.int64)
    load[:8] = 4 * 160
    load[8:] = 7
    assert mod.expert_matmul_ops(cfg, traffic, load) \
        == 3 * 3 * 2 * 3072 * 1024 * 8 * 4 * 160
    assert mod.expert_matmul_ops(cfg, traffic, np.stack([load, load])) \
        == 2 * mod.expert_matmul_ops(cfg, traffic, load)
    pairs = 2 * 12 * (4096 * 4097 // 2) + 3 * 18 * windowed
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 4 * 128 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * pairs}
    assert mod.embedding_grad_bytes(cfg, traffic) == 4 * 3072 * 25088
    assert mod.embedding_grad_bytes is not mod.base.embedding_grad_bytes


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (32, 0)
    assert CATALOG["vocab_size"] == 4 * HELD["vocab_size"]
    assert CATALOG["num_experts"] == 32 * HELD["num_experts"]
    assert all(4 * held == whole for held, whole in zip(
        HELD["num_attention_heads_per_layer"],
        CATALOG["num_attention_heads_per_layer"]))
    assert CATALOG["rope_parameters"]["full_attention"][
        "attention_factor"] == pytest.approx(0.1 * math.log(128) + 1,
                                             rel=1e-15)
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic"}
    assert set(cfg["assumed"]) >= {
        "router", "shared_expert", "qk_norm", "partial_rotary", "yarn",
        "gate", "window", "learning_rate", "clip_norm", "adam",
        "initialisation", "precision"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    for key in ("deployment", "distorts", "measured"):
        assert isinstance(cfg[key], str) and cfg[key]
    assert set(cfg["reference"]["tolerance"]) == set(COMPARED)
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert c["mixer_layers"] == ["attention"] * 5
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert c["window_layers"] == [None, 512, 512, 512, None]
    assert [g["num_attention_heads"] for g in c["geometry_layers"]] \
        == [12, 18, 18, 18, 12]
    assert [g["rotary_dim"] for g in c["geometry_layers"]] \
        == [64, 128, 128, 128, 64]
    assert c["rope_tables"] == [("yarn", 64), ("default", 128)]
    assert (c["num_experts"], c["experts_held"], c["first_expert"],
            c["num_experts_per_tok"], c["intermediate_size"],
            c["dense_intermediate_size"],
            c["shared_expert_intermediate_size"], c["routed_scaling_factor"],
            c["num_dense_layers"], c["attention_gate"], c["qk_norm"]) \
        == (256, 8, 0, 10, 1024, 12288, 1024, 2.5, 1, "per_head", "head")


def test_the_program_counts_the_published_parameters():
    """679,764,224 trained parameters, by layer as ISSUE 64 counts them,
    from the program's own variables at the published widths (no array is
    made)."""
    import paddle_tpu as fluid
    cell = _cell()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    by_layer = {}
    for p in main.global_block().all_parameters():
        assert p.trainable
        key = p.name.split(".")[0]
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
    assert by_layer == {
        "layer_0": 124299520, "layer_1": 101514496, "layer_2": 101514496,
        "layer_3": 101514496, "layer_4": 96777472, "embedding": 77070336,
        "head": 77070336, "final_norm": 3072}
    assert sum(by_layer.values()) == 679764224
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes["layer_0.wq"] == (3072, 1536)
    assert shapes["layer_1.wq"] == (3072, 2304)
    assert shapes["layer_1.wg"] == (3072, 18)
    assert shapes["layer_1.wo"] == (2304, 3072)
    assert shapes["layer_1.experts.router"] == (3072, 256)
    assert shapes["layer_1.experts.w_gate"] == (8, 3072, 1024)
    # the whole model by the same per-layer arithmetic: 117.56 B
    full, sliding = 44187904, 63136000
    whole = 12 * full + 36 * sliding + 113246208 + 47 * 2426145792 \
        + 48 * 2 * 3072 + 2 * 100352 * 3072 + 3072
    assert round(whole / 1e9, 2) == 117.56
    types = [op.type for op in main.global_block().ops]
    assert types.count("moe_ffn") == 4
    assert types.count("fused_attention") == 5
    assert types.count("rotary_embedding") == 10


def test_blocked_reference_is_the_plain_reference():
    """configs/laguna.py:reference against models/causal_lm_reference.py on
    random weights at the tiny sizes, in float32: the forward fetches, and
    the four gradients against jax.grad of the plain reference's whole loss
    (the same numbers: a parameter of the last layers reaches the loss
    through those layers alone)."""
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
        (np.ones(p.shape) if p.name.endswith("norm") else np.zeros(p.shape))
        + (0.1 if len(p.shape) == 1 else 0.06)
        * rng.standard_normal(p.shape), jnp.float32)
        for p in main.global_block().all_parameters()]
    batch = mod.make_batch(cfg, traffic, jax.random.key(1))
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    found = {}
    (loss, (logits, load)), grads = jax.value_and_grad(
        lambda p: plain.loss_fn(cfg, p, batch["ids"], batch["pos"],
                                batch["labels"]), has_aux=True)(params)
    plain.loss_fn(cfg, params, batch["ids"], batch["pos"], batch["labels"],
                  found=found)
    want = {"loss": loss, "logits": logits[..., :128],
            "gate_1": found["head_gate"][1],
            "attention_1": found["attention_layers"][1][..., :128],
            "attention_4": found["attention_layers"][4][..., :128]}
    for i in (0, 1):
        want["q_%d" % i] = found["core_q"][i][:, :, :1]
        want["k_%d" % i] = found["core_k"][i][:, :, :1]
    for fetch, (layer, role, _) in mod.GRADIENTS.items():
        grad = grads[names.index("layer_%d.%s" % (layer, role))]
        want[fetch] = grad[..., :128, :128]
    for name, ref in want.items():
        ref = np.asarray(ref)
        err = np.abs(np.asarray(got[name]).reshape(ref.shape) - ref).max() \
            / np.abs(ref).max()
        assert err < 2e-5, (name, err)
    np.testing.assert_array_equal(np.asarray(got["expert_load"]),
                                  np.asarray(load))
    assert got["router_margin"].shape == (2, 64)
    assert (np.asarray(got["router_margin"])
            <= np.asarray(got["experts_margin"])).all()
    # the two rotary tables written out in the module are the builder's
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    pos = jnp.arange(64)
    for kind, layer in (("full_attention", 0), ("sliding_attention", 1)):
        g = c["geometry_layers"][layer]
        cos, _ = mod.rotary_table(cfg["rope_parameters"][kind],
                                  g["rotary_dim"], pos)
        freq = np.asarray(g["rope_inv_freq"], np.float32) \
            if g["rope_inv_freq"] is not None else np.float32(
                g["rope_theta"]) ** (-np.arange(0, g["rotary_dim"], 2,
                                                dtype=np.float32)
                                     / g["rotary_dim"])
        ref = np.cos(np.arange(64, dtype=np.float32)[:, None] * freq) \
            * g["rope_table_scale"]
        assert np.abs(np.asarray(cos) - ref).max() < 2e-5, kind


def test_the_new_readers_on_the_programs_counters(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)

    def reader(name):
        return manifest.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))

    windowed, gated = reader("windowed_layer_share"), \
        reader("per_head_gate_layer_share")
    assert windowed.read({}) is None and gated.read({}) is None
    built = fresh.counter("ptpu_causal_lm_layers_total", "")
    # a program from before the geometry by layer: no `window` label, and
    # Qwen3-Next's gate a channel reads "true"
    built.inc(4, mixer="attention", ffn="experts", gate="true")
    assert windowed.read({}) is None and gated.read({}) is None
    built.inc(2, mixer="attention", ffn="experts", gate="per_head",
              window="0", heads="12")
    assert windowed.read({}) == pytest.approx(0.0)
    assert gated.read({}) == pytest.approx(100 * 2 / 6.0)
    built.inc(3, mixer="attention", ffn="experts", gate="per_head",
              window="512", heads="18")
    assert windowed.read({}) == pytest.approx(100 * 3 / 9.0)
    assert gated.read({}) == pytest.approx(100 * 5 / 9.0)
    # the flash kernels' share of the peak from the module's own count, a
    # LAYER at a time
    cell = _cell()
    share = reader("flash_roofline_share")
    ops = cell.config_module.flash_kernel_ops(cell.config, cell.traffic)
    record = {"cell": cell, "peak": {"bf16_flops_per_s": 197e12},
              "window": {"attempted": 1}, "trace": {"busy_s": 1.0, "top_ops": [
                  ["ptpu_flash_fwd.1 custom-call tpu_custom_call", 0.004],
                  ["ptpu_flash_bwd_dkdv.1 custom-call tpu_custom_call",
                   0.006],
                  ["ptpu_flash_bwd_dq.1 custom-call tpu_custom_call",
                   0.005]]}}
    got = share.read(record)
    if got is not None:
        assert 0 < got < 100
    assert sum(ops.values()) == 18 * 128 * (
        2 * 12 * (4096 * 4097 // 2) + 3 * 18 * 1966336)


def test_the_manifest_promises_the_new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert "laguna_s_2_1" in [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 14          # the fifteenth
    assert sum(w["chips"] == 4 for w in bench["workloads"][:15]) == 1
    new = {"windowed_layer_share", "per_head_gate_layer_share"}
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"]
               if m["name"] in new)
    listed = {m["name"] for key in ("end_to_end", "per_layer")
              for m in bench[key] if CELL in m.get("workloads", ())}
    assert listed == new | {
        "tokens_per_s_per_chip", "step_mfu", "pallas_ms_per_step",
        "flash_fwd_ms_per_step", "flash_bwd_dkdv_ms_per_step",
        "flash_bwd_dq_ms_per_step", "flash_roofline_share",
        "softmax_xent_ms_per_step", "embedding_grad_ms_per_step",
        "embedding_grad_roofline_share", "expert_matmul_ms_per_step",
        "expert_matmul_roofline_share"}
    # the lists the cell joined have it behind what they had (a later
    # cell comes behind it)
    for key in ("end_to_end", "per_layer"):
        for m in bench[key]:
            if CELL in m.get("workloads", ()) and m["name"] not in new:
                at = m["workloads"].index(CELL)
                assert m["workloads"][at - 1] == \
                    "nemotron_3_super_120b_a12b_train_t4096", m["name"]
    assert bench["workloads"][14]["traffic"] == "train_1seq_t4096"
    assert len(bench["workloads"][14]["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "laguna_s_2_1")
    assert entry["reduced"] == list(HELD) or set(entry["reduced"]) \
        == set(HELD)
    assert len(entry["why"]) <= 200
