"""The configuration nemotron_3_super_120b_a12b and the cell
nemotron_3_super_120b_a12b_train_t4096, on the CPU: the cell's path
rehearsed on a tiny Nemotron-3-Super-shaped configuration of this
directory's own (tests/tiny_nemotron_3_super: seven one-branch layers at
toy widths, two Mamba groups, 2 of 16 experts held under top-3, so the row
buffer is the cut one), every mutant of tests/mutant_nemotron_3_super.py
refused, the operations count at the published sizes against a hand count,
the program's parameters against ISSUE 61's arithmetic, the blocked
reference against the plain one, the new readers on the program's counters,
and what the manifest promises of the new entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nemotron_3_super.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_nemotron_3_super", "manifest.json")
TINY_CELL = "tiny_nemotron_3_super_t64"
CELL = "nemotron_3_super_120b_a12b_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16: every key of it is in the
# configuration's file, and only the eight of the cut differ
PERIOD = "MEMEMEM*EME"
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
HELD = {"num_hidden_layers": 11, "mamba_num_heads": 16, "n_groups": 1,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "n_routed_experts": 8, "vocab_size": 16384,
        "num_nextn_predict_layers": 0}
COMPARED = ("loss", "logits", "logits_mean", "scan", "delta", "latent",
            "routed", "routed_out", "shared", "attention", "state",
            "latent_grad", "latent_down_grad", "w_up_grad", "a_log_grad",
            "dt_bias_grad", "d_grad")


def _mutants():
    import mutant_nemotron_3_super as mutants
    return [name for name in mutants.HAVE_TO_FAIL
            if not name.startswith("reference_")]


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        # the scan's, the flash and the grouped-matmul kernels in the
        # interpreter at T=64: what a TPU runs
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
    assert set(verdicts) == {"reference", "loss_fell", "dropless", "finite",
                             "no_compile_in_window", "placement"}
    for name in COMPARED:
        assert name + " off by" in line
    # a CPU run reports counts and no device number: three layers of seven
    # are Mamba-2 mixers and three LatentMoE, every one a single branch
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "ssd_layer_share",
         "latent_moe_layer_share", "single_branch_layer_share"}
        if trace else set())
    if trace:
        for name, value in (("ssd_layer_share", 300 / 7.0),
                            ("latent_moe_layer_share", 300 / 7.0),
                            ("single_branch_layer_share", 100.0)):
            assert out["metrics"][name]["value"] == pytest.approx(value)


@pytest.mark.parametrize("mutant", _mutants())
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_nemotron_3_super.py"), mutant)
    assert out["correct"] is False
    # a wrong count of assignments (top_k_21) is not dropless either; a
    # mechanism that takes the experts' weight off may also keep the loss
    # from falling in a third of a second
    if mutant == "top_k_21":
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
    out, verdicts, _ = _run(("tests", "mutant_nemotron_3_super.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no hybrid_override_pattern (the
    parent of the PR that added it) `build` raises before anything is
    built, by name: the driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "PATTERN")
    with pytest.raises(NotImplementedError, match="hybrid_override_pattern"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """ISSUE 61's arithmetic at T = 4096: 426 M forward multiply-adds a
    token, the shared expert 52 %, the head 16 %, the five mixers 16 %,
    router and latent projections 12 %, the held routed experts 2 %; the
    kernels' counts at what is held."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    assert macs["scan_projections"] == 5 * (4096 * 2320 + 1024 * 4096)
    assert macs["scan"] == 5 * 16 * 2 * 128 * 64
    assert macs["attention_projections"] == 2 * 4096 * (4 + 1) * 128
    assert macs["attention"] == pytest.approx(2048.5 * 4 * 2 * 128)
    assert macs["router_and_latent"] == 5 * (4096 * 512 + 2 * 4096 * 1024)
    assert macs["shared_expert"] == 5 * 2 * 4096 * 5376
    assert macs["routed_experts"] == pytest.approx(
        5 * (22 * 8 / 512.0) * 2 * 1024 * 2688)
    assert macs["head"] == 4096 * 16384
    total = sum(macs.values())
    assert total == pytest.approx(426.3e6, rel=1e-3)
    share = {k: round(100 * v / total) for k, v in macs.items()}
    assert (share["shared_expert"], share["head"], share["scan_projections"],
            share["router_and_latent"], share["routed_experts"]) \
        == (52, 16, 16, 12, 2)
    assert mod.ops_per_sample(cfg, traffic) == pytest.approx(6 * total)
    assert mod.samples_per_step(cfg, traffic) == 4096
    # two matmuls an expert of [1024 x 2688], three passes: a load of 176
    # rows on each of the 8 held experts in each of 5 layers
    load = np.zeros((512,), np.int64)
    load[:8] = 5 * 176
    load[8:] = 7
    assert mod.expert_matmul_ops(cfg, traffic, load) \
        == 3 * 2 * 2 * 1024 * 2688 * 8 * 5 * 176
    assert mod.expert_matmul_ops(cfg, traffic, np.stack([load, load])) \
        == 2 * mod.expert_matmul_ops(cfg, traffic, load)
    pairs = 4096 * 4097 // 2 * 4
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 4 * 128 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * pairs}
    assert mod.embedding_grad_bytes(cfg, traffic) == 4 * 4096 * 16384
    calls = mod.ssd_kernel_ops(cfg, traffic, 128)
    assert [len(calls[k]) for k in ("ptpu_ssd_fwd", "ptpu_ssd_bwd")] \
        == [10, 5]
    q = 64.5
    assert calls["ptpu_ssd_fwd"][0] == (
        2 * 4096 * (q * 128 + 16 * (q * 64 + 2 * 128 * 64)),
        4096 * (4 * 16 * 64 + 4 * 128 + 4 * 16))


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (64, 0)
    assert CATALOG["vocab_size"] == 8 * HELD["vocab_size"]
    assert CATALOG["mamba_num_heads"] == 8 * HELD["mamba_num_heads"]
    assert CATALOG["n_routed_experts"] == 64 * HELD["n_routed_experts"]
    assert CATALOG["hybrid_override_pattern"][:11] == PERIOD
    assert len(CATALOG["hybrid_override_pattern"]) == 88
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic", "distorts",
                                                    "measured"}
    assert set(cfg["assumed"]) >= {
        "w_in_columns", "group_norm", "positions", "router", "expert_bias",
        "scaling_factor", "latent", "module", "rescale_prenorm_residual",
        "dt_bias", "identities", "initialisation", "adam", "learning_rate",
        "clip_norm", "precision", "data", "recomputation"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    assert set(cfg["reference"]["tolerance"]) == set(COMPARED)
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    kinds = cell.config_module._kinds(c)
    assert kinds == [{"M": "mamba2", "E": "experts", "*": "attention"}[k]
                     for k in PERIOD]
    assert c["rope_theta"] is None and c["attention_scale"] is None
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_n_groups"], c["head_dim"]) \
        == (16, 64, 128, 4, 1, 128)
    assert (c["num_experts"], c["experts_held"], c["first_expert"],
            c["num_experts_per_tok"], c["moe_latent_size"],
            c["intermediate_size"], c["shared_expert_intermediate_size"]) \
        == (512, 8, 0, 22, 1024, 2688, 5376)


def test_the_program_counts_the_published_parameters():
    """700,865,520 parameters, by kind of layer as ISSUE 61 counts them,
    from the program's own variables at the published widths (no array is
    made): 700,862,960 of them trained, and five correction biases of 512
    that are held and not trained (the issue's sum has them in)."""
    import paddle_tpu as fluid
    cell = _cell()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    by_layer, held = {}, 0
    for p in main.global_block().all_parameters():
        key = p.name.split(".")[0]
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
        if not p.trainable:
            held += int(np.prod(p.shape))
    want = {"layer_%d" % i: {"M": 13708592, "E": 98570752, "*": 5246976}[k]
            for i, k in enumerate(PERIOD)}
    want.update(embedding=67108864, head=67108864, final_norm=4096)
    assert by_layer == want
    assert sum(by_layer.values()) == 700865520 and held == 5 * 512
    types = [op.type for op in main.global_block().ops]
    assert types.count("ssd_scan") == 5 and types.count("moe_ffn") == 5
    assert types.count("fused_attention") == 1
    assert "rotary_embedding" not in types


def test_blocked_reference_is_the_plain_reference():
    """configs/nemotron_3_super.py:reference against
    models/causal_lm_reference.py on random weights at the tiny sizes, in
    float32: the forward fetches, and the five gradients against jax.grad
    of the plain reference's whole loss (the same numbers: a parameter of
    the last layers reaches the loss through those layers alone)."""
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
        (np.ones(p.shape) if p.name.endswith(("norm", ".d"))
         else np.zeros(p.shape))
        + (0.1 if len(p.shape) == 1 else 0.06)
        * rng.standard_normal(p.shape), jnp.float32)
        for p in main.global_block().all_parameters()]
    for i, name in enumerate(names):
        if name.endswith(".a_log"):
            params[i] = jnp.log(1.0 + jnp.abs(params[i]) * 20)
        elif name.endswith(".dt_bias"):
            params[i] = params[i] - 3.0
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
            "scan": found["scan"][..., :128], "delta": found["delta"],
            "latent": found["latent"], "routed": found["routed"],
            "routed_out": found["routed_out"][..., :128],
            "shared": found["shared"][..., :128],
            "attention": found["attention"][..., :128],
            "state": found["layers"][-1][..., :128]}
    for fetch, (layer, role) in (("latent_down_grad", (6, "latent_down")),
                                 ("w_up_grad", (6, "experts.w_up")),
                                 ("a_log_grad", (5, "a_log")),
                                 ("dt_bias_grad", (5, "dt_bias")),
                                 ("d_grad", (5, "d"))):
        grad = grads[names.index("layer_%d.%s" % (layer, role))]
        want[fetch] = grad
    for name, ref in want.items():
        ref = np.asarray(ref)
        err = np.abs(np.asarray(got[name]).reshape(ref.shape) - ref).max() \
            / np.abs(ref).max()
        assert err < 2e-5, (name, err)
    np.testing.assert_array_equal(np.asarray(got["expert_load"]),
                                  np.asarray(load))
    # the gradient that reaches the last `E` layer's u, by what it sums to:
    # dL/dW_dn = N(state entering the layer)^T du (all 64 latent columns
    # are inside the probe at the tiny size)
    entering = plain.rms_norm(found["layers"][5],
                              params[names.index("layer_6.norm")], 1e-5)
    summed = np.asarray(entering).reshape(-1, 128).T \
        @ np.asarray(got["latent_grad"]).reshape(-1, 64)
    ref = np.asarray(grads[names.index("layer_6.latent_down")])
    assert np.abs(summed - ref).max() / np.abs(ref).max() < 2e-5
    assert got["router_margin"].shape == (2, 64)
    assert (np.asarray(got["router_margin"])
            <= np.asarray(got["last_margin"])).all()
    assert (np.asarray(got["router_margin"])
            <= np.asarray(got["experts_margin"])).all()


def test_the_new_readers_on_the_programs_counters(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)

    def reader(name):
        return manifest.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))

    latent, single = reader("latent_moe_layer_share"), \
        reader("single_branch_layer_share")
    assert latent.read({}) is None and single.read({}) is None
    built = fresh.counter("ptpu_causal_lm_layers_total", "")
    built.inc(4, mixer="attention", ffn="experts")  # a program from before
    assert latent.read({}) is None and single.read({}) is None
    built.inc(5, mixer="mamba2", ffn="none", branches="1", latent="0")
    built.inc(1, mixer="attention", ffn="none", branches="1", latent="0")
    assert latent.read({}) is None          # no latent layer yet
    assert single.read({}) == pytest.approx(100 * 6 / 10.0)
    built.inc(5, mixer="none", ffn="experts", branches="1", latent="1024")
    assert latent.read({}) == pytest.approx(100 * 5 / 15.0)
    built.inc(1, mixer="attention", ffn="experts", branches="2",
              latent="1024", module="mtp")
    assert latent.read({}) == pytest.approx(100 * 6 / 16.0)
    assert single.read({}) == pytest.approx(100 * 11 / 16.0)
    # the experts' share of the peak from the module's own count: two
    # matmuls an assignment, so the same time reads two thirds of what
    # configs/causal_lm.py's three would
    cell = _cell()
    share = reader("expert_matmul_roofline_share")
    load = np.zeros((3, 512), np.int64)
    load[:, :8] = 5 * 176
    record = {"cell": cell, "peak": {"bf16_flops_per_s": 197e12},
              "window": {"attempted": 3, "fetches": {"expert_load": load}},
              "trace": {"busy_s": 1.0, "top_ops": [
                  ["ptpu_expert_gmm_fwd.1 custom-call tpu_custom_call", 0.003],
                  ["ptpu_expert_gmm_drows.1 custom-call tpu_custom_call", 0.003],
                  ["ptpu_expert_gmm_dweights.1 custom-call tpu_custom_call",
                   0.003]]}}
    got = share.read(record)
    ops = 3 * 2 * 2 * 1024 * 2688 * 8 * 5 * 176
    assert got == pytest.approx(100 * ops / (0.003 * 197e12))
    assert 0 < got < 100


def test_the_manifest_promises_the_new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert "nemotron_3_super_120b_a12b" in [c["name"]
                                            for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 13          # the fourteenth
    assert sum(w["chips"] == 4 for w in bench["workloads"][:14]) == 1
    new = {"latent_moe_layer_share", "single_branch_layer_share"}
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
        "expert_matmul_roofline_share", "ssd_scan_ms_per_step",
        "ssd_scan_roofline_share", "ssd_layer_share"}
    # every list the cell joined has it last
    assert all(m["workloads"][-1] == CELL
               for key in ("end_to_end", "per_layer") for m in bench[key]
               if CELL in m.get("workloads", ()))
    assert bench["workloads"][13]["traffic"] == "train_1seq_t4096"
    assert len(bench["workloads"][13]["why"]) <= 200
