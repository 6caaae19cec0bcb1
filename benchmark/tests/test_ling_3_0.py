"""The configuration ling_3_0_flash and the cell ling_3_0_flash_train_t4096,
on the CPU: the cell's path rehearsed on a tiny Ling-shaped configuration of
this directory's own (tests/tiny_ling_3_0: chip 1 of 4, a whole group of
experts held), every mutant of tests/mutant_ling_3_0.py refused, the
operations count at the published sizes against a hand count, the blocked
reference against the plain one, the new readers on a recorded `top_ops` and
on the program's counters, and what the manifest promises of the new
entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ling_3_0.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_ling_3_0", "manifest.json")
TINY_CELL = "tiny_ling_3_0_t64"
CELL = "ling_3_0_flash_train_t4096"
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
# the numbers of architectures.jsonl's `config` of Ling-3.0-flash (the lists
# of clamps are held whole below): every key of it is in the configuration's
# file, and only the seven counts of the cut differ
CATALOG = {
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 512, "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "up_proj_norm": False, "use_bias": False, "use_kda_lora": False,
    "use_mla_nope": False, "use_nGPT": False, "use_qk_norm": True,
    "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False,
    "vocab_size": 157184, "model_type": "bailing_hybrid",
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
HELD = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
        "num_attention_heads": 8, "num_key_value_heads": 8, "num_experts": 8,
        "vocab_size": 39296, "num_nextn_predict_layers": 0}
MOSAIC = " custom-call tpu_custom_call"
MUTANTS = ["scalar_decay", "decay_bf16", "beta_off", "conv_silu_off",
           "kda_gate_off", "head_norm_after_gate", "no_group_limit",
           "group_by_top1", "mla_gate_off", "rotary_off"]


def _run(script, *extra, seed=5, seconds=0.3, check=True):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    if not check:
        return proc
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
    assert "2304 of 2304 assignments counted" in line   # 6 x 3 x 128
    assert "the 4 held experts computed" in line
    # a CPU run reports counts and no device number: the two shares of
    # layers are the program's counters', read with or without a chip
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "kda_layer_share",
         "group_limited_router_layer_share"} if trace else set())
    if trace:
        assert out["metrics"]["kda_layer_share"]["value"] \
            == pytest.approx(600 / 7)
        assert out["metrics"]["group_limited_router_layer_share"]["value"] \
            == 100.0


@pytest.mark.parametrize("mutant", MUTANTS)
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_ling_3_0.py"), mutant)
    assert out["correct"] is False and out["failed"] == 0
    assert verdicts.pop("reference") is False
    verdicts.pop("loss_fell")
    assert all(verdicts.values())


def test_a_gate_without_its_bound_is_not_finite():
    """-softplus(z) for kda_lower_bound x sigmoid(z): a decay past -5.9 a
    token, and the chunked form answers with NaN, which is under no
    tolerance and fails `finite` besides."""
    out, verdicts, _ = _run(("tests", "mutant_ling_3_0.py"),
                            "gate_unbounded")
    assert out["correct"] is False
    assert verdicts["reference"] is False and verdicts["finite"] is False


def test_latent_layers_elsewhere_are_refused_by_the_reference():
    """`latent_every_4th` builds other layers than the configuration says:
    the reference, which reads the pattern from the configuration's own
    keys, meets parameters of other shapes and the run ends in its error."""
    proc = _run(("tests", "mutant_ling_3_0.py"), "latent_every_4th",
                check=False)
    assert proc.returncode != 0
    assert '"correct": true' not in proc.stdout
    assert "Error" in proc.stderr


@pytest.mark.parametrize("weights,correct", [("bf16", True), ("fp8", False)])
def test_the_reference_in_the_precision_below_is_refused(weights, correct):
    """bf16 is the precision the configuration states and stays correct;
    float8 e4m3 weights, the nearest below, fail a tolerance."""
    out, verdicts, _ = _run(("tests", "mutant_ling_3_0.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program without fluid.layers.kda_delta_rule (the parent of the
    PR that added it) `build` raises before anything is built: the driver
    sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(fluid.layers, "kda_delta_rule")
    with pytest.raises(NotImplementedError, match="kda_delta_rule"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """Seven layers at the published widths, 8 of 32 heads, T=4096, a
    token's forward multiply-adds. A KDA mixer: 2560 x (5 x 1024 + 8 + 1024)
    = 15.75e6 of projections, 4 x 3 x 1024 = 0.01e6 of convolutions, 8 x 3 x
    128 x 128 = 0.39e6 of recurrence: six of them 96.93e6. The latent layer:
    2560 x 1536 + 2560 x 576 + 512 x 2048 + 2560 x 8 + 1024 x 2560 = 9.10e6
    and 8,390,656 pairs / 4096 x 8 x (192 + 128) = 5.24e6. The dense FFN 3 x
    2560 x 6144 = 47.19e6. An expert layer's router 2560 x 512 = 1.31e6,
    held experts 8 x 8 / 512 x 3 x 2560 x 768 = 0.74e6, shared expert 5.90e6:
    six of them 47.68e6. Head 2560 x 39296 = 100.60e6. Twice the sum, three
    passes: 1840.4e6."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    hand = {"kda_projections": 94.49e6, "kda_convolutions": 0.07e6,
            "kda_rule": 2.36e6, "latent_projections": 9.10e6,
            "latent_attention": 5.24e6, "dense_ffn": 47.19e6,
            "router": 7.86e6, "experts": 4.42e6, "shared_expert": 35.39e6,
            "head": 100.60e6}
    assert {k: round(v / 1e6, 1) for k, v in macs.items()} \
        == {k: round(v / 1e6, 1) for k, v in hand.items()}
    assert abs(mod.ops_per_sample(cfg, traffic) - 1840.4e6) < 0.1e6
    total = sum(macs.values())
    share = {k: round(100 * sum(v for n, v in macs.items()
                                if n.startswith(k)) / total)
             for k in ("kda", "latent", "head", "dense")}
    assert share == {"kda": 32, "latent": 5, "head": 33, "dense": 15}
    assert round(100 * (macs["router"] + macs["experts"]
                        + macs["shared_expert"]) / total) == 16
    assert mod.samples_per_step(cfg, traffic) == 4096
    # the flash kernels of the one latent layer: 2 x (192 + 128) forward, 2
    # x (192 + 128 + 128 + 192) and 2 x (192 + 128 + 192) backward, a pair
    # and query head, 8 heads
    pairs = 8 * (4096 * 4097 // 2) * traffic["batch"]
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 640 * pairs, "ptpu_flash_bwd_dkdv": 1280 * pairs,
        "ptpu_flash_bwd_dq": 1024 * pairs}
    # the KDA kernels, by what they are given at chunks of 64: a tile is a
    # (sequence, head, chunk) of six layers; operands qe, kd, w, u [64, 128]
    # and m [64, 64] bf16 and erow [128] float32 are 74,240 bytes, o and dO
    # 16,384, a state 32,768; a [64, 128] x [128, 128] product is 2,097,152
    # operations and a [64, 64] x [64, 128] 1,048,576
    tiles = 6 * traffic["batch"] * 8 * 64
    assert mod.KDA_KERNELS == ("ptpu_kda_fwd", "ptpu_kda_bwd")
    assert mod.kda_kernel_ops(cfg, traffic, 64) == {
        "ptpu_kda_fwd": [
            (7340032 * tiles, (74240 + 16384) * tiles),
            (4194304 * tiles, (3 * 16384 + 512 + 32768) * tiles)],
        "ptpu_kda_bwd": [
            (16777216 * tiles, (2 * 74240 + 32768 + 16384) * tiles)]}
    # more than the recurrence's least, which ops_per_sample counts
    assert (7340032 + 4194304 + 16777216) * tiles \
        > 18 * 128 * 128 * 8 * 6 * 4096 * traffic["batch"]


def test_configuration_keeps_every_published_number():
    cell = _cell()
    cfg = cell.config
    if os.path.exists(CATALOG_FILE):
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f]
        catalog = next(r for r in rows if r["name"] == "Ling-3.0-flash")
        assert catalog["config"] == CATALOG
        assert cfg["source"] == catalog["source_url"]
    differs = {k for k, v in CATALOG.items() if cfg.get(k, "absent") != v}
    assert differs == set(HELD) == set(cfg["reduced"])
    assert {k: cfg[k] for k in HELD} == HELD
    assert {k: cfg["share"]["published"][k] for k in HELD} \
        == {k: CATALOG[k] for k in HELD}
    assert (cfg["share"]["chips"], cfg["share"]["chip"]) == (64, 0)
    assert CATALOG["num_experts"] == 64 * HELD["num_experts"]
    assert CATALOG["vocab_size"] == 4 * HELD["vocab_size"]
    assert CATALOG["num_attention_heads"] == 4 * HELD["num_attention_heads"]
    assert cfg["layer_indices"] == [1, 2, 3, 4, 5, 6, 7]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic"}
    assert set(cfg["assumed"]) >= {
        "adam", "learning_rate", "clip_norm", "auxiliary_losses",
        "initialisation", "precision", "multi_token_prediction",
        "decay_gate", "qk_norm", "output_gate", "head_norm",
        "layer_group_size", "group_limit", "decay_start", "expert_bias",
        "data"}
    assert set(cfg["measured"]) >= {"parameters", "compiled_gib"}
    assert set(cfg["reference"]["tolerance"]) == {
        "loss", "decay", "kda_out", "kda_ctx", "logits", "logits_mean",
        "queries_keys", "latent_ctx", "state"}
    assert (cfg["reference"]["router_margin"],
            cfg["reference"]["group_margin"]) == (0.05, 0.02)
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (512, 8, 0)
    assert c["mixer_layers"] == ["kda"] * 4 + ["attention"] + ["kda"] * 2
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 6
    assert c["group_limited"] and (c["n_group"], c["topk_group"]) == (8, 4)
    assert (c["rotary_dim"], c["intermediate_size"],
            c["shared_expert_intermediate_size"]) == (64, 768, 768)


def test_the_programs_own_parameter_count():
    """The program built at the published widths (no array is made): the
    count `measured.parameters` states, part by part as ISSUE 71's table
    has it."""
    import paddle_tpu as fluid
    cell = _cell()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}

    def part(prefix, roles):
        return sum(sizes[prefix + r] for r in roles)
    kda = ("wq", "conv_q", "wk", "conv_k", "wv", "conv_v", "wf", "dt_bias",
           "a_log", "wbeta", "o_norm", "wg", "wo")
    assert part("layer_0.", kda) == 15762568          # 15.76 M
    assert part("layer_4.", ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wg",
                             "wo")) == 9097728        # 9.10 M
    experts = ("experts.router", "experts.expert_bias", "experts.w_gate",
               "experts.w_up", "experts.w_down", "shared_expert.w_gate",
               "shared_expert.w_up", "shared_expert.w_down")
    assert part("layer_1.", experts) == 54395392      # 54.40 M
    assert part("layer_0.", ("w_gate", "w_up", "w_down")) == 47185920
    assert sizes["embedding"] + sizes["head"] == 201195520
    total = sum(sizes.values())
    assert total == cell.config["measured"]["parameters"]["count"]
    assert abs(total - 678.5e6) < 0.2e6


def test_manifest_holds_the_new_entries():
    """A prefix check: the cell and its configuration are where this PR put
    them (seventeenth and fifteenth), whatever later PRs append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["configs"]][14] == "ling_3_0_flash"
    assert m["configs"][14]["reduced"] == _cell().config["reduced"]
    assert m["workloads"][16] == dict(
        m["workloads"][16], name=CELL, config="ling_3_0_flash",
        traffic="train_1seq_t4096", chips=1)
    assert len(m["workloads"][16]["why"]) <= 200
    assert len(m["configs"][14]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"][:17]) == 1
    reports = {e["name"] for key in ("end_to_end", "per_layer")
               for e in m[key] if CELL in e.get("workloads", [CELL])}
    assert reports >= {
        "tokens_per_s_per_chip", "mfu", "peak_hbm_gib", "setup_s",
        "pallas_ms_per_step", "step_mfu", "flash_fwd_ms_per_step",
        "flash_bwd_dkdv_ms_per_step", "flash_bwd_dq_ms_per_step",
        "softmax_xent_ms_per_step", "flash_roofline_share",
        "expert_matmul_ms_per_step", "expert_matmul_roofline_share",
        "embedding_grad_ms_per_step", "embedding_grad_roofline_share",
        "moe_ffn_ms_per_step", "moe_routing_ms_per_step",
        "named_device_share", "kda_ms_per_step", "kda_roofline_share",
        "kda_layer_share", "group_limited_router_layer_share"}
    assert not reports & {"block_diffusion_layer_share", "ffn_rows_per_token",
                          "gated_delta_ms_per_step", "layer_norm_ms_per_step"}
    new = {e["name"]: e for e in m["per_layer"]}
    for name, unit, better, source, layer in (
            ("kda_ms_per_step", "ms", "lower", "device_trace", "kernels"),
            ("kda_roofline_share", "%", "higher", "device_trace", "kernels"),
            ("kda_layer_share", "%", "higher", "program_counter",
             "program build and lowering"),
            ("group_limited_router_layer_share", "%", "higher",
             "program_counter", "program build and lowering")):
        assert new[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tokens_per_s_per_chip",
            "workloads": [CELL]}


def test_blocked_reference_equals_the_plain_one():
    """benchmark/configs/ling_3_0.py:reference cuts the arithmetic of
    paddle_tpu/models/causal_lm_reference.py into blocks (the recurrence
    under a checkpoint a block of tokens, a sequence and a query head, an
    expert, rows of the head) and changes none of it."""
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
    mod.HEAD_ROWS, mod.SCAN_BLOCK = 16, 16  # 8 blocks of rows, 4 of tokens
    got = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))(params,
                                                                   batch)
    found = {}
    loss, (logits, load) = plain.loss_fn(cfg, params, batch["ids"],
                                         batch["pos"], batch["labels"],
                                         found=found)
    assert checks.normalised_error(got["loss"], loss) < 1e-6
    assert checks.normalised_error(
        got["logits"], logits[:, :, :mod.PROBE_COLUMNS]) < 1e-5
    assert checks.normalised_error(got["decay"],
                                   found["kda_g"][:, :, :1]) < 1e-6
    assert checks.normalised_error(got["kda_out"],
                                   found["kda_out"][:, :, :1]) < 1e-5
    assert checks.normalised_error(got["kda_ctx"],
                                   found["kda_ctx"][:, :, 0]) < 1e-5
    assert checks.normalised_error(
        got["state"], found["states"][-1][..., :mod.PROBE_COLUMNS]) < 1e-5
    np.testing.assert_array_equal(got["expert_load"], load)
    assert got["expert_load"].shape == (16,)
    for name in ("router_margin", "group_margin"):
        margin = np.asarray(got[name])
        assert margin.shape == (traffic["batch"], traffic["seq_len"])
        assert (margin >= 0).all() and np.isfinite(margin).any()


def test_the_margin_of_a_router_that_chooses_groups_first():
    """Two margins a token: the held group's (how far group 0 is from
    leaving or entering the kept groups) and the held set's on what the
    kept groups leave. A token whose kept groups exclude the held experts'
    group has no held assignment to lose: its set's margin is 1, as far as
    one goes, and its group's says how far the group is from coming in."""
    import jax.numpy as jnp
    mod = _cell().config_module
    c = dict(n_group=4, topk_group=2, num_experts_per_tok=2, first_expert=0,
             experts_held=2, num_experts=8)
    scores = jnp.asarray([
        # groups 0.9+0.8, 0.5+0.4, 0.3+0.2, 0.2+0.1: group 0 far inside
        [0.9, 0.8, 0.5, 0.4, 0.3, 0.2, 0.2, 0.1],
        # group 0 level with the third group at the cut
        [0.5, 0.4, 0.9, 0.8, 0.5, 0.4, 0.2, 0.1],
        # group 0 far out of the kept two
        [0.1, 0.1, 0.9, 0.8, 0.7, 0.6, 0.2, 0.1]], jnp.float32)
    groups, held = (np.asarray(m) for m in mod._router_margin(scores, c))
    assert groups[0] == pytest.approx((1.7 - 0.5) / 1.7)
    assert groups[1] == 0.0
    assert groups[2] == pytest.approx((1.3 - 0.2) / 1.3)
    # token 0: held experts 0 and 1 are the top 2, the third best 0.5
    assert held[0] == pytest.approx(1 - 0.5 / 0.8, rel=1e-5)
    assert held[2] == 1.0


# --- the new readers on a recorded top_ops -------------------------------------

TOP_OPS = [
    ["fusion.85 fusion kOutput", 0.5],
    ["ptpu_kda_fwd.3" + MOSAIC, 0.012],
    ["ptpu_kda_fwd.4" + MOSAIC, 0.012],
    ["ptpu_kda_bwd.1" + MOSAIC, 0.04],
    ["ptpu_flash_fwd" + MOSAIC, 0.005],
    # not the kernels': a transform's wrapper, another instruction, the
    # scalar-decay rule's kernels
    ["jvp_ptpu_kda_fwd_.2" + MOSAIC, 0.25],
    ["ptpu_kda_fwd.9 fusion kLoop", 0.25],
    ["ptpu_gated_delta_fwd.1" + MOSAIC, 0.25]]


def _record(cell, top_ops=TOP_OPS, steps=8):
    trace = None if top_ops is None else {
        "busy_s": 4.0, "top_ops": top_ops, "category_s": {}}
    return {"trace": trace, "window": {"attempted": steps}, "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _reader(name):
    from benchmark import manifest
    return manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_kda_readers_on_a_recorded_trace(monkeypatch):
    from paddle_tpu.observability.registry import REGISTRY
    ms, share = _reader("kda_ms_per_step"), _reader("kda_roofline_share")
    cell = _cell()
    assert ms.read(_record(cell)) == pytest.approx(1e3 * 0.064 / 8)
    # the chunk is the program's to say, by its counter's kind="kda"
    # samples: none lowered on the kernel path, no share; the scalar-decay
    # rule's chunk is not this one's
    counter = REGISTRY.counter(share.COUNTER, "")
    monkeypatch.setattr(counter, "_values", {})
    counter.inc(kind="kda", chunk="32", path="scan")
    counter.inc(kind="gated_delta", chunk="128", path="kernel")
    assert share.lowered_chunk() is None
    assert share.read(_record(cell)) is None
    counter.inc(6, kind="kda", chunk="64", sub_block="16", path="kernel")
    assert share.lowered_chunk() == 64
    # the least time: every call of both kernels is bound by its bytes
    tiles = 6 * cell.traffic["batch"] * 8 * 64
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
            name="qwen3_next_80b_a3b_train_t4096"))) is None
    counter.inc(kind="kda", chunk="128", path="kernel")
    assert share.read(_record(cell)) is None


def test_the_layer_shares_read_the_programs_counter(monkeypatch):
    from paddle_tpu.observability.registry import REGISTRY
    kda, limited = (_reader("kda_layer_share"),
                    _reader("group_limited_router_layer_share"))
    counter = REGISTRY.counter("ptpu_causal_lm_layers_total", "")
    monkeypatch.setattr(counter, "_values", {})
    # a program from before the mixer: nothing to read, no exception
    assert kda.read({}) is None and limited.read({}) is None
    counter.inc(4, mixer="attention", ffn="experts")
    assert kda.read({}) is None and limited.read({}) is None
    counter.inc(1, mixer="kda", ffn="dense")
    counter.inc(5, mixer="kda", ffn="experts", groups="8", kept_groups="4")
    counter.inc(1, mixer="attention", ffn="experts", groups="8",
                kept_groups="4")
    assert kda.read({}) == pytest.approx(100 * 6 / 11)
    assert limited.read({}) == pytest.approx(100 * 6 / 10)
