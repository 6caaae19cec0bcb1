"""The configuration sdar_30b_a3b_chat and the cell
sdar_30b_a3b_chat_train_t4096, on the CPU: the cell's path rehearsed on a
tiny SDAR-shaped configuration of this directory's own (tests/tiny_sdar:
four layers at toy widths under the block-diffusion objective, 8 query
heads on 2, blocks of 4 at T = 64, 2 of 16 experts held under top-4), every
mutant of tests/mutant_sdar.py refused, the operations count at the
published sizes against a hand count, the program's parameters against
ISSUE 66's arithmetic, the blocked reference against the plain one, the new
readers on the program's counters, and what the manifest promises of the new
entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sdar.py -q -p no:cacheprovider
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
TINY = os.path.join(HERE, "tiny_sdar", "manifest.json")
TINY_CELL = "tiny_sdar_t64"
CELL = "sdar_30b_a3b_chat_train_t4096"
# architectures.jsonl of the model-configs guide, `config` of
# SDAR-30B-A3B-Chat: every key of it is in the configuration's file, and
# only the three of the cut differ
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
HELD = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
COMPARED = ("loss", "logits", "logits_mean", "q_0", "k_0", "attention_0",
            "attention_3", "routed", "state", "wq_3_grad", "wk_3_grad",
            "wv_0_grad", "expert_gate_2_grad", "mask_row_grad")


def _mutants():
    import mutant_sdar as mutants
    return [name for name in mutants.HAVE_TO_FAIL
            if not name.startswith("reference_")]


def _run(script, *extra, seed=5, seconds=0.3):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", *script)]
        + list(extra) + ["--manifest", TINY, "--workload", TINY_CELL,
                         "--rehearse", "--seed", str(seed), "--seconds",
                         str(seconds)],
        # the flash kernels in the interpreter at 128 rows: what a TPU runs
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
    # a CPU run reports counts and no device number: four layers of four
    # under the mask, 1.75 FFN rows a token a layer
    assert set(out["metrics"]) == (
        {"compile_requests", "cache_hit_share", "block_diffusion_layer_share",
         "ffn_rows_per_token"} if trace else set())
    if trace:
        assert out["metrics"]["block_diffusion_layer_share"]["value"] \
            == pytest.approx(100.0)
        assert out["metrics"]["ffn_rows_per_token"]["value"] \
            == pytest.approx(1.75)


@pytest.mark.parametrize("mutant", _mutants())
def test_a_broken_mechanism_is_not_correct(mutant):
    out, verdicts, _ = _run(("tests", "mutant_sdar.py"), mutant)
    assert out["correct"] is False
    # a wrong count of assignments (top_k_7) is not dropless either
    if mutant == "top_k_7":
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
    out, verdicts, _ = _run(("tests", "mutant_sdar.py"),
                            "reference_%s_weights" % weights)
    assert out["correct"] is correct
    assert verdicts["reference"] is correct


def test_the_parent_program_is_refused_at_build(monkeypatch):
    """On a program whose causal_lm has no objective but next-token (the
    parent of the PR that added it) `build` raises before anything is
    built, by name: the driver sees the parent fail cleanly and soon."""
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    cell = _cell(TINY, TINY_CELL)
    monkeypatch.delattr(causal_lm, "_objective")
    with pytest.raises(NotImplementedError, match="no training objective"):
        cell.config_module.build(fluid, cell.config, cell.traffic)


def test_operations_against_the_hand_count():
    """ISSUE 66's arithmetic at T = 4096: 334 M forward multiply-adds a data
    token, attention's projections 43 %, its core 35 %, the held experts 10
    %, the head 12 %, the router under 1 %; the kernels' counts over the
    visible pairs of both copies."""
    cell = _cell()
    mod, cfg, traffic = cell.config_module, cell.config, cell.traffic
    macs = mod.forward_macs(cfg, traffic)
    qkv, wo = 2048 * 128 * (32 + 2 * 4), 4096 * 2048
    assert macs["attention_projections"] == 4 * 2 * qkv + 7 * wo
    t = 4096
    assert mod.visible_pairs(t, 4) == {"noised": t * (t + 4) // 2,
                                       "clean": t * (t + 4) // 2}
    assert sum(mod.visible_pairs(t, 4).values()) == t * t + 4 * t == 16793600
    # by rows: the mask written out at a small T agrees with the formula
    small = np.asarray(mod.mask(32, 4))
    assert small[:32].sum() == mod.visible_pairs(32, 4)["noised"]
    assert small[32:].sum() == mod.visible_pairs(32, 4)["clean"]
    assert macs["attention"] == pytest.approx(
        3.5 * (t + 4) * 32 * 256)
    assert macs["router"] == 7 * 2048 * 128
    assert macs["routed_experts"] == pytest.approx(7 * 1.0 * 3 * 2048 * 768)
    assert macs["head"] == 2048 * 18992
    total = sum(macs.values())
    assert total == pytest.approx(333.9e6, rel=1e-3)
    share = {k: round(100.0 * v / total, 1) for k, v in macs.items()}
    assert share == {"attention_projections": 42.7, "attention": 35.2,
                     "routed_experts": 9.9, "head": 11.6, "router": 0.5}
    assert mod.ops_per_sample(cfg, traffic) == pytest.approx(6 * total)
    assert mod.samples_per_step(cfg, traffic) == 4096
    # 8.2 TFLOP a step
    assert 6 * total * 4096 == pytest.approx(8.2e12, rel=3e-3)
    # three matmuls an expert of [2048 x 768], three passes: a load of 512
    # rows on each of the 16 held experts in each of 3.5 layers' worth
    load = np.zeros((128,), np.int64)
    load[:16] = 1792
    load[16:] = 7
    assert mod.expert_matmul_ops(cfg, traffic, load) \
        == 3 * 3 * 2 * 2048 * 768 * 16 * 1792
    assert mod.expert_matmul_ops(cfg, traffic, np.stack([load, load])) \
        == 2 * mod.expert_matmul_ops(cfg, traffic, load)
    pairs = 4 * 32 * (t * t + 4 * t)
    assert mod.flash_kernel_ops(cfg, traffic) == {
        "ptpu_flash_fwd": 4 * 128 * pairs,
        "ptpu_flash_bwd_dkdv": 8 * 128 * pairs,
        "ptpu_flash_bwd_dq": 6 * 128 * pairs}
    assert mod.embedding_grad_bytes(cfg, traffic) == 4 * 2048 * (18992 + 8192)
    # the kernels' block visits at tiles of 512: 80 a head for 64.06
    # blocks' worth of visible pairs
    from paddle_tpu.ops import pallas_kernels as pk
    visits = sum(int(end) - int(first) for i in range(16)
                 for first, end in pk._bd_blocks(i, 512, 512, (4, t)))
    assert visits == 80
    assert (t * t + 4 * t) / 512.0 ** 2 == pytest.approx(64.06, abs=5e-3)
    back = sum(int(end) - int(first) for i in range(16)
               for first, end in pk._bd_blocks(i, 512, 512, (4, t), True))
    assert back == 80


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
    assert CATALOG["num_experts"] == 8 * HELD["num_experts"]
    assert set(cfg["reduced_why"]) >= set(HELD) | {"arithmetic"}
    assert set(cfg["assumed"]) >= {
        "block_length", "noise_schedule", "loss_weight", "no_shift",
        "normalisation", "mask_token_id", "rows", "router", "qk_norm",
        "rotary", "learning_rate", "clip_norm", "adam", "initialisation",
        "precision"}
    assert all(isinstance(v, str) and v for v in cfg["assumed"].values())
    for key in ("deployment", "distorts", "measured"):
        assert isinstance(cfg[key], str) and cfg[key]
    assert set(cfg["reference"]["tolerance"]) == set(COMPARED)
    assert cell.traffic["seq_len"] == 4096 and cell.chips == 1
    assert cell.traffic["batch"] == 1
    assert (cfg["objective"], cfg["block_length"], cfg["mask_token_id"],
            cfg["noise_eps"]) == ("block_diffusion", 4, 18991, 1e-3)
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    assert c["mixer_layers"] == ["attention"] * 4
    assert c["ffn_layers"] == ["experts"] * 4
    assert c["window_layers"] == [None] * 4
    assert (c["num_experts"], c["experts_held"], c["first_expert"],
            c["num_experts_per_tok"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["rotary_dim"], c["qk_norm"],
            c["norm_topk_prob"], c["shared_expert_intermediate_size"]) \
        == (128, 16, 0, 8, 768, 32, 4, 128, 128, "head", True, 0)
    assert c["block_diffusion"] == {"block_length": 4,
                                    "mask_token_id": 18991,
                                    "noise_eps": 1e-3}


def test_the_program_counts_the_published_parameters():
    """456,346,624 trained parameters, by layer as ISSUE 66 counts them,
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
        "layer_0": 94638336, "layer_1": 94638336, "layer_2": 94638336,
        "layer_3": 94638336, "embedding": 38895616, "head": 38895616,
        "final_norm": 2048}
    assert sum(by_layer.values()) == 456346624
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes["layer_0.wq"] == (2048, 4096)
    assert shapes["layer_0.wk"] == (2048, 512)
    assert shapes["layer_0.wo"] == (4096, 2048)
    assert shapes["layer_0.q_norm"] == (128,)
    assert shapes["layer_1.experts.router"] == (2048, 128)
    assert shapes["layer_1.experts.w_gate"] == (16, 2048, 768)
    # the whole model by the same per-layer arithmetic: 30.53 B
    whole = 48 * (19140864 + 128 * 4718592) + 2 * 151936 * 2048 + 2048
    assert round(whole / 1e9, 2) == 30.53
    block = main.global_block()
    types = [op.type for op in block.ops]
    assert types.count("moe_ffn") == 4
    assert types.count("fused_attention") == 4
    assert types.count("rotary_embedding") == 8
    assert types.count("lookup_table") == 1
    cores = [op for op in block.ops if op.type == "fused_attention"]
    assert all(op.attrs["block_diffusion"] == [4, 4096]
               and not op.attrs["causal"] for op in cores)
    assert [tuple(block.var(op.input("X")[0]).shape)[1] for op in block.ops
            if op.type == "moe_ffn"] == [8192, 8192, 8192, 4096]


def test_blocked_reference_is_the_plain_reference():
    """configs/sdar.py:reference against models/causal_lm_reference.py on
    random weights at the tiny sizes, in float32: the forward fetches, and
    the five gradients against jax.grad of the plain reference's loss."""
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

    def loss(p):
        return plain.block_diffusion_loss(
            cfg, p, batch["ids"], batch["noisy_ids"], batch["pos"],
            batch["loss_weight"])
    (value, (logits, load)), grads = jax.value_and_grad(
        loss, has_aux=True)(params)
    found = {}
    plain.block_diffusion_loss(cfg, params, batch["ids"], batch["noisy_ids"],
                               batch["pos"], batch["loss_weight"],
                               found=found)
    want = {"loss": value, "logits": logits[..., :128],
            "attention_0": found["attention_layers"][0][..., :128],
            "attention_3": found["attention_layers"][3][..., :128],
            "q_0": found["core_q"][0][:, :, :1],
            "k_0": found["core_k"][0][:, :, :1],
            "routed": found["routed"][..., :128],
            "state": found["state"][..., :128],
            "mask_row_grad": grads[0][cfg["mask_token_id"]][None]}
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
    assert got["experts_margin"].shape == (2, 128)
    # the mask written out in the module is the plain reference's and the
    # dense path's
    from paddle_tpu.parallel.ring_attention import block_diffusion_mask
    for t, length in ((16, 4), (24, 1), (8, 8)):
        assert (np.asarray(mod.mask(t, length))
                == np.asarray(plain.block_diffusion_mask(t, length))).all()
        assert (np.asarray(mod.mask(t, length))
                == np.asarray(block_diffusion_mask(length, t))).all()
    # the batch: masked positions alone carry a weight, 1 / t of their block
    weight, noisy = np.asarray(batch["loss_weight"]), \
        np.asarray(batch["noisy_ids"])
    assert ((noisy == cfg["mask_token_id"]) == (weight > 0)).all()
    assert (np.asarray(batch["ids"]) < cfg["mask_token_id"]).all()
    assert weight[weight > 0].min() >= 1.0


def test_the_new_readers_on_the_programs_counters(monkeypatch):
    from benchmark import manifest
    from paddle_tpu.observability import registry
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)

    def reader(name):
        return manifest.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))

    cell = _cell()
    record = {"cell": cell}
    masked, rows = reader("block_diffusion_layer_share"), \
        reader("ffn_rows_per_token")
    assert masked.read(record) is None and rows.read(record) is None
    built = fresh.counter("ptpu_causal_lm_layers_total", "")
    # a program from before the objective: no `mask` label, no rows
    built.inc(4, mixer="attention", ffn="experts", gate="false")
    assert masked.read(record) is None and rows.read(record) is None
    built.inc(4, mixer="attention", ffn="experts", gate="false",
              mask="block_diffusion", block_length="4")
    assert masked.read(record) == pytest.approx(50.0)
    counted = fresh.counter("ptpu_causal_lm_rows_total", "")
    for part, copy, n in (("attention", "noised", 4), ("attention", "clean",
                                                       4),
                          ("ffn", "noised", 4), ("ffn", "clean", 3),
                          ("head", "noised", 1)):
        counted.inc(n * 4096, part=part, copy=copy)
    assert rows.read(record) == pytest.approx(1.75)
    # the flash kernels' share of the peak from the module's own count
    share = reader("flash_roofline_share")
    ops = cell.config_module.flash_kernel_ops(cell.config, cell.traffic)
    record = {"cell": cell, "peak": {"bf16_flops_per_s": 197e12},
              "window": {"attempted": 1}, "trace": {"busy_s": 1.0, "top_ops": [
                  ["ptpu_flash_fwd.1 custom-call tpu_custom_call", 0.014],
                  ["ptpu_flash_bwd_dkdv.1 custom-call tpu_custom_call",
                   0.026],
                  ["ptpu_flash_bwd_dq.1 custom-call tpu_custom_call",
                   0.020]]}}
    got = share.read(record)
    if got is not None:
        assert 0 < got < 100
    assert sum(ops.values()) == 18 * 128 * 4 * 32 * (4096 * 4100)


def test_the_manifest_promises_the_new_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert "sdar_30b_a3b_chat" in [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 15          # the sixteenth
    assert sum(w["chips"] == 4 for w in bench["workloads"][:16]) == 1
    new = {"block_diffusion_layer_share", "ffn_rows_per_token"}
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
    # the lists the cell joined have it behind what they had
    for key in ("end_to_end", "per_layer"):
        for m in bench[key]:
            if CELL in m.get("workloads", ()) and m["name"] not in new:
                at = m["workloads"].index(CELL)
                assert m["workloads"][at - 1] == \
                    "laguna_s_2_1_train_t4096", m["name"]
    assert bench["workloads"][15]["traffic"] == "train_1seq_t4096"
    assert len(bench["workloads"][15]["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "sdar_30b_a3b_chat")
    assert set(entry["reduced"]) == set(HELD)
    assert len(entry["why"]) <= 200
