"""models/causal_lm.py under SDAR's block-diffusion objective (`model_type:
sdar_moe`, `objective: block_diffusion`; tiny widths, seeded weights): a
noised and a clean copy of every sequence side by side (2 T rows), attention
under the block-diffusion mask, a 1/t-weighted loss on the masked positions
alone, over a Qwen3-MoE-shaped layer (8 query heads on 2, a QK-norm a head,
top-4 of 16 experts renormalised). The Program against
models/causal_lm_reference.py: loss, logits, `ExpertLoad`, each layer's
attention output on both copies, q and k as the core reads them, and every
parameter's gradient against jax.grad; the objective's invariants (the clean
copy does not move when the noised ids do; with block_length = T the noised
copy is plain bidirectional attention and reads no clean row; with no
position masked the loss is 0); the share test (the partial FFN outputs of
all 8 expert ranks add up to the uncut reference's layer); what `resolve()`
reads of the family's keys and what it refuses under the objective; and a
config without the objective builds the program it built before."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_causal_lm_laguna import program_digest  # noqa: E402

# the published config's keys at toy sizes, and the objective's block
CFG = dict(
    model_type="sdar_moe", vocab_size=96, hidden_size=32,
    intermediate_size=64, num_hidden_layers=3, num_attention_heads=8,
    num_key_value_heads=2, head_dim=8, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=24, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], tie_word_embeddings=False, use_sliding_window=False,
    sliding_window=None, max_window_layers=3, rope_scaling=None,
    rope_theta=1000000, hidden_act="silu", max_position_embeddings=64,
    qk_norm="head", initializer_range=0.2, router_aux_loss_coef=0,
    router_z_loss_coef=0, objective="block_diffusion", block_length=4,
    mask_token_id=95, noise_eps=1e-3)
B, T = 2, 32
TOLERANCE = 2e-4        # float32 against float32, another order of sums
OFF_IDENTITY = ("norm",)
LAYER = ["input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
         "post_attention_norm", "experts.router", "experts.w_gate",
         "experts.w_up", "experts.w_down"]


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed(seed=0, cfg=CFG, masked=None):
    """The four feeds: clean ids over the data words (every word but the
    mask id), the noised ids and the weights of `block_diffusion_batch`."""
    ids = np.random.RandomState(seed).randint(
        0, cfg["mask_token_id"], (B, T)).astype("int64")
    noisy, weight = reference.block_diffusion_batch(
        jax.random.key(seed + 1), jnp.asarray(ids), cfg["block_length"],
        cfg["mask_token_id"], cfg["noise_eps"])
    noisy, weight = np.asarray(noisy), np.asarray(weight)
    if masked is False:
        noisy, weight = ids.copy(), np.zeros_like(weight)
    return {"ids": ids, "noisy_ids": noisy,
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "loss_weight": weight}


def _build(cfg=CFG, seq_len=T, train=True):
    """The training program, or (`train` false) its forward pass alone: no
    optimizer op, so that several runs read the same weights."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = (causal_lm.build_train if train
                              else causal_lm.causal_lm)(cfg, seq_len)
    return main, startup, dict(loss=loss, logits=logits, load=load)


PARAMETERS = [p.name for p in _build()[0].global_block().all_parameters()]


def _inside(block):
    cores = [op for op in block.ops if op.type == "fused_attention"]
    behind = [next(op for op in block.ops
                   if "layer_%d.wo" % i in op.input_arg_names)
              .output("Out")[0] for i in range(len(cores))]
    return {"attention": behind,
            "q": [core.input("Q")[0] for core in cores],
            "k": [core.input("K")[0] for core in cores]}


def _run(cfg=CFG, feeds=None, grads=False, seed=5):
    """(main, params, the weights the comparison used, the fetches) of the
    program on each of `feeds`: the training program's one step with every
    parameter's gradient, or the forward pass alone."""
    main, startup, out = _build(cfg, train=grads)
    block = main.global_block()
    params = block.all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(seed)
    inside = _inside(block)
    names = ["loss", "logits", "load"]
    fetch = [out[name] for name in names] + [
        block.var(v) for kind in sorted(inside) for v in inside[kind]] \
        + ([p.name + "@GRAD" for p in params] if grads else [])
    found = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in params:
            if p.name.endswith(OFF_IDENTITY):
                w = np.asarray(scope.get(p.name))
                scope.set(p.name, jnp.asarray(
                    w + 0.2 * rng.standard_normal(w.shape).astype("f")))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        for feed in feeds or [_feed()]:
            got = exe.run(main, feed=feed, fetch_list=fetch)
            one = dict(zip(names, got))
            at = len(names)
            for kind in sorted(inside):
                one[kind] = got[at:at + len(inside[kind])]
                at += len(inside[kind])
            one["grads"] = dict(zip((p.name for p in params), got[at:]))
            found.append(one)
    return main, params, weights, found


@pytest.fixture(scope="module")
def program():
    main, params, weights, found = _run(grads=True)
    return main, params, weights, found[0]


def _reference(cfg, weights, feed, found=None):
    feed = {k: jnp.asarray(v) for k, v in feed.items()}
    return reference.block_diffusion_loss(
        cfg, weights, feed["ids"], feed["noisy_ids"], feed["pos"],
        feed["loss_weight"], found=found)


@pytest.fixture(scope="module")
def want(program):
    _, params, weights, _ = program
    found = {}
    loss, (logits, load) = _reference(CFG, weights, _feed(), found)
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    grads = jax.jit(jax.grad(lambda p: reference.block_diffusion_loss(
        CFG, p, feed["ids"], feed["noisy_ids"], feed["pos"],
        feed["loss_weight"])[0]))([jnp.asarray(w) for w in weights])
    return dict(loss=loss, logits=logits, load=load,
                attention=found["attention_layers"], q=found["core_q"],
                k=found["core_k"],
                grads=dict(zip((p.name for p in params), grads)))


# ---- resolve ----------------------------------------------------------------

def test_resolve_reads_sdars_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["attention"] * 3
    assert c["ffn_layers"] == ["experts"] * 3
    assert c["window_layers"] == [None] * 3
    assert (c["num_experts"], c["experts_held"], c["first_expert"],
            c["intermediate_size"], c["num_experts_per_tok"]) \
        == (16, 16, 0, 24, 4)
    assert c["norm_topk_prob"] and c["qk_norm"] == "head"
    assert c["head_dim"] == 8 and c["rotary_dim"] == 8
    assert c["rope_theta"] == 1000000 and not c["tie_word_embeddings"]
    assert c["block_diffusion"] == {"block_length": 4, "mask_token_id": 95,
                                    "noise_eps": 1e-3}
    # absent, the objective is next-token as it always was
    plain = causal_lm.resolve({k: v for k, v in CFG.items()
                               if k != "objective"})
    assert plain["block_diffusion"] is None


def test_resolve_cuts_a_share_of_the_experts_and_the_words():
    c = causal_lm.resolve(dict(
        CFG, num_hidden_layers=2, num_experts=2, vocab_size=12,
        mask_token_id=11, share=dict(chips=8, chip=3, published=dict(
            num_hidden_layers=3, num_experts=16, vocab_size=96))))
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (16, 2, 6)
    assert c["block_diffusion"]["mask_token_id"] == 11


@pytest.mark.parametrize("change, error, match", [
    (dict(objective="masked_lm"), NotImplementedError, "objective"),
    (dict(sliding_window_layout=[1, 0, 0], sliding_window_size=8),
     NotImplementedError, "without a window"),
    (dict(use_sliding_window=True), NotImplementedError,
     "use_sliding_window"),
    (dict(total_ut_steps=2, num_experts=0, num_experts_per_tok=0),
     NotImplementedError, "looped stack"),
    (dict(hc_mult=2), NotImplementedError, "several residual streams"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "multi-token-prediction"),
    (dict(layer_types=["conv", "full_attention", "conv"], conv_L_cache=3),
     NotImplementedError, "a mixer other than attention"),
    (dict(full_attention_interval=3, linear_num_key_heads=2,
          linear_num_value_heads=2, linear_key_head_dim=8,
          linear_value_head_dim=8, linear_conv_kernel_dim=4),
     NotImplementedError, "a mixer other than attention"),
    (dict(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=4, v_head_dim=8, qk_norm=False,
          num_key_value_heads=8), NotImplementedError, "latent attention"),
    (dict(tie_word_embeddings=True), NotImplementedError, "a tied head"),
    (dict(mask_token_id=96), ValueError, "mask_token_id"),
    (dict(mask_token_id=None), ValueError, "mask_token_id"),
    (dict(block_length=0), ValueError, "block_length"),
    (dict(block_length=None), ValueError, "block_length")])
def test_resolve_refuses_under_the_objective(change, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **change))


def test_a_block_length_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="does not divide"):
        _build(dict(CFG, block_length=5))


def test_the_core_refuses_the_mask_beside_another():
    q = jnp.zeros((1, 16, 2, 8))
    from paddle_tpu.ops import pallas_kernels as pk
    for extra in (dict(causal=True), dict(window=4),
                  dict(kv_len=jnp.array([16]))):
        with pytest.raises(ValueError, match="whole mask"):
            pk.flash_attention(q, q, q, block_diffusion=(4, 8), **extra)
    with pytest.raises(ValueError, match="T = 2 L"):
        pk.flash_attention(q, q, q, block_diffusion=(4, 16))


# ---- the Program ------------------------------------------------------------

def test_program_has_the_feeds_the_rows_and_the_parameters(program):
    main, params, _, _ = program
    names = [p.name for p in params]
    want = ["embedding"]
    for i in range(3):
        want += ["layer_%d.%s" % (i, role) for role in LAYER]
    assert names == want + ["final_norm", "head"]
    block = main.global_block()
    assert [v for v in ("ids", "noisy_ids", "pos", "loss_weight", "labels")
            if block.has_var(v)] == ["ids", "noisy_ids", "pos",
                                     "loss_weight"]
    lookups = [op for op in block.ops if op.type == "lookup_table"]
    assert len(lookups) == 1            # ONE embedding, 2 T ids
    assert tuple(block.var(lookups[0].output("Out")[0]).shape)[1:] \
        == (2 * T, 32)
    cores = [op for op in block.ops if op.type == "fused_attention"]
    assert [op.attrs["block_diffusion"] for op in cores] == [[4, T]] * 3
    assert not any(op.attrs["causal"] or "window" in op.attrs
                   for op in cores)
    # the last layer takes the noised rows behind the core
    shapes = [tuple(block.var(v).shape)[1] for v in _inside(block)[
        "attention"]]
    assert shapes == [2 * T, 2 * T, T]
    routed = [op for op in block.ops if op.type == "moe_ffn"]
    assert [tuple(block.var(op.input("X")[0]).shape)[1] for op in routed] \
        == [2 * T, 2 * T, T]
    assert all(op.attrs["norm_topk_prob"] and op.attrs["top_k"] == 4
               for op in routed)
    head = next(op for op in block.ops if "head" in op.input_arg_names)
    assert tuple(block.var(head.output("Out")[0]).shape)[1:] == (T, 96)


def test_the_counters_say_mask_and_rows():
    def samples(family):
        return {tuple(sorted(labels.items())): value for labels, value in
                (REGISTRY.snapshot().get(family)
                 or {"samples": []})["samples"]}

    layers_before = samples("ptpu_causal_lm_layers_total")
    rows_before = samples("ptpu_causal_lm_rows_total")
    _build()
    layers = {k: v - layers_before.get(k, 0)
              for k, v in samples("ptpu_causal_lm_layers_total").items()
              if v - layers_before.get(k, 0)}
    assert [(dict(k)["mask"], dict(k)["block_length"], v)
            for k, v in layers.items()] == [("block_diffusion", "4", 3)]
    rows = {(dict(k)["part"], dict(k)["copy"]): v - rows_before.get(k, 0)
            for k, v in samples("ptpu_causal_lm_rows_total").items()}
    assert rows == {("attention", "noised"): 3 * T,
                    ("attention", "clean"): 3 * T,
                    ("ffn", "noised"): 3 * T, ("ffn", "clean"): 2 * T,
                    ("head", "noised"): T}
    # a next-token model counts under the labels it always had, and no rows
    rows_before = samples("ptpu_causal_lm_rows_total")
    _build({k: v for k, v in CFG.items() if k != "objective"})
    assert samples("ptpu_causal_lm_rows_total") == rows_before
    assert any("mask" not in labels and labels.get("ffn") == "experts"
               for labels, _ in REGISTRY.snapshot()[
                   "ptpu_causal_lm_layers_total"]["samples"])


@pytest.mark.parametrize("name", ["loss", "logits"])
def test_forward_matches_the_reference(program, want, name):
    assert _error(program[3][name], want[name]) < TOLERANCE


def test_expert_load_is_the_references(program, want):
    load = np.asarray(program[3]["load"])
    np.testing.assert_array_equal(load, np.asarray(want["load"]))
    # two copies in layers 0 and 1, the noised rows alone in the last
    assert load.sum() == (2 + 2 + 1) * B * T * 4


@pytest.mark.parametrize("layer", range(3))
@pytest.mark.parametrize("what", ["attention", "q", "k"])
def test_every_layers_attention_matches_on_both_copies(program, want, what,
                                                       layer):
    got, ref = program[3][what][layer], want[what][layer]
    rows = T if what == "attention" and layer == 2 else 2 * T
    assert np.asarray(ref).shape == (
        (B, rows, 32) if what == "attention"
        else (B, 2 * T, 8 if what == "q" else 2, 8))
    assert _error(got, ref) < TOLERANCE
    if rows == 2 * T:       # each copy by itself, not one hiding the other
        got, ref = (np.asarray(x).reshape(np.asarray(ref).shape)
                    for x in (got, ref))
        assert _error(got[:, :T], ref[:, :T]) < TOLERANCE
        assert _error(got[:, T:], ref[:, T:]) < TOLERANCE


@pytest.mark.parametrize("name", PARAMETERS)
def test_gradient_matches_jax_grad_of_the_reference(program, want, name):
    assert _error(program[3]["grads"][name], want["grads"][name]) \
        < 5 * TOLERANCE


def test_the_flash_kernels_give_the_dense_paths_step(monkeypatch, program):
    """The same program with the core on the flash kernels (interpreted, at
    tiles of 16 over 64 rows) in place of the dense path."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    monkeypatch.setitem(kernel_config.DEFAULT_TILES, "attn",
                        dict(kernel_config.DEFAULT_TILES["attn"],
                             block_q=16, block_k=16))
    jax.clear_caches()
    _, _, _, found = _run(grads=True)
    jax.clear_caches()
    for name in ("loss", "logits"):
        assert _error(found[0][name], program[3][name]) < TOLERANCE
    for name in ("layer_0.wv", "layer_2.wq", "embedding"):
        assert _error(found[0]["grads"][name], program[3]["grads"][name]) \
            < 5 * TOLERANCE


@pytest.mark.parametrize("mutant", [
    "mask_row_causal", "clean_sees_noised", "noised_sees_own_clean_block",
    "noised_blind_to_clean", "block_diagonal_one_way", "clean_row_causal",
    "block_length_8", "positions_run_on", "labels_shifted",
    "loss_unweighted"])
def test_the_reference_tells_a_broken_model(program, want, mutant,
                                            monkeypatch):
    """The reference under another reading of the objective is off the
    program by far more than rounding."""
    _, _, weights, _ = program
    feed = _feed()
    t = T
    row = np.arange(2 * t)
    noised = row < t
    position = np.where(noised, row, row - t)
    block = position // 4
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = block[:, None], block[None, :]
    right = (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))
    assert (np.asarray(reference.block_diffusion_mask(t, 4)) == right).all()
    masks = {
        "mask_row_causal": row[:, None] >= row[None, :],
        "clean_sees_noised": right | (~qn & kn & (kb <= qb)),
        "noised_sees_own_clean_block": right | (qn & ~kn & (kb <= qb)),
        "noised_blind_to_clean": right & ~(qn & ~kn),
        "block_diagonal_one_way": right & ~(
            qn & kn & (position[None, :] > position[:, None])),
        "clean_row_causal": right & ~(
            ~qn & ~kn & (position[None, :] > position[:, None])),
        "block_length_8": np.asarray(reference.block_diffusion_mask(t, 8))}
    if mutant in masks:
        monkeypatch.setattr(reference, "block_diffusion_mask",
                            lambda *_: jnp.asarray(masks[mutant]))
    elif mutant == "positions_run_on":
        real = reference.attention
        monkeypatch.setattr(
            reference, "attention", lambda a, pos, *rest, **kw: real(
                a, jnp.concatenate([pos[:, :t], pos[:, t:] + t], 1), *rest,
                **kw))
    elif mutant == "labels_shifted":
        feed = dict(feed, ids=feed["ids"])
        real = jnp.take_along_axis
        monkeypatch.setattr(
            jnp, "take_along_axis", lambda x, idx, axis: real(
                x, jnp.roll(idx, -1, axis=1), axis=axis))
    elif mutant == "loss_unweighted":
        feed = dict(feed, loss_weight=(feed["loss_weight"] > 0).astype("f"))
    loss, (logits, _) = _reference(CFG, weights, feed)
    off = max(_error(logits, want["logits"]), _error(loss, want["loss"]))
    assert off > 20 * TOLERANCE


# ---- the objective's invariants ---------------------------------------------

def test_the_clean_copy_does_not_move_when_the_noised_ids_do():
    """A clean row sees no noised row, in any layer: with other noised ids
    (another mask draw) every layer's attention output, q and k on the
    clean copy's rows are the same to the bit, and the noised copy's are
    not."""
    one, other = _feed(), _feed()
    other["noisy_ids"] = np.where(
        np.arange(T) % 3 == 0, CFG["mask_token_id"], other["ids"])
    assert (one["noisy_ids"] != other["noisy_ids"]).any()
    _, _, _, found = _run(feeds=[one, other])
    for layer in range(2):              # the last layer has no clean row
        for what in ("attention", "q", "k"):
            a, b = (np.asarray(f[what][layer]) for f in found)
            np.testing.assert_array_equal(a[:, T:], b[:, T:])
        a, b = (np.asarray(f["attention"][layer]) for f in found)
        assert np.abs(a[:, :T] - b[:, :T]).max() > 1e-3


def test_one_block_is_plain_bidirectional_attention_on_the_noised_copy():
    """block_length = T: every noised row sees every noised row and no
    clean one, so the noised copy's logits are those of the same stack
    under NO mask on the noised ids alone."""
    cfg = dict(CFG, block_length=T)
    feed = _feed(cfg=cfg)
    _, _, weights, found = _run(cfg, feeds=[feed])
    assert not np.asarray(
        reference.block_diffusion_mask(T, T))[:T, T:].any()
    # the plain stack: the reference's layers on the T noised rows under an
    # all-true mask, written out here
    c = causal_lm.resolve(cfg)
    params = iter(jnp.asarray(w) for w in weights)
    take = lambda n: [next(params) for _ in range(n)]   # noqa: E731
    with jax.default_matmul_precision("highest"):
        h = take(1)[0][jnp.asarray(feed["noisy_ids"])]
        for i in range(3):
            n1, wq, wk, wv, qn, kn, wo, n3, router, wg, wu, wd = take(12)
            h = h + reference.attention(
                reference.rms_norm(h, n1, 1e-6), jnp.asarray(feed["pos"]),
                wq, wk, wv, qn, kn, wo, reference.layer_config(c, i),
                visible=jnp.ones((T, T), bool))
            m = reference.rms_norm(h, n3, 1e-6).reshape(B * T, 32)
            h = h + reference.routed_experts(m, router, wg, wu, wd, c)[
                0].reshape(B, T, 32)
        w_f, w_lm = take(2)
        logits = reference.rms_norm(h, w_f, 1e-6) @ w_lm
    assert _error(found[0]["logits"], logits) < TOLERANCE


def test_with_no_position_masked_the_loss_is_zero():
    _, _, weights, found = _run(feeds=[_feed(masked=False)])
    assert float(np.ravel(found[0]["loss"])[0]) == 0.0
    assert np.abs(np.asarray(found[0]["logits"])).max() > 0.1
    assert float(_reference(CFG, weights, _feed(masked=False))[0]) == 0.0


def test_the_batch_masks_by_block_and_weighs_by_one_over_t():
    ids = jnp.asarray(_feed()["ids"])
    noisy, weight = reference.block_diffusion_batch(
        jax.random.key(3), jnp.tile(ids, (64, 1)), 4, 95, 1e-3)
    noisy, weight = np.asarray(noisy), np.asarray(weight)
    masked = noisy == 95
    assert (masked == (weight > 0)).all()
    assert (noisy[~masked] == np.tile(np.asarray(ids), (64, 1))[~masked]).all()
    # one level a block: the masked positions of a block share their weight
    by_block = weight.reshape(-1, T // 4, 4)
    for block in by_block.reshape(-1, 4)[:200]:
        assert len(set(block[block > 0])) <= 1
    assert weight[masked].min() >= 1.0
    # t uniform: half the positions are masked, and E[w] = 1
    assert abs(masked.mean() - 0.5) < 0.03
    assert abs(weight.mean() - 1.0) < 0.1


# ---- the share --------------------------------------------------------------

def test_the_expert_ranks_partial_outputs_sum_to_the_whole_layer():
    """Expert rank k of 8 holds experts 2k and 2k + 1 of 16 and computes
    them with the Program's routed_ffn, every rank routing over all 16
    columns and taking the top 4 of all; the eight partial sums are the
    uncut reference's layer, over 2 T rows of which a quarter are one
    token (the mask id's embedding)."""
    rng = np.random.RandomState(9)
    d = 32
    c = causal_lm.resolve(CFG)
    table = rng.randn(96, d).astype("f")
    feed = _feed()
    rows = table[np.concatenate([feed["noisy_ids"], feed["ids"]], 1)[0]]
    m = reference.rms_norm(jnp.asarray(rows), jnp.asarray(
        rng.rand(d) + 0.5, jnp.float32), 1e-6)
    with jax.default_matmul_precision("highest"):
        router = jnp.asarray(rng.randn(d, 16), jnp.float32)
        wg, wu = (jnp.asarray(rng.randn(16, d, 24) * 0.2, jnp.float32)
                  for _ in range(2))
        wd = jnp.asarray(rng.randn(16, 24, d) * 0.2, jnp.float32)
        whole = reference.routed_experts(m, router, wg, wu, wd, c)[0]
        parts = [moe.routed_ffn(
            m, router, wg[2 * k:2 * k + 2], wu[2 * k:2 * k + 2],
            wd[2 * k:2 * k + 2], top_k=4, norm_topk_prob=True,
            first_expert=2 * k)[0] for k in range(8)]
    assert _error(sum(parts), whole) < TOLERANCE
    assert max(_error(part, whole) for part in parts) > 0.1


def test_the_program_takes_a_share_through_the_layers():
    """The cut the cell has, through the Program: chip 3 of 8 holds experts
    6 and 7 of 16 and words 36 .. 47 as its own 0 .. 11, the mask id the
    last held word; loss, logits and load are the reference's given the
    same share."""
    cfg = dict(CFG, num_hidden_layers=2, num_experts=2, vocab_size=12,
               mask_token_id=11, share=dict(chips=8, chip=3, published=dict(
                   num_hidden_layers=3, num_experts=16, vocab_size=96)))
    feed = _feed(cfg=cfg)
    _, _, weights, found = _run(cfg, feeds=[feed])
    loss, (logits, load) = _reference(cfg, weights, feed)
    assert _error(found[0]["loss"], loss) < TOLERANCE
    assert _error(found[0]["logits"], logits) < TOLERANCE
    np.testing.assert_array_equal(np.asarray(found[0]["load"]),
                                  np.asarray(load))
    assert np.asarray(load).sum() == 3 * B * T * 4


# ---- what the other models' programs keep -----------------------------------

# The training programs of OLMoE's, SmallThinker's and Laguna's tiny
# rehearsals and of their cells at published widths (the nearest language
# model, the two other grouped-query ones), as test_causal_lm_laguna.py's
# digest of every op's type, attrs, inputs and outputs and every parameter's
# name, shape and whether it trains. The digests are the PARENT's (commit
# 4aaa733, computed by that function from a `git archive` of it).
PROGRAMS = {
    "tiny_olmoe": "eace2f826c7177d3",
    "tiny_smallthinker": "748317048510eaca",
    "tiny_laguna": "2409269fe7062dc4",
    "olmoe_1b_7b_train_t4096": "cac95f97a9179a2e",
    "smallthinker_21b_a3b_train_t8192": "9a0bbaba4f432d73",
    "laguna_s_2_1_train_t4096": "7bec6d107a3e3d80",
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_a_config_without_the_objective_builds_the_program_it_did(name):
    assert program_digest(name) == PROGRAMS[name]


if __name__ == "__main__":
    for name in sys.argv[1:]:
        print('    "%s": "%s",' % (name, program_digest(name)))
