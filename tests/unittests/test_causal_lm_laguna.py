"""models/causal_lm.py at Laguna-S-2.1's shape (`model_type: laguna`; tiny
widths, seeded weights): attention whose geometry is a LAYER's (4 query
heads on 2, half a head turned under YaRN, on the full layers; 6 on 2, the
whole head turned at a second theta, behind a window of 8 on the sliding
ones; a sigmoid gate a head from a projection of its own), a leading dense
layer by `mlp_only_layers`, 16 softmax-routed experts top-3 times a scaling
factor beside a gated shared expert. The Program against
models/causal_lm_reference.py: loss, logits, `ExpertLoad`, each layer's
attention output, gate and q and k as the core reads them, and every
parameter's gradient against jax.grad; the share test: for a full layer, a
sliding layer and an expert FFN the partial results of all shares add up to
the uncut reference's layer; `attention_factor` given and computed agree;
what `resolve()` reads of the family's keys and what it still refuses; and
a config without the per-layer keys builds the program it built before."""
import hashlib
import json
import math
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

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

PERIOD = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention"]
FACTOR = 0.1 * math.log(128) + 1        # 1.4852030263919618
YARN = dict(rope_theta=500000, rope_type="yarn", factor=128,
            original_max_position_embeddings=16, beta_slow=1, beta_fast=32,
            attention_factor=1.4852030263919618, partial_rotary_factor=0.5)
# the published config's keys at toy sizes: five layers (the dense one,
# then a period: sliding, sliding, sliding, full), heads of 8
CFG = dict(
    model_type="laguna", vocab_size=96, hidden_size=32, intermediate_size=64,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, attention_bias=False, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False,
    gating="per-head", sliding_window=8,
    rope_parameters={"full_attention": YARN, "sliding_attention": dict(
        rope_type="default", rope_theta=10000, partial_rotary_factor=1)},
    layer_types=(PERIOD * 2)[:5], moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    gating_types=["per_head"] * 5, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    moe_router_logit_softcapping=0, qk_norm="head", initializer_range=0.2,
    router_aux_loss_coef=0, router_z_loss_coef=0)
B, T = 2, 32
TOLERANCE = 2e-4        # float32 against float32, another order of sums
# parameters that start at an identity (a weight of 1): drawn off it before
# the comparison, or a rule that drops one would pass
OFF_IDENTITY = ("norm",)
ATTENTION = ["input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "wo"]


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed():
    tok = np.random.RandomState(0).randint(0, CFG["vocab_size"], (B, T + 1))
    return {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:, None]}


def _build(cfg=CFG, seq_len=T):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, seq_len)
    return main, startup, dict(loss=loss, logits=logits, load=load)


PARAMETERS = [p.name for p in _build()[0].global_block().all_parameters()]


def _inside(block):
    """What the Program computes inside its layers, by layer: the
    attention's output behind W_o, the gate a head, and q and k as the core
    reads them."""
    cores = [op for op in block.ops if op.type == "fused_attention"]
    outs = {core.output("Out")[0] for core in cores}
    gates = [op.input("Y")[0] for op in block.ops
             if op.type == "elementwise_mul" and op.input("X")[0] in outs]
    behind = [next(op for op in block.ops
                   if "layer_%d.wo" % i in op.input_arg_names)
              .output("Out")[0] for i in range(len(cores))]
    return {"attention": behind, "gate": gates,
            "q": [core.input("Q")[0] for core in cores],
            "k": [core.input("K")[0] for core in cores]}


@pytest.fixture(scope="module")
def program():
    main, startup, out = _build()
    block = main.global_block()
    params = block.all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(5)
    inside = _inside(block)
    names = ["loss", "logits", "load"]
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in params:
            if p.name.endswith(OFF_IDENTITY):
                w = np.asarray(scope.get(p.name))
                scope.set(p.name, jnp.asarray(
                    w + 0.2 * rng.standard_normal(w.shape).astype("f")))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        fetched = [block.var(v) for kind in sorted(inside)
                   for v in inside[kind]]
        got = exe.run(main, feed=_feed(), fetch_list=[
            out[name] for name in names] + fetched
            + [name + "@GRAD" for name in PARAMETERS])
    found = dict(zip(names, got))
    at = len(names)
    for kind in sorted(inside):
        found[kind] = got[at:at + len(inside[kind])]
        at += len(inside[kind])
    found["grads"] = dict(zip(PARAMETERS, got[at:]))
    return main, params, weights, found


@pytest.fixture(scope="module")
def want(program):
    _, params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    found = {}
    loss, (logits, load) = reference.loss_fn(
        CFG, weights, feed["ids"], feed["pos"], feed["labels"], found=found)
    _, grads = jax.jit(lambda p: reference.loss_and_grads(
        CFG, p, feed["ids"], feed["pos"], feed["labels"]))(weights)
    return dict(loss=loss, logits=logits, load=load,
                attention=found["attention_layers"], gate=found["head_gate"],
                q=found["core_q"], k=found["core_k"],
                grads=dict(zip((p.name for p in params), grads)))


# ---- resolve ----------------------------------------------------------------

def test_resolve_reads_lagunas_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["attention"] * 5
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert c["num_dense_layers"] == 1 and c["routed_scaling_factor"] == 2.5
    assert c["window_layers"] == [None, 8, 8, 8, None]
    assert c["rope_layers"] == [True] * 5
    assert c["attention_gate"] == "per_head" and c["geometry_by_layer"]
    assert [g["num_attention_heads"] for g in c["geometry_layers"]] \
        == [4, 6, 6, 6, 4]
    assert [g["rotary_dim"] for g in c["geometry_layers"]] == [4, 8, 8, 8, 4]
    assert [g["rope_theta"] for g in c["geometry_layers"]] \
        == [500000, 10000, 10000, 10000, 500000]
    assert [g["rope_type"] for g in c["geometry_layers"]] \
        == ["yarn", "default", "default", "default", "yarn"]
    # the scores' scale stays head_dim^-1/2; cos and sin carry the factor
    assert all(g["attention_scale"] is None for g in c["geometry_layers"])
    assert [g["rope_table_scale"] for g in c["geometry_layers"]] \
        == [YARN["attention_factor"], 1.0, 1.0, 1.0, YARN["attention_factor"]]
    # ONE table a KIND of layer: the two full layers read the same list
    full = [g["rope_inv_freq"] for g in c["geometry_layers"]
            if g["rope_type"] == "yarn"]
    assert full[0] is full[1] and len(full[0]) == 2
    assert all(g["rope_inv_freq"] is None for g in c["geometry_layers"]
               if g["rope_type"] == "default")
    assert c["rope_tables"] == [("yarn", 4), ("default", 8)]
    assert (c["num_experts"], c["experts_held"], c["first_expert"],
            c["intermediate_size"], c["dense_intermediate_size"],
            c["shared_expert_intermediate_size"]) == (16, 16, 0, 24, 64, 24)
    assert c["router_scoring"] == "softmax" and c["shared_expert_gate"]
    # a model without the keys keeps ONE geometry
    plain = causal_lm.resolve(dict(
        vocab_size=96, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=48))
    assert not plain["geometry_by_layer"] and plain["rope_tables"] == []
    assert plain["geometry_layers"] == [{}, {}]
    assert causal_lm._layer(plain, 1)["num_attention_heads"] == 4


def test_resolve_cuts_the_published_lists_to_a_share():
    """The cut the benchmark's cell has: the first layers of the whole
    model's lists, a share of the heads by layer held to the published
    ones, 4 of 16 experts held on chip 2."""
    c = causal_lm.resolve(dict(
        CFG, num_hidden_layers=3, num_attention_heads=2,
        num_key_value_heads=1, num_experts=4, vocab_size=48,
        layer_types=PERIOD * 2, mlp_layer_types=["dense"] + ["sparse"] * 7,
        gating_types=["per_head"] * 8,
        num_attention_heads_per_layer=[2, 3, 3, 3] * 2,
        share=dict(chips=4, chip=2, published=dict(
            num_hidden_layers=8, num_attention_heads=4,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 2,
            num_key_value_heads=2, num_experts=16, vocab_size=96))))
    assert [g["num_attention_heads"] for g in c["geometry_layers"]] \
        == [2, 3, 3]
    assert c["window_layers"] == [None, 8, 8]
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (16, 4, 8)


def test_attention_factor_given_and_computed_agree():
    """0.1 ln(factor) + 1: `yarn_table`'s m(1) / m(0), the published key,
    and what a set without the key resolves to."""
    assert YARN["attention_factor"] == pytest.approx(FACTOR, rel=1e-15)
    _, computed, scale = causal_lm.yarn_table(YARN, 500000, 4, 8)
    assert computed == pytest.approx(YARN["attention_factor"], rel=1e-12)
    assert scale == pytest.approx(8 ** -0.5)
    without = {k: v for k, v in YARN.items() if k != "attention_factor"}
    c = causal_lm.resolve(dict(CFG, rope_parameters=dict(
        CFG["rope_parameters"], full_attention=without)))
    assert c["geometry_layers"][0]["rope_table_scale"] \
        == pytest.approx(YARN["attention_factor"], rel=1e-12)
    given = causal_lm.resolve(dict(CFG, rope_parameters=dict(
        CFG["rope_parameters"], full_attention=dict(
            YARN, attention_factor=1.25))))
    assert given["geometry_layers"][0]["rope_table_scale"] == 1.25


@pytest.mark.parametrize("change, error, match", [
    (dict(mlp_only_layers=[1]), NotImplementedError, "leading run"),
    (dict(mlp_only_layers=[0, 2]), NotImplementedError, "mlp_only_layers"),
    (dict(rope_parameters=dict(CFG["rope_parameters"], sliding_attention=dict(
        rope_type="linear", rope_theta=10000, factor=2))),
     NotImplementedError, "rope_type default or yarn"),
    (dict(rope_parameters=dict(full_attention=YARN)), NotImplementedError,
     "none for 'sliding_attention'"),
    (dict(rope_parameters=dict(CFG["rope_parameters"], full_attention={
        k: v for k, v in YARN.items() if k != "factor"})), ValueError,
     "factor"),
    (dict(gating=True), NotImplementedError, "gating 'per-head'"),
    (dict(gating="per-channel"), NotImplementedError, "gating"),
    (dict(gating_types=["per_head"] * 4 + ["none"]), ValueError,
     "gating_types"),
    (dict(mlp_layer_types=["sparse"] * 5), ValueError, "mlp_layer_types"),
    (dict(moe_apply_router_weight_on_input=True), NotImplementedError,
     "moe_apply_router_weight_on_input"),
    (dict(moe_router_logit_softcapping=30.0), NotImplementedError,
     "moe_router_logit_softcapping"),
    (dict(num_attention_heads_per_layer=[4, 6, 5, 6, 4]), ValueError,
     "no multiple of 2 key/value heads"),
    (dict(num_attention_heads_per_layer=[4, 6, 6]), ValueError,
     "3 entries for 5 layers"),
    (dict(sliding_window=None), ValueError, "sliding_window"),
    (dict(layer_types=["full_attention", "chunked_attention"] + PERIOD[:3]),
     NotImplementedError, "chunked_attention"),
    (dict(rope_scaling=dict(type="yarn", factor=2,
                            original_max_position_embeddings=16)),
     NotImplementedError, "rope_scaling"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "multi-token-prediction"),
    (dict(share=dict(chips=2, chip=0, published=dict(
        num_key_value_heads=4,
        num_attention_heads_per_layer=[8, 12, 12, 12, 9]))), ValueError,
     "not the share of the published")])
def test_resolve_refuses(change, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **change))


def test_a_sliding_layer_needs_the_geometry_by_layer():
    """`sliding_attention` is a kind of layer to a config with the family's
    keys alone: LFM2's and granite's `layer_types` know what they knew."""
    cfg = {k: v for k, v in CFG.items()
           if k not in ("rope_parameters", "num_attention_heads_per_layer")}
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        causal_lm.resolve(cfg)


# ---- the Program ------------------------------------------------------------

def test_program_has_the_layers_parameters_in_order(program):
    main, params, _, _ = program
    names = [p.name for p in params]
    dense = ["post_attention_norm", "w_gate", "w_up", "w_down"]
    experts = ["post_attention_norm", "experts.router", "experts.w_gate",
               "experts.w_up", "experts.w_down", "shared_expert.w_gate",
               "shared_expert.w_up", "shared_expert.w_down",
               "shared_expert.gate"]
    want = ["embedding"]
    for i in range(5):
        want += ["layer_%d.%s" % (i, role)
                 for role in ATTENTION + (dense if i == 0 else experts)]
    assert names == want + ["final_norm", "head"]
    shapes = {p.name: tuple(p.shape) for p in params}
    # W_q, W_g and W_o by the LAYER's heads, whatever the hidden size is
    for i, heads in enumerate(CFG["num_attention_heads_per_layer"]):
        assert shapes["layer_%d.wq" % i] == (32, heads * 8)
        assert shapes["layer_%d.wg" % i] == (32, heads)
        assert shapes["layer_%d.wo" % i] == (heads * 8, 32)
        assert shapes["layer_%d.wk" % i] == (32, 16)
        assert shapes["layer_%d.q_norm" % i] == (8,)
    assert shapes["layer_0.w_gate"] == (32, 64)
    assert shapes["layer_1.experts.w_gate"] == (16, 32, 24)
    assert shapes["layer_1.shared_expert.gate"] == (32, 1)
    block = main.global_block()
    cores = [op for op in block.ops if op.type == "fused_attention"]
    assert [op.attrs.get("window") for op in cores] == [None, 8, 8, 8, None]
    assert all(op.attrs["scale"] is None for op in cores)
    turns = [op for op in block.ops if op.type == "rotary_embedding"]
    assert len(turns) == 10
    assert [op.attrs.get("rotary_dim") for op in turns[::2]] \
        == [4, None, None, None, 4]
    assert [op.attrs["base"] for op in turns[::2]] \
        == [500000.0, 10000.0, 10000.0, 10000.0, 500000.0]
    assert [op.attrs.get("table_scale") for op in turns[::2]] \
        == [YARN["attention_factor"], None, None, None,
            YARN["attention_factor"]]
    assert [len(op.attrs.get("inv_freq", ())) for op in turns[::2]] \
        == [2, 0, 0, 0, 2]
    routed = [op for op in block.ops if op.type == "moe_ffn"]
    assert len(routed) == 4
    assert all(op.attrs["scale"] == 2.5 and op.attrs["norm_topk_prob"]
               and "scoring" not in op.attrs for op in routed)


def test_the_counters_tell_the_layers_apart():
    fresh_before = {
        tuple(sorted(labels.items())): value for labels, value in
        (REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
         or {"samples": []})["samples"]}
    tables_before = {
        tuple(sorted(labels.items())): value for labels, value in
        (REGISTRY.snapshot().get("ptpu_rope_tables_total")
         or {"samples": []})["samples"]}
    _build()
    snap = REGISTRY.snapshot()

    def new(family, before):
        return {key: value - before.get(key, 0) for key, value in (
            (tuple(sorted(labels.items())), value)
            for labels, value in snap[family]["samples"])
            if value - before.get(key, 0)}

    layers = new("ptpu_causal_lm_layers_total", fresh_before)
    by = {(dict(k)["heads"], dict(k)["window"], dict(k)["rope"],
           dict(k)["rotary_dim"], dict(k)["ffn"]): v
          for k, v in layers.items()}
    assert by == {("4", "0", "yarn", "4", "dense"): 1,
                  ("6", "8", "default", "8", "experts"): 3,
                  ("4", "0", "yarn", "4", "experts"): 1}
    assert all(dict(k)["gate"] == "per_head" and dict(k)["kv_heads"] == "2"
               for k in layers)
    # a table a KIND of layer, two a program here: a table made a layer
    # would count five
    assert new("ptpu_rope_tables_total", tables_before) == {
        (("kind", "default"), ("rotary_dim", "8")): 1,
        (("kind", "yarn"), ("rotary_dim", "4")): 1}
    # a program under rope_scaling counts its one table; one without counts
    # none and its layers keep the labels they always had
    tables_before = {tuple(sorted(labels.items())): value for labels, value
                     in snap["ptpu_rope_tables_total"]["samples"]}
    plain = dict(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                 num_attention_heads=4, intermediate_size=48)
    _build(plain)
    _build(dict(plain, rope_scaling=dict(
        type="yarn", factor=4, original_max_position_embeddings=16)))
    snap = REGISTRY.snapshot()
    assert new("ptpu_rope_tables_total", tables_before) == {
        (("kind", "yarn"), ("rotary_dim", "4")): 1}
    counted = [labels for labels, _ in
               snap["ptpu_causal_lm_layers_total"]["samples"]
               if labels.get("ffn") == "dense"
               and labels.get("rotary_dim") == "4"
               and labels.get("gate") == "false"]
    assert counted and not any(
        key in labels for labels in counted
        for key in ("heads", "kv_heads", "window", "rope"))


@pytest.mark.parametrize("name", ["loss", "logits"])
def test_forward_matches_the_reference(program, want, name):
    assert _error(program[3][name], want[name]) < TOLERANCE


def test_expert_load_is_the_references(program, want):
    load = np.asarray(program[3]["load"])
    np.testing.assert_array_equal(load, np.asarray(want["load"]))
    assert load.sum() == 4 * B * T * 3


@pytest.mark.parametrize("layer", range(5))
@pytest.mark.parametrize("what", ["attention", "gate", "q", "k"])
def test_every_layers_attention_matches(program, want, what, layer):
    got, ref = program[3][what][layer], want[what][layer]
    assert np.asarray(ref).shape == (
        (B, T, 32) if what == "attention" else
        (B, T, CFG["num_attention_heads_per_layer"][layer]) if what == "gate"
        else (B, T, CFG["num_attention_heads_per_layer"][layer]
              if what == "q" else 2, 8))
    assert _error(got, ref) < TOLERANCE


@pytest.mark.parametrize("name", PARAMETERS)
def test_gradient_matches_jax_grad_of_the_reference(program, want, name):
    assert _error(program[3]["grads"][name], want["grads"][name]) \
        < 5 * TOLERANCE


@pytest.mark.parametrize("mutant", [
    "window_off", "window_on_full", "window_7", "tables_swapped",
    "yarn_off", "attention_factor_1", "theta_shared", "scaling_factor_1"])
def test_the_reference_tells_a_broken_model(program, want, mutant):
    """The reference reads the config's own keys: a model built under
    another reading of them is off by far more than rounding."""
    rope = CFG["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    cfg = {
        "window_off": dict(CFG, sliding_window=T),
        "window_on_full": dict(CFG, layer_types=[PERIOD[1]] * 5,
                               rope_parameters=dict(
                                   sliding_attention=sliding)),
        "window_7": dict(CFG, sliding_window=7),
        "tables_swapped": dict(CFG, rope_parameters=dict(
            full_attention=sliding, sliding_attention=full)),
        "yarn_off": dict(CFG, rope_parameters=dict(rope, full_attention=dict(
            rope_type="default", rope_theta=500000,
            partial_rotary_factor=0.5))),
        "attention_factor_1": dict(CFG, rope_parameters=dict(
            rope, full_attention=dict(full, attention_factor=1.0))),
        "theta_shared": dict(CFG, rope_parameters=dict(
            rope, sliding_attention=dict(sliding, rope_theta=500000))),
        "scaling_factor_1": dict(CFG, moe_routed_scaling_factor=1),
    }[mutant]
    if mutant == "window_on_full":
        cfg["num_attention_heads_per_layer"] = \
            CFG["num_attention_heads_per_layer"]
    _, _, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    _, (logits, _) = reference.loss_fn(cfg, weights, feed["ids"],
                                       feed["pos"], feed["labels"])
    assert _error(logits, want["logits"]) > 20 * TOLERANCE


# ---- the share --------------------------------------------------------------

def _attention_weights(rng, d, heads, hkv, hd):
    w = {"wq": rng.randn(d, heads * hd) * 0.2,
         "wk": rng.randn(d, hkv * hd) * 0.2,
         "wv": rng.randn(d, hkv * hd) * 0.2,
         "q_norm": rng.rand(hd) + 0.5, "k_norm": rng.rand(hd) + 0.5,
         "wo": rng.randn(heads * hd, d) * 0.2,
         "wg": rng.randn(d, heads) * 0.5}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


@pytest.mark.parametrize("kind", ["full", "sliding", "experts"])
def test_the_shares_of_a_layer_sum_to_the_whole_layer(kind):
    """One layer of each kind on one input. Attention: head rank g of 2
    holds key/value head g with its query heads (2 of 4 on a full layer, 3
    of 6 on a sliding one: those heads' columns of W_q and W_g, rows of
    W_o); the two partial sums behind W_o add up to the uncut layer. The
    expert FFN: expert rank k of 4 holds experts 4k .. 4k + 3 and computes
    them with the Program's routed_ffn, every rank routing over all 16; the
    four partial sums plus the gated shared expert, counted once, are the
    uncut reference's layer."""
    rng = np.random.RandomState(9)
    d, t, hd = 32, T, 8
    x = jnp.asarray(rng.randn(1, t, d), jnp.float32)
    a = reference.rms_norm(x, jnp.asarray(rng.rand(d) + 0.5, jnp.float32),
                           1e-6)
    c = causal_lm.resolve(CFG)
    with jax.default_matmul_precision("highest"):
        if kind == "experts":
            m = a.reshape(t, d)
            router = jnp.asarray(rng.randn(d, 16), jnp.float32)
            wg, wu = (jnp.asarray(rng.randn(16, d, 24) * 0.2, jnp.float32)
                      for _ in range(2))
            wd = jnp.asarray(rng.randn(16, 24, d) * 0.2, jnp.float32)
            shared = [jnp.asarray(rng.randn(*s) * 0.2, jnp.float32)
                      for s in ((d, 24), (d, 24), (24, d), (d, 1))]
            beside = reference.shared_expert(m, *shared)
            whole = reference.routed_experts(m, router, wg, wu, wd, c)[0] \
                + beside
            parts = [moe.routed_ffn(
                m, router, wg[4 * k:4 * k + 4], wu[4 * k:4 * k + 4],
                wd[4 * k:4 * k + 4], top_k=3, norm_topk_prob=True,
                first_expert=4 * k, scale=2.5)[0] for k in range(4)]
            parts.append(beside)
        else:
            layer = 0 if kind == "full" else 1
            heads = CFG["num_attention_heads_per_layer"][layer]
            group = heads // 2
            cl = reference.layer_config(c, layer)
            assert cl["window"] == (None if kind == "full" else 8)
            w = _attention_weights(rng, d, heads, 2, hd)
            pos = jnp.arange(t)[None]

            def share(g):
                q = slice(g * group * hd, (g + 1) * group * hd)
                kv = slice(g * hd, (g + 1) * hd)
                return reference.attention(
                    a, pos, w["wq"][:, q], w["wk"][:, kv], w["wv"][:, kv],
                    w["q_norm"], w["k_norm"], w["wo"][q], cl,
                    w["wg"][:, g * group:(g + 1) * group])

            whole = reference.attention(
                a, pos, w["wq"], w["wk"], w["wv"], w["q_norm"], w["k_norm"],
                w["wo"], cl, w["wg"])
            parts = [share(0), share(1)]
    assert _error(sum(parts), whole) < TOLERANCE
    assert _error(parts[0], whole) > 0.1


def test_the_program_takes_a_share_through_the_layers():
    """The cut the cell has, through the Program: chip 1 of 4 holds 2 / 3
    query heads on 1 key/value head by layer and experts 4 .. 7 of 16, and
    its loss, logits and load are the reference's given the same share."""
    cfg = dict(
        CFG, num_hidden_layers=3, num_attention_heads=2,
        num_key_value_heads=1, num_experts=4,
        num_attention_heads_per_layer=[2, 3, 3, 3, 2],
        share=dict(chips=4, chip=1, published=dict(
            num_hidden_layers=5,
            num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            num_key_value_heads=2, num_experts=16)))
    main, startup, out = _build(cfg)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        loss, logits, load = exe.run(
            main, feed=_feed(),
            fetch_list=[out["loss"], out["logits"], out["load"]])
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    want_loss, (want_logits, want_load) = reference.loss_fn(
        cfg, weights, feed["ids"], feed["pos"], feed["labels"])
    assert _error(loss, want_loss) < TOLERANCE
    assert _error(logits, want_logits) < TOLERANCE
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    block = main.global_block()
    assert tuple(block.var("layer_1.wq").shape) == (32, 24)
    assert tuple(block.var("layer_1.experts.w_up").shape)[0] == 4


# ---- what the other models' programs keep -----------------------------------

# The training programs of three tiny rehearsals whose models share the code
# this file's model touched (SmallThinker's window and rotary by layer,
# Qwen3-Next's partial rotary, QK-norm a head, elementwise gate and gated
# shared expert, Xing4.0's YaRN table), and of every decoder cell at
# published widths, as a digest of every op's type, attrs, inputs and outputs
# and every parameter's name, shape and whether it trains. The digests are
# the PARENT's (commit 7dd3c31, computed by this function from a `git
# archive` of it): a program that moved fails here by name. After a change
# that is meant to move one, print the new digest with
# `python tests/unittests/test_causal_lm_laguna.py <name>`.
PROGRAMS = {
    "tiny_smallthinker": "748317048510eaca",
    "tiny_qwen3_next": "45b7d07d3cc9de04",
    "tiny_xing4_0": "3fccd5ba26d94e15",
    "tiny_lfm2": "caf2eb76707d4f92",
    "tiny_glm_4_7_flash": "4309e8a65e220098",
    "tiny_phi4_mini_flash": "6476b73c3d5181cf",
    "smallthinker_21b_a3b_train_t8192": "9a0bbaba4f432d73",
    "qwen3_next_80b_a3b_train_t4096": "f38beacbd17761b4",
    "xing4_0_29b_a4b_train_1seq": "6c68ccc9aff02b0d",
    "nemotron_3_super_120b_a12b_train_t4096": "0a6bc3c6ba1e2781",
}


def program_digest(name):
    sys.path.insert(0, REPO)
    from benchmark import manifest
    path = os.path.join(REPO, "BENCHMARK.json")
    if name.startswith("tiny_"):
        path = os.path.join(REPO, "benchmark", "tests", name,
                            "manifest.json")
        with open(path) as f:
            name = json.load(f)["workloads"][0]["name"]
    cell = manifest.load_cell(path, name)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cell.config_module.build(fluid, cell.config, cell.traffic)
    said = []
    for block in main.blocks:
        for op in block.ops:
            said.append([op.type, sorted(
                (k, repr(v)) for k, v in op.attrs.items()),
                sorted((k, list(v)) for k, v in op.inputs.items()),
                sorted((k, list(v)) for k, v in op.outputs.items())])
    for p in main.global_block().all_parameters():
        said.append([p.name, list(p.shape), bool(getattr(p, "trainable",
                                                         True))])
    return hashlib.sha256(json.dumps(said, sort_keys=True).encode()) \
        .hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_a_config_without_the_keys_builds_the_program_it_did(name):
    assert program_digest(name) == PROGRAMS[name]


def test_the_elementwise_gate_is_what_it_was():
    """Qwen3-Next's gate: a twice-wide W_q, no `wg`, and the label
    `gate="true"`."""
    cfg = dict(vocab_size=96, hidden_size=16, num_hidden_layers=1,
               num_attention_heads=4, intermediate_size=48,
               attention_gate=True)
    main, _, _ = _build(cfg)
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes["layer_0.wq"] == (16, 32) and "layer_0.wg" not in shapes
    assert any(labels.get("gate") == "true" for labels, _ in
               REGISTRY.snapshot()["ptpu_causal_lm_layers_total"]["samples"])


if __name__ == "__main__":
    for name in sys.argv[1:]:
        print('    "%s": "%s",' % (name, program_digest(name)))
