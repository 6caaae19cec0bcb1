"""models/causal_lm.py at NVIDIA-Nemotron-3-Super's shape (`model_type:
nemotron_h`; tiny widths, seeded weights): layers of ONE branch by
`hybrid_override_pattern` (a Mamba-2 mixer of two groups, attention without
positions on two key/value heads, a LatentMoE of 16 ungated ReLU^2 experts
top-3 in a latent narrower than the hidden size beside a whole shared
expert, a dense ReLU^2 MLP) and the `*E` multi-token-prediction module ON.
The Program against models/causal_lm_reference.py: loss, logits, the
module's, `ExpertLoad`, the state every layer leaves, and every trained
parameter's gradient against jax.grad; the share test: for one layer of
each kind the partial results of all shares add up to the uncut reference's
layer; the scan op with groups, both paths, against the recurrence token by
token; what `resolve()` reads of the family's keys and what it still
refuses; one group builds granite's program as it was, and the cells that
share the touched code build the programs they did."""
import hashlib
import json
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
from paddle_tpu.ops import ssd_kernels as ssd
from paddle_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# the published config's keys at toy sizes: six layers, one of every kind
# and two of the frequent ones; 8 Mamba heads of 8 in 2 groups on 16 states;
# 4 query heads on 2 key/value heads of 8; 16 experts of 24 in a latent of 16
# under a hidden size of 32, top-3, a shared expert of 40
PATTERN = "MEM*E-"
CFG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=6,
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, intermediate_size=24,
    moe_intermediate_size=24, moe_latent_size=16,
    moe_shared_expert_intermediate_size=40, n_routed_experts=16,
    num_experts_per_tok=3, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=5, n_group=1, topk_group=1, mamba_num_heads=8,
    mamba_head_dim=8, ssm_state_size=16, conv_kernel=4, n_groups=2, expand=2,
    chunk_size=128, use_conv_bias=True, mamba_proj_bias=False,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, mlp_hidden_act="relu2",
    mamba_hidden_act="silu", mlp_bias=False, attention_bias=False,
    use_bias=False, rope_theta=10000, partial_rotary_factor=1,
    tie_word_embeddings=False, num_nextn_predict_layers=1,
    mtp_hybrid_override_pattern="*E", initializer_range=0.2,
    expert_bias_initializer_range=0.1)
B, T = 2, 24
TOLERANCE = 2e-4                # float32 against float32: another order of
#                                 sums (chunks against tokens, sorted rows)
# parameters that start at an identity (a bias of 0, a weight of 1): drawn
# off it before the comparison, or a rule that drops one would pass
OFF_IDENTITY = (".bias", ".d", "gated_norm", "norm")
KINDS = {"M": "mamba2", "*": "attention", "E": "experts", "-": "dense"}


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed():
    tok = np.random.RandomState(0).randint(0, CFG["vocab_size"], (B, T + 2))
    return {"ids": tok[:, :-2],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:-1, None], "labels_next": tok[:, 2:, None]}


def _build(cfg=CFG):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    extras = {}
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, T, extras=extras)
    return main, startup, dict(extras, loss=loss, logits=logits, load=load)


def _trained(main):
    return [p for p in main.global_block().all_parameters()
            if getattr(p, "trainable", True)]


# every trained parameter, by name, for the gradients' parametrisation
PARAMETERS = [p.name for p in _trained(_build()[0])]


def _states(block):
    """The residual state each trunk layer leaves: what the next layer's
    norm (the final norm, behind the last) reads."""
    reads = {op.input("Scale")[0]: op.input("X")[0] for op in block.ops
             if op.type == "rms_norm"}
    layers = CFG["num_hidden_layers"]
    return [reads["layer_%d.norm" % (i + 1)] for i in range(layers - 1)] \
        + [reads["final_norm"]]


@pytest.fixture(scope="module")
def program():
    main, startup, out = _build()
    block = main.global_block()
    params = block.all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(5)
    names = ["loss", "logits", "load", "main_loss", "mtp_loss", "mtp_logits"]
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in params:
            if p.name.endswith(OFF_IDENTITY):
                w = np.asarray(scope.get(p.name))
                scope.set(p.name, jnp.asarray(
                    w + 0.2 * rng.standard_normal(w.shape).astype("f")))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        got = exe.run(main, feed=_feed(), fetch_list=[
            out[name] for name in names] + [block.var(v)
                                            for v in _states(block)]
            + [name + "@GRAD" for name in PARAMETERS])
    found = dict(zip(names, got))
    found["states"] = got[len(names):len(names) + len(_states(block))]
    found["grads"] = dict(zip(PARAMETERS, got[-len(PARAMETERS):]))
    return main, params, weights, found


@pytest.fixture(scope="module")
def want(program):
    _, params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    found = {}
    loss, (logits, load) = reference.loss_fn(
        CFG, weights, feed["ids"], feed["pos"], feed["labels"],
        labels_next=feed["labels_next"], found=found)
    _, grads = jax.jit(lambda p: reference.loss_and_grads(
        CFG, p, feed["ids"], feed["pos"], feed["labels"],
        labels_next=feed["labels_next"]))(weights)
    return dict(found, loss=loss, logits=logits, load=load,
                grads=dict(zip((p.name for p in params), grads)))


# ---- resolve ----------------------------------------------------------------

def test_resolve_reads_nemotron_hs_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["mamba2", "none", "mamba2", "attention",
                                 "none", "none", "attention"]
    assert c["ffn_layers"] == ["none", "experts", "none", "none", "experts",
                               "dense", "experts"]
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_n_groups"]) == (8, 8, 16, 4, 2)
    assert c["mamba_conv_bias"] is True and c["rms_norm_eps"] == 1e-5
    assert c["norm_type"] == "rms_norm"
    # no positional term in any layer, whatever rope_theta says
    assert c["rope_theta"] is None and c["rope_layers"] == [False] * 7
    assert c["hidden_act"] == "relu2" and c["ffn_gated"] is False
    assert c["moe_latent_size"] == 16 and c["intermediate_size"] == 24
    assert c["shared_expert_intermediate_size"] == 40
    assert c["shared_expert_gate"] is False
    assert (c["router_scoring"], c["use_expert_bias"],
            c["router_renorm_epsilon"], c["routed_scaling_factor"]) \
        == ("sigmoid", True, 1e-20, 5)
    assert c["router_aux_loss_coef"] == c["router_z_loss_coef"] == 0
    assert c["num_experts"] == c["experts_held"] == 16
    assert c["mtp_layers"] == 1
    # a model without the pattern keeps its gated FFNs and its rotary
    plain = causal_lm.resolve(dict(
        vocab_size=96, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=48))
    assert plain["ffn_gated"] is True and plain["rope_theta"] == 10000.0
    assert plain["moe_latent_size"] == 0


def test_resolve_cuts_the_published_pattern_to_a_share():
    """The cut the benchmark's cell has: the first layers of the whole
    pattern, a share of the heads (their count checked against the
    published ones), one group held, 4 of 16 experts held on chip 2."""
    c = causal_lm.resolve(dict(
        CFG, num_hidden_layers=3, num_nextn_predict_layers=0,
        mamba_num_heads=4, n_groups=1, num_attention_heads=2,
        num_key_value_heads=1, n_routed_experts=4, vocab_size=48,
        share=dict(chips=4, chip=2, published=dict(
            num_hidden_layers=6, mamba_num_heads=8, n_groups=2,
            num_attention_heads=4, num_key_value_heads=2,
            n_routed_experts=16, vocab_size=96))))
    assert c["mixer_layers"] == ["mamba2", "none", "mamba2"]
    assert c["ffn_layers"] == ["none", "experts", "none"]
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (16, 4, 8)
    assert c["mamba_n_heads"] == 4 and c["mamba_n_groups"] == 1


@pytest.mark.parametrize("change, error, match", [
    (dict(hybrid_override_pattern="M-E*E-", mlp_bias=True),
     NotImplementedError, "mlp_bias"),
    (dict(topk_group=2), NotImplementedError, "topk_group"),
    (dict(num_nextn_predict_layers=2), NotImplementedError,
     "num_nextn_predict_layers"),
    (dict(mtp_hybrid_override_pattern="ME"), NotImplementedError,
     "mtp_hybrid_override_pattern"),
    (dict(hybrid_override_pattern="MEM*EX"), NotImplementedError, "X"),
    (dict(hybrid_override_pattern="MEM*"), NotImplementedError,
     "4 for 6 layers"),
    (dict(hybrid_override_pattern=PATTERN * 2), NotImplementedError,
     "12 for 6 layers"),
    (dict(mlp_hidden_act="silu"), NotImplementedError, "mlp_hidden_act"),
    (dict(mamba_proj_bias=True), NotImplementedError, "mamba_proj_bias"),
    (dict(use_bias=True), NotImplementedError, "use_bias"),
    (dict(n_groups=3), ValueError, "whole groups of 3"),
    (dict(mamba_num_heads=4), ValueError, "4 Mamba-2 heads of 8"),
    (dict(n_routed_experts=0), ValueError, "n_routed_experts"),
    (dict(total_ut_steps=2), NotImplementedError, "total_ut_steps"),
    (dict(hc_mult=2), NotImplementedError, "hc_mult"),
    (dict(tie_word_embeddings=True), NotImplementedError,
     "tie_word_embeddings")])
def test_resolve_refuses(change, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **change))


def test_a_latent_space_needs_the_pattern():
    with pytest.raises(NotImplementedError, match="moe_latent_size"):
        causal_lm.resolve(dict(
            vocab_size=96, hidden_size=16, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=48, num_experts=4,
            num_experts_per_tok=2, moe_latent_size=8))


# ---- the program's shape ----------------------------------------------------

def test_program_has_the_three_kinds_parameters_in_order(program):
    main = program[0]
    names = [p.name for p in main.global_block().all_parameters()]
    mixer = ["norm", "w_in", "conv", "conv.bias", "dt_bias", "a_log", "d",
             "gated_norm", "w_out"]
    experts = ["latent_down", "experts.router", "experts.expert_bias",
               "experts.w_up", "experts.w_down", "latent_up",
               "shared_expert.w_up", "shared_expert.w_down"]
    own = {"M": mixer, "*": ["norm", "wq", "wk", "wv", "wo"],
           "E": ["norm"] + experts, "-": ["norm", "w_up", "w_down"]}
    expected = ["embedding"] + [
        "layer_%d.%s" % (i, role) for i, letter in enumerate(PATTERN)
        for role in own[letter]] + ["final_norm"] + [
        "layer_6." + role for role in ["enorm", "hnorm", "eh_proj",
                                       "input_norm", "wq", "wk", "wv", "wo",
                                       "post_attention_norm"] + experts
        + ["shared_head.norm"]] + ["head"]
    assert names == expected
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    # [z 64; x 64, B and C 2 groups of 16; dt 8]
    assert shapes["layer_0.w_in"] == (32, 64 + 64 + 2 * 2 * 16 + 8)
    assert shapes["layer_0.gated_norm"] == (64,)
    assert shapes["layer_1.latent_down"] == (32, 16)
    assert shapes["layer_1.experts.router"] == (32, 16)     # reads 32
    assert shapes["layer_1.experts.w_up"] == (16, 16, 24)   # experts read 16
    assert shapes["layer_1.experts.w_down"] == (16, 24, 16)
    assert shapes["layer_1.shared_expert.w_up"] == (32, 40)
    bias = main.global_block().var("layer_1.experts.expert_bias")
    assert bias.trainable is False


def test_an_expert_layers_op_says_what_is_not_the_default(program):
    block = program[0].global_block()
    routed = [op for op in block.ops if op.type == "moe_ffn"]
    assert len(routed) == 3                 # two `E` layers and the module's
    for op in routed:
        assert "WGate" not in op.inputs and op.input("RouterX")
        assert op.attrs["activation"] == "relu2"
        assert op.attrs["scoring"] == "sigmoid" and op.attrs["scale"] == 5.0
        assert op.attrs["norm_epsilon"] == 1e-20
    assert not [op for op in block.ops if op.type == "rotary_embedding"]
    scans = [op for op in block.ops if op.type == "ssd_scan"]
    assert len(scans) == 2
    assert all(len(block.var(op.input("B")[0]).shape) == 4 for op in scans)
    norms = [op for op in block.ops if op.type == "rms_norm"
             and op.input("Scale")[0].endswith("gated_norm")]
    assert [op.attrs["begin_scale_axis"] for op in norms] == [2, 2]


def test_the_counters_tell_the_layers_apart():
    def total(snapshot, family, **where):
        return sum(value for labels, value
                   in snapshot.get(family, {"samples": []})["samples"]
                   if all(labels.get(k) == v for k, v in where.items()))

    before = REGISTRY.snapshot()
    main, startup, out = _build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out["loss"]])
    after = REGISTRY.snapshot()
    for where, count in (
            (dict(mixer="mamba2", ffn="none", branches="1", latent="0"), 2),
            (dict(mixer="attention", ffn="none", branches="1"), 1),
            (dict(mixer="none", ffn="experts", branches="1", latent="16",
                  gated="false", shared="40"), 2),
            (dict(mixer="none", ffn="dense", branches="1", gated="false"), 1),
            (dict(mixer="attention", ffn="experts", branches="2",
                  module="mtp", latent="16"), 1)):
        assert total(after, "ptpu_causal_lm_layers_total", **where) \
            - total(before, "ptpu_causal_lm_layers_total", **where) == count
    where = dict(top_k="3", experts="16", held="16", activation="relu2",
                 gated="false", router_input="32", scoring="sigmoid",
                 bias="true", scale="5", rows="all")
    assert total(after, "ptpu_moe_layers_total", **where) \
        - total(before, "ptpu_moe_layers_total", **where) == 3
    where = dict(heads="8", head_dim="8", states="16", groups="2",
                 path="scan")
    assert total(after, "ptpu_ssd_scan_layers_total", **where) \
        - total(before, "ptpu_ssd_scan_layers_total", **where) == 2


# ---- the program against the reference --------------------------------------

@pytest.mark.parametrize("name", ["loss", "logits", "main_loss", "mtp_loss",
                                  "mtp_logits"])
def test_forward_matches_the_reference(program, want, name):
    assert _error(program[3][name], want[name]) < TOLERANCE


def test_expert_load_is_the_references(program, want):
    load = np.asarray(program[3]["load"])
    # two `E` layers and the module's: 3 x B x T x top_k assignments
    assert load.sum() == 3 * B * T * 3
    np.testing.assert_array_equal(load, np.asarray(want["load"]))


@pytest.mark.parametrize("layer", range(len(PATTERN)))
def test_every_layer_kinds_output_matches(program, want, layer):
    assert _error(program[3]["states"][layer], want["layers"][layer]) \
        < TOLERANCE, KINDS[PATTERN[layer]]


@pytest.mark.parametrize("name", PARAMETERS)
def test_gradient_matches_jax_grad_of_the_reference(program, want, name):
    assert _error(program[3]["grads"][name], want["grads"][name]) \
        < TOLERANCE


def test_the_expert_bias_has_no_gradient(program):
    block = program[0].global_block()
    assert "layer_1.experts.expert_bias" not in PARAMETERS
    assert not block.has_var("layer_1.experts.expert_bias@GRAD")


# ---- the scan op with groups ------------------------------------------------

def _scan_inputs(groups, heads=8, p=64, n=16, t=40, seed=3):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(2, t, heads, p), jnp.float32)
    delta = jax.nn.softplus(jnp.asarray(rng.randn(2, t, heads) - 1.0,
                                        jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.rand(heads), jnp.float32))
    b, c = (jnp.asarray(rng.randn(2, t, groups, n) * 0.5, jnp.float32)
            for _ in range(2))
    d = jnp.asarray(rng.rand(heads) + 0.5, jnp.float32)
    return x, delta, a, b, c, d


@pytest.mark.parametrize("path", ["scan", "kernel"])
@pytest.mark.parametrize("groups", [2, 4])
def test_scan_with_groups_matches_the_recurrence(groups, path):
    """Forward and the six gradients, chunks of 16 over a T of 40 (padded):
    head h reads group h // (H / G). On the kernel path (the interpreter
    here) a grid step's heads are one group's: 8 heads of 64, two a lane
    tile, in 2 groups (two tiles a step) and in 4 (one)."""
    args = _scan_inputs(groups)
    g = jnp.asarray(np.random.RandomState(4).randn(*args[0].shape),
                    jnp.float32)

    def chunked(*args):
        return ssd.ssd_scan(*args, path=path, chunk=16)

    with jax.default_matmul_precision("highest"):
        want_y, pull = jax.vjp(reference.ssd_scan, *args)
        got_y, got_pull = jax.vjp(chunked, *args)
        assert _error(got_y, want_y) < TOLERANCE
        for got, wanted in zip(got_pull(g), pull(g)):
            assert _error(got, wanted) < TOLERANCE


def test_one_group_given_as_four_dimensions_is_the_one_group():
    x, delta, a, b, c, d = _scan_inputs(1)
    whole = ssd.ssd_scan(x, delta, a, b[:, :, 0], c[:, :, 0], d, path="scan",
                         chunk=16)
    grouped = ssd.ssd_scan(x, delta, a, b, c, d, path="scan", chunk=16)
    assert _error(grouped, whole) < 1e-6


def test_groups_that_do_not_divide_the_heads_are_refused():
    x, delta, a, b, c, d = _scan_inputs(3)
    with pytest.raises(ValueError, match="G a divisor of H"):
        ssd.ssd_scan(x, delta, a, b, c, d, path="scan")


# ---- the ungated experts alone ----------------------------------------------

def _expert_inputs(seed=6, n=48, d=32, latent=16, f=24, e=16):
    rng = np.random.RandomState(seed)
    return dict(
        m=jnp.asarray(rng.randn(n, d), jnp.float32),
        down=jnp.asarray(rng.randn(d, latent) * 0.2, jnp.float32),
        router=jnp.asarray(rng.randn(d, e), jnp.float32),
        bias=jnp.asarray(rng.randn(e) * 0.1, jnp.float32),
        wu=jnp.asarray(rng.randn(e, latent, f) * 0.3, jnp.float32),
        wd=jnp.asarray(rng.randn(e, f, latent) * 0.3, jnp.float32))


ROUTED = dict(top_k=3, norm_topk_prob=True, activation="relu2",
              scoring="sigmoid", scale=5.0, norm_eps=1e-20)


@pytest.mark.parametrize("held, first", [(16, 0), (4, 8), (2, 14)])
def test_ungated_experts_match_the_reference_with_every_gradient(held,
                                                                 first):
    """routed_ffn without w_gate, the router reading another width than the
    experts: every expert held, a share of 4 and a share of 2 < top_k (the
    row buffer cut to 2 N rows), forward and the gradients of the experts'
    input, the router's input, the router and both matrices."""
    w = _expert_inputs()
    c = causal_lm.resolve(CFG)
    own = slice(first, first + held)

    def program(m, router, wu, wd):
        return moe.routed_ffn(m @ w["down"], router, None, wu, wd,
                              router_x=m, expert_bias=w["bias"],
                              first_expert=first, **ROUTED)[0]

    def plain(m, router, wu, wd):
        return reference.routed_experts(
            m @ w["down"], router, None, wu, wd, c, router_x=m,
            first_expert=first, expert_bias=w["bias"])[0]

    args = (w["m"], w["router"], w["wu"][own], w["wd"][own])
    g = jnp.asarray(np.random.RandomState(8).randn(48, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_y, pull = jax.vjp(plain, *args)
        got_y, got_pull = jax.vjp(program, *args)
        assert _error(got_y, want_y) < TOLERANCE
        for got, wanted in zip(got_pull(g), pull(g)):
            assert _error(got, wanted) < TOLERANCE


def test_a_gated_activation_is_refused_without_a_gate():
    w = _expert_inputs()
    with pytest.raises(ValueError, match="relu2"):
        moe.routed_ffn(w["m"] @ w["down"], w["router"], None, w["wu"],
                       w["wd"], top_k=3, router_x=w["m"], activation="silu")
    # the layer refuses it when the op is appended
    with pytest.raises(ValueError, match="relu2"):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", [8, 16], dtype="float32")
            fluid.layers.moe_ffn(x, 4, 8, 2, gated=False, activation="silu",
                                 param_attr=fluid.ParamAttr(name="e"))


# ---- the share tied to the model --------------------------------------------

def _mixer_weights(rng, d, heads, p, n, groups):
    di = heads * p
    columns = 2 * di + 2 * groups * n + heads
    return dict(
        w_in=jnp.asarray(rng.randn(d, columns) * 0.2, jnp.float32),
        conv=jnp.asarray(rng.randn(di + 2 * groups * n, 4) * 0.3,
                         jnp.float32),
        conv_bias=jnp.asarray(rng.randn(di + 2 * groups * n) * 0.1,
                              jnp.float32),
        dt_bias=jnp.asarray(rng.randn(heads) * 0.3, jnp.float32),
        a_log=jnp.asarray(np.log(rng.rand(heads) * 4 + 0.5), jnp.float32),
        d=jnp.asarray(rng.rand(heads) + 0.5, jnp.float32),
        norm=jnp.asarray(rng.rand(di) + 0.5, jnp.float32),
        w_out=jnp.asarray(rng.randn(di, d) * 0.2, jnp.float32))


def _mixer_share(w, rank, heads, p, n, groups):
    """Tensor rank `rank` of `groups`: its group of B and C with the
    group's heads: the columns of W_in and the convolution's channels of
    its z, x, B, C and dt, its heads' scalars, its channels of the norm's
    weight and its rows of W_out."""
    di, held = heads * p, heads // groups
    z = np.arange(rank * held * p, (rank + 1) * held * p)
    xbc = np.concatenate([z, di + np.arange(rank * n, (rank + 1) * n),
                          di + groups * n + np.arange(rank * n,
                                                      (rank + 1) * n)])
    dt = np.arange(rank * held, (rank + 1) * held)
    columns = np.concatenate([z, di + xbc, 2 * di + 2 * groups * n + dt])
    return (w["w_in"][:, columns], w["conv"][xbc], w["conv_bias"][xbc],
            w["dt_bias"][dt], w["a_log"][dt], w["d"][dt], w["norm"][z],
            w["w_out"][z])


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_of_a_layer_sum_to_the_whole_layer(kind):
    """One layer of each kind on one input. `M`: tensor rank g of 4 holds
    Mamba group g with its 2 heads, and the norm a group makes its part the
    whole layer's: the four partial sums behind W_out add up to the uncut
    mixer of 4 groups. `*`: rank g holds query heads 2g, 2g + 1 on
    key/value head g. `E`: expert rank k of 4 holds experts 4k .. 4k + 3
    and computes them with the Program's routed_ffn, every rank routing
    over all 16 from the hidden state; the four partial sums through W_up
    plus the shared expert, counted once, are the uncut reference's
    layer."""
    rng = np.random.RandomState(9)
    d, t = 32, 24
    x = jnp.asarray(rng.randn(1, t, d), jnp.float32)
    a = reference.rms_norm(x, jnp.asarray(rng.rand(d) + 0.5, jnp.float32),
                           1e-5)
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            heads, p, n, groups = 8, 8, 16, 4
            w = _mixer_weights(rng, d, heads, p, n, groups)
            whole = reference.mamba2(
                a, w["w_in"], w["conv"], w["conv_bias"], w["dt_bias"],
                w["a_log"], w["d"], w["norm"], w["w_out"], 1e-5,
                groups=groups)
            parts = [reference.mamba2(
                a, *_mixer_share(w, rank, heads, p, n, groups), 1e-5)
                for rank in range(groups)]
        elif kind == "*":
            hd = 8
            c = causal_lm.resolve(CFG)
            cl = reference.layer_config(c, 3)
            wq = jnp.asarray(rng.randn(d, 8 * hd) * 0.2, jnp.float32)
            wk, wv = (jnp.asarray(rng.randn(d, 4 * hd) * 0.2, jnp.float32)
                      for _ in range(2))
            wo = jnp.asarray(rng.randn(8 * hd, d) * 0.2, jnp.float32)
            pos = jnp.arange(t)[None]
            whole = reference.attention(a, pos, wq, wk, wv, None, None, wo,
                                        cl)
            parts = [reference.attention(
                a, pos, wq[:, 16 * i:16 * i + 16], wk[:, 8 * i:8 * i + 8],
                wv[:, 8 * i:8 * i + 8], None, None, wo[16 * i:16 * i + 16],
                cl) for i in range(4)]
        else:
            c = causal_lm.resolve(CFG)
            w = _expert_inputs(seed=10, n=t)
            up = jnp.asarray(rng.randn(16, d) * 0.2, jnp.float32)
            shared = (jnp.asarray(rng.randn(d, 40) * 0.2, jnp.float32),
                      jnp.asarray(rng.randn(40, d) * 0.2, jnp.float32))
            m = a.reshape(t, d)
            whole = reference.routed_experts(
                m @ w["down"], w["router"], None, w["wu"], w["wd"], c,
                router_x=m, expert_bias=w["bias"])[0] @ up \
                + reference.relu2_mlp(m, *shared)
            parts = [moe.routed_ffn(
                m @ w["down"], w["router"], None, w["wu"][4 * k:4 * k + 4],
                w["wd"][4 * k:4 * k + 4], router_x=m, expert_bias=w["bias"],
                first_expert=4 * k, **ROUTED)[0] @ up for k in range(4)]
            parts.append(reference.relu2_mlp(m, *shared))
    assert _error(sum(parts), whole) < TOLERANCE
    assert _error(parts[0], whole) > 0.1


def test_the_op_takes_a_share_through_the_layer():
    """The cut the cell has, through the Program: chip 2 of 4 holds experts
    8 .. 11 of 16, and its `E` layer's state is the reference's with
    first_expert 8."""
    cfg = dict(
        CFG, num_hidden_layers=2, num_nextn_predict_layers=0,
        n_routed_experts=4, share=dict(chips=4, chip=2, published=dict(
            num_hidden_layers=6, n_routed_experts=16)))
    main, startup, out = _build(cfg)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        feed = {k: v for k, v in _feed().items() if k != "labels_next"}
        loss, logits, load = exe.run(
            main, feed=feed,
            fetch_list=[out["loss"], out["logits"], out["load"]])
    feed = {k: jnp.asarray(v) for k, v in feed.items()}
    want_loss, (want_logits, want_load) = reference.loss_fn(
        cfg, weights, feed["ids"], feed["pos"], feed["labels"])
    assert _error(loss, want_loss) < TOLERANCE
    assert _error(logits, want_logits) < TOLERANCE
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert main.global_block().var("layer_1.experts.w_up").shape[0] == 4


# ---- what the other models' programs keep -----------------------------------

GRANITE = dict(
    vocab_size=96, hidden_size=16, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    shared_intermediate_size=48,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_conv_bias=True,
    mamba_proj_bias=False, num_local_experts=0, num_experts_per_tok=0,
    position_embedding_type="nope", embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_scaling=8, tie_word_embeddings=True)


def test_one_group_builds_the_op_it_always_was():
    """granite-4.0-h-micro's mixer: B and C [B, T, N], no `groups` in the
    scan's counter labels, the gated norm over all d_i channels with no
    `begin_scale_axis`, the parameter names it had, and no label of the
    one-branch layers on its layers' counter."""
    main, _, _ = _build(GRANITE)
    block = main.global_block()
    scans = [op for op in block.ops if op.type == "ssd_scan"]
    assert len(scans) == 3 and not any(
        k for op in scans for k in op.attrs if not k.startswith("__"))
    assert all(len(block.var(op.input("B")[0]).shape) == 3 for op in scans)
    for op in block.ops:
        if op.type == "rms_norm":
            assert "begin_scale_axis" not in op.attrs
    names = [p.name for p in block.all_parameters()]
    assert names[:11] == ["embedding", "layer_0.input_norm", "layer_0.w_in",
                          "layer_0.conv", "layer_0.conv.bias",
                          "layer_0.dt_bias", "layer_0.a_log", "layer_0.d",
                          "layer_0.gated_norm", "layer_0.w_out",
                          "layer_0.post_attention_norm"]
    counted = [labels for labels, _ in REGISTRY.snapshot()[
        "ptpu_causal_lm_layers_total"]["samples"]
        if labels.get("ffn") == "dense" and labels.get("mixer") == "mamba2"]
    assert counted and not any(
        key in labels for labels in counted
        for key in ("branches", "latent", "gated"))


# The training programs of the cells that share the code this file's model
# touched (the scan op, moe_ffn and its routing, feed_forward, the layer
# loop), at published widths, as a digest of every op's type, attrs, inputs
# and outputs and every parameter's name, shape and whether it trains. The
# digests are the PARENT's (commit a7d7ed2, computed by this function from a
# `git archive` of it): a program that moved fails here by name. After a
# change that is meant to move one, print the new digest with
# `python tests/unittests/test_causal_lm_nemotron_3_super.py <cell>`.
PROGRAMS = {
    "olmoe_1b_7b_train_t4096": "cac95f97a9179a2e",
    "smallthinker_21b_a3b_train_t8192": "9a0bbaba4f432d73",
    "qwen3_next_80b_a3b_train_t4096": "f38beacbd17761b4",
    "ouro_2_6b_train_t4096": "5c8470d3a3397a9b",
    "lfm2_8b_a1b_train_t8192": "2d9ecbd58e2c9dcf",
    "xing4_0_29b_a4b_train_1seq": "6c68ccc9aff02b0d",
    "glm_4_7_flash_train_t4096": "4d23a2cf309537ad",
    "phi4_mini_flash_train_t8192": "9b149c910b830c3e",
    "granite_4_0_h_micro_train_t2048": "91597c100d9dff26",
}


def program_digest(cell_name):
    sys.path.insert(0, REPO)
    from benchmark import manifest
    cell = manifest.load_cell(os.path.join(REPO, "BENCHMARK.json"),
                              cell_name)
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


@pytest.mark.parametrize("cell", sorted(PROGRAMS))
def test_a_cell_that_shares_the_code_builds_the_program_it_did(cell):
    assert program_digest(cell) == PROGRAMS[cell]


if __name__ == "__main__":
    for name in sys.argv[1:]:
        print('    "%s": "%s",' % (name, program_digest(name)))
