"""models/causal_lm.py at LFM2's shape (tiny widths, seeded weights): the
Program against models/causal_lm_reference.py for loss, logits and every
parameter's gradient (the tied matrix's is the sum of its two uses, the
expert bias has none); the bias in the choice and not in the weights; the
gated convolution on both paths; what `resolve()` refuses; and that the
builder emits the programs it emitted before for the configurations that
have none of the new keys."""
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# chip 1 of the 2 that share a layer: experts 4..7 of 8, half a vocabulary
# of 128; a leading dense layer with a short_conv mixer, then one period:
# attention (4 query heads on 2 key/value heads of 8), three short_conv
CFG = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    moe_intermediate_size=8, num_experts=4, num_experts_per_tok=3,
    norm_topk_prob=True, norm_eps=1e-5, rope_theta=1e6, conv_L_cache=3,
    conv_bias=False, num_dense_layers=1, use_expert_bias=True,
    routed_scaling_factor=1,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    tie_word_embeddings=True, router_scoring="sigmoid", qk_norm="head",
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0, initializer_range=0.3,
    embedding_initializer_range=0.5, expert_bias_initializer_range=0.3,
    share=dict(chips=2, chip=1, published=dict(num_experts=8,
                                               vocab_size=128)))
B, T = 2, 48
TOLERANCE = 2e-4                # float32 against float32: another order of
#                                 sums (gradients read 4e-5 at most)


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed(seed=0):
    tok = np.random.RandomState(seed).randint(0, CFG["vocab_size"],
                                              (B, T + 1))
    return {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:, None]}


COUNTED = {
    "conv_dense": ("ptpu_causal_lm_layers_total", dict(
        mixer="short_conv", rotary_dim="0", gate="false", conv="3",
        ffn="dense", shared="0", sandwich="false", module="trunk",
        reads="own", differential="false")),
    "conv_experts": ("ptpu_causal_lm_layers_total", dict(
        mixer="short_conv", rotary_dim="0", gate="false", conv="3",
        ffn="experts", shared="0", sandwich="false", module="trunk",
        reads="own", differential="false")),
    "attention_experts": ("ptpu_causal_lm_layers_total", dict(
        mixer="attention", rotary_dim="8", gate="false", conv="0",
        ffn="experts", shared="0", sandwich="false", module="trunk",
        reads="own", differential="false")),
    "tied_head": ("ptpu_causal_lm_heads_total", dict(tied="true")),
    "moe": ("ptpu_moe_layers_total", dict(
        top_k="3", experts="8", held="4", activation="silu",
        router_input="own", path=moe.GROUPED_MATMUL, rows="held",
        scoring="sigmoid", bias="true", scale="1")),
    "conv_op": ("ptpu_causal_conv_layers_total", dict(
        path="xla", width="3", channels="32", activation="none"))}


def _counts():
    return {k: REGISTRY.counter(name, "").value(**labels)
            for k, (name, labels) in COUNTED.items()}


def _run_program(amp=False, cfg=CFG):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    before = _counts()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        loss, logits, load = causal_lm.build_train(cfg, T)
    block = main.global_block()
    params = block.all_parameters()
    trained = [p for p in params if p.name + "@GRAD" in block.vars]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits, load]
                      + [p.name + "@GRAD" for p in trained])
        state = set(scope.names())
        after_step = {p.name: np.asarray(scope.get(p.name)) for p in params}
    after = _counts()
    got = {"loss": out[0], "logits": out[1], "expert_load": out[2],
           "grads": dict(zip((p.name for p in trained), out[3:])),
           "counted": {k: after[k] - before[k] for k in after},
           "ops": [op.type for op in block.ops], "state": state,
           "after_step": after_step}
    return params, weights, got


@pytest.fixture(scope="module")
def program():
    return _run_program()


@pytest.fixture(scope="module")
def want(program):
    params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, load)), grads = jax.jit(
        lambda p: reference.loss_and_grads(CFG, p, feed["ids"], feed["pos"],
                                           feed["labels"]))(weights)
    return {"loss": loss, "logits": logits, "expert_load": load,
            "grads": dict(zip((p.name for p in params), grads))}


def test_resolve_reads_lfm2s_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["short_conv", "attention"] \
        + ["short_conv"] * 3
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 4
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (8, 4, 4)
    assert (c["intermediate_size"], c["dense_intermediate_size"]) == (8, 48)
    assert c["rms_norm_eps"] == 1e-5            # norm_eps, under our name
    assert c["head_dim"] == c["rotary_dim"] == 8
    # what modeling_lfm2_moe.py always does is the config's to say, key by
    # key: the model's name sets nothing
    named = causal_lm.resolve(dict(
        {k: v for k, v in CFG.items() if k not in (
            "router_scoring", "qk_norm", "use_expert_bias")},
        model_type="lfm2_moe"))
    assert (named["router_scoring"], named["qk_norm"]) == ("softmax", False)
    # without layer_types the interval still decides; without experts every
    # layer's FFN is dense whatever num_dense_layers says
    plain = causal_lm.resolve(dict(
        vocab_size=8, hidden_size=8, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=4, num_dense_layers=1))
    assert plain["mixer_layers"] == ["attention"] * 2
    assert plain["ffn_layers"] == ["dense"] * 2
    assert plain["dense_intermediate_size"] == 4


@pytest.mark.parametrize("edit,error,match", [
    (dict(conv_bias=True), NotImplementedError, "conv_bias"),
    (dict(router_scoring="tanh"), NotImplementedError, "router_scoring"),
    (dict(router_aux_loss_coef=0.01), NotImplementedError,
     "router_aux_loss_coef"),
    (dict(router_z_loss_coef=0.001), NotImplementedError,
     "router_z_loss_coef"),
    (dict(router_scoring="softmax"), NotImplementedError,
     "use_expert_bias"),
    (dict(layer_types=["conv", "full_attention", "window", "conv", "conv"]),
     NotImplementedError, "layer_types"),
    (dict(layer_types=["conv", "full_attention"]), NotImplementedError,
     "layer_types"),
    (dict(layer_types=["conv", "conv", "full_attention", "conv", "conv",
                       "conv"]), NotImplementedError, "6 for 5 layers"),
    (dict(total_ut_steps=2, num_experts=0), NotImplementedError,
     "short_conv"),
    (dict(tie_word_embeddings="head_only"), NotImplementedError,
     "tie_word_embeddings")])
def test_resolve_refuses_what_the_builder_cannot_build(edit, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_resolve_names_the_key_a_short_convolution_lacks():
    cfg = {k: v for k, v in CFG.items() if k != "conv_L_cache"}
    with pytest.raises(ValueError, match="conv_L_cache"):
        causal_lm.resolve(cfg)


def test_moe_ffn_refuses_a_bias_under_softmax_and_an_unknown_scoring():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data("x", [6, 16])
        with pytest.raises(ValueError, match="expert bias"):
            fluid.layers.moe_ffn(x, 4, 8, 2, expert_bias_attr=True)
        with pytest.raises(ValueError, match="scoring"):
            fluid.layers.moe_ffn(x, 4, 8, 2, scoring="tanh")


def test_program_has_the_models_shapes(program):
    params, weights, got = program
    conv = [(32,), (32, 96), (32, 3), (32, 32), (32,)]
    full = [(32,), (32, 32), (32, 16), (32, 16), (8,), (8,), (32, 32), (32,)]
    dense = [(32, 48), (32, 48), (48, 32)]
    experts = [(32, 8), (8,), (4, 32, 8), (4, 32, 8), (4, 8, 32)]
    assert [w.shape for w in weights] == [(64, 32)] + conv + dense + full \
        + experts + 3 * (conv + experts) + [(32,)]
    names = [p.name for p in params]
    assert names[:5] == ["embedding", "layer_0.input_norm", "layer_0.w_in",
                         "layer_0.conv", "layer_0.w_out"]
    assert names[-6:-1] == ["layer_4.experts.router",
                            "layer_4.experts.expert_bias",
                            "layer_4.experts.w_gate", "layer_4.experts.w_up",
                            "layer_4.experts.w_down"]
    assert "head" not in names              # tied: no parameter of its own
    for kind, count in (("causal_conv1d", 4), ("fused_attention", 1),
                        ("rotary_embedding", 2), ("moe_ffn", 4),
                        ("lookup_table", 1), ("matmul", 1)):
        assert got["ops"].count(kind) == count, kind
    assert got["counted"] == {"conv_dense": 1, "conv_experts": 3,
                              "attention_experts": 1, "tied_head": 1,
                              "moe": 4, "conv_op": 4}


def test_program_agrees_with_the_reference(program, want):
    _, _, got = program
    assert _error(got["loss"], want["loss"]) < 2e-6
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    assert got["expert_load"].shape == (8,)
    assert got["expert_load"].sum() == 4 * 3 * B * T    # four expert layers
    assert 0 < got["expert_load"][4:].sum() < got["expert_load"].sum()


def test_every_gradient_agrees_with_the_reference(program, want):
    """The tied matrix's gradient is the lookup's scatter-add plus the
    head's matmul, in one variable; the reference's is jax.grad's through
    both uses of one array."""
    params, _, got = program
    trained = [p for p in params if p.trainable]
    assert len(trained) == len(params) - 4
    errors = {p.name: _error(got["grads"][p.name], want["grads"][p.name])
              for p in trained}
    assert max(errors.values()) < TOLERANCE, errors
    assert all(np.abs(want["grads"][p.name]).max() > 0 for p in trained)


def test_the_tied_gradient_is_the_sum_of_its_two_uses(program, want):
    """Cut either use in the reference and the embedding's gradient is no
    longer the Program's."""
    params, weights, got = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}

    def loss_with(embedding, head):
        # an untied copy of the model: the head reads `head`
        logits = reference.passes(
            dict(CFG, tie_word_embeddings=False),
            [embedding] + weights[1:] + [head.T], feed["ids"],
            feed["pos"])[0][-1]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   feed["labels"], axis=-1)
        return nll.mean()

    e = jnp.asarray(weights[0])
    lookup, head = jax.grad(loss_with, argnums=(0, 1))(e, e)
    assert _error(got["grads"]["embedding"], lookup + head) < TOLERANCE
    assert _error(got["grads"]["embedding"], lookup) > 0.1
    assert _error(got["grads"]["embedding"], head) > 0.1


def test_the_bias_is_held_and_not_trained(program, want):
    """No gradient variable, no Adam moments, not moved by a step; the
    reference's gradient for it is exactly zero (lax.top_k's indices carry
    none)."""
    params, weights, got = program
    biases = [p for p in params if p.name.endswith("expert_bias")]
    assert len(biases) == 4 and not any(p.trainable for p in biases)
    for p, w in zip(params, weights):
        moments = [n for n in got["state"]
                   if n.startswith("moment") and "_%s_" % p.name in n]
        if p in biases:
            assert p.name not in got["grads"] and not moments
            np.testing.assert_array_equal(got["after_step"][p.name], w)
            assert not np.asarray(want["grads"][p.name]).any()
            assert 0.05 < np.abs(w).mean() < 1.0        # drawn, not zeros
        else:
            assert len(moments) == 2, (p.name, moments)
            assert (got["after_step"][p.name] != w).any(), p.name


def test_the_bias_is_the_same_draw_under_every_run_seed():
    """The bias is drawn from a stream of its own, layer by layer a draw of
    its own, while every weight around it follows the program's seed."""
    def biases(seed):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            causal_lm.build_train(CFG, T)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            return [np.asarray(scope.get("layer_%d.experts.%s" % (i, role)))
                    for i in (1, 2) for role in ("expert_bias", "router")]

    b1, r1, b2, _ = biases(3)
    c1, s1, c2, _ = biases(4)
    np.testing.assert_array_equal(b1, c1)
    np.testing.assert_array_equal(b2, c2)
    assert (b1 != b2).any() and (r1 != s1).any()
    assert 0.1 < np.concatenate([b1, b2]).std() < 0.6      # range 0.3


def test_amp_stays_close_to_the_reference(want):
    _, _, got = _run_program(amp=True)
    assert _error(got["loss"], want["loss"]) < 5e-3
    assert got["expert_load"].sum() == 4 * 3 * B * T


# --- the router ---------------------------------------------------------------

def _router_inputs(seed=1, n=40, d=16, e=8, f=8):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, e) * 0.5, jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * 0.3, jnp.float32)
    return x, router, wg, wu, wd


REF_C = {"num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
         "router_scoring": "sigmoid"}


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_routed_ffn_scores_by_sigmoid_like_the_reference(norm, scale):
    x, router, wg, wu, wd = _router_inputs()
    bias = jnp.asarray(np.random.RandomState(2).randn(8) * 0.4, jnp.float32)
    c = dict(REF_C, norm_topk_prob=norm, routed_scaling_factor=scale)
    with jax.default_matmul_precision("highest"):
        got = moe.routed_ffn(x, router, wg, wu, wd, 2, norm,
                             scoring="sigmoid", expert_bias=bias, scale=scale)
        want = reference.routed_experts(x, router, wg, wu, wd, c,
                                        expert_bias=bias)
    assert _error(got[0], want[0]) < 1e-5
    np.testing.assert_array_equal(got[3], want[3])
    # the two terms of a softmax router are zeros here
    assert not np.asarray(got[1]).any() and not np.asarray(got[2]).any()


def test_selection_follows_s_plus_b_and_weights_follow_s():
    """One token, scores known: s = sigmoid(logits) descending over experts
    0..3. A bias that lifts expert 2 over expert 1 changes WHICH experts
    run (0 and 2 for 0 and 1) and leaves expert 0's weight what s alone
    makes it: s_0 / (s_0 + s_2 + 1e-6), not a value with b in it."""
    d, e = 4, 4
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]], jnp.float32)
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], jnp.float32)
    router = jnp.zeros((d, e), jnp.float32).at[0].set(logits[0])
    # expert i returns (silu(1) * 1) * (i + 1) in channel 0
    wg = jnp.zeros((e, d, 1), jnp.float32).at[:, 0, 0].set(1.0)
    wu = wg
    wd = jnp.zeros((e, 1, d), jnp.float32).at[:, 0, 0].set(
        jnp.arange(1.0, e + 1))
    s = np.asarray(jax.nn.sigmoid(logits[0]))
    unit = float(jax.nn.silu(1.0))

    def run(bias):
        with jax.default_matmul_precision("highest"):
            out, _, _, load = moe.routed_ffn(
                x, router, wg, wu, wd, 2, True, scoring="sigmoid",
                expert_bias=bias)
        return float(out[0, 0]), np.asarray(load)

    out, load = run(jnp.zeros((e,), jnp.float32))
    np.testing.assert_array_equal(load, [1, 1, 0, 0])
    assert out == pytest.approx(
        unit * (s[0] * 1 + s[1] * 2) / (s[0] + s[1] + 1e-6), rel=1e-5)
    bias = jnp.asarray([0.0, 0.0, 0.2, 0.0], jnp.float32)
    assert s[2] + 0.2 > s[1]
    out, load = run(bias)
    np.testing.assert_array_equal(load, [1, 0, 1, 0])
    assert out == pytest.approx(
        unit * (s[0] * 1 + s[2] * 3) / (s[0] + s[2] + 1e-6), rel=1e-5)
    # with the bias in the weights it would read otherwise
    wrong = unit * (s[0] * 1 + (s[2] + 0.2) * 3) / (s[0] + s[2] + 0.2)
    assert abs(out - wrong) > 0.05
    # a bias that flips nothing changes nothing
    out_small, load_small = run(bias * 0.1)
    np.testing.assert_array_equal(load_small, [1, 1, 0, 0])


def test_the_routers_gradient_comes_through_s_and_the_bias_has_none():
    x, router, wg, wu, wd = _router_inputs(3)
    bias = jnp.asarray(np.random.RandomState(5).randn(8) * 0.4, jnp.float32)
    g = jnp.asarray(np.random.RandomState(6).randn(*x.shape), jnp.float32)

    def ours(router, bias):
        return (moe.routed_ffn(x, router, wg, wu, wd, 2, True,
                               scoring="sigmoid", expert_bias=bias)[0]
                * g).sum()

    def theirs(router, bias):
        return (reference.routed_experts(x, router, wg, wu, wd, REF_C,
                                         expert_bias=bias)[0] * g).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, argnums=(0, 1))(router, bias)
        want = jax.grad(theirs, argnums=(0, 1))(router, bias)
    assert _error(got[0], want[0]) < 1e-4 and np.abs(want[0]).max() > 0
    assert not np.asarray(got[1]).any() and not np.asarray(want[1]).any()


# --- the gated short convolution ------------------------------------------------

def _mixer_once(monkeypatch, pallas, t=48, d=128):
    """One forward and backward of causal_lm.short_conv over a fed x at a
    shape the kernels' blocks divide: ({fetch: value}, weights, x, dy)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    c = causal_lm.resolve(dict(CFG, hidden_size=d, num_attention_heads=4))
    rng = np.random.RandomState(4)
    x = rng.randn(2, t, d).astype("float32")
    dy = rng.randn(2, t, d).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", [t, d])
        xv.stop_gradient = False
        out = causal_lm.short_conv(xv, causal_lm._layer(c, 0))
        loss = fluid.layers.reduce_sum(out * fluid.layers.data("dy", [t, d]))
        fluid.backward.append_backward(loss)
        params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        names = ["y", "dx"] + ["d" + p.name for p in params]
        vals = exe.run(main, feed={"x": x, "dy": dy}, fetch_list=[
            out, "x@GRAD"] + [p.name + "@GRAD" for p in params])
    return dict(zip(names, vals)), weights, x, dy


@pytest.mark.parametrize("pallas", ["conv", "0"])
def test_short_conv_forward_and_gradients(monkeypatch, pallas):
    """Three taps, no activation, a gate going in and one coming out: the
    mixer through the Program, with the convolution's two Pallas passes in
    the interpreter ("conv") and with the jax.numpy passes ("0"), against
    jax.vjp of the reference's three shifted multiply-adds."""
    before = {path: REGISTRY.counter("ptpu_causal_conv_layers_total", "")
              .value(path=path, width="3", channels="128", activation="none")
              for path in ("kernel", "xla")}
    got, weights, x, dy = _mixer_once(monkeypatch, pallas)
    after = {path: REGISTRY.counter("ptpu_causal_conv_layers_total", "")
             .value(path=path, width="3", channels="128", activation="none")
             for path in before}
    ran = "kernel" if pallas == "conv" else "xla"
    assert {p: after[p] - before[p] for p in before} \
        == {ran: 1, {"kernel": "xla", "xla": "kernel"}[ran]: 0}
    w_in, w_conv, w_out = (jnp.asarray(w) for w in weights)
    assert w_conv.shape == (128, 3)
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(reference.short_conv, jnp.asarray(x), w_in, w_conv,
                         w_out)
        dx, dw_in, dw_conv, dw_out = vjp(jnp.asarray(dy))
    want = {"y": y, "dx": dx, "dlayer_0.w_in": dw_in,
            "dlayer_0.conv": dw_conv, "dlayer_0.w_out": dw_out}
    assert set(want) == set(got)
    errors = {k: _error(got[k], want[k]) for k in want}
    assert max(errors.values()) < 1e-4, errors


def test_short_conv_is_causal_with_zeros_before_the_sequence():
    """y_t reads v_(t-2), v_(t-1), v_t with w[:, 0] on the oldest: a change
    at token 5 moves tokens 5, 6, 7 of its own sequence and nothing else."""
    rng = np.random.RandomState(8)
    a = jnp.asarray(rng.randn(2, 12, 8), jnp.float32)
    w_in = jnp.asarray(rng.randn(8, 24), jnp.float32)
    w_conv = jnp.asarray(rng.randn(8, 3), jnp.float32)
    w_out = jnp.eye(8, dtype=jnp.float32)
    base = reference.short_conv(a, w_in, w_conv, w_out)
    moved = reference.short_conv(a.at[0, 5].add(1.0), w_in, w_conv, w_out)
    changed = np.abs(np.asarray(moved - base)).max(-1) > 1e-6
    assert changed[0].tolist() == [False] * 5 + [True] * 3 + [False] * 4
    assert not changed[1].any()
    # token 0 sees zeros before it: only the last tap weighs it
    b, gate, u = jnp.split(a @ w_in, 3, axis=-1)
    np.testing.assert_allclose(base[:, 0], gate[:, 0] * (b * u)[:, 0]
                               * w_conv[:, 2], rtol=1e-5)


def test_the_gate_multiplies_are_named_short_conv_on_device_time():
    """The two multiplies of each short_conv mixer, and their grad ops,
    lower under op:short_conv/... and op:short_conv_grad/...: the table by
    op type of `python -m paddle_tpu.profiler` has the gates as rows of
    their own, apart from a SwiGLU's multiply."""
    from paddle_tpu.core import lowering
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        causal_lm.build_train(CFG, T)
    ops = main.global_block().ops
    kinds = [lowering.scope_type(op) for op in ops]
    assert kinds.count("short_conv") == 8 == kinds.count("short_conv_grad")
    named = [op for op in ops if lowering.scope_type(op) == "short_conv"]
    assert {op.type for op in named} == {"elementwise_mul"}
    assert lowering.parse_op_scope("jit(fn)/" + lowering.op_scope(named[0])
                                   + "/mul")[0] == "short_conv"
    # the dense layer's SwiGLU multiply keeps its own name
    assert "elementwise_mul" in kinds and "elementwise_mul_grad" in kinds


# --- the builder emits yesterday's programs -------------------------------------

# (sha256 of every op's type, attrs and slots and of every parameter's name
# and shape, the number of those lines) of the training program each of the
# grid's four configurations built at the parent commit (0e5fb3d), at T = 32
# and a vocabulary of 64: none of them has a key PR 39 added, so the builder
# emits for them what it emitted then, op for op
PARENT_PROGRAMS = {
    "olmoe_1b_7b": ("783709248e34da44", 127),
    "smallthinker_21b_a3b": ("7b129097247f711a", 315),
    "qwen3_next_80b_a3b": ("8c52377528e76a47", 623),
    "ouro_2_6b": ("4279248872769230", 679)}


def _program_digest(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, vocab_size=64)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        causal_lm.build_train(cfg, 32, learning_rate=cfg["learning_rate"])
    lines = []
    for block in main.blocks:
        for op in block.ops:
            attrs = {k: v for k, v in sorted(op.attrs.items())
                     if isinstance(v, (bool, int, float, str, list, tuple,
                                       type(None)))
                     and k not in ("fwd_uid",)}
            lines.append("%s %s %s %s" % (
                op.type, json.dumps(attrs, sort_keys=True, default=str),
                sorted((s, len(n)) for s, n in op.inputs.items()),
                sorted((s, len(n)) for s, n in op.outputs.items())))
    for p in main.global_block().all_parameters():
        lines.append("%s %s %s" % (p.name, tuple(p.shape), p.trainable))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16], \
        len(lines)


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_builder_emits_the_parents_program(name):
    assert _program_digest(name) == PARENT_PROGRAMS[name]
