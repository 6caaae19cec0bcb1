"""models/causal_lm.py at Qwen3-Next's shape (tiny widths, seeded weights):
the Program against models/causal_lm_reference.py for loss, logits and every
parameter's gradient; the convolution, both norms, partial rotary, the
output gate and the shared expert each against their equation; the share
tied to the model; the counters' labels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY

# chip 1 of the 2 that share a layer: experts 4..7 of 8, half a vocabulary
# of 128; four layers, one period: three gated delta nets (2 key heads on 4
# value heads of 8), then gated full attention (4 query heads on 2 key/value
# heads of 16, rotary on the first 4 channels)
CFG = dict(
    qk_norm="head", norm_zero_centered=True, attention_gate=True,
    vocab_size=64, hidden_size=32,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=80, moe_intermediate_size=8,
    num_experts=4, num_experts_per_tok=3, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1e7, rope_scaling=None,
    partial_rotary_factor=0.25, full_attention_interval=4,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, shared_expert_intermediate_size=8,
    decoder_sparse_step=1, mlp_only_layers=[], hidden_act="silu",
    tie_word_embeddings=False, router_aux_loss_coef=0.0,
    router_z_loss_coef=0.0, initializer_range=0.3,
    share=dict(chips=2, chip=1, published=dict(num_experts=8,
                                               vocab_size=128)))
B, T = 2, 40                    # not a multiple of the delta rule's chunk
TOLERANCE = 5e-4                # float32 against float32: another order of
#                                 sums, and a chunked form of the recurrence
#                                 (gradients read 2.0e-4 at most, logits 7e-5)


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed(seed=0, t=T):
    tok = np.random.RandomState(seed).randint(0, CFG["vocab_size"],
                                              (B, t + 1))
    return {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(t), (B, t)).copy(),
            "labels": tok[:, 1:, None]}


def _counter(name, **labels):
    return REGISTRY.counter(name, "").value(**labels)


def _counts():
    from paddle_tpu.ops.kernel_config import DEFAULT_TILES
    from paddle_tpu.parallel.moe import GROUPED_MATMUL
    return {
        "moe": _counter("ptpu_moe_layers_total", top_k="3", experts="8",
                        held="4", activation="silu", router_input="own",
                        path=GROUPED_MATMUL, rows="held",
                        scoring="softmax", bias="false", scale="1"),
        "full": _counter("ptpu_attention_layers_total", kind="full",
                         window="0", q_heads="4", kv_heads="2", path="dense",
                         head_dim="16", heads_a_block="none"),
        "delta": _counter("ptpu_linear_attention_layers_total",
                          kind="gated_delta", k_heads="2", v_heads="4",
                          d_k="8", d_v="8",
                          chunk=str(DEFAULT_TILES["gdr"]["chunk"]),
                          path="scan"),
        "built_full": _counter("ptpu_causal_lm_layers_total",
                               mixer="attention", rotary_dim="4", gate="true",
                               conv="0", ffn="experts", shared="8",
                               sandwich="false", module="trunk",
                               reads="own", differential="false"),
        "built_delta": _counter("ptpu_causal_lm_layers_total",
                                mixer="gated_delta", rotary_dim="0",
                                gate="false", conv="4", ffn="experts",
                                shared="8", sandwich="false", module="trunk",
                                reads="own", differential="false")}


def _run_program(amp, pallas=None, monkeypatch=None, cfg=CFG, t=T):
    if monkeypatch is not None:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    before = _counts()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        loss, logits, load = causal_lm.build_train(cfg, t)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(t=t), fetch_list=[loss, logits, load]
                      + [p.name + "@GRAD" for p in params])
    after = _counts()
    got = {"loss": out[0], "logits": out[1], "expert_load": out[2],
           "grads": dict(zip((p.name for p in params), out[3:])),
           "counted": {k: after[k] - before[k] for k in after},
           "ops": [op.type for op in main.global_block().ops]}
    return params, weights, got


@pytest.fixture(scope="module")
def program():
    return _run_program(amp=False)


@pytest.fixture(scope="module")
def want(program):
    params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, load)), grads = jax.jit(
        lambda p: reference.loss_and_grads(CFG, p, feed["ids"], feed["pos"],
                                           feed["labels"]))(weights)
    return {"loss": loss, "logits": logits, "expert_load": load,
            "grads": dict(zip((p.name for p in params), grads))}


def test_resolve_reads_qwen3_nexts_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["gated_delta"] * 3 + ["attention"]
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (8, 4, 4)
    assert c["intermediate_size"] == 8      # an expert's, not the dense 80
    assert c["rotary_dim"] == 4
    assert (c["qk_norm"], c["norm_zero_centered"], c["attention_gate"]) \
        == ("head", True, True)
    assert c["shared_expert_intermediate_size"] == 8
    # what the modeling file always applies is the config's to say, key by
    # key: the model's name sets nothing
    named = causal_lm.resolve(dict(
        {k: v for k, v in CFG.items() if k not in (
            "qk_norm", "norm_zero_centered", "attention_gate")},
        model_type="qwen3_next"))
    assert (named["qk_norm"], named["norm_zero_centered"],
            named["attention_gate"]) == (False, False, False)
    # every other model keeps attention on every layer and the whole head
    plain = causal_lm.resolve(dict(
        vocab_size=8, hidden_size=8, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=4))
    assert plain["mixer_layers"] == ["attention"] * 2
    assert plain["rotary_dim"] == plain["head_dim"] == 4
    assert not plain["norm_zero_centered"] and not plain["attention_gate"]


@pytest.mark.parametrize("edit,error,match", [
    (dict(mlp_only_layers=[1]), NotImplementedError, "mlp_only_layers"),
    (dict(decoder_sparse_step=2), NotImplementedError,
     "decoder_sparse_step"),
    (dict(rope_scaling={"type": "linear"}), NotImplementedError,
     "rope_scaling"),
    (dict(qk_norm="group"), NotImplementedError, "qk_norm"),
    (dict(partial_rotary_factor=0.2), ValueError, "partial_rotary_factor"),
    (dict(linear_num_value_heads=3), ValueError, "linear value heads"),
    (dict(share=dict(chips=2, chip=2, published=dict(num_experts=8))),
     ValueError, "cannot hold")])
def test_resolve_refuses_what_the_builder_cannot_build(edit, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_resolve_names_the_keys_a_delta_net_lacks():
    cfg = {k: v for k, v in CFG.items() if k != "linear_conv_kernel_dim"}
    with pytest.raises(ValueError, match="linear_conv_kernel_dim"):
        causal_lm.resolve(cfg)


def test_program_has_the_models_shapes(program):
    params, weights, got = program
    shapes = [w.shape for w in weights]
    delta = [(32,), (32, 2 * (2 * 8 + 2 * 2 * 8)), (32, 8), (64, 4), (4,),
             (4,), (8,), (32, 32), (32,)]
    experts = [(32, 8), (4, 32, 8), (4, 32, 8), (4, 8, 32),
               (32, 8), (32, 8), (8, 32), (32, 1)]
    full = [(32,), (32, 4 * 2 * 16), (32, 32), (32, 32), (16,), (16,),
            (64, 32), (32,)]
    assert shapes == [(64, 32)] + 3 * (delta + experts) + full + experts \
        + [(32,), (32, 64)]
    assert len(params) == len(shapes)
    for kind, count in (("gated_delta_rule", 3), ("causal_conv1d", 3),
                        ("fused_attention", 1), ("rotary_embedding", 2),
                        ("moe_ffn", 4)):
        assert got["ops"].count(kind) == count, kind
    # the zero-centred norms start at 0, the gated norm's weight at 1, the
    # decay's parameters as modeling_qwen3_next.py sets them
    assert not weights[1].any() and not weights[9].any()
    assert (weights[7] == 1).all() and (weights[5] == 1).all()
    assert (np.exp(weights[6]) > 0).all() and (np.exp(weights[6]) < 16).all()


def test_program_agrees_with_the_reference(program, want):
    _, _, got = program
    assert _error(got["loss"], want["loss"]) < 2e-6
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    assert got["expert_load"].shape == (8,)
    assert got["expert_load"].sum() == 4 * 3 * B * T
    assert 0 < got["expert_load"][4:].sum() < got["expert_load"].sum()


def test_every_gradient_agrees_with_the_reference(program, want):
    params, _, got = program
    errors = {p.name: _error(got["grads"][p.name], want["grads"][p.name])
              for p in params}
    assert max(errors.values()) < TOLERANCE, errors
    assert all(np.abs(want["grads"][p.name]).max() > 0 for p in params)


def test_the_kernel_path_agrees_with_the_reference(monkeypatch, want):
    """The same Program with the gated delta kernels on (interpreted)."""
    params, _, got = _run_program(False, pallas="gdr",
                                  monkeypatch=monkeypatch)
    assert got["counted"]["delta"] == 0     # counted under path="kernel"
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    errors = {p.name: _error(got["grads"][p.name], want["grads"][p.name])
              for p in params}
    assert max(errors.values()) < TOLERANCE, errors


def test_the_convolutions_kernels_agree_with_its_xla_path(monkeypatch):
    """The same Program at heads of 16 and 48 tokens, where the three
    convolutions run over [2, 48, 128] and the kernels' blocks divide that:
    loss, logits and every gradient with the two Pallas passes
    (interpreted) equal those with the jax.numpy passes, and the counter
    says which ran. The interpreter's approximate reciprocal is bf16's (4e-3
    off, 1.5e-5 after the kernels' Newton step; the chip's leaves 1e-7), and
    four layers carry that into gradients 5e-3 apart: the logistic stands in
    for it here, and test_causal_conv_kernels.py holds the step itself."""
    from paddle_tpu.ops import causal_conv_kernels
    monkeypatch.setattr(causal_conv_kernels, "_sigmoid", jax.nn.sigmoid)
    cfg = dict(CFG, linear_key_head_dim=16, linear_value_head_dim=16)
    labels = dict(width="4", channels="128", activation="silu")

    def run(pallas):
        before = {p: _counter("ptpu_causal_conv_layers_total", path=p,
                              **labels) for p in ("kernel", "xla")}
        params, _, got = _run_program(False, pallas=pallas,
                                      monkeypatch=monkeypatch, cfg=cfg, t=48)
        return params, got, {
            p: _counter("ptpu_causal_conv_layers_total", path=p, **labels)
            - before[p] for p in before}

    params, got, counted = run("conv")
    _, want, counted_xla = run("0")
    assert counted == {"kernel": 3, "xla": 0}
    assert counted_xla == {"kernel": 0, "xla": 3}
    assert _error(got["loss"], want["loss"]) < 2e-6
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    errors = {p.name: _error(got["grads"][p.name], want["grads"][p.name])
              for p in params}
    assert max(errors.values()) < TOLERANCE, errors
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])


def test_amp_program_agrees_with_the_reference(want):
    _, _, got = _run_program(amp=True)
    assert got["logits"].dtype == jnp.bfloat16
    # bf16 at a width of 32, and a state that carries a moved assignment's
    # effect to every later token of its sequence
    assert _error(got["logits"], want["logits"]) < 2.5e-1
    assert _error(got["loss"], want["loss"]) < 5e-3
    assert got["expert_load"].sum() == 4 * 3 * B * T


def test_the_new_counters_and_labels(program):
    """ptpu_linear_attention_layers_total counts forward gated_delta_rule
    ops by heads, widths, the chunk and the path, and
    ptpu_attention_layers_total says the head's width: what an op can
    observe. What the model puts around its ops (the channels rotary turns,
    the output gate, the convolution's taps, the shared expert's width) is
    ptpu_causal_lm_layers_total's, a count a layer built."""
    assert program[2]["counted"] == {"moe": 4, "full": 1, "delta": 3,
                                     "built_full": 1, "built_delta": 3}


MUTANTS = ["no_decay", "beta_one", "no_l2norm", "conv_off", "rope_whole_head",
           "output_gate_off", "norm_not_zero_centred", "shared_gate_off",
           "wrong_key_head", "top2"]


@pytest.mark.parametrize("mutant", MUTANTS)
def test_reference_tells_a_broken_model(monkeypatch, program, mutant):
    """The reference with one mechanism changed is further from the Program
    than the tolerance: the comparison sees each of them."""
    _, weights, got = program
    cfg = dict(CFG)
    if mutant == "no_decay":
        rule = reference.delta_rule
        monkeypatch.setattr(reference, "delta_rule",
                            lambda q, k, v, g, beta: rule(q, k, v, 0 * g,
                                                          beta))
    elif mutant == "beta_one":
        rule = reference.delta_rule
        monkeypatch.setattr(reference, "delta_rule",
                            lambda q, k, v, g, beta: rule(q, k, v, g,
                                                          0 * beta + 1))
    elif mutant == "no_l2norm":
        monkeypatch.setattr(reference, "l2norm", lambda x, eps=1e-6: x)
    elif mutant == "conv_off":
        monkeypatch.setattr(reference, "causal_conv", lambda x, w: x)
    elif mutant == "rope_whole_head":
        cfg["partial_rotary_factor"] = 1.0
    elif mutant == "output_gate_off":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: 0 * x + 1)
    elif mutant == "norm_not_zero_centred":
        cfg["norm_zero_centered"] = False
    elif mutant == "shared_gate_off":
        monkeypatch.setattr(
            reference, "shared_expert", lambda m, wg, wu, wd, ws:
            (jax.nn.silu(m @ wg) * (m @ wu)) @ wd)
    elif mutant == "wrong_key_head":    # key head j on value heads j, j + 2
        repeat = jnp.repeat
        monkeypatch.setattr(
            jnp, "repeat", lambda x, n, axis=None: jnp.concatenate(
                [x] * n, axis) if axis == 2 and x.ndim == 4
            and x.shape[-1] == 8 else repeat(x, n, axis=axis))
    elif mutant == "top2":
        cfg["num_experts_per_tok"] = 2
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    if mutant == "norm_not_zero_centred":
        # zero-centred weights start at 0: without the 1 the stream is dead
        weights = [w + 0.5 if not w.any() else w for w in weights]
        healthy = reference.forward(CFG, weights, feed["ids"],
                                    feed["pos"])[0]
        logits = reference.forward(cfg, weights, feed["ids"], feed["pos"])[0]
        assert _error(healthy, logits) > 10 * TOLERANCE
        return
    logits = reference.forward(cfg, weights, feed["ids"], feed["pos"])[0]
    assert _error(got["logits"], logits) > 10 * TOLERANCE


# --- each new piece against its equation -------------------------------------

def _one_op(build, feed):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = {n: fluid.layers.data(name=n, shape=list(a.shape[1:]),
                                     dtype=str(a.dtype))
                for n, a in feed.items()}
        out = build(data)
        params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(5)
        for p in params:        # off their initial 0 and 1
            scope.set(p.name, rng.randn(*p.shape).astype("float32"))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        got, = exe.run(main, feed=feed, fetch_list=[out])
    return got, weights


def _x(*shape, seed=3):
    return np.random.RandomState(seed).randn(*shape).astype("float32")


def test_causal_conv1d_is_four_shifted_adds():
    x = _x(2, 9, 6)
    got, (w,) = _one_op(lambda d: fluid.layers.causal_conv1d(d["x"], 4), {
        "x": x})
    want = np.zeros_like(x)
    for t in range(9):
        for m in range(4):
            if t - 3 + m >= 0:
                want[:, t] += w[:, m] * x[:, t - 3 + m]
    assert w.shape == (6, 4)
    assert _error(got, want) < 1e-6
    silu, _ = _one_op(lambda d: fluid.layers.causal_conv1d(d["x"], 4,
                                                           act="silu"),
                      {"x": x})
    assert _error(silu, want / (1 + np.exp(-want))) < 1e-6
    with pytest.raises(ValueError, match="act"):
        fluid.layers.causal_conv1d(None, 4, act="relu")


def test_rms_norm_zero_centred_and_gated():
    x, z = _x(2, 5, 3, 8), _x(2, 5, 3, 8, seed=4)
    hat = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    got, (w,) = _one_op(lambda d: fluid.layers.rms_norm(
        d["x"], epsilon=1e-6, zero_centered=True), {"x": x})
    assert w.shape == (8,)
    assert _error(got, (1 + w) * hat) < 1e-6
    got, (w,) = _one_op(lambda d: fluid.layers.rms_norm(
        d["x"], epsilon=1e-6, gate=d["z"]), {"x": x, "z": z})
    assert _error(got, w * hat * z / (1 + np.exp(-z))) < 1e-6
    # as it was: scale * x_hat, the scale initialised to 1
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fluid.layers.rms_norm(fluid.layers.data("x", [5, 8]),
                              zero_centered=True)
        fluid.layers.rms_norm(fluid.layers.data("y", [5, 8]))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        zero, one = (np.asarray(scope.get(p.name))
                     for p in main.global_block().all_parameters())
    assert not zero.any() and (one == 1).all()


def test_rotary_turns_the_first_channels_only():
    x = _x(2, 7, 3, 16)
    pos = np.broadcast_to(np.arange(7), (2, 7)).astype("int64").copy()
    got, _ = _one_op(lambda d: fluid.layers.rotary_embedding(
        d["x"], d["pos"], base=1e7, rotary_dim=4), {"x": x, "pos": pos})
    want = np.asarray(reference.rope(jnp.asarray(x), jnp.asarray(pos), 1e7,
                                     4))
    assert _error(got, want) < 1e-6
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    angle = pos[..., None, None] * 1e7 ** (-np.arange(0, 4, 2) / 4)
    by_hand = np.concatenate(
        [x[..., :2] * np.cos(angle) - x[..., 2:4] * np.sin(angle),
         x[..., 2:4] * np.cos(angle) + x[..., :2] * np.sin(angle)], -1)
    assert _error(got[..., :4], by_hand) < 1e-5
    whole, _ = _one_op(lambda d: fluid.layers.rotary_embedding(
        d["x"], d["pos"], base=1e7), {"x": x, "pos": pos})
    assert _error(whole, np.asarray(reference.rope(
        jnp.asarray(x), jnp.asarray(pos), 1e7))) < 1e-6


def _attention_alone(c, x, pos):
    got, weights = _one_op(
        lambda d: causal_lm.attention(d["x"], d["pos"], c),
        {"x": x, "pos": pos})
    return got, weights


def test_the_output_gate_and_the_norm_a_head():
    """causal_lm.attention with Qwen3-Next's keys against the reference's,
    and against the equation where the two pieces are taken out by hand:
    the gate's half of Wq zeroed gives ctx * sigmoid(0) = ctx / 2 of the
    ungated layer on the same weights."""
    c = causal_lm._layer(causal_lm.resolve(CFG), 3)
    x = _x(2, 12, 32)
    pos = np.broadcast_to(np.arange(12), (2, 12)).astype("int64").copy()
    got, weights = _attention_alone(c, x, pos)
    wq, wk, wv, qn, kn, wo = (jnp.asarray(w) for w in weights)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(jnp.asarray(x), jnp.asarray(pos), wq, wk,
                                   wv, qn, kn, wo, c)
        assert _error(got, want) < 1e-5
        assert qn.shape == kn.shape == (16,)        # one weight a head
        # the gate's columns zeroed: sigmoid(0) = 1/2 of the ungated layer
        heads = np.asarray(wq).reshape(32, 4, 32).copy()
        ungated = reference.attention(
            jnp.asarray(x), jnp.asarray(pos),
            jnp.asarray(heads[:, :, :16].reshape(32, 64)), wk, wv, qn, kn,
            wo, dict(c, attention_gate=False))
        heads[:, :, 16:] = 0
        halved = reference.attention(
            jnp.asarray(x), jnp.asarray(pos),
            jnp.asarray(heads.reshape(32, 128)), wk, wv, qn, kn, wo, c)
    assert _error(halved, ungated / 2) < 1e-5


def test_the_shared_expert_is_added_once_with_its_gate():
    c = causal_lm.resolve(CFG)
    x = _x(2, 6, 32)
    got, weights = _one_op(
        lambda d: causal_lm.feed_forward(d["x"], c)[0], {"x": x})
    router, wg, wu, wd, sg, su, sd, ws = (jnp.asarray(w) for w in weights)
    m = jnp.asarray(x).reshape(12, 32)
    with jax.default_matmul_precision("highest"):
        routed = reference.routed_experts(m, router, wg, wu, wd, c)[0]
        silu = (m @ sg) / (1 + jnp.exp(-(m @ sg)))
        shared = (silu * (m @ su)) @ sd / (1 + jnp.exp(-(m @ ws)))
    assert ws.shape == (32, 1)
    assert _error(got.reshape(12, 32), routed + shared) < 1e-5
    assert _error(shared, reference.shared_expert(m, sg, su, sd, ws)) < 1e-6


# --- the share tied to the model ---------------------------------------------

def test_four_shares_and_one_shared_expert_sum_to_the_layer():
    """One whole layer (gated delta net, then 16 experts top-3 beside a
    gated shared expert) by the uncut reference, and by four shares of four
    experts through the Program's own layers: the mixer and the shared
    expert are every chip's own and are counted once, the routed parts of
    the four shares are summed."""
    whole = dict(CFG, num_experts=16, num_hidden_layers=1, share=None)
    x = _x(2, 24, 32)
    c16 = causal_lm.resolve(whole)

    def layer(c):
        def build(d):
            a = causal_lm._norm(d["x"], c)
            h = d["x"] + causal_lm.gated_delta_net(a, c)
            return h, causal_lm._norm(h, c)
        return build

    # the mixer and the post-norm, once, through the Program
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        h, m = layer(c16)({"x": fluid.layers.data("x", [24, 32])})
        mixer_params = main.global_block().all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(9)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in mixer_params:
            if len(p.shape) == 1 and p.shape[0] == 32:  # norms off their 0
                scope.set(p.name, (0.3 * rng.randn(32)).astype("float32"))
        mixer_weights = [np.asarray(scope.get(p.name)) for p in mixer_params]
        h_val, m_val = exe.run(main, feed={"x": x}, fetch_list=[h, m])

    # the experts' weights of the whole layer, then each share's routed part
    router = (0.5 * rng.randn(32, 16)).astype("float32")
    wg, wu = ((0.3 * rng.randn(16, 32, 8)).astype("float32")
              for _ in range(2))
    wd = (0.3 * rng.randn(16, 8, 32)).astype("float32")
    shared = [(0.3 * rng.randn(*s)).astype("float32")
              for s in ((32, 8), (32, 8), (8, 32), (32, 1))]
    routed, rows = [], 0
    for chip in range(4):
        c = causal_lm.resolve(dict(whole, num_experts=4, share=dict(
            chips=4, chip=chip, published=dict(num_experts=16))))
        assert (c["first_expert"], c["experts_held"]) == (4 * chip, 4)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            out, (_, _, load) = causal_lm.feed_forward(
                fluid.layers.data("m", [24, 32]), c)
            params = main.global_block().all_parameters()
        sl = slice(4 * chip, 4 * chip + 4)
        values = [router, wg[sl], wu[sl], wd[sl]] + shared
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for p, value in zip(params, values):
                scope.set(p.name, value)
            out_val, load_val = exe.run(main, feed={"m": m_val},
                                        fetch_list=[out, load])
        rows += int(load_val[sl].sum())
        routed.append(out_val)
    m2 = jnp.asarray(m_val).reshape(48, 32)
    with jax.default_matmul_precision("highest"):
        shared_out = np.asarray(reference.shared_expert(
            m2, *(jnp.asarray(w) for w in shared))).reshape(2, 24, 32)
        # every share added its own copy of the shared expert: count it once
        got = h_val + sum(routed) - 3 * shared_out
    assert rows == 3 * 48               # every assignment, once
    # the uncut layer, by the reference, on the same weights
    with jax.default_matmul_precision("highest"):
        params16 = [jnp.asarray(w) for w in mixer_weights]
        a = reference.rms_norm(jnp.asarray(x), params16[0], 1e-6, True)
        h_ref = jnp.asarray(x) + reference.gated_delta_net(
            a, *params16[1:8], c16)
        m_ref = reference.rms_norm(h_ref, params16[8], 1e-6, True)
        out_ref = h_ref + (reference.routed_experts(
            m_ref.reshape(48, 32), jnp.asarray(router), jnp.asarray(wg),
            jnp.asarray(wu), jnp.asarray(wd), c16)[0]
            + reference.shared_expert(m_ref.reshape(48, 32), *(
                jnp.asarray(w) for w in shared))).reshape(2, 24, 32)
    assert _error(got, out_ref) < TOLERANCE
    assert all(np.abs(r - shared_out).max() > 0 for r in routed)
