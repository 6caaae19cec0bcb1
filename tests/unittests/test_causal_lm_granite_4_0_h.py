"""models/causal_lm.py at granite-4.0-h-micro's shape (tiny widths, seeded
weights): the Program against models/causal_lm_reference.py for loss, logits
and every trained parameter's gradient; the state-space-dual scan op alone
against `lax.scan` over tokens (both paths, forward and the gradients of x,
Delta, A, B, C, D) at chunks of 16, 32 and the whole T, at a T that is no
multiple of the chunk (padded) and under Delta A = -6 a token; the gate
before the norm; each multiplier's place; no rotary op; what `resolve()`
reads of the new keys, of a `layer_types` list cut to its first layers, and
what it still refuses."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import ssd_kernels as ssd

# the published list's shape at a tenth: two Mamba-2 mixers, the attention
# layer, a third mixer, of a published 8; 4 heads of 8 on 16 states; 4 query
# heads on 2 key/value heads of 16
PUBLISHED_TYPES = ["mamba", "mamba", "attention", "mamba"] * 2
CFG = dict(
    vocab_size=96, hidden_size=16, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    shared_intermediate_size=48, layer_types=PUBLISHED_TYPES,
    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_conv_bias=True,
    mamba_proj_bias=False, mamba_chunk_size=256, num_local_experts=0,
    num_experts_per_tok=0, position_embedding_type="nope", rope_theta=10000,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8, rms_norm_eps=1e-5,
    tie_word_embeddings=True, hidden_act="silu", attention_bias=False,
    initializer_range=0.2,
    share=dict(chips=1, chip=0, published=dict(num_hidden_layers=8)))
B, T = 2, 24
TOLERANCE = 2e-4                # float32 against float32: another order of
#                                 sums (chunks of matmuls against tokens)
# parameters that start at an identity (a bias of 0, a weight of 1): drawn
# off it before the comparison, or a rule that drops one would pass
OFF_IDENTITY = (".bias", ".d", "gated_norm", "final_norm", "_norm")


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


def _run_program(cfg=CFG, fetch_grads=True):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, _ = causal_lm.build_train(cfg, T)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(5)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in params:
            if p.name.endswith(OFF_IDENTITY):
                w = np.asarray(scope.get(p.name))
                scope.set(p.name, jnp.asarray(
                    w + 0.2 * rng.standard_normal(w.shape).astype("f")))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        names = [p.name + "@GRAD" for p in params] if fetch_grads else []
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits] + names)
    return main, params, weights, {
        "loss": out[0], "logits": out[1],
        "grads": dict(zip(names, out[2:]))}


@pytest.fixture(scope="module")
def program():
    return _run_program()


def _reference(cfg, weights):
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    return jax.jit(lambda p: reference.loss_and_grads(
        cfg, p, feed["ids"], feed["pos"], feed["labels"]))(weights)


@pytest.fixture(scope="module")
def want(program):
    _, params, weights, _ = program
    (loss, (logits, _)), grads = _reference(CFG, weights)
    return {"loss": loss, "logits": logits,
            "grads": dict(zip((p.name for p in params), grads))}


# ---- resolve ----------------------------------------------------------------

def test_resolve_reads_granitemoehybrids_keys():
    c = causal_lm.resolve(CFG)
    # the first four of the published eight
    assert c["mixer_layers"] == ["mamba2", "mamba2", "attention", "mamba2"]
    assert c["rope_theta"] is None and c["rope_layers"] == [False] * 4
    assert c["attention_scale"] == 0.015625          # not 16 ** -0.5
    assert c["dense_intermediate_size"] == 48 and c["mlp_gate_up_fused"]
    assert c["ffn_layers"] == ["dense"] * 4 and c["num_experts"] == 0
    assert c["head_dim"] == 4 and c["norm_type"] == "rms_norm"
    for key, value in (("embedding_multiplier", 12), ("logits_scaling", 8),
                       ("residual_multiplier", 0.22)):
        assert c[key] == value
    # each multiplier is 1 where a config does not have it, and rope stays
    plain = causal_lm.resolve(dict(
        vocab_size=96, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=48))
    assert (plain["embedding_multiplier"], plain["residual_multiplier"],
            plain["logits_scaling"], plain["attention_scale"]) == (1, 1, 1,
                                                                   None)
    assert plain["rope_theta"] == 10000.0
    # the whole published list is the whole model
    whole = causal_lm.resolve(dict(
        {k: v for k, v in CFG.items() if k != "share"},
        num_hidden_layers=8))
    assert whole["mixer_layers"].count("mamba2") == 6
    assert whole["mixer_layers"].count("attention") == 2


@pytest.mark.parametrize("change, error, match", [
    (dict(position_embedding_type="alibi"), NotImplementedError,
     "position_embedding_type"),
    (dict(mamba_proj_bias=True), NotImplementedError, "mamba_proj_bias"),
    (dict(mamba_n_groups=3), ValueError, "whole groups of 3"),
    (dict(num_local_experts=4, num_experts_per_tok=2), NotImplementedError,
     "num_local_experts"),
    (dict(mamba_n_heads=3), ValueError, "Mamba-2 heads"),
    (dict(total_ut_steps=2), NotImplementedError, "Mamba-2"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "multi-token-prediction"),
    # a longer list of several kinds is cut only to the share's published
    # depth's first layers
    (dict(share=dict(chips=1, chip=0, published=dict(num_hidden_layers=9))),
     NotImplementedError, "8 for 4 layers"),
    (dict(layer_types=["mamba", "window", "attention", "mamba"]),
     NotImplementedError, "window"),
    (dict(logits_scaling=0), ValueError, "logits_scaling")])
def test_resolve_refuses(change, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **change))


def test_mamba_in_layer_types_needs_the_mamba_2_keys():
    cfg = {k: v for k, v in CFG.items() if not k.startswith("mamba_n_heads")}
    with pytest.raises(NotImplementedError, match="mamba"):
        causal_lm.resolve(cfg)


# ---- the program against the reference --------------------------------------

def test_loss_and_logits_match_the_reference(program, want):
    _, _, _, got = program
    assert _error(got["loss"], want["loss"]) < TOLERANCE
    assert _error(got["logits"], want["logits"]) < TOLERANCE


def test_every_trained_parameters_gradient_matches(program, want):
    _, params, _, got = program
    assert len(params) == 1 + 3 * 12 + 8 + 1
    for p in params:
        error = _error(got["grads"][p.name + "@GRAD"], want["grads"][p.name])
        assert error < TOLERANCE, (p.name, error)


def test_the_parameters_are_the_mixers_own(program):
    _, params, _, _ = program
    names = [p.name for p in params]
    assert names[1:13] == ["layer_0." + role for role in (
        "input_norm", "w_in", "conv", "conv.bias", "dt_bias", "a_log", "d",
        "gated_norm", "w_out", "post_attention_norm", "w_gate_up",
        "w_down")]
    shapes = {p.name: tuple(p.shape) for p in params}
    # [z; xBC; dt]: 32 + (32 + 2 x 16) + 4 columns; the convolution over x,
    # B and C side by side; the norm over all 32 channels at once
    assert shapes["layer_0.w_in"] == (16, 32 + 64 + 4)
    assert shapes["layer_0.conv"] == (64, 4)
    assert shapes["layer_0.conv.bias"] == (64,)
    assert shapes["layer_0.gated_norm"] == (32,)
    assert shapes["layer_0.a_log"] == shapes["layer_0.d"] == (4,)
    assert shapes["layer_2.wq"] == (16, 16) and shapes["layer_2.wk"] == (16, 8)
    assert "head" not in names and names[-1] == "final_norm"


def test_no_rotary_op_and_one_scan_a_mamba_layer(program):
    main, _, _, _ = program
    types = [op.type for op in main.global_block().ops]
    assert "rotary_embedding" not in types
    assert types.count("ssd_scan") == 3
    assert types.count("fused_attention") == 1
    assert types.count("causal_conv1d") == 3
    core = next(op for op in main.global_block().ops
                if op.type == "fused_attention")
    assert core.attrs["scale"] == 0.015625


def test_the_layers_and_the_scans_are_counted():
    before = REGISTRY.snapshot()

    def total(snapshot, family, **where):
        found = snapshot.get(family, {"samples": []})
        return sum(value for labels, value in found["samples"]
                   if all(labels.get(k) == v for k, v in where.items()))

    _run_program(fetch_grads=False)
    after = REGISTRY.snapshot()
    for where, count in ((dict(mixer="mamba2", conv="4", ffn="dense"), 3),
                         (dict(mixer="attention", rotary_dim="0"), 1)):
        assert total(after, "ptpu_causal_lm_layers_total", **where) \
            - total(before, "ptpu_causal_lm_layers_total", **where) == count
    where = dict(heads="4", head_dim="8", states="16", path="scan",
                 chunk="128")
    assert total(after, "ptpu_ssd_scan_layers_total", **where) \
        - total(before, "ptpu_ssd_scan_layers_total", **where) == 3


# ---- what each key does ------------------------------------------------------

@pytest.mark.parametrize("key, other", [
    ("embedding_multiplier", 1), ("residual_multiplier", 1),
    ("attention_multiplier", 0.25), ("logits_scaling", 1)])
def test_each_multiplier_sits_where_the_reference_has_it(key, other, program):
    """The program under another value of one multiplier agrees with the
    reference under that value, and not with the reference under the
    published one: the key reaches the place the equations give it."""
    cfg = dict(CFG, **{key: other})
    _, _, weights, got = _run_program(cfg, fetch_grads=False)
    (loss, (logits, _)), _ = _reference(cfg, weights)
    assert _error(got["logits"], logits) < TOLERANCE
    assert _error(got["loss"], loss) < TOLERANCE
    (_, (published, _)), _ = _reference(CFG, weights)
    assert _error(got["logits"], published) > 50 * TOLERANCE


def test_the_gate_comes_before_the_norm():
    """RMSNorm(y * SiLU(z)) is not RMSNorm(y) * SiLU(z), which
    layers.rms_norm(gate=) computes: the mixer builds the first."""
    rng = np.random.RandomState(3)
    y, z = (rng.standard_normal((2, 6, 32)).astype("f") for _ in range(2))
    w = (1 + 0.2 * rng.standard_normal(32)).astype("f")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        yv = fluid.layers.data("y", [6, 32])
        zv = fluid.layers.data("z", [6, 32])
        first = fluid.layers.rms_norm(yv * fluid.layers.swish(zv),
                                      param_attr=fluid.ParamAttr(name="w"))
        second = fluid.layers.rms_norm(yv, gate=zv,
                                       param_attr=fluid.ParamAttr(name="w"))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set("w", jnp.asarray(w))
        gate_first, norm_first = exe.run(main, feed={"y": y, "z": z},
                                         fetch_list=[first, second])
    want = reference.rms_norm(y * np.asarray(jax.nn.silu(z)), w, 1e-5)
    assert _error(gate_first, want) < 1e-5
    assert _error(norm_first, want) > 0.1
    # and the mixer's own ops: a multiply feeds the norm, no Gate input
    main, _, _, _ = _run_program(fetch_grads=False)
    ops = main.global_block().ops
    norm = next(op for op in ops if op.type == "rms_norm"
                and op.input("Scale")[0] == "layer_0.gated_norm")
    assert "Gate" not in norm.inputs
    feeds = next(op for op in ops if norm.input("X")[0] in op.output("Out"))
    assert feeds.type == "elementwise_mul"


# ---- the scan op alone --------------------------------------------------------

def _scan_case(t, strong=False, h=4, p=64, n=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal((2, t, h, p)).astype("f")
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (2, t, h)))
    a = -rng.uniform(1, 16, h)
    if strong:                  # Delta A = -6 a token: every head forgets
        delta, a = np.ones((2, t, h)), np.full(h, -6.0)
    b, c = (rng.standard_normal((2, t, n)).astype("f") for _ in range(2))
    d = rng.standard_normal(h)
    return tuple(jnp.asarray(v, jnp.float32)
                 for v in (x, delta, a, b, c, d))


def _with_grads(fn, args, weight):
    value, vjp = jax.vjp(fn, *args)
    return (value,) + vjp(weight)


@pytest.mark.parametrize("path", ["scan", "kernel"])
@pytest.mark.parametrize("t, chunk, strong", [
    (64, 16, False), (64, 32, False), (64, 64, False),
    (50, 16, False),            # no multiple of the chunk: padded
    (64, 16, True)])
def test_the_scan_op_matches_the_recurrence_over_tokens(path, t, chunk,
                                                        strong):
    """Forward and the gradients of x, Delta, A, B, C and D, one result
    whatever the chunk; a T that is no multiple of the chunk is PADDED with
    tokens that neither write nor decay; at Delta A = -6 a token (exp(c) of
    a chunk's running sum underflows, exp(-c) would overflow) nothing is
    inf or nan. The kernels run in the interpreter here."""
    args = _scan_case(t, strong)
    weight = jnp.asarray(np.random.RandomState(1).standard_normal(
        args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(reference.ssd_scan, args, weight)
        got = _with_grads(lambda *v: ssd.ssd_scan(*v, path=path, chunk=chunk),
                          args, weight)
    for name, g, w in zip(("y", "dx", "ddelta", "da", "db", "dc", "dd"), got,
                          want):
        assert np.isfinite(np.asarray(g)).all(), name
        # A's gradient under the strong decay is exp(-6) of the others: the
        # recurrence's own float32 sums are that far from the chunked form's
        limit = 1e-3 if strong and name == "da" else 2e-5
        assert _error(g, w) < limit, (name, _error(g, w))


def test_the_kernels_hold_the_decays_gradient_under_bf16_operands():
    """With bf16 operands, as under AMP, the kernels' gradient of A stays
    as near the float32 recurrence as the other gradients: it is summed a
    chunk at a time and without a token's own term (ssd_kernels.py's
    docstring has what it read otherwise)."""
    args = _scan_case(128, strong=True, seed=2)
    args = tuple(v.astype(jnp.bfloat16).astype(jnp.float32)
                 if i in (0, 3, 4) else v for i, v in enumerate(args))
    weight = jnp.asarray(np.random.RandomState(1).standard_normal(
        args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(reference.ssd_scan, args, weight)
    got = _with_grads(lambda *v: ssd.ssd_scan(
        *v, path="kernel", chunk=32, operand_dtype=jnp.bfloat16), args,
        weight)
    errors = {name: _error(g, w) for name, g, w in zip(
        ("y", "dx", "ddelta", "da", "db", "dc", "dd"), got, want)}
    assert max(errors.values()) < 2e-2, errors


def test_the_op_refuses_what_it_cannot_compute():
    x, delta, a, b, c, d = _scan_case(16)
    with pytest.raises(ValueError, match="multiple of 8"):
        ssd.ssd_scan(x, delta, a, b, c, d, chunk=12)
    with pytest.raises(ValueError, match="path"):
        ssd.ssd_scan(x, delta, a, b, c, d, path="xla")
    with pytest.raises(ValueError, match="b and c"):
        ssd.ssd_scan(x, delta, a, b[:, :8], c, d)
    with pytest.raises(ValueError, match="divides 128"):
        ssd.ssd_scan(x[..., :48], delta, a, b, c, d, path="kernel")
    assert ssd.applies(64, 64) and ssd.applies(4, 32)
    assert not ssd.applies(3, 64) and not ssd.applies(4, 48)


def test_the_reference_scan_in_segments_is_the_same_scan():
    args = _scan_case(48, p=8)
    found, whole = {}, {}
    a = reference.ssd_scan(*args, found=whole)
    b = reference.ssd_scan(*args, found=found, segment=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(whole["state"]),
                                  np.asarray(found["state"]))
    assert found["state"].shape == (2, 4, 16, 8)
