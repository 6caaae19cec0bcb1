"""models/causal_lm.py (the decoder-only builder, OLMoE-shaped) and the ops
it brought, rms_norm, rotary_embedding and moe_ffn, against the plain
reference models/causal_lm_reference.py, on the CPU at a small size: 2
layers, hidden 64, 4 heads of 16, 8 experts of 32, top-2, vocabulary 128,
T=32, seeded random weights. One compiled float32 program is a module
fixture that the model tests and the mutants share.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm, causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY

from op_test import check_grad_fd, run_op

CFG = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, intermediate_size=32, num_experts=8,
           num_experts_per_tok=2, norm_topk_prob=False, rope_theta=10000.0,
           qk_norm=True)
B, T = 3, 32

# float32 on the CPU against a float32 reference: both sum the same products
# in another order (grouped matmuls over sorted assignments against one
# masked expert at a time; the flash-free dense attention against einsum),
# which moves a value by a few ulp of its largest term, 1e-7 to 7e-7 of the
# largest value over every logit and every parameter's gradient when
# measured. 1e-5 leaves ten times that and is a thousand times under the
# smallest mutant below (top-1 for top-2 moves the logits by 2.0e-2, ReLU
# for SiLU 2.2e-2, renormalised weights 5.6e-2, no rotary or QK-norm 0.48).
TOLERANCE = 1e-5
# bf16 matmul inputs round at 2^-9 = 2e-3 a value; two layers deep the
# logits were off by 5e-3 of the largest. 2e-2 is four times that, and at
# this toy size it equals what the two smallest mutants move (2.0e-2,
# 2.2e-2): the float32 comparison above is what catches those.
AMP_TOLERANCE = 2e-2


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


def _moe_layers_lowered():
    from paddle_tpu.parallel.moe import GROUPED_MATMUL
    return REGISTRY.counter("ptpu_moe_layers_total", "").value(
        top_k="2", experts="8", held="8", activation="silu",
        router_input="own", path=GROUPED_MATMUL, rows="all",
        scoring="softmax", bias="false", scale="1")


def _run_program(amp):
    """One training step of the Program from seeded weights: (parameter
    values before the step, {fetch: value})."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        loss, logits, load = causal_lm.build_train(CFG, T)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    lowered_before = _moe_layers_lowered()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits, load]
                      + [p.name + "@GRAD" for p in params])
    got = {"loss": out[0], "logits": out[1], "expert_load": out[2],
           "grads": dict(zip((p.name for p in params), out[3:])),
           "moe_layers_lowered": _moe_layers_lowered() - lowered_before}
    return [p.name for p in params], weights, got


@pytest.fixture(scope="module")
def program():
    return _run_program(amp=False)


@pytest.fixture(scope="module")
def want(program):
    names, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, load)), grads = jax.jit(
        lambda p: reference.loss_and_grads(CFG, p, feed["ids"], feed["pos"],
                                           feed["labels"]))(weights)
    return {"loss": loss, "logits": logits, "expert_load": load,
            "grads": dict(zip(names, grads))}


def test_program_agrees_with_the_reference(program, want):
    names, _, got = program
    assert len(names) == 27     # 1 + 2 x 12 + 2
    assert _error(got["loss"], want["loss"]) < TOLERANCE
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    assert got["expert_load"].sum() == 2 * 2 * B * T   # layers x top_k x N


def test_every_gradient_agrees_with_the_reference(program, want):
    names, _, got = program
    errors = {n: _error(got["grads"][n], want["grads"][n]) for n in names}
    assert max(errors.values()) < TOLERANCE, errors
    assert all(np.abs(want["grads"][n]).max() > 0 for n in names)


def test_amp_program_agrees_with_the_reference(want):
    """Once under enable_mixed_precision: bf16 matmuls and experts, float32
    router, norms, rotary angles, loss and master weights."""
    _, _, got = _run_program(amp=True)
    assert got["logits"].dtype == jnp.bfloat16
    assert got["grads"]["layer_0.experts.w_gate"].dtype == np.float32
    assert _error(got["logits"], want["logits"]) < AMP_TOLERANCE
    assert _error(got["loss"], want["loss"]) < 1e-3
    assert got["expert_load"].sum() == 2 * 2 * B * T
    # top-2 of 8 flips for few tokens, if any, between bf16 and float32
    assert np.abs(got["expert_load"] - want["expert_load"]).sum() <= 8


def _without_qk_norm(weights):
    """The parameter list a model without QK-norm would have."""
    drop = {5 + 12 * layer + i for layer in range(2) for i in (0, 1)}
    return [w for i, w in enumerate(weights) if i not in drop]


MUTANTS = {
    "top_1_for_top_2": (dict(CFG, num_experts_per_tok=1), None),
    "renormalised_weights": (dict(CFG, norm_topk_prob=True), None),
    "no_qk_norm": (dict(CFG, qk_norm=False), _without_qk_norm),
    "rotary_off": (dict(CFG, rope_theta=None), None),
    "relu_for_silu": (CFG, None),
}


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_the_tolerance_catches(mutant, program, want, monkeypatch):
    """Each thing the comparison must see, broken in the reference: the
    Program's logits are then further from it than the tolerance allows
    (the float32 tolerance for all five; what each moves is in the assert
    message of a failure)."""
    _, weights, got = program
    cfg, edit = MUTANTS[mutant]
    if mutant == "relu_for_silu":
        monkeypatch.setattr(jax.nn, "silu", jax.nn.relu)
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    logits = reference.forward(
        cfg, edit(weights) if edit else weights, feed["ids"], feed["pos"])[0]
    moved = _error(got["logits"], logits)
    assert moved > 10 * TOLERANCE, moved
    assert _error(want["logits"], logits) > 10 * TOLERANCE


def test_builder_refuses_what_it_cannot_build():
    # grouped queries are built since PR 31; a count of key/value heads that
    # does not divide the query heads is still no model
    with pytest.raises(ValueError, match="key/value"):
        causal_lm.resolve(dict(CFG, num_key_value_heads=3))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        causal_lm.resolve(dict(CFG, rope_scaling={"type": "linear"}))
    with pytest.raises(ValueError, match="factor"):     # yarn builds (PR 43)
        causal_lm.resolve(dict(CFG, rope_scaling={"type": "yarn"}))
    # a tied head is built since PR 39; the key is a boolean
    with pytest.raises(NotImplementedError, match="tie_word_embeddings"):
        causal_lm.resolve(dict(CFG, tie_word_embeddings="input_only"))


def test_dense_swiglu_variant_trains():
    """The same builder without experts: a dense SwiGLU FFN, no rotary, no
    QK-norm; agrees with the reference and the loss falls."""
    cfg = dict(CFG, num_experts=0, num_hidden_layers=1, qk_norm=False,
               rope_theta=None)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, T, learning_rate=1e-2)
    assert load is None
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        losses = [float(exe.run(main, feed=_feed(), fetch_list=[loss])[0][0])
                  for _ in range(8)]
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    first, _ = reference.loss_fn(cfg, weights, feed["ids"], feed["pos"],
                                 feed["labels"])
    assert abs(losses[0] - float(first)) < 1e-5 * float(first)
    assert losses[-1] < losses[0]


def test_lowering_counts_the_moe_layers(program):
    """ptpu_moe_layers_total counts forward moe_ffn ops, not the replay of
    a grad op, by experts a token, stored experts and grouped-matmul route."""
    assert program[2]["moe_layers_lowered"] == 2


# --- the ops ----------------------------------------------------------------

def _rms(x, w, eps, axes):
    x = x.astype(np.float64)
    return w * x / np.sqrt((x * x).mean(axes, keepdims=True) + eps)


@pytest.mark.parametrize("shape,begin,dtype", [
    ((6, 16), 1, "float32"), ((2, 5, 16), 2, "float32"),
    ((2, 3, 4, 8), 2, "float32"), ((4, 16), 1, "bfloat16")])
def test_rms_norm_op(shape, begin, dtype):
    rng = np.random.RandomState(1)
    x = rng.randn(*shape).astype("float32")
    w = rng.rand(*shape[begin:]).astype("float32") + 0.5
    if dtype == "bfloat16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16))
    y, = run_op("rms_norm", {"X": x, "Scale": w.reshape(-1)},
                attrs={"epsilon": 1e-5, "begin_norm_axis": begin},
                out_slots=("Y",))
    assert y.dtype == x.dtype and y.shape == x.shape
    want = _rms(np.asarray(x, np.float32), w, 1e-5,
                tuple(range(begin, len(shape))))
    # bfloat16: statistics in float32, one rounding of the result
    assert _error(y, want) < (1e-6 if dtype == "float32" else 4e-3)


@pytest.mark.parametrize("slot", ["X", "Scale"])
def test_rms_norm_grad(slot):
    rng = np.random.RandomState(2)
    check_grad_fd("rms_norm",
                  {"X": rng.randn(3, 8).astype("float32"),
                   "Scale": (rng.rand(8) + 0.5).astype("float32")},
                  slot, attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
                  out_slots=("Y",))


def _rope_numpy(x, pos, base):
    d = x.shape[-1]
    out = np.empty(x.shape, np.float64)
    for i in range(d // 2):
        angle = pos[:, :, None] * base ** (-2.0 * i / d)
        a, b = x[..., i].astype(np.float64), x[..., i + d // 2]
        out[..., i] = a * np.cos(angle) - b * np.sin(angle)
        out[..., i + d // 2] = b * np.cos(angle) + a * np.sin(angle)
    return out


@pytest.mark.parametrize("positions", ["arange", "offset", "ragged"])
def test_rotary_embedding_op(positions):
    """Positions are an input: 0..T-1, a decode step's offset, or another
    row a sequence. Position 0 is the identity; a turn keeps a pair's norm;
    q.k of two rotated vectors depends on their distance alone."""
    rng = np.random.RandomState(3)
    x = rng.randn(2, 6, 3, 8).astype("float32")
    pos = {"arange": np.broadcast_to(np.arange(6), (2, 6)),
           "offset": np.broadcast_to(np.arange(6) + 4090, (2, 6)),
           "ragged": np.array([[0, 1, 2, 3, 4, 5], [7, 7, 0, 2, 9, 1]])}[
        positions].astype("int32")
    y, = run_op("rotary_embedding", {"X": x, "Pos": pos},
                attrs={"base": 10000.0})
    assert _error(y, _rope_numpy(x, pos, 10000.0)) < 1e-5
    zero = (pos == 0)
    np.testing.assert_array_equal(y[zero], x[zero])
    np.testing.assert_allclose((y ** 2).sum(-1), (x ** 2).sum(-1), rtol=1e-5)


def test_rotary_embedding_is_relative_and_has_a_gradient():
    rng = np.random.RandomState(4)
    q = np.tile(rng.randn(1, 1, 1, 8), (1, 4, 1, 1)).astype("float32")
    k = np.tile(rng.randn(1, 1, 1, 8), (1, 4, 1, 1)).astype("float32")
    pos = np.array([[0, 3, 10, 13]], "int32")
    rq, = run_op("rotary_embedding", {"X": q, "Pos": pos})
    rk, = run_op("rotary_embedding", {"X": k, "Pos": pos})
    dots = (rq[0, :, 0] @ rk[0, :, 0].T)
    assert abs(dots[1, 0] - dots[3, 2]) < 1e-5      # both 3 apart
    assert abs(dots[1, 0] - dots[2, 0]) > 1e-3      # 3 against 10 apart
    check_grad_fd("rotary_embedding",
                  {"X": rng.randn(1, 4, 2, 8).astype("float32"), "Pos": pos},
                  "X", attrs={"base": 10000.0})


E, D, F, N = 8, 16, 12, 24
MOE_SLOTS = ("Out", "BalanceLoss", "ZLoss", "ExpertLoad")


def _moe_inputs(routing):
    rng = np.random.RandomState(5)
    x = rng.randn(4, N // 4, D).astype("float32")
    router = rng.randn(D, E).astype("float32")
    if routing == "all_to_one":      # positive tokens, one dominant column
        x = np.abs(x)
        router[:, 3] = 10.0
    elif routing == "one_starved":
        x = np.abs(x)
        router[:, 5] = -10.0
    return {"X": x, "Router": router,
            "WGate": rng.randn(E, D, F).astype("float32") * 0.3,
            "WUp": rng.randn(E, D, F).astype("float32") * 0.3,
            "WDown": rng.randn(E, F, D).astype("float32") * 0.3}


def _moe_reference(ins, top_k, norm):
    c = dict(num_experts=E, num_experts_per_tok=top_k, norm_topk_prob=norm)
    with jax.default_matmul_precision("highest"):
        return reference.routed_experts(
            jnp.asarray(ins["X"]).reshape(N, D), ins["Router"], ins["WGate"],
            ins["WUp"], ins["WDown"], c)


@pytest.mark.parametrize("top_k,routing,norm", [
    (1, "random", False), (2, "random", False), (8, "random", False),
    (2, "random", True), (1, "all_to_one", False),
    (2, "one_starved", False)])
def test_moe_ffn_op(top_k, routing, norm):
    """Dropless whatever the imbalance: every assignment is computed, so
    the output is the dense masked reference's and sum(ExpertLoad) ==
    top_k * N always."""
    ins = _moe_inputs(routing)
    out, balance, z, load = run_op(
        "moe_ffn", ins, attrs={"top_k": top_k, "norm_topk_prob": norm},
        out_slots=MOE_SLOTS)
    want_out, want_balance, want_z, want_load = _moe_reference(ins, top_k,
                                                               norm)
    assert out.shape == ins["X"].shape and load.dtype == np.int32
    assert load.sum() == top_k * N
    np.testing.assert_array_equal(load, want_load)
    assert _error(out, want_out) < TOLERANCE
    assert abs(balance[0] - float(want_balance)) < 1e-5 * float(want_balance)
    assert abs(z[0] - float(want_z)) < 1e-5 * float(want_z)
    if routing == "all_to_one":
        assert load[3] == N and load.sum() == N
    if routing == "one_starved":
        assert load[5] == 0
    if top_k == E:
        assert (load == N).all()


@pytest.mark.parametrize("routing", ["random", "one_starved"])
def test_moe_ffn_grads(routing):
    """Every input's gradient of sum(Out), an expert that saw no token
    included (its weights' gradient is exactly zero)."""
    ins = _moe_inputs(routing)
    slots = ("X", "Router", "WGate", "WUp", "WDown")
    got = run_op("moe_ffn", ins, attrs={"top_k": 2, "norm_topk_prob": False},
                 out_slots=MOE_SLOTS, fetch_grads=slots)[len(MOE_SLOTS):]
    want = jax.grad(lambda i: _moe_reference(i, 2, False)[0].sum())(
        {k: jnp.asarray(v) for k, v in ins.items()})
    for slot, g in zip(slots, got):
        assert _error(g, want[slot]) < TOLERANCE, slot
    if routing == "one_starved":
        assert not np.asarray(got[2])[5].any()


def test_moe_ffn_layer_checks_top_k():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4, D])
        with pytest.raises(ValueError, match="top_k"):
            fluid.layers.moe_ffn(x, num_experts=E, d_expert=F, top_k=E + 1)
        out, balance, z, load = fluid.layers.moe_ffn(
            x, num_experts=E, d_expert=F, top_k=2)
    assert tuple(out.shape) == (-1, 4, D) and tuple(load.shape) == (E,)
    assert load.dtype == "int32" and tuple(balance.shape) == (1,)
