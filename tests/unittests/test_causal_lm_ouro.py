"""A looped decoder in models/causal_lm.py (Ouro: `total_ut_steps` passes of
one stack of layers over the same weights, sandwich norms, an exit gate that
weighs the passes' cross-entropies) against the plain reference
models/causal_lm_reference.py, on the CPU at a small size: 2 layers run 4
times, hidden 32, 4 heads of 8, a SwiGLU of 48, vocabulary 64, T=16, seeded
random weights. One compiled float32 program is a module fixture.
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.models import causal_lm, causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY

DENSE = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, intermediate_size=48, rms_norm_eps=1e-6,
             rope_theta=1e6)
CFG = dict(DENSE, total_ut_steps=4, sandwich_norm=True, exit_gate=True,
           exit_entropy_coef=0.05)
B, T, P = 2, 16, 4
# a layer: four norms, four attention projections, three of the SwiGLU
NAMES = ["embedding"] + [
    "layer_%d.%s" % (i, role) for i in range(2) for role in (
        "input_norm", "wq", "wk", "wv", "wo", "mixer_out_norm",
        "post_attention_norm", "w_gate", "w_up", "w_down", "ffn_out_norm")
] + ["final_norm", "exit_gate.w", "exit_gate.b", "head"]
# float32 against float32, the same products summed in another order: 3e-7
# to 1.6e-6 of the largest value when measured, the gate's weight the most
TOLERANCE = 1e-5


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


def _build(cfg=CFG, amp=False, recompute=True):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    extras = {}
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        loss, logits, load = causal_lm.build_train(
            cfg, T, recompute=recompute, extras=extras)
        passes = extras["pass_logits"]
        extras["pass_logits"] = fluid.layers.concat(passes, axis=1) \
            if len(passes) > 1 else passes[0]
    return main, startup, dict(extras, loss=loss, logits=logits)


def _run_program(cfg=CFG, amp=False, recompute=True, gate_bias=None):
    """One training step from seeded weights: (the parameters' values before
    the step, {fetch: value})."""
    main, startup, fetch = _build(cfg, amp, recompute)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if gate_bias is not None:
            scope.set("exit_gate.b", np.full((1,), gate_bias, np.float32))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        names = sorted(fetch)
        out = exe.run(main, feed=_feed(), fetch_list=[fetch[n] for n in names]
                      + [p.name + "@GRAD" for p in params])
    got = dict(zip(names, out))
    got["grads"] = dict(zip((p.name for p in params), out[len(names):]))
    got["program"] = main
    return [p.name for p in params], weights, got


def _reference(weights, cfg=CFG):
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, p, _)), grads = jax.jit(jax.value_and_grad(
        lambda w: reference.loss_fn(cfg, w, feed["ids"], feed["pos"],
                                    feed["labels"], with_passes=True),
        has_aux=True))([jnp.asarray(w) for w in weights])
    return {"loss": loss, "logits": logits, "p": p, "grads": grads}


def _count(name, **labels):
    return REGISTRY.counter(name, "").value(**labels)


COUNTED = {
    "built": ("ptpu_causal_lm_layers_total", dict(
        mixer="attention", rotary_dim="8", gate="false", conv="0",
        ffn="dense", shared="0", sandwich="true", module="trunk",
        reads="own", differential="false")),
    "passes": ("ptpu_layer_passes_total", dict(passes="4", layers="2",
                                               form="scan")),
    "forward": ("ptpu_remat_ops_total", dict(kind="forward",
                                             op="fused_attention")),
    "replayed": ("ptpu_remat_ops_total", dict(kind="replayed",
                                              op="fused_attention")),
    "heads": ("ptpu_remat_ops_total", dict(
        kind="forward", op="softmax_with_cross_entropy")),
    "heads_replayed": ("ptpu_remat_ops_total", dict(
        kind="replayed", op="softmax_with_cross_entropy")),
    "loop_kept": ("ptpu_lowering_grad_ops_total", dict(path="kept",
                                                       op="rnn_scan"))}


def _counted():
    return {key: _count(name, **labels)
            for key, (name, labels) in COUNTED.items()}


@pytest.fixture(scope="module")
def program():
    before = _counted()
    names, weights, got = _run_program()
    got["counted"] = {k: v - before[k] for k, v in _counted().items()}
    return names, weights, got


@pytest.fixture(scope="module")
def want(program):
    return _reference(program[1])


def test_the_parameters_are_those_of_the_layers_built(program):
    """Two layers' worth, each weight once: not four times that."""
    names, weights, _ = program
    assert names == NAMES
    assert sum(w.size for w in weights) == 2 * (
        4 * 32 * 32 + 3 * 32 * 48 + 4 * 32) + 2 * 64 * 32 + 32 + 33


def test_loss_agrees_with_the_reference(program, want):
    assert _error(program[2]["loss"], want["loss"]) < TOLERANCE


@pytest.mark.parametrize("t", range(P))
def test_logits_of_every_pass_agree_with_the_reference(program, want, t):
    got = program[2]["pass_logits"].reshape(B, P, T, -1)[:, t]
    assert _error(got, want["logits"][t]) < TOLERANCE
    if t == P - 1:
        assert _error(program[2]["logits"], want["logits"][t]) < TOLERANCE


def test_exit_distribution_agrees_and_sums_to_one(program, want):
    p = program[2]["exit_p"]
    assert p.shape == (B, P, T)
    assert _error(p, jnp.moveaxis(want["p"], 0, 1)) < TOLERANCE
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(want["p"]).sum(0), 1.0, atol=1e-6)
    # the gate's bias starts at 0: near (1/2, 1/4, 1/8, 1/8), every pass
    # carries loss
    assert np.abs(p.mean((0, 2)) - [0.5, 0.25, 0.125, 0.125]).max() < 0.05


@pytest.mark.parametrize("name", NAMES)
def test_gradient_agrees_with_the_reference(program, want, name):
    """Every parameter's: a stack weight's is the sum over its four uses,
    as jax.grad of the reference's Python loop has it."""
    names, _, got = program
    grad = want["grads"][names.index(name)]
    assert np.abs(grad).max() > 0
    assert _error(got["grads"][name], grad) < TOLERANCE


@pytest.mark.parametrize("bias,t", [(30.0, 0), (-30.0, P - 1)])
def test_a_saturated_gate_leaves_one_pass_its_loss(bias, t):
    """lambda = 1 at every pass: all of p on pass 1, the loss is its
    cross-entropy; lambda = 0: all on the last pass. Finite: 0 log 0 = 0."""
    _, weights, got = _run_program(gate_bias=bias)
    want = _reference(weights)
    labels = jnp.asarray(_feed()["labels"])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(want["logits"][t], -1),
                               labels, axis=-1).mean()
    assert np.isfinite(got["loss"]).all()
    assert _error(got["loss"], nll) < TOLERANCE
    assert _error(got["loss"], want["loss"]) < TOLERANCE
    assert np.abs(got["exit_p"][:, t] - 1.0).max() < 1e-6
    assert all(np.isfinite(g).all() for g in got["grads"].values())


def _loop_op(main):
    return next(op for op in main.global_block().ops
                if op.type == "rnn_scan")


def test_recomputation_changes_neither_loss_nor_gradients(program):
    """A looped model recomputes by default (the fixture); the same program
    with every activation kept gives the same step."""
    names, _, got = program
    assert _loop_op(got["program"]).attrs["recompute"] is True
    _, _, kept = _run_program(recompute=False)
    assert "recompute" not in _loop_op(kept["program"]).attrs
    assert _error(kept["loss"], got["loss"]) < 1e-6
    for name in names:
        assert _error(kept["grads"][name], got["grads"][name]) < 1e-5, name


def _op_types(cfg):
    main, _, _ = _build(cfg)
    return [lowering.scope_type(op) for op in main.global_block().ops], \
        [tuple(p.shape) for p in main.global_block().all_parameters()]


@pytest.mark.parametrize("cfg,ops,sha1", [
    (DENSE, 161, "e19e2c99bb9e5e7ef168aaad47c9d8ff937fe9b6"),
    (dict(DENSE, num_experts=4, num_experts_per_tok=2, qk_norm=True), 184,
     "4a9442eff8f0532d815b59fe94c4bcd5e483ee7b"),
], ids=["dense", "experts"])
def test_one_pass_builds_the_program_it_built_before(cfg, ops, sha1):
    """total_ut_steps 1, no sandwich, no gate: op for op the program of the
    commit before the loop (its op types in order, recorded there), with no
    recomputation and no pass written on any op."""
    types, _ = _op_types(dict(cfg, total_ut_steps=1, sandwich_norm=False,
                              exit_gate=False))
    assert types == _op_types(cfg)[0] and len(types) == ops
    assert hashlib.sha1(" ".join(types).encode()).hexdigest() == sha1
    main, _, _ = _build(cfg)
    assert len(main.blocks) == 1
    assert not any(lowering.PASS_ATTR in op.attrs
                   for op in main.global_block().ops)


def test_a_loop_without_a_gate_trains_the_last_pass(program):
    """exit_gate false: one head, on the last pass's state; sandwich_norm
    false: two norms a layer."""
    cfg = dict(DENSE, total_ut_steps=3)
    names, weights, got = _run_program(cfg)
    assert len(names) == 1 + 2 * 9 + 2
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, _)), grads = reference.loss_and_grads(
        cfg, weights, feed["ids"], feed["pos"], feed["labels"])
    assert _error(got["loss"], loss) < TOLERANCE
    assert _error(got["logits"], logits) < TOLERANCE
    assert max(_error(got["grads"][n], g)
               for n, g in zip(names, grads)) < TOLERANCE


@pytest.mark.parametrize("change,error", [
    (dict(early_exit_threshold=0.9), NotImplementedError),
    (dict(num_experts=4, num_experts_per_tok=2), NotImplementedError),
    (dict(total_ut_steps=1), ValueError),
    (dict(total_ut_steps=0), ValueError),
], ids=["early_exit", "routed_experts", "gate_without_a_loop", "no_pass"])
def test_resolve_refuses_what_it_cannot_build(change, error):
    with pytest.raises(error):
        causal_lm.resolve(dict(CFG, **change))


def test_amp_program_agrees_with_the_reference(want):
    """bf16 matmuls and attention; float32 norms, rotary angles, gate, p and
    loss. Two layers four times deep the logits were off by 1.1e-2."""
    _, _, got = _run_program(amp=True)
    assert got["pass_logits"].dtype == jnp.bfloat16
    assert got["exit_p"].dtype == np.float32
    assert got["grads"]["layer_0.wq"].dtype == np.float32
    assert _error(got["loss"], want["loss"]) < 1e-3
    assert _error(got["exit_p"], jnp.moveaxis(want["p"], 0, 1)) < 2e-2
    assert _error(got["pass_logits"].reshape(B, P, T, -1),
                  jnp.stack(want["logits"], 1)) < 4e-2


def test_layers_are_counted_built_and_passes_once(program):
    """ptpu_causal_lm_layers_total counts the 2 layers built, not the 8
    applications; ptpu_layer_passes_total once a model; ptpu_remat_ops_total
    every forward op as often as it runs a step (a layer's op four times)
    and, replayed, the loop's body once more a trip, the heads never; the
    loop's grad op calls the linearization its forward op kept."""
    assert program[2]["counted"] == {
        "built": 2, "passes": 1, "forward": 8, "replayed": 8, "heads": 4,
        "heads_replayed": 0, "loop_kept": 1}


def test_the_passes_are_one_loop_op_under_its_scope(program):
    """The stack is the sub-block of one rnn_scan op of four trips with no
    step input, built once: 2 layers' ops, not 8. The loop op and its grad
    op lower under "pass:1-4/op:rnn_scan.../"; the head, the gate, the loss
    and the optimizer under no pass."""
    main = program[2]["program"]
    loop = _loop_op(main)
    assert loop.inputs["X"] == [] and loop.attrs["max_len"] == P
    assert loop.attrs[lowering.PASS_ATTR] == "1-4"
    body = [op.type for op in main.blocks[loop.attrs["sub_block"]].ops]
    assert body.count("fused_attention") == 2
    assert body.count("rms_norm") == 2 * 4 + 1          # the final norm too
    by_pass = {}
    for op in main.global_block().ops:
        scope = lowering.op_scope(op)
        by_pass.setdefault(lowering.parse_pass_scope(scope), []).append(
            lowering.parse_op_scope(scope)[0])
    assert by_pass["1-4"] == ["rnn_scan", "rnn_scan_grad"]
    assert {"softmax_with_cross_entropy", "adam", "logsigmoid", "mul",
            "lookup_table"} <= set(by_pass[None])
    assert not {"fused_attention", "rms_norm", "swish"} & set(by_pass[None])


@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/pass:1-4/op:rnn_scan/static_rnn_0.out_0/while/body/"
     "op:mul/fc_4.tmp_0/dot_general", ("1-4", "mul")),
    ("jit(fn)/pass:1-4/op:rnn_scan_grad/x~GRAD/transpose(jvp(pass:1-4/"
     "op:rnn_scan/y))/while/body/op:mul/fc_4.tmp_0/dot", ("1-4", "mul")),
    ("jit(fn)/op:adam/head/add", (None, "adam")),
    ("jit(fn)/pass:3/op:rms_norm/a/pass:4x/mul", ("3", "rms_norm")),
])
def test_pass_scope_is_parsed_back(op_name, want):
    assert (lowering.parse_pass_scope(op_name),
            lowering.parse_op_scope(op_name)[0]) == want


def test_the_lowered_step_carries_the_pass_scope(program):
    main = program[2]["program"]
    loss = next(op for op in main.global_block().ops
                if op.type == "mean").outputs["Out"][0]
    feed = _feed()
    state_rw, state_ro, state_out = lowering.analyze_state(
        main, sorted(feed), [loss])
    fn = lowering.build_program_fn(main, sorted(feed), [loss], state_rw,
                                   state_ro, state_out)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(_build()[1])
        args = ([jnp.asarray(feed[n]) for n in sorted(feed)],
                [scope.get(n) for n in state_rw],
                [scope.get(n) for n in state_ro], jnp.uint32(0))
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert "pass:1-4/op:rnn_scan/" in text
    assert "pass:1-4/op:rnn_scan_grad/" in text
    assert "/op:fused_attention/" in text
