"""A grad op uses the linearization its forward op kept (core/lowering.py:
_linearizations, _lower_op_inner, _lower_grad_of): every Pallas forward
kernel runs once a step. Kernels are forced on and interpreted, as in
test_pallas_kernels.py; the replay every other op keeps is the reference."""
import inspect
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import lowering, registry
from paddle_tpu.core.framework import grad_var_name
from paddle_tpu.models import image_classification
from paddle_tpu.observability.registry import REGISTRY

CALLS_PALLAS = {"fused_attention", "layer_norm", "softmax_with_cross_entropy",
                "sequence_pool", "sequence_softmax", "lstm", "lstmp",
                "gated_delta_rule", "causal_conv1d", "mhc_pre", "mhc_post",
                "mhc_expand", "mhc_reduce", "selective_scan", "ssd_scan",
                "rotary_embedding", "kda_delta_rule"}


@pytest.fixture(autouse=True)
def kernels_on(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")    # interpreted, off a TPU
    monkeypatch.setenv("FLAGS_flash_min_seq", "8")  # T=16 is over it


def _replay(monkeypatch):
    """Force today's fallback everywhere: no forward op keeps anything."""
    monkeypatch.setattr(lowering, "_linearizations", lambda ctx, ops: {})


def _train_program(amp=False):
    """layer_norm, flash attention and softmax_xent in one training step;
    two of each norm so that `kept` counts more than one grad op a type."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[16, 32], dtype="float32")
        lab = layers.data(name="lab", shape=[1], dtype="int64")
        h = layers.layer_norm(x, begin_norm_axis=2)
        q = layers.reshape(layers.fc(input=h, size=16, num_flatten_dims=2),
                           shape=[-1, 16, 2, 8])
        a = layers.fused_attention(q, q, q, causal=True)
        h = layers.layer_norm(layers.reshape(a, shape=[-1, 256]))
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(input=h, size=32), lab))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    if amp:
        main.enable_mixed_precision()
    return main, startup, loss


def _feeds(main, names):
    rng = np.random.RandomState(0)
    block = main.global_block()
    out = []
    for n in names:
        v = block.var(n)
        shape = tuple(4 if d == -1 else d for d in v.shape)
        out.append(rng.randint(0, 8, shape).astype("int32")
                   if "int" in str(v.dtype)
                   else rng.randn(*shape).astype("float32"))
    return out


def _lowered(main, startup, feed_names, fetch_names):
    """(fn, args): the step as build_program_fn lowers it, on the weights
    the startup program made."""
    rw, ro, out = lowering.analyze_state(main, feed_names, fetch_names)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        vals = {n: np.asarray(scope.find_var(n).get_tensor())
                for n in set(rw) | set(ro)}
    fn = lowering.build_program_fn(main, feed_names, fetch_names, rw, ro, out)
    args = (_feeds(main, feed_names), [vals[n] for n in rw],
            [vals[n] for n in ro])
    return (lambda f, a, b: fn(f, a, b, 0)[0]), args


def _param_grads(main):
    return [grad_var_name(p.name)
            for p in main.global_block().all_parameters()]


def _kernel_calls(fn, args):
    """{kernel name: pallas_call equations in the step's jaxpr}."""
    counts = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                counts[name] = counts.get(name, 0) + 1
                continue        # the kernel's own body is no step
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


def _grad_op_counts():
    return {(dict(k)["path"], dict(k)["op"]): v for k, v in
            REGISTRY.counter("ptpu_lowering_grad_ops_total").samples()}


def _counted(fn, args):
    """What one trace of `fn` adds to the counter: {(path, op type): n}."""
    before = _grad_op_counts()
    jax.make_jaxpr(lambda *a: fn(*a))(*args)    # a trace of its own, uncached
    after = _grad_op_counts()
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v != before.get(k, 0)}


# --- (a) every forward kernel once ------------------------------------------

def test_each_forward_kernel_runs_once_where_the_replay_runs_it_twice(
        monkeypatch):
    main, startup, loss = _train_program()
    fn, args = _lowered(main, startup, ["x", "lab"], [loss.name])
    assert _kernel_calls(fn, args) == {
        "ptpu_layer_norm_fwd": 2, "ptpu_flash_fwd": 1,
        "ptpu_softmax_xent_fwd": 1, "ptpu_flash_bwd_dkdv": 1,
        "ptpu_flash_bwd_dq": 1}
    _replay(monkeypatch)
    fn, args = _lowered(main, startup, ["x", "lab"], [loss.name])
    assert _kernel_calls(fn, args) == {
        "ptpu_layer_norm_fwd": 4, "ptpu_flash_fwd": 2,
        "ptpu_softmax_xent_fwd": 2, "ptpu_flash_bwd_dkdv": 1,
        "ptpu_flash_bwd_dq": 1}


# --- (b) the same loss and gradients as the replay --------------------------

@pytest.mark.parametrize("amp", [False, True], ids=["f32", "amp"])
def test_loss_and_gradients_equal_the_replays(amp, monkeypatch):
    main, startup, loss = _train_program(amp=amp)
    fetch = [loss.name] + _param_grads(main)
    assert len(fetch) == 9
    fn, args = _lowered(main, startup, ["x", "lab"], fetch)
    kept = jax.jit(fn)(*args)
    _replay(monkeypatch)
    fn, args = _lowered(main, startup, ["x", "lab"], fetch)
    replayed = jax.jit(fn)(*args)
    for name, a, b in zip(fetch, kept, replayed):
        assert a.dtype == b.dtype and np.isfinite(np.asarray(a)).all(), name
        assert np.abs(np.asarray(b)).max() > 0, name
        np.testing.assert_allclose(np.asarray(a, "float32"),
                                   np.asarray(b, "float32"), rtol=1e-5,
                                   atol=1e-6, err_msg=name)


def test_the_executor_trains_on_kept_linearizations():
    main, startup, loss = _train_program()
    x, lab = _feeds(main, ["x", "lab"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(np.asarray(exe.run(
            main, feed={"x": x, "lab": lab}, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(6)]
    assert losses[-1] < losses[0] - 0.05, losses


# --- (c) where it is used twice --------------------------------------------

def _calc_gradient_twice():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[32], dtype="float32")
        x.stop_gradient = False
        y = layers.layer_norm(x)
        s1 = layers.reduce_sum(layers.square(y))
        s2 = layers.reduce_sum(layers.scale(y, scale=3.0))
        (g1,) = fluid.calc_gradient(s1, x)
        (g2,) = fluid.calc_gradient(s2, x)
    return main, startup, (g1, g2)


def test_one_kept_linearization_serves_two_grad_ops(monkeypatch):
    main, startup, (g1, g2) = _calc_gradient_twice()
    assert g1.name == g2.name       # both accumulate into x@GRAD
    fn, args = _lowered(main, startup, ["x"], [g1.name])
    assert _kernel_calls(fn, args) == {"ptpu_layer_norm_fwd": 1}
    assert _counted(fn, args)[("kept", "layer_norm")] == 2
    kept = jax.jit(fn)(*args)
    _replay(monkeypatch)
    fn, args = _lowered(main, startup, ["x"], [g1.name])
    assert _kernel_calls(fn, args) == {"ptpu_layer_norm_fwd": 3}
    np.testing.assert_allclose(np.asarray(kept[0]),
                               np.asarray(jax.jit(fn)(*args)[0]), rtol=1e-5,
                               atol=1e-6)


def test_a_grad_op_in_a_while_sub_block(monkeypatch):
    """The forward op and its grad op both live in the loop's sub-block and
    are traced in one lax.while_loop body: the block keeps its own
    linearizations, and the enclosing block's are out of reach."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4, 32], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        acc = layers.zeros(shape=[4, 32], dtype="float32")
        j = layers.zeros(shape=[1], dtype="int32")
        j.stop_gradient = True
        n = layers.fill_constant(shape=[1], dtype="int32", value=3)
        cond = layers.less_than(x=j, y=n)
        w = layers.While(cond=cond)
        with w.block():
            xin = layers.elementwise_add(x, acc)
            xin.stop_gradient = False
            y = layers.layer_norm(xin)
            (g,) = fluid.calc_gradient(
                layers.reduce_sum(layers.square(y)), xin)
            layers.sums(input=[acc, g], out=acc)
            j = layers.increment(j)
            layers.less_than(x=j, y=n, cond=cond)
    sub = main.blocks[1]
    assert {"layer_norm", "grad_of"} <= {op.type for op in sub.ops}
    fn, args = _lowered(main, startup, ["x"], [acc.name])
    assert _counted(fn, args)[("kept", "layer_norm")] == 1
    kept = np.asarray(jax.jit(fn)(*args)[0])
    assert np.abs(kept).max() > 0
    _replay(monkeypatch)
    fn, args = _lowered(main, startup, ["x"], [acc.name])
    assert ("kept", "layer_norm") not in _counted(fn, args)
    np.testing.assert_allclose(kept, np.asarray(jax.jit(fn)(*args)[0]),
                               rtol=1e-5, atol=1e-6)


def test_a_for_test_clone_lowers_as_before():
    """No grad op, nothing kept: the forward ops lower outside jax.vjp."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[16, 32], dtype="float32")
        lab = layers.data(name="lab", shape=[1], dtype="int64")
        h = layers.layer_norm(layers.reshape(x, shape=[-1, 512]))
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(input=h, size=32), lab))
        test = main.clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    fn, args = _lowered(test, startup, ["x", "lab"], [loss.name])
    assert _counted(fn, args) == {}
    assert _kernel_calls(fn, args) == {"ptpu_layer_norm_fwd": 1,
                                       "ptpu_softmax_xent_fwd": 1}
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert "op:layer_norm/" in text and "jvp(" not in text
    trained = _lowered(main, startup, ["x", "lab"], [loss.name])
    np.testing.assert_allclose(
        np.asarray(jax.jit(fn)(*args)[0]),
        np.asarray(jax.jit(trained[0])(*trained[1])[0]), rtol=1e-6)


# --- (d) the counter ---------------------------------------------------------

def test_the_counter_reads_kept_and_replayed_by_forward_op_type(monkeypatch):
    main, startup, loss = _train_program()
    grad_ops = [op.attrs["fwd_type"] for op in main.global_block().ops
                if op.type == "grad_of"]
    fn, args = _lowered(main, startup, ["x", "lab"], [loss.name])
    counted = _counted(fn, args)
    assert {t: n for (path, t), n in counted.items() if path == "kept"} == {
        "layer_norm": 2, "fused_attention": 1,
        "softmax_with_cross_entropy": 1}
    replayed = {t: n for (path, t), n in counted.items()
                if path == "replayed"}
    assert not set(replayed) & CALLS_PALLAS
    assert {"mul", "reshape", "mean", "elementwise_add"} <= set(replayed)
    assert sum(counted.values()) == len(grad_ops)
    _replay(monkeypatch)
    fn, args = _lowered(main, startup, ["x", "lab"], [loss.name])
    counted = _counted(fn, args)
    assert sum(counted.values()) == len(grad_ops)
    assert all(path == "replayed" for path, _ in counted)


# --- (e) which ops, and that the others do not move --------------------------

def test_the_rules_that_import_pallas_kernels_carry_the_field():
    import paddle_tpu.ops  # noqa: F401 — registers every rule
    reach = set()
    for op_type, od in registry._OPS.items():
        src = inspect.getsource(od.lower)
        if re.search(r"import pallas_kernels|pallas_kernels\.|"
                     r"gated_delta_kernels|causal_conv_kernels|mhc_kernels|"
                     r"selective_scan_kernels|ssd_kernels|rotary_kernels|"
                     r"kda_kernels|rms_norm_kernels",
                     src):
            reach.add(op_type)
    # `rms_norm` reaches a kernel (the transpose of a head's norm, PR 72)
    # and keeps no linearization: its forward pass is jax.numpy lines, which
    # XLA merges with the grad op's replay as it does for every op that
    # keeps none, and the block norms of every decoder cell lower as they
    # did (test_device_names.py counts the calls on a described v5e)
    assert reach == CALLS_PALLAS | {"rms_norm"}
    assert {t for t, od in registry._OPS.items()
            if od.calls_pallas} == CALLS_PALLAS


def test_a_program_without_those_ops_lowers_to_the_same_jaxpr(monkeypatch):
    """The tiny ResNet-50 of test_image_models.py: with the mechanism and
    with the replay forced the step is one jaxpr, text for text, which is
    what makes the benchmark's two ResNet cells a control."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        image, label, avg_cost, acc = image_classification.build_train(
            model="resnet50", class_dim=10, image_shape=(3, 32, 32),
            learning_rate=0.01)
    assert not {op.type for op in main.global_block().ops} & CALLS_PALLAS
    feeds = ["image", "label"]
    rw, ro, out = lowering.analyze_state(main, feeds, [avg_cost.name])
    block = main.global_block()

    def shapes(names):
        return [jax.ShapeDtypeStruct(
            tuple(2 if d == -1 else d for d in block.var(n).shape),
            np.dtype(block.var(n).dtype)) for n in names]

    def text():
        fn = lowering.build_program_fn(main, feeds, [avg_cost.name], rw, ro,
                                       out)
        return str(jax.make_jaxpr(lambda f, a, b: fn(f, a, b, 0))(
            shapes(feeds), shapes(rw), shapes(ro)))
    with_mechanism = text()
    _replay(monkeypatch)
    assert with_mechanism == text()
    assert "conv_general_dilated" in with_mechanism
