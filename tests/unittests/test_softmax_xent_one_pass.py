"""softmax_with_cross_entropy on its kernel path (PR 44): where the kernel
is on, the labels hard, the logits 2-D and nothing reads the dense `Softmax`
output, the op touches [N, V] once forward (`ptpu_softmax_xent_fwd`), in the
dtype the logits come in (AMP does not upcast them), builds no Softmax, and
its backward is dlogits alone, jax.numpy on the same logits for XLA to fuse
into the matmuls that read it. Every other program lowers as the parent
commit lowered it."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import lowering
from paddle_tpu.core.framework import grad_var_name
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import kernel_config, pallas_kernels as pk

N, D, V = 24, 16, 40


def _formula(logits, labels, g):
    """(loss, dlogits) in float32 from the logits as given, dlogits rounded
    once to their dtype."""
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1, keepdims=True)
    loss = lse - jnp.take_along_axis(x, labels[:, None], axis=1)
    d = (jnp.exp(x - lse) - jax.nn.one_hot(labels, x.shape[1])) * g
    return loss, d.astype(logits.dtype)


# --- the kernel and its backward ---------------------------------------------

@pytest.mark.parametrize("dtype,n,v,block_n", [
    (jnp.float32, 13, 37, None),        # fewer rows than one tile
    (jnp.bfloat16, 13, 37, None),
    (jnp.bfloat16, 40, 300, 16),        # a ragged last block of 8 rows
    (jnp.float32, 100, 130, 16),        # ragged by 4 rows, V over one tile
    (jnp.bfloat16, 100, 200, 32),
    (jnp.bfloat16, 24, 37984, None),    # SmallThinker's columns: 296.75 tiles
    (jnp.float32, 24, 37984, None),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_loss_and_dlogits_are_the_float32_formulas(dtype, n, v, block_n):
    rng = np.random.RandomState(n + v)
    logits = jnp.asarray(rng.randn(n, v) * 3.0, dtype)
    labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    labels = labels.at[0].set(v - 1).at[1].set(0)
    g = jnp.asarray(rng.rand(n, 1) + 0.5, jnp.float32)
    loss, vjp = jax.vjp(lambda x: pk.softmax_xent(
        x, labels, block_n=block_n, interpret=True), logits)
    dlogits, = vjp(g)
    want_loss, want_d = _formula(logits, labels, g)
    assert loss.dtype == jnp.float32 and loss.shape == (n, 1)
    assert dlogits.dtype == dtype and dlogits.shape == (n, v)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss),
                               rtol=1e-5, atol=1e-5)
    # one rounding of the same float32 value: equal to an ulp of the dtype
    np.testing.assert_allclose(
        np.asarray(dlogits, np.float32), np.asarray(want_d, np.float32),
        rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)


@pytest.mark.parametrize("n,v,dtype,rows", [
    (8192, 37984, jnp.bfloat16, 16), (16384, 50304, jnp.bfloat16, 16),
    (4096, 151936, jnp.bfloat16, 16), (16384, 32000, jnp.float32, 8),
    (16384, 32000, jnp.bfloat16, 16), (4096, 1024, jnp.bfloat16, 256),
    (1000, 512, jnp.float32, 512), (6, 10, jnp.float32, 8),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_the_tile_follows_the_tables_bytes(n, v, dtype, rows):
    """DEFAULT_TILES["xent"] is a budget in bytes for the float32 copy of
    one tile, as layer_norm's: one sublane granule of rows (8, or 16 for a
    2-byte dtype) at every vocabulary a cell has, more where a row is
    short; the grid is not padded, its last block may be ragged."""
    assert kernel_config.DEFAULT_TILES["xent"] == {"tile_bytes": 1 << 20}
    logits = jax.ShapeDtypeStruct((n, v), dtype)
    assert pk._xent_rows(logits, None) == rows
    assert pk._xent_rows(logits, 24) == 24
    jaxpr = jax.make_jaxpr(lambda x, lab: pk.softmax_xent(
        x, lab, interpret=True))(logits, jax.ShapeDtypeStruct((n,),
                                                              jnp.int32))
    call, = _pallas_calls(jaxpr.jaxpr)
    assert call.params["grid_mapping"].grid == (-(-n // rows),)
    assert tuple(call.invars[0].aval.shape) == (n, v)    # no pad


# --- through a Program -------------------------------------------------------

def _program(amp=False, soft=False, read=None):
    """x -> fc -> softmax_with_cross_entropy -> mean, SGD. `read`: None, or
    "loss" (the dense Softmax enters the loss through a later op and gets a
    gradient)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[D], dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[V] if soft else [1],
                                dtype="float32" if soft else "int64")
        # no bias: a float32 bias would make the sum float32 under AMP
        logits = fluid.layers.fc(input=x, size=V, bias_attr=False)
        helper = fluid.layers.nn.LayerHelper("softmax_with_cross_entropy")
        softmax = helper.create_tmp_variable(dtype=logits.dtype)
        loss = helper.create_tmp_variable(dtype=logits.dtype)
        helper.append_op(type="softmax_with_cross_entropy",
                         inputs={"Logits": [logits], "Label": [lab]},
                         outputs={"Softmax": [softmax], "Loss": [loss]},
                         attrs={"soft_label": soft})
        cost = fluid.layers.mean(loss)
        if read == "loss":
            cost = cost + fluid.layers.mean(softmax * softmax)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    if amp:
        main.enable_mixed_precision()
    return main, startup, cost, softmax, logits


def _step(main, startup, fetches, soft=False):
    """(fn, args) of the step as build_program_fn lowers it."""
    feeds = ["x", "lab"]
    rw, ro, out = lowering.analyze_state(main, feeds, fetches)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        vals = {n: np.asarray(scope.find_var(n).get_tensor())
                for n in set(rw) | set(ro)}
    rng = np.random.RandomState(5)
    lab = rng.rand(N, V).astype("float32") if soft \
        else rng.randint(0, V, (N, 1)).astype("int32")
    if soft:
        lab /= lab.sum(-1, keepdims=True)
    fn = lowering.build_program_fn(main, feeds, fetches, rw, ro, out)
    args = ([rng.randn(N, D).astype("float32"), lab],
            [vals[n] for n in rw], [vals[n] for n in ro])
    return (lambda f, a, b: fn(f, a, b, 0)[0]), args


def _pallas_calls(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def _upcasts_of(jaxpr, shape, found=None):
    """convert_element_type equations bf16 -> float32 at `shape`."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type" \
                and eqn.invars[0].aval.shape == shape \
                and eqn.invars[0].aval.dtype == jnp.bfloat16 \
                and eqn.outvars[0].aval.dtype == jnp.float32:
            found.append(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _upcasts_of(sub, shape, found)
    return found


def _counted():
    return {tuple(sorted(dict(k).items())): v for k, v in REGISTRY.counter(
        "ptpu_softmax_xent_layers_total").samples()}


def _labels_of_one_trace(fn, args):
    before = _counted()
    jaxpr = jax.make_jaxpr(fn)(*args)
    after = _counted()
    new = [dict(k) for k, v in after.items() if v != before.get(k, 0)]
    assert len(new) == 1 and sum(after.values()) - sum(before.values()) == 1
    return new[0], jaxpr


@pytest.mark.parametrize("amp", [False, True], ids=["f32", "amp"])
def test_the_logits_go_in_as_they_come_and_no_softmax_is_built(
        amp, monkeypatch):
    """Kernel on, nothing reads Softmax: one Mosaic call, the logits go in
    as the matmul gave them (bf16 under AMP: the forward has no float32
    [N, V]), no log_softmax anywhere in the step, loss float32; loss and
    every gradient as with the kernel off."""
    main, startup, cost, _, logits = _program(amp=amp)
    fetch = [cost.name] + [grad_var_name(p.name)
                           for p in main.global_block().all_parameters()]
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent")
    fn, args = _step(main, startup, fetch)
    labels, jaxpr = _labels_of_one_trace(fn, args)
    dt = "bfloat16" if amp else "float32"
    assert labels == {"path": "kernel", "logits": dt, "softmax": "unread"}
    call, = _pallas_calls(jaxpr.jaxpr)
    assert call.params["name"] == "ptpu_softmax_xent_fwd"
    assert call.invars[0].aval.str_short(short_dtypes=True) == \
        "%s[%d,%d]" % ("bf16" if amp else "f32", N, V)
    text = str(jaxpr)
    assert "log_softmax" not in text and "logsumexp" not in text
    # under AMP two casts of a bf16 [N, V] array up are left, both in the
    # backward: dlogits' own, from the logits (XLA fuses it into the dots
    # that read dlogits), and mul_grad's of dlogits; the loss op's is not
    assert len(_upcasts_of(jaxpr.jaxpr, (N, V))) == (2 if amp else 0)
    got = jax.jit(fn)(*args)
    assert got[0].dtype == jnp.float32
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    fn, args = _step(main, startup, fetch)
    labels, jaxpr = _labels_of_one_trace(fn, args)
    assert labels == {"path": "xla", "logits": "float32", "softmax": "unread"}
    want = jax.jit(fn)(*args)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2 if amp else 1e-4, atol=2e-3 if amp else 1e-6)


@pytest.mark.parametrize("how", ["fetched", "in_the_loss", "persistable"])
@pytest.mark.parametrize("amp", [False, True], ids=["f32", "amp"])
def test_a_program_that_reads_softmax_gets_it_and_its_gradient(
        amp, how, monkeypatch):
    """A fetch, a later op or persistable state reads the Softmax variable:
    the op lowers as it did (the kernel for the loss, the dense Softmax
    beside it; float32 logits under AMP), and Softmax, loss and gradients
    are what the kernel-off path gives."""
    main, startup, cost, softmax, _ = _program(
        amp=amp, read="loss" if how == "in_the_loss" else None)
    if how == "persistable":
        softmax.persistable = True
    fetch = [cost.name] + [grad_var_name(p.name)
                           for p in main.global_block().all_parameters()]
    if how != "persistable":
        fetch.append(softmax.name)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent")
    fn, args = _step(main, startup, fetch)
    labels, jaxpr = _labels_of_one_trace(fn, args)
    assert labels == {"path": "kernel", "logits": "float32",
                      "softmax": "read"}
    assert [c.params["name"] for c in _pallas_calls(jaxpr.jaxpr)] == [
        "ptpu_softmax_xent_fwd"]
    got = jax.jit(fn)(*args)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    fn, args = _step(main, startup, fetch)
    want = jax.jit(fn)(*args)
    if how != "persistable":
        assert got[-1].shape == (N, V)
        np.testing.assert_allclose(np.asarray(got[-1]).sum(-1), 1.0,
                                   rtol=1e-2 if amp else 1e-5)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2 if amp else 1e-4, atol=2e-3 if amp else 1e-6)


def _xent_op(program):
    op, = [o for o in program.global_block().ops
           if o.type == "softmax_with_cross_entropy"]
    return op, lowering.registry.get(op.type)


@pytest.mark.parametrize("how", ["input", "out_names"])
def test_a_read_in_a_sub_block_counts(how):
    """An op of another block of the program reads the variable, or a
    control-flow op names it in a list attribute (a step output of its
    sub-block)."""
    main, startup, cost, softmax, _ = _program()
    sub = main.create_block()
    sub.append_op(type="scale",
                  inputs={"X": [softmax.name if how == "input" else "x"]},
                  outputs={"Out": [softmax.name + ".scaled"]},
                  attrs={"scale": 2.0, "out_names": [softmax.name] * (
                      how == "out_names")}, infer_shape=False)
    main.rollback()
    op, od = _xent_op(main)
    assert od.optional_outputs == ("Softmax",)
    assert lowering._unread_outputs(lowering.LowerCtx(main), od,
                                    op.outputs) == frozenset()


def test_nothing_reads_the_softmax_of_a_plain_training_program():
    """The grad op names the forward op's inputs and Loss@GRAD, not its
    outputs; a fetch (build_program_fn's leaves_step) is a read."""
    main = _program()[0]
    op, od = _xent_op(main)
    assert lowering._unread_outputs(lowering.LowerCtx(main), od,
                                    op.outputs) == {"Softmax"}
    ctx = lowering.LowerCtx(main)
    ctx.leaves_step = {op.outputs["Softmax"][0]}
    assert lowering._unread_outputs(ctx, od, op.outputs) == frozenset()


@pytest.mark.parametrize("soft", [False, True], ids=["hard", "soft"])
def test_soft_labels_rank_and_the_flag_decide_the_path(soft, monkeypatch):
    """Soft labels, logits that are not 2-D and a kernel that is off take
    XLA's path, which builds the Softmax whoever reads it."""
    from paddle_tpu.ops.nn_ops import softmax_xent_form
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent")
    logits = jax.ShapeDtypeStruct((N, V), jnp.bfloat16)
    attrs = {"soft_label": soft}
    ctx = lowering.LowerCtx(fluid.Program())
    assert softmax_xent_form(ctx, logits, attrs) == (
        ("xla", True) if soft else ("kernel", True))
    ctx.unread_outputs = frozenset({"Softmax"})
    assert softmax_xent_form(ctx, logits, attrs) == (
        ("xla", True) if soft else ("kernel", False))
    assert softmax_xent_form(
        ctx, jax.ShapeDtypeStruct((2, N, V), jnp.float32), attrs) == (
            "xla", True)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn")
    assert softmax_xent_form(ctx, logits, attrs) == ("xla", True)


def test_under_a_mesh_the_same_path_and_the_same_losses(monkeypatch):
    """A mesh is no condition of the path (the kernel ran under one before,
    and dlogits is XLA's): a data-parallel AMP program over the 8 devices
    counts `kernel, bfloat16, unread` and reads the kernel-off losses."""
    def losses(flag):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        main, startup, cost, _, _ = _program(amp=True)
        rng = np.random.RandomState(3)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            pexe = fluid.ParallelExecutor(main_program=main,
                                          loss_name=cost.name)
            return [float(np.mean(pexe.run(fetch_list=[cost.name], feed={
                "x": rng.randn(N, D).astype("float32"),
                "lab": rng.randint(0, V, (N, 1)).astype("int64")})[0]))
                for _ in range(2)]
    before = _counted()
    got = losses("xent")
    new = [dict(k) for k, v in _counted().items() if v != before.get(k, 0)]
    assert new == [{"path": "kernel", "logits": "bfloat16",
                    "softmax": "unread"}]
    np.testing.assert_allclose(got, losses("0"), rtol=2e-2)


# the step's jaxpr as the parent commit (PR 43) traced it: sha256 of its
# text, first 16 digits
_PARENT = {("hard", "f32"): "bc4c0f622a300f33",
           ("hard", "amp"): "fb175a91428d8ad6",
           ("soft", "f32"): "1b22909567fb5861",
           ("soft", "amp"): "ab5af348b888efe1"}


@pytest.mark.parametrize("labels,precision", sorted(_PARENT))
def test_the_xla_path_and_soft_labels_lower_as_the_parent_did(
        labels, precision, monkeypatch):
    """Kernels off (the default off a TPU), hard and soft labels, float32
    and AMP: the training step is the parent's jaxpr, text for text, the
    unread Softmax still a result of the differentiated function there."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    soft = labels == "soft"
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[24], dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[40] if soft else [1],
                                dtype="float32" if soft else "int64")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=x, size=40), lab, soft_label=soft))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    if precision == "amp":
        main.enable_mixed_precision()
    feeds = ["x", "lab"]
    rw, ro, out = lowering.analyze_state(main, feeds, [loss.name])
    block = main.global_block()

    def shapes(names):
        return [jax.ShapeDtypeStruct(
            tuple(8 if d == -1 else d for d in block.var(n).shape),
            np.dtype("int32" if "int" in str(block.var(n).dtype)
                     else block.var(n).dtype)) for n in names]
    fn = lowering.build_program_fn(main, feeds, [loss.name], rw, ro, out)
    text = str(jax.make_jaxpr(lambda f, a, b: fn(f, a, b, 0))(
        shapes(feeds), shapes(rw), shapes(ro)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PARENT[
        (labels, precision)]


def test_profile_report_says_which_path_a_loss_took(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "xent")
    main, startup, cost, _, _ = _program(amp=True)
    fn, args = _step(main, startup, [cost.name])
    jax.make_jaxpr(fn)(*args)
    lines = profiler._softmax_xent_lines()
    assert any("loss by kernel on bfloat16 logits, Softmax unread" in ln
               for ln in lines), lines
