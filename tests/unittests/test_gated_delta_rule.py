"""The gated delta rule op (ops/gated_delta_kernels.py): the chunked forward
and backward, on the Pallas kernels (interpreted here) and on lax.scan,
against the token-by-token recurrence of models/causal_lm_reference.py and
jax.grad of it (the ten shapes of that comparison are a file of their own,
test_gated_delta_recurrence.py: the suite's longest function in one
process, and under `--dist loadfile` a file is one worker's); (I + L)^-1 and the shape of its products; `_prepare` on
bf16 against float32 inputs; the op through a Program; its counter."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.models import causal_lm_reference as plain
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import gated_delta_kernels as gdk
from paddle_tpu.ops import kernel_config

TOLERANCE = 2e-5        # float32 against float32, another summation order


def _inputs(t, hk=2, hv=4, dk=16, dv=24, b=2, seed=0, g_scale=2.0,
            g_shift=0.0):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(b, t, hk, dk), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, hv, dv), jnp.float32)
    g = -jnp.asarray(rng.rand(b, t, hv) * g_scale + g_shift, jnp.float32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(b, t, hv), jnp.float32))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The judge: l2 norms and the scale, key heads repeated, then the
    recurrence a token at a time."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(plain.l2norm(x), rep, axis=2) for x in (q, k))
    return plain.delta_rule(q * q.shape[-1] ** -0.5, k, v, g, beta)


def _error(got, want, floor=0.0):
    """Largest error over the largest value (or `floor`, for a gradient
    that is all rounding: d/dg where exp(g) is 1e-6)."""
    return float(jnp.abs(got - want).max()
                 / jnp.maximum(jnp.abs(want).max(), floor))


def _forward_and_grads(fn, args, ct):
    with jax.default_matmul_precision("highest"):
        out = fn(*args)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                         argnums=tuple(range(5)))(*args)
    return out, grads


def test_kernel_and_scan_paths_run_the_same_arithmetic():
    """Same chunked form, same operand dtypes and accumulators: under bf16
    operands the two forwards differ by the order of a few float32 sums
    only (the backwards round their cotangents at other places: bf16's
    reach), and both stay within bf16's reach of the float32 recurrence."""
    args = _inputs(96, dk=32, dv=32)
    ct = jnp.ones(args[2].shape, jnp.float32)

    def run(path):
        return _forward_and_grads(
            lambda *a: gdk.gated_delta_rule(
                *a, path=path, chunk=32, operand_dtype=jnp.bfloat16), args,
            ct)

    (ko, kg), (so, sg) = run("kernel"), run("scan")
    assert _error(ko, so) < 1e-5
    for a, b in zip(kg, sg):
        assert _error(a, b) < 1e-2
    want, want_grads = _forward_and_grads(_recurrence, args, ct)
    assert _error(ko, want) < 2e-2
    for a, b in zip(kg, want_grads):
        assert _error(a, b) < 4e-2


def test_bf16_inputs_come_back_bf16():
    args = tuple(a.astype(jnp.bfloat16) if i < 3 else a
                 for i, a in enumerate(_inputs(40)))
    out = gdk.gated_delta_rule(*args, path="kernel", chunk=16)
    assert out.dtype == jnp.bfloat16
    want = _recurrence(*(a.astype(jnp.float32) for a in args))
    assert _error(out.astype(jnp.float32), want) < 3e-2


@pytest.mark.parametrize("n", [16, 32, 64, 128])
@pytest.mark.parametrize("kind", ["random", "alike"])
def test_unit_lower_inverse(n, kind):
    """(I + L)^-1 and its backward rule; `alike` is every key the same and
    no decay, L all ones under the diagonal, where the power series over
    the whole chunk would cancel to nothing (binomials of n in float32)."""
    rng = np.random.RandomState(n)
    low = np.tril(np.ones((3, n, n)) if kind == "alike"
                  else rng.randn(3, n, n) * 0.3, -1)
    want = np.linalg.inv(np.eye(n) + low)
    got = gdk.unit_lower_inverse(jnp.asarray(low, jnp.float32))
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    ct = rng.randn(3, n, n)
    grad = jax.grad(lambda x: jnp.sum(gdk.unit_lower_inverse(x) * ct))(
        jnp.asarray(low, jnp.float32))
    want_grad = np.tril(-np.swapaxes(want, 1, 2) @ ct
                        @ np.swapaxes(want, 1, 2), -1)
    assert np.abs(np.asarray(grad) - want_grad).max() \
        < 1e-4 * np.abs(want_grad).max()


@pytest.mark.parametrize("keys", ["random", "alike"])
def test_unit_lower_inverse_of_what_prepare_builds(keys):
    """At the batch shape of the Qwen3-Next cell ([sequences, value heads,
    chunks, 64, 64], fewer heads and chunks), L as `_prepare` builds it:
    beta_i (k_i . k_j) exp(c_i - c_j) under the diagonal, from l2-normalised
    keys (`alike`: one key a chunk, so every k_i . k_j is 1) and a slow
    decay, against numpy's inverse in float64 (alike keys condition the
    inverse worst: the recursion over slices read 1e-5 there too)."""
    rng = np.random.RandomState(5)
    shape, dk = (1, 4, 8, 64), 128
    k = rng.randn(*(shape[:3] + (1 if keys == "alike" else 64, dk)))
    k = np.broadcast_to(k / np.linalg.norm(k, axis=-1, keepdims=True),
                        shape + (dk,))
    beta = 1.0 / (1.0 + np.exp(-rng.randn(*shape)))
    c = np.cumsum(-rng.rand(*shape) * 0.02, -1)
    low = np.tril(beta[..., None] * (k @ np.swapaxes(k, -1, -2))
                  * np.exp(c[..., :, None] - c[..., None, :]), -1)
    want = np.linalg.inv(np.eye(64) + low)
    got = np.asarray(gdk.unit_lower_inverse(jnp.asarray(low, jnp.float32)))
    assert got.shape == shape + (64,)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    custom_vjp call, a jitted jax.numpy function)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for held in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            held = getattr(held, "jaxpr", held)
            if hasattr(held, "eqns"):
                for inner in _equations(held):
                    yield inner


@pytest.mark.parametrize("n,products", [(16, 6), (32, 8), (64, 10),
                                        (128, 12)])
def test_unit_lower_inverse_multiplies_with_the_chunks_in_the_lanes(
        n, products):
    """6 + 2 log2(n / 16) products forward, every one float32 multiplies
    and a sum on arrays whose minor axis is the batch of chunks, and not
    one batched matmul: a [.., 16, 16] or [.., 64, 64] float32 operand is
    padded to 128 lanes on the chip, which is what both the recursion over
    slices and ten matmuls of whole blocks paid (PERF.md, PR 36). The
    backward rule keeps its two matmuls of whole blocks at "highest"."""
    shape = (1, 4, 8, n, n)
    batch = 1 * 4 * 8
    low = jax.ShapeDtypeStruct(shape, jnp.float32)
    eqns = list(_equations(jax.make_jaxpr(gdk._inverse)(low).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "dot_general" not in names
    assert names.count("reduce_sum") == names.count("mul") == products
    for e in eqns:
        out = e.outvars[0].aval
        if e.primitive.name in ("mul", "reduce_sum") or (
                e.primitive.name in ("add", "neg") and out.ndim > 2):
            assert out.shape[-1] == batch and out.dtype == jnp.float32
    grad = jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(gdk.unit_lower_inverse(x))))(low)
    dots = [e for e in _equations(grad.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for e in dots:
        assert [v.aval.shape[-2:] for v in e.invars] == [(n, n)] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
        assert all(p == jax.lax.Precision.HIGHEST
                   for p in e.params["precision"])


@pytest.mark.parametrize("t,chunk", [(64, 64), (75, 32)],
                         ids=["whole_chunks", "ragged"])
def test_prepare_on_bf16_inputs_equals_prepare_on_their_float32_copies(
        t, chunk):
    """`chunks()` pads, reshapes and moves the head axis in the dtype the
    inputs arrive in and converts after: a convert commutes with a
    permutation, so not one bit of what the chunk pass is given moves."""
    q, k, v, g, beta = _inputs(t, dk=32, dv=32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = gdk._prepare(q, k, v, g, beta, chunk=chunk, dt=jnp.bfloat16)
    want = gdk._prepare(*(x.astype(jnp.float32) for x in (q, k, v)), g, beta,
                        chunk=chunk, dt=jnp.bfloat16)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("bad", ["chunk", "path", "heads", "g"])
def test_refuses_what_it_does_not_compute(bad):
    q, k, v, g, beta = _inputs(16)
    kw = {}
    if bad == "chunk":
        kw["chunk"] = 48
    elif bad == "path":
        kw["path"] = "dense"
    elif bad == "heads":
        v = v[:, :, :3]
        g, beta = g[:, :, :3], beta[:, :, :3]
    else:
        g = g[:, :, :2]
    with pytest.raises(ValueError):
        gdk.gated_delta_rule(q, k, v, g, beta, **kw)


# --- the op through a Program ----------------------------------------------

def _linear_layers(**labels):
    return REGISTRY.counter("ptpu_linear_attention_layers_total", "").value(
        kind="gated_delta", **labels)


def _run_op(monkeypatch, pallas, amp=False):
    """One forward and backward of fluid.layers.gated_delta_rule, its inputs
    fed: ({fetch: value}, the feeds, the path's count before and after)."""
    if pallas:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "gdr")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    q, k, v, g, beta = (np.asarray(a) for a in _inputs(70))
    feed = {"q": q, "k": k, "v": v, "g": g, "beta": beta}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        data = [fluid.layers.data(name=n, shape=list(a.shape[1:]),
                                  dtype="float32") for n, a in feed.items()]
        for var in data:
            var.stop_gradient = False
        out = fluid.layers.gated_delta_rule(*data)
        weight = fluid.layers.data(name="ct", shape=list(v.shape[1:]),
                                   dtype="float32")
        loss = fluid.layers.reduce_sum(out * weight)
        fluid.backward.append_backward(loss)
    feed["ct"] = np.random.RandomState(1).randn(*v.shape).astype("float32")
    labels = dict(k_heads="2", v_heads="4", d_k="16", d_v="24",
                  chunk=str(kernel_config.DEFAULT_TILES["gdr"]["chunk"]),
                  path="kernel" if pallas else "scan")
    before = _linear_layers(**labels)
    fetch = [out.name] + [n + "@GRAD" for n in "q k v g beta".split()]
    got = fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                               fetch_list=fetch)
    return dict(zip(["out", "q", "k", "v", "g", "beta"], got)), feed, \
        _linear_layers(**labels) - before


@pytest.mark.parametrize("pallas", [True, False], ids=["kernel", "scan"])
def test_the_op_and_its_grad_op_through_a_program(monkeypatch, pallas):
    """fluid.layers.gated_delta_rule under Executor.run, on the path
    kernel_config decides (PADDLE_TPU_PALLAS=gdr: the interpreted kernels;
    nothing set on the CPU: lax.scan), against the recurrence and jax.grad
    of it; the counter says which path was lowered, once (the grad op calls
    the linearization the forward op kept)."""
    got, feed, counted = _run_op(monkeypatch, pallas)
    args = tuple(jnp.asarray(feed[n]) for n in "q k v g beta".split())
    want, want_grads = _forward_and_grads(_recurrence, args,
                                          jnp.asarray(feed["ct"]))
    assert counted == 1
    assert _error(got["out"], want) < TOLERANCE
    for name, w in zip("q k v g beta".split(), want_grads):
        assert _error(got[name], w) < 5 * TOLERANCE, name


def test_under_amp_the_decay_stays_float32(monkeypatch):
    """Under AMP q, k, v reach the matmuls as bf16 and g, beta stay
    float32: the result is within bf16's reach of the float32 recurrence,
    which a bf16 running sum of g over 70 tokens would not be."""
    got, feed, _ = _run_op(monkeypatch, True, amp=True)
    args = tuple(jnp.asarray(feed[n]) for n in "q k v g beta".split())
    want, want_grads = _forward_and_grads(_recurrence, args,
                                          jnp.asarray(feed["ct"]))
    assert _error(got["out"], want) < 2e-2
    assert _error(got["g"], want_grads[3]) < 4e-2


def test_the_kernels_lower_under_the_ops_scopes(monkeypatch):
    """ptpu_gated_delta_fwd under the forward op and, for the states, under
    the grad op; ptpu_gated_delta_bwd under the grad op; neither wrapped by
    a transform's name (`jvp_ptpu_..._`). Read off the compiled step's
    op_names: since PR 60 a kernel's call is a jax.jit of its own, which
    lowers as one function whose locations start at the kernel's name, and
    it is XLA's inlining that writes a call site's scope before them."""
    import re
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gdr")
    main, startup = fluid.Program(), fluid.Program()
    shapes = {"q": (32, 2, 16), "k": (32, 2, 16), "v": (32, 4, 16),
              "g": (32, 4), "beta": (32, 4)}
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = [fluid.layers.data(name=n, shape=list(s), dtype="float32")
                for n, s in shapes.items()]
        for var in data:
            var.stop_gradient = False
        loss = fluid.layers.mean(fluid.layers.gated_delta_rule(*data))
        fluid.backward.append_backward(loss)
    names = list(shapes)
    fetch = [loss.name, "q@GRAD"]
    rw, ro, out = lowering.analyze_state(main, names, fetch)
    fn = lowering.build_program_fn(main, names, fetch, rw, ro, out)
    args = [np.zeros((2,) + s, "float32") for s in shapes.values()]
    text = jax.jit(lambda *a: fn(list(a), [], [], 0)).lower(
        *args).compile().as_text()
    under = {}
    for path in set(re.findall(r'op_name="([^"]*)"', text)):
        if path.startswith("ptpu_"):
            continue        # a reduction's own adder: no call, none inlined
        for part in path.split("/"):
            if "ptpu_" in part:
                under.setdefault(part, set()).add(
                    lowering.parse_op_scope(path)[0])
    assert under == {
        "ptpu_gated_delta_fwd": {"gated_delta_rule", "gated_delta_rule_grad"},
        "ptpu_gated_delta_bwd": {"gated_delta_rule_grad"}}


def test_the_chunk_and_the_switch_live_in_kernel_config(monkeypatch):
    from paddle_tpu.ops.linear_attention_ops import gated_delta_path
    assert "gdr" in kernel_config.KERNEL_OPS
    assert set(kernel_config.DEFAULT_TILES["gdr"]) == {"chunk", "block_h"}
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert gated_delta_path() == "scan"         # the CPU, nothing set
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert gated_delta_path() == "kernel"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,xent")
    assert gated_delta_path() == "scan"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gdr")
    assert gated_delta_path() == "kernel"
