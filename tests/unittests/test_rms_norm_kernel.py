"""ops/rms_norm_kernels.py: the transpose of rms_norm over a head as one
Pallas pass (interpreted here, PADDLE_TPU_PALLAS=rms_head) against the
`jax.vjp` of the jax.numpy lines of `_rms_norm_math`, at every geometry a
benchmark cell norms a head at. The forward pass is the lines on both paths
(y is theirs to the bit); the transpose is written out by hand, with a
head's sums over its lanes and dscale's sum over rows in the kernel's order.
So dx is held to the lines' within one unit in the last place of bf16 on at
most 1 element in 10,000 (in float32: within four units of the largest
element), dscale within 1e-5; the predicate sends what the kernel does not
compute (a block norm, a gated or a grouped norm, a head of 64, a mesh, rows
no block divides) to jax's own transpose, and the rule's StableHLO is then
the text it is with the kernel off; the counter says which path an op took;
nothing but the op's inputs crosses the passes.

Both paths are compiled without XLA's fusion passes on the CPU, as
test_rotary_kernel.py does and for its reason."""
import hashlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import control_ops, kernel_config, pallas_import
from paddle_tpu.ops import rms_norm_kernels as rk
from paddle_tpu.ops import rotary_kernels
from paddle_tpu.ops.nn_ops import rms_norm_path
from test_rotary_kernel import _differing

CTX = types.SimpleNamespace(mesh=None, amp=False)
HEAD = {"begin_norm_axis": 3, "epsilon": 1e-6}


def _rule(x, scale, gate, attrs, pallas, monkeypatch):
    """The registered rule's Y, on the path PADDLE_TPU_PALLAS names."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    ins = {"X": [x], "Scale": [scale]}
    if gate is not None:
        ins["Gate"] = [gate]
    return registry.get("rms_norm").lower(CTX, ins, attrs)["Y"][0]


def _unfused(fn, *args):
    return jax.jit(fn).lower(*args).compile(compiler_options={
        "xla_disable_hlo_passes":
            "fusion,cpu-instruction-fusion,multi_output_fusion"})(*args)


def _forward_and_grads(x, scale, gate, ct, attrs, pallas, monkeypatch):
    """(y, dx, dscale) of the rule under the cotangent ct."""
    def all_three(x, scale, ct):
        y, vjp = jax.vjp(lambda x, scale: _rule(
            x, scale, gate, attrs, pallas, monkeypatch), x, scale)
        return (y,) + vjp(ct)
    return _unfused(all_three, x, scale, ct)


def assert_close(got, want, what):
    """bf16: one unit in the last place on at most 1 element in 10,000;
    float32: four units of the largest element."""
    assert got.dtype == want.dtype and got.shape == want.shape
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0
    if got.dtype == jnp.bfloat16:
        n, worst = _differing(got, want)
        assert worst <= 1 and n * 10000 <= got.size, \
            "%s: %d of %d elements differ, the largest by %d ulp" % (
                what, n, got.size, worst)
    else:
        got, want = np.asarray(got), np.asarray(want)
        assert np.abs(got - want).max() <= 2.0 ** -21 * np.abs(want).max(), \
            what


def _operands(shape, dtype, scale_shape=None, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    x, ct, gate = (jax.random.normal(k, shape).astype(dtype)
                   for k in keys[:3])
    scale = 0.2 * jax.random.normal(keys[3], scale_shape or shape[-1:])
    return x, scale.astype(jnp.float32), gate, ct


@pytest.fixture(params=[None, 16, 48],
                ids=["all-rows-a-block", "blocks-of-16", "blocks-of-48"])
def budget(request, monkeypatch):
    """The tile as it is (a test's few rows are one block: whole chunks of
    the kernels' loop and a remainder), or one that holds 16 or 48 rows
    whatever the width: 48 rows are three blocks of 16, 96 are two of 48 (a
    chunk and a remainder each)."""
    if request.param:
        monkeypatch.setattr(
            rk, "block_rows",
            lambda n, width, itemsize: rotary_kernels.block_rows(
                n, width, itemsize, 0, request.param * width * itemsize))
    return request.param


# (B, T, heads, head, attrs beside HEAD): SDAR's q and k, Laguna's heads by
# layer (12 and 18 on 2), Qwen3-Next's 16 on 2 of 256 around a weight stored
# at 0, Ling's o_norm behind the delta rule, Phi-4-mini-flash's subln
CELLS = {
    "sdar-q-32x128": (1, 48, 32, 128, {}),
    "sdar-k-4x128": (1, 48, 4, 128, {}),
    "laguna-full-q-12x128": (1, 48, 12, 128, {}),
    "laguna-sliding-q-18x128": (1, 48, 18, 128, {}),
    "laguna-k-2x128": (1, 96, 2, 128, {}),
    "qwen3next-q-16x256-zero-centred": (2, 24, 16, 256,
                                        {"zero_centered": True}),
    "qwen3next-k-2x256-zero-centred": (2, 48, 2, 256,
                                       {"zero_centered": True}),
    "ling-o-norm-8x128": (1, 96, 8, 128, {}),
    "phi4-subln-20x128": (1, 48, 20, 128, {"epsilon": 1e-5}),
    # rows no sublane tile divides: one block, all of x
    "37-rows-b2": (2, 37, 6, 128, {}),
    "one-token": (3, 1, 4, 128, {}),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("case", sorted(CELLS))
def test_the_kernels_against_the_lines_and_their_vjp(monkeypatch, budget,
                                                     case, dtype):
    """y, dx and dscale on the kernels' path against the lines', over one
    block or several."""
    b, t, h, d, more = CELLS[case]
    attrs = dict(HEAD, **more)
    x, scale, _, ct = _operands((b, t, h, d), dtype)
    itemsize = x.dtype.itemsize
    if budget and (b * t) % 16 and b * t > budget:
        # no block divides these rows and they are no one block: the lines'
        assert not rk.applies(x.shape)
        monkeypatch.setattr(kernel_config, "dispatch_platform",
                            lambda: "tpu")
        assert rms_norm_path(CTX, x, {"Scale": [scale]}, attrs) == "xla"
        return
    assert rk.applies(x.shape)
    if budget:
        assert rk.block_rows(b * t, rk._group(h) * d, 4) == min(budget, b * t)
    got = _forward_and_grads(x, scale, None, ct, attrs, "rms_head",
                             monkeypatch)
    want = _forward_and_grads(x, scale, None, ct, attrs, "0", monkeypatch)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))   # y
    assert_close(got[1], want[1], case + " dx")
    ds, ds_want = np.asarray(got[2]), np.asarray(want[2])
    assert ds.dtype == np.float32 and ds.shape == (d,)
    assert np.abs(ds - ds_want).max() <= 1e-5 * np.abs(ds_want).max(), case


# what the kernels do not compute, at the cells' geometries: a block norm,
# Qwen3-Next's gated norm behind the delta rule, a norm a group under one
# weight over all groups (granite's, Nemotron's), LFM2's heads of 64, a norm
# over the last two axes
OTHER = {
    "block-norm-3d": ((2, 24, 256), {"begin_norm_axis": 2}, None, False),
    "gated-32x128": ((1, 24, 32, 128), dict(HEAD), None, True),
    "a-weight-a-group": ((1, 24, 4, 128),
                         dict(HEAD, begin_scale_axis=2), (4 * 128,), False),
    "lfm2-q-32x64": ((1, 24, 32, 64), dict(HEAD), None, False),
    "lfm2-k-8x64": ((1, 24, 8, 64), dict(HEAD), None, False),
    "over-heads-and-channels": ((1, 24, 4, 128),
                                {"begin_norm_axis": 2, "epsilon": 1e-6},
                                (4 * 128,), False),
}


@pytest.mark.parametrize("case", sorted(OTHER))
def test_what_the_kernels_do_not_compute_keeps_the_lines(monkeypatch, case):
    """With the kernel on, the rule's jaxpr at these geometries holds no
    pallas_call, its StableHLO is the text it is with the kernel off (by
    digest), and its results are the lines' to the bit."""
    shape, attrs, scale_shape, gated = OTHER[case]
    x, scale, gate, ct = _operands(shape, jnp.bfloat16, scale_shape)
    gate = gate if gated else None
    ins = {"Scale": [scale], "Gate": [gate] if gated else []}
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert rms_norm_path(CTX, x, ins, attrs) == "xla"
    monkeypatch.undo()

    def lowered(pallas):
        text = jax.jit(lambda x, scale: _rule(
            x, scale, gate, attrs, pallas, monkeypatch)).lower(
                x, scale).as_text()
        assert "pallas" not in text and "tpu_custom_call" not in text
        return hashlib.sha256(text.encode()).hexdigest()
    assert lowered("rms_head") == lowered("0") == lowered("1")
    got = _forward_and_grads(x, scale, gate, ct, attrs, "rms_head",
                             monkeypatch)
    want = _forward_and_grads(x, scale, gate, ct, attrs, "0", monkeypatch)
    for u, v in zip(got, want):
        assert np.array_equal(np.asarray(u), np.asarray(v))


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_the_predicate_reads_what_the_rule_sees(monkeypatch):
    """pallas_on("rms_head") (a TPU, or the variable), one device, a 4-D x
    normed over its last axis of whole lane tiles under a weight [D], no
    gate, rows a block divides; no other switch."""
    x = _sds(1, 32, 4, 128)
    ins = {"Scale": [_sds(128, dtype=jnp.float32)]}
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert rms_norm_path(CTX, x, ins, HEAD) == "xla"    # the CPU, nothing set
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert rms_norm_path(CTX, x, ins, HEAD) == "kernel"
    assert rms_norm_path(CTX, x, ins, dict(HEAD, begin_norm_axis=-1)) \
        == "kernel"
    assert rms_norm_path(CTX, x, ins, dict(HEAD, zero_centered=True)) \
        == "kernel"
    # every cell's own shapes
    for shape in ((1, 8192, 32, 128), (1, 8192, 4, 128), (1, 4096, 12, 128),
                  (1, 4096, 18, 128), (1, 4096, 2, 128), (1, 4096, 16, 256),
                  (1, 4096, 2, 256), (1, 4096, 8, 128), (1, 8192, 20, 128)):
        wide = {"Scale": [_sds(shape[3], dtype=jnp.float32)]}
        assert rms_norm_path(CTX, _sds(*shape), wide, HEAD) == "kernel"
    # a block norm, the default begin_norm_axis, two axes normed over
    assert rms_norm_path(CTX, _sds(1, 32, 512), {"Scale": [_sds(512)]},
                         {"begin_norm_axis": 2}) == "xla"
    assert rms_norm_path(CTX, x, {"Scale": [_sds(32 * 4 * 128)]}, {}) == "xla"
    assert rms_norm_path(CTX, x, {"Scale": [_sds(4 * 128)]},
                         {"begin_norm_axis": 2}) == "xla"
    # a weight a group, a gate, a head of 64, a head that is no lane tile
    assert rms_norm_path(CTX, x, {"Scale": [_sds(4 * 128)]},
                         dict(HEAD, begin_scale_axis=2)) == "xla"
    assert rms_norm_path(CTX, x, dict(ins, Gate=[x]), HEAD) == "xla"
    assert rms_norm_path(CTX, x, dict(ins, Gate=[]), HEAD) == "kernel"
    assert rms_norm_path(CTX, _sds(1, 32, 8, 64), {"Scale": [_sds(64)]},
                         HEAD) == "xla"
    assert rms_norm_path(CTX, _sds(1, 32, 4, 192), {"Scale": [_sds(192)]},
                         HEAD) == "xla"
    # rows that no block divides and that are no one block: the lines
    assert rms_norm_path(CTX, _sds(1, 8200, 32, 128), ins, HEAD) == "xla"
    assert rms_norm_path(CTX, _sds(1, 2040, 1, 128), ins, HEAD) == "kernel"
    assert rms_norm_path(CTX, _sds(1, 4090, 1, 128), ins, HEAD) == "xla"
    meshed = types.SimpleNamespace(mesh=object(), amp=False)
    assert rms_norm_path(meshed, x, ins, HEAD) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,rope")
    assert rms_norm_path(CTX, x, ins, HEAD) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "rms_head")
    assert rms_norm_path(CTX, x, ins, HEAD) == "kernel"
    # the grad op replays the rule: nothing of the forward call has a
    # reader there (test_device_names.py counts the calls on a described
    # v5e), and no decoder cell's block norms keep a linearization for it
    assert not registry.get("rms_norm").calls_pallas
    with pytest.raises(ValueError, match="float32 scale \\[D\\]"):
        rk.rms_norm(None, jnp.zeros((1, 32, 4, 128)), jnp.zeros((64,)), 1e-6)


# (rows of x, heads, head) -> the heads and the rows of x's float32 image a
# grid step takes at the tile: SDAR's q and k, Laguna's, Qwen3-Next's,
# Ling's, Phi-4's, head counts with no divisor up to eight, what is all of
# x, and rows that no block divides
BLOCKS = {
    "sdar-q": ((8192, 32, 128), 8, 256),
    "sdar-k": ((8192, 4, 128), 4, 512),
    "laguna-q-12": ((4096, 12, 128), 6, 256),
    "laguna-q-18": ((4096, 18, 128), 6, 256),
    "laguna-k-2": ((4096, 2, 128), 2, 1024),
    "qwen3next-q-16x256": ((4096, 16, 256), 8, 128),
    "qwen3next-k-2x256": ((4096, 2, 256), 2, 512),
    "ling-o-norm": ((4096, 8, 128), 8, 256),
    "phi4-subln": ((8192, 20, 128), 5, 256),
    "seven-heads": ((8192, 7, 128), 7, 256),
    "thirteen-heads-one-a-step": ((8192, 13, 128), 1, 2048),
    "seventeen-blocks-of-480": ((8160, 4, 128), 4, 480),
    "all-of-x": ((40, 32, 128), 8, 40),
    "no-block-divides-8200-rows": ((8200, 32, 128), 8, None),
    "a-row-wider-than-the-tile": ((64, 1, 1 << 19), 1, None),
}


@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_a_block_divides_the_rows_inside_the_tile(case):
    """A grid step takes a group of heads (the most, up to eight, that
    divide H) and a block of rows of it, of x's float32 image, that stays
    inside DEFAULT_TILES["rms_head"], is whole sublane tiles and DIVIDES the
    rows (rotary_kernels.block_rows says why no block reaches past the
    array's end), or is all of x; None where there is no such block. The
    pass holds three such blocks at most, two buffers each, under Mosaic's
    16 MiB."""
    (n, h, d), group, want = BLOCKS[case]
    tile = kernel_config.DEFAULT_TILES["rms_head"]["tile_bytes"]
    assert rk._group(h) == group and h % group == 0
    step = group * d * 4
    rows = rk.block_rows(n, group * d, 4)
    assert rows == want
    assert rk.applies((1, n, h, d)) == (rows is not None)
    if rows is None:
        return
    assert n % rows == 0 and rows * step <= tile
    if rows < n:
        assert rows % 16 == 0
        assert all(n % more or more * step > tile
                   for more in range(rows + 16, n, 16))
    assert 3 * 2 * rows * step < 12 << 20


def test_the_tile_is_the_tables_own_and_nothing_else_sets_it():
    assert kernel_config.DEFAULT_TILES["rms_head"] == {"tile_bytes": 1 << 20}
    assert list(kernel_config.DEFAULT_TILES)[-1] == "rms_head"
    assert "rms_head" in kernel_config.KERNEL_OPS
    import inspect
    assert list(inspect.signature(rk.rms_norm).parameters) \
        == ["lines", "x", "scale", "eps"]


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(inner)


def test_nothing_but_the_ops_inputs_crosses_the_passes(monkeypatch):
    """One forward call, one backward call, by name; what the forward pass
    keeps for the backward is x as it came and the weight: no float32
    image, no statistics."""
    x, scale, _, ct = _operands((1, 32, 4, 128), jnp.bfloat16)

    def rule(x, scale):
        return _rule(x, scale, None, HEAD, "rms_head", monkeypatch)
    jaxpr = jax.make_jaxpr(lambda x, scale, ct: jax.vjp(rule, x, scale)[1](
        ct))(x, scale, ct)
    assert [e.params["name"] for e in _pallas_calls(jaxpr.jaxpr)] \
        == ["ptpu_rms_norm_bwd"]
    _, vjp = jax.vjp(rule, x, scale)
    kept = sorted((v.shape, str(v.dtype)) for v in jax.tree.leaves(vjp)
                  if hasattr(v, "shape"))
    assert kept == [((1, 32, 4, 128), "bfloat16"), ((128,), "float32")]


def test_the_forward_pass_is_the_lines_on_both_paths(monkeypatch):
    """The forward pass holds no kernel: the rule's forward StableHLO with
    the kernel on is the text it is with the kernel off but for the
    custom_vjp's call frame, and a recomputing loop has no kernel output
    to keep or to replay."""
    x, scale, _, _ = _operands((1, 32, 4, 128), jnp.bfloat16)

    def forward(pallas):
        return jax.make_jaxpr(lambda x, scale: _rule(
            x, scale, None, HEAD, pallas, monkeypatch))(x, scale)
    assert not list(_pallas_calls(forward("rms_head").jaxpr))
    assert not pallas_import.costs_its_bytes("ptpu_rms_norm_bwd")
    assert "rms_norm" not in open(control_ops.__file__).read()
    on = _unfused(lambda x, scale: _rule(
        x, scale, None, HEAD, "rms_head", monkeypatch), x, scale)
    off = _unfused(lambda x, scale: _rule(
        x, scale, None, HEAD, "0", monkeypatch), x, scale)
    assert np.array_equal(np.asarray(on), np.asarray(off))


# --- the op through a Program ----------------------------------------------

def _counted(**labels):
    return REGISTRY.counter("ptpu_rms_norm_calls_total", "").value(**labels)


def _run_op(monkeypatch, pallas, shape, **kw):
    """One forward and backward of fluid.layers.rms_norm over a fed x in a
    small Program: ({fetch: value}, what the counter gained a path)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    rng = np.random.RandomState(3)
    feed = {"x": rng.randn(*shape).astype("float32"),
            "ct": rng.randn(*shape).astype("float32")}
    gated = kw.pop("gated", False)
    if gated:
        feed["gate"] = rng.randn(*shape).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=list(shape[1:]),
                              dtype="float32")
        x.stop_gradient = False
        ct = fluid.layers.data(name="ct", shape=list(shape[1:]),
                               dtype="float32")
        if gated:
            kw["gate"] = fluid.layers.data(
                name="gate", shape=list(shape[1:]), dtype="float32")
        out = fluid.layers.rms_norm(
            x, param_attr=fluid.ParamAttr(name="w"), **kw)
        loss = fluid.layers.reduce_sum(out * ct)
        fluid.backward.append_backward(loss)
    assert [op.type for op in main.global_block().ops].count("rms_norm") == 1
    labels = {} if len(shape) != 4 else dict(
        heads=str(shape[2]), head_dim=str(shape[3]))
    before = {p: _counted(path=p, **labels) for p in ("kernel", "xla")}
    replayed = REGISTRY.counter("ptpu_lowering_grad_ops_total", "").value(
        path="replayed", op="rms_norm")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed,
                      fetch_list=[out.name, "x@GRAD", "w@GRAD"])
    counted = {p: _counted(path=p, **labels) - before[p] for p in before}
    replayed = REGISTRY.counter("ptpu_lowering_grad_ops_total", "").value(
        path="replayed", op="rms_norm") - replayed
    return dict(zip(["y", "dx", "dw"], got)), counted, replayed


PROGRAMS = {
    "sdar-k": ((2, 37, 4, 128), dict(begin_norm_axis=3, epsilon=1e-6),
               {"kernel": 1, "xla": 0}),
    "qwen3next-k-zero-centred": (
        (1, 40, 2, 256), dict(begin_norm_axis=-1, epsilon=1e-6,
                              zero_centered=True), {"kernel": 1, "xla": 0}),
    "phi4-subln": ((1, 40, 20, 128), dict(begin_norm_axis=3),
                   {"kernel": 1, "xla": 0}),
    "gated": ((1, 40, 4, 128), dict(begin_norm_axis=3, gated=True),
              {"kernel": 0, "xla": 1}),
    "a-weight-a-group": ((1, 40, 4, 128), dict(
        begin_norm_axis=-1, begin_scale_axis=-2), {"kernel": 0, "xla": 1}),
    "lfm2-heads-of-64": ((1, 40, 8, 64), dict(begin_norm_axis=3),
                         {"kernel": 0, "xla": 1}),
    # a block norm has no head: the counter does not see it
    "block-norm": ((2, 40, 256), dict(begin_norm_axis=2),
                   {"kernel": 0, "xla": 0}),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_the_op_and_its_grad_op_through_a_program(monkeypatch, case):
    """fluid.layers.rms_norm and its grad op under Executor.run with the
    kernel on and off: Y, X@GRAD and the weight's gradient the same
    (float32 here, compiled as the Executor compiles), the counter under
    the path the predicate names, once (the grad op's replay of the rule is
    not counted), with the heads and a head's width as labels."""
    shape, kw, want = PROGRAMS[case]
    on, counted, replayed = _run_op(monkeypatch, "rms_head", shape, **kw)
    assert counted == want and replayed == 1
    off, counted, replayed = _run_op(monkeypatch, "0", shape, **kw)
    assert counted == {"kernel": 0, "xla": sum(want.values())}
    assert replayed == 1
    for name in ("y", "dx", "dw"):
        assert on[name].shape == off[name].shape
        assert np.abs(off[name]).max() > 0
        if not want["kernel"]:
            assert np.array_equal(on[name], off[name]), name
        assert np.abs(on[name] - off[name]).max() \
            <= 1e-5 * np.abs(off[name]).max(), name
    # and the norm is a norm: a head's mean square is 1 under a weight of 1
    if not {"gated", "zero_centered"} & set(kw) and len(shape) == 4:
        assert np.allclose(np.square(on["y"]).mean(-1), 1.0, atol=1e-3)


def test_a_training_step_holds_the_kernel_once(monkeypatch):
    """`ptpu_rms_norm_bwd` once, under the grad op; the forward op and the
    grad op's replay of the rule hold the lines and no kernel."""
    from paddle_tpu.core import lowering
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "rms_head")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[32, 4, 128], dtype="float32")
        x.stop_gradient = False
        out = fluid.layers.rms_norm(x, begin_norm_axis=3)
        loss = fluid.layers.reduce_sum(out * out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rw, ro, outs = lowering.analyze_state(main, ["x"], [loss.name])
    fn = lowering.build_program_fn(main, ["x"], [loss.name], rw, ro, outs)
    args = ([jnp.zeros((2, 32, 4, 128))], [jnp.ones((128,))] * len(rw),
            [jnp.ones((1,))] * len(ro))
    jaxpr = jax.make_jaxpr(lambda feed, rw, ro: fn(feed, rw, ro, 0))(*args)
    assert [e.params["name"] for e in _pallas_calls(jaxpr.jaxpr)] \
        == ["ptpu_rms_norm_bwd"]
