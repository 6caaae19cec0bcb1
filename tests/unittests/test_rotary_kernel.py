"""ops/rotary_kernels.py: rotary_embedding's one Pallas pass (interpreted
here, PADDLE_TPU_PALLAS=rope) against the jax.numpy lines the rule otherwise
takes. The kernel changes no arithmetic: it computes the rule's own float32
products and sum from the rule's own cos and sin, and its transpose is the
same kernel at the negated angle, which is what jax's transpose of the lines
computes. So the two paths are held EQUAL, outputs and input gradients, at
every geometry a benchmark cell has; the predicate sends what the kernel does
not compute (heads of 64, the interleaved layout, a head that turns in part)
to the lines; the counter says which path an op took; a recomputing loop
replays the kernel and does not keep its result.

"Equal" is `array_equal`, or one unit in the last place of x's dtype on at
most 1 element in 10,000. On the CPU the two paths are compiled without
XLA's fusion passes (as tests/unittests/test_kernel_entries_trace_once.py
does): LLVM contracts a fused multiply and add into one rounding wherever a
fusion puts them in one loop, and which path gets which fusion is the
compiler's choice, not the kernel's. Through the Executor, which compiles as
it always does, float32 results are held to that one contraction."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import control_ops, kernel_config
from paddle_tpu.ops import rotary_kernels as rk
from paddle_tpu.ops.nn_ops import rotary_path

CTX = types.SimpleNamespace(mesh=None, amp=False)
YARN = {"inv_freq": [float(f) for f in 5e5 ** (-np.arange(32) / 32.0) / 3.0],
        "table_scale": 1.4852030263919618}


def _rule(x, pos, attrs, pallas, monkeypatch):
    """The registered rule's Out, on the path PADDLE_TPU_PALLAS names."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    return registry.get("rotary_embedding").lower(
        CTX, {"X": [x], "Pos": [pos]}, attrs)["Out"][0]


def _unfused(fn, *args):
    """fn(*args), compiled with XLA's fusion passes off (module
    docstring)."""
    return jax.jit(fn).lower(*args).compile(compiler_options={
        "xla_disable_hlo_passes":
            "fusion,cpu-instruction-fusion,multi_output_fusion"})(*args)


def _forward_and_grad(x, pos, ct, attrs, pallas, monkeypatch):
    def both(x, ct):
        y, vjp = jax.vjp(
            lambda x: _rule(x, pos, attrs, pallas, monkeypatch), x)
        return y, vjp(ct)[0]
    return _unfused(both, x, ct)


def _differing(a, b):
    """(elements that differ, the largest difference in units of the last
    place) of two arrays of one float dtype."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]
    a, b = (v.view(bits).astype(np.int64) for v in (a, b))
    low = int(np.iinfo(bits).min)
    a, b = (np.where(v < 0, low - v, v) for v in (a, b))
    off = np.abs(a - b)
    return int((off > 0).sum()), int(off.max())


def assert_equal(got, want, what=""):
    """`array_equal`, or one unit in the last place on at most 1 element in
    10,000."""
    n, worst = _differing(got, want)
    assert worst <= 1 and n * 10000 <= np.asarray(got).size, \
        "%s: %d of %d elements differ, the largest by %d ulp" % (
            what, n, np.asarray(got).size, worst)


def _operands(b, t, h, d, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    x, ct = (jax.random.normal(k, (b, t, h, d)).astype(dtype)
             for k in keys[:2])
    pos = jax.random.randint(keys[2], (b, t), 0, 8192).astype(jnp.int32)
    return x, pos, ct


@pytest.fixture(params=[None, 16, 64],
                ids=["all-rows-a-block", "blocks-of-16", "blocks-of-48"])
def budget(request, monkeypatch):
    """The tile as it is (a test's few rows are one block: whole chunks of
    the kernel's loop and a remainder), or one that holds 16 or 64 rows
    whatever the width: 48 rows are three blocks of 16, 96 are two of 48 (a
    chunk and a remainder each)."""
    if request.param:
        real = rk.block_rows
        monkeypatch.setattr(
            rk, "block_rows",
            lambda n, width, itemsize, head_dim, tile: real(
                n, width, itemsize, head_dim,
                request.param * (width * itemsize + 2 * head_dim * 4)))
    return request.param


# (B, T, heads, head, attrs): the whole heads a cell turns (SDAR's q and k,
# OLMoE's and Ouro's, SmallThinker's 7 on 1, Laguna's sliding layers'), a
# whole head under a table and a factor on cos and sin, a head of 256
WHOLE = {
    "sdar-q-32x128": (1, 48, 32, 128, {"base": 1e6}),
    "sdar-k-4x128": (1, 48, 4, 128, {"base": 1e6}),
    "olmoe-16x128-b2": (2, 48, 16, 128, {"base": 1e4}),
    "ouro-16x128": (1, 96, 16, 128, {"base": 1e6}),
    "six-heads-b2": (2, 48, 6, 128, {"base": 1.5e6}),
    "smallthinker-q-7x128": (1, 48, 7, 128, {"base": 1.5e6}),
    "smallthinker-k-1x128-b2": (2, 48, 1, 128, {"base": 1.5e6}),
    "laguna-sliding-q-18x128": (1, 48, 18, 128, {"base": 1e4}),
    "laguna-sliding-k-2x128": (1, 48, 2, 128, {"base": 1e4}),
    "twelve-heads-base-1e7": (1, 48, 12, 128, {"base": 1e7}),
    "a-table-and-a-factor": (1, 48, 4, 128, dict(
        base=5e5, table_scale=YARN["table_scale"], inv_freq=[
            float(f) for f in 5e5 ** (-np.arange(64) / 64.0) / 3.0])),
    "a-head-of-256": (2, 24, 2, 256, {"base": 1e6}),
    # rows no sublane tile divides: one block, all of x
    "37-rows-b2": (2, 37, 6, 128, {"base": 1.5e6}),
    "one-token": (3, 1, 4, 128, {"base": 1e6}),
}
# what the kernel does not compute, at the cells' geometries: Laguna's YaRN
# layers and Qwen3-Next's quarter (a head that turns in part), the latent
# cells' interleaved rope parts, LFM2's heads of 64
OTHER = {
    "laguna-yarn-64-of-128": (1, 40, 12, 128, dict(
        base=5e5, rotary_dim=64, **YARN)),
    "qwen3next-64-of-256": (2, 24, 16, 256, {"base": 1e7, "rotary_dim": 64}),
    "glm-interleaved-20x64": (1, 40, 20, 64, {"base": 1e6,
                                              "layout": "interleaved"}),
    "xing-interleaved-1x64-table": (1, 40, 1, 64, dict(
        base=1e4, layout="interleaved", inv_freq=YARN["inv_freq"])),
    "lfm2-32x64": (1, 40, 32, 64, {"base": 1e6}),
    "interleaved-at-128": (1, 24, 2, 128, {"base": 1e4,
                                           "layout": "interleaved"}),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("case", sorted(WHOLE))
def test_the_kernel_is_the_rules_arithmetic(monkeypatch, budget, case,
                                            dtype):
    """y and dx on the kernel's path equal the lines', over one block or
    several."""
    b, t, h, d, attrs = WHOLE[case]
    x, pos, ct = _operands(b, t, h, d, dtype)
    if budget and (b * t) % 16 and b * t > budget:
        # no block divides these rows and they are no one block: the lines'
        assert not rk.applies(x.shape, x.dtype.itemsize, d, "half")
        return
    assert rk.applies(x.shape, x.dtype.itemsize,
                      attrs.get("rotary_dim") or d,
                      attrs.get("layout", "half"))
    got = _forward_and_grad(x, pos, ct, attrs, "rope", monkeypatch)
    want = _forward_and_grad(x, pos, ct, attrs, "0", monkeypatch)
    for name, u, v in zip(("y", "dx"), got, want):
        assert u.dtype == dtype and float(jnp.abs(v).max()) > 0
        assert_equal(u, v, "%s %s" % (case, name))
        assert np.array_equal(np.asarray(u), np.asarray(v)), name


@pytest.mark.parametrize("case", sorted(OTHER))
def test_what_the_kernel_does_not_compute_keeps_the_lines(monkeypatch, case):
    """With the kernel on, the rule's jaxpr at these geometries holds no
    pallas_call, its StableHLO is the text it is with the kernel off, and
    its results are the lines' to the bit."""
    b, t, h, d, attrs = OTHER[case]
    x, pos, ct = _operands(b, t, h, d, jnp.bfloat16)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert rotary_path(CTX, x, pos, attrs) == "xla"
    monkeypatch.undo()
    jaxpr = jax.make_jaxpr(
        lambda x: _rule(x, pos, attrs, "rope", monkeypatch))(x)
    assert "pallas_call" not in str(jaxpr)
    # and what the rule lowers to is the same text, kernel on or off

    def lowered(pallas):
        return jax.jit(lambda x: _rule(
            x, pos, attrs, pallas, monkeypatch)).lower(x).as_text()
    assert lowered("rope") == lowered("0")
    got = _forward_and_grad(x, pos, ct, attrs, "rope", monkeypatch)
    want = _forward_and_grad(x, pos, ct, attrs, "0", monkeypatch)
    for u, v in zip(got, want):
        assert np.array_equal(np.asarray(u), np.asarray(v))


def test_the_transpose_is_the_kernel_at_the_negated_angle(monkeypatch):
    """One entry, one name: the backward pass calls `_call` with -sf, keeps
    the two tables and nothing of x."""
    x, pos, ct = _operands(1, 32, 4, 128, jnp.bfloat16)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "rope")
    jaxpr = jax.make_jaxpr(lambda x, ct: jax.vjp(
        lambda x: _rule(x, pos, {"base": 1e6}, "rope", monkeypatch),
        x)[1](ct))(x, ct)

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)
    found = list(calls(jaxpr.jaxpr))
    assert [e.params["name"] for e in found] == ["ptpu_rotary"] * 2
    _, vjp = jax.vjp(
        lambda x: _rule(x, pos, {"base": 1e6}, "rope", monkeypatch), x)
    kept = [v for v in jax.tree.leaves(vjp) if hasattr(v, "shape")]
    assert kept and all(v.shape == (32, 128) and v.dtype == jnp.float32
                        for v in kept)


# (rows of x, heads, head, itemsize) -> rows a grid step takes at 2 MiB:
# SDAR's q and k, OLMoE's, Laguna's 18, SmallThinker's 7 on 1 (H = 1: the
# tables are four times x's bytes), H = 2, float32, what is all of x, and
# rows that no block divides
BLOCKS = {
    "sdar-q": ((8192, 32, 128, 2), 128),
    "sdar-k": ((8192, 4, 128, 2), 1024),
    "olmoe": ((16384, 16, 128, 2), 256),
    "laguna-q-18": ((4096, 18, 128, 2), 256),
    "smallthinker-q-7": ((8192, 7, 128, 2), 512),
    "one-head-more-rows-than-a-block": ((8192, 1, 128, 2), 1024),
    "two-heads-more-rows-than-a-block": ((8192, 2, 128, 2), 1024),
    "two-heads-of-256-float32": ((8192, 2, 256, 4), 512),
    "ten-blocks-of-816": ((8160, 4, 128, 2), 816),
    "all-of-x": ((40, 32, 128, 4), 40),
    "all-of-x-1637-rows-one-head": ((1637, 1, 128, 2), 1637),
    "no-block-divides-8200-rows": ((8200, 32, 128, 2), None),
    "a-row-wider-than-the-tile": ((8192, 8192, 128, 4), None),
}


@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_a_block_divides_the_rows_inside_the_budget(case):
    """x's block and the two float32 table blocks a grid step reads stay
    inside tile_bytes together; the block is whole sublane tiles and
    DIVIDES the rows (no block reaches past the array's end: block_rows
    says why), or is all of x; None where there is no such block."""
    (n, h, d, itemsize), want = BLOCKS[case]
    tile = kernel_config.DEFAULT_TILES["rope"]["tile_bytes"]
    rows = rk.block_rows(n, h * d, itemsize, d, tile)
    assert rows == want
    assert rk.applies((1, n, h, d), itemsize, d, "half") == (rows is not None)
    if rows is None:
        return
    step = h * d * itemsize + 2 * d * 4
    assert n % rows == 0 and rows * step <= tile
    if rows < n:
        assert rows % 16 == 0
        # the next larger block that divides the rows would not fit
        assert all(n % more or more * step > tile
                   for more in range(rows + 16, n, 16))
    # x in and out and the tables, two buffers each, under Mosaic's 16 MiB
    assert 2 * rows * (2 * h * d * itemsize + 2 * d * 4) < 12 << 20


def test_the_tile_is_the_tables_own_and_nothing_else_sets_it():
    assert set(kernel_config.DEFAULT_TILES["rope"]) == {"tile_bytes"}
    assert "rope" in kernel_config.KERNEL_OPS
    import inspect
    assert list(inspect.signature(rk.rotary).parameters) == ["x", "cf", "sf"]


def test_the_predicate_reads_what_the_rule_sees(monkeypatch):
    """pallas_on("rope") (a TPU, or the variable), one device, integer
    positions, and a rotation the kernel computes; no other switch."""
    x = jnp.zeros((1, 32, 4, 128), jnp.bfloat16)
    pos = jnp.zeros((1, 32), jnp.int32)
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert rotary_path(CTX, x, pos, {}) == "xla"        # the CPU, nothing set
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert rotary_path(CTX, x, pos, {}) == "kernel"
    assert rotary_path(CTX, x, pos, {"rotary_dim": 128}) == "kernel"
    assert rotary_path(CTX, x, pos, {"rotary_dim": 64}) == "xla"
    assert rotary_path(CTX, x, pos, {"layout": "interleaved"}) == "xla"
    assert rotary_path(CTX, x[..., :64], pos, {}) == "xla"
    assert rotary_path(CTX, x[:, :, :3], pos, {}) == "kernel"
    assert rotary_path(CTX, x[:, :, :1], pos, {}) == "kernel"
    # rows that no block divides and that are no one block: the lines
    many = jax.ShapeDtypeStruct((1, 8200, 32, 128), jnp.bfloat16)
    assert rotary_path(CTX, many, jax.ShapeDtypeStruct(
        (1, 8200), jnp.int32), {}) == "xla"
    assert rotary_path(CTX, jax.ShapeDtypeStruct(
        (1, 8192, 32, 128), jnp.bfloat16), pos, {}) == "kernel"
    assert rotary_path(CTX, x, pos.astype(jnp.float32), {}) == "xla"
    meshed = types.SimpleNamespace(mesh=object(), amp=False)
    assert rotary_path(meshed, x, pos, {}) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,conv")
    assert rotary_path(CTX, x, pos, {}) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "rope")
    assert rotary_path(CTX, x, pos, {}) == "kernel"
    assert registry.get("rotary_embedding").calls_pallas
    with pytest.raises(ValueError, match="tables \\[B\\*T, D\\]"):
        rk.rotary(x, jnp.zeros((32, 64)), jnp.zeros((32, 64)))


def test_a_recomputing_loop_replays_a_kernel_that_costs_its_bytes(
        monkeypatch):
    """The loop's checkpoint keeps a Pallas forward kernel's outputs, but
    not those of a kernel whose entry declares itself a pass over its
    bytes, and `ptpu_rotary`'s does, where it is defined: the policy holds
    no kernel's name."""
    from paddle_tpu.ops import pallas_import
    prim = types.SimpleNamespace(name="pallas_call")
    assert control_ops._kept_by(prim, [], {"name": "ptpu_rotary"}) is None
    assert control_ops._kept_by(prim, [], {"name": "ptpu_flash_fwd"}) \
        == "kernel_output"
    assert control_ops._kept_by(prim, [], {"name": None}) == "kernel_output"
    assert not control_ops.keeps_across_passes(prim, name="ptpu_rotary")
    assert "ptpu_rotary" not in open(control_ops.__file__).read()
    # declared by the entry, and by nothing else
    monkeypatch.setattr(pallas_import, "_COSTS_ITS_BYTES", set())
    assert control_ops._kept_by(prim, [], {"name": "ptpu_rotary"}) \
        == "kernel_output"
    pallas_import.kernel_entry("ptpu_rotary", costs_its_bytes=True)
    assert control_ops._kept_by(prim, [], {"name": "ptpu_rotary"}) is None
    pallas_import.kernel_entry("ptpu_other")
    assert not pallas_import.costs_its_bytes("ptpu_other")


# --- the op through a Program ----------------------------------------------

def _counted(**labels):
    return REGISTRY.counter("ptpu_rotary_calls_total", "").value(**labels)


def _grad_ops(path):
    return REGISTRY.counter("ptpu_lowering_grad_ops_total", "").value(
        path=path, op="rotary_embedding")


def _run_op(monkeypatch, pallas, shape, **attrs):
    """One forward and backward of fluid.layers.rotary_embedding over a fed
    x in a two-op Program: ({fetch: value}, what the counter gained a
    path, the grad ops that used a kept linearization)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    rng = np.random.RandomState(3)
    feed = {"x": rng.randn(*shape).astype("float32"),
            "ct": rng.randn(*shape).astype("float32"),
            "pos": rng.randint(0, 4096, shape[:2]).astype("int64")}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=list(shape[1:]),
                              dtype="float32")
        x.stop_gradient = False
        pos = fluid.layers.data(name="pos", shape=[shape[1]], dtype="int64")
        ct = fluid.layers.data(name="ct", shape=list(shape[1:]),
                               dtype="float32")
        out = fluid.layers.rotary_embedding(x, pos, **attrs)
        loss = fluid.layers.reduce_sum(out * ct)
        fluid.backward.append_backward(loss)
    assert [op.type for op in main.global_block().ops].count(
        "rotary_embedding") == 1
    labels = dict(heads=str(shape[2]), head_dim=str(shape[3]),
                  rotary_dim=str(attrs.get("rotary_dim") or shape[3]))
    before = {p: _counted(path=p, **labels) for p in ("kernel", "xla")}
    kept = _grad_ops("kept")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=[out.name, "x@GRAD"])
    counted = {p: _counted(path=p, **labels) - before[p] for p in before}
    return dict(zip(["y", "dx"], got)), feed, counted, _grad_ops("kept") - kept


PROGRAMS = {
    "sdar-k": ((2, 37, 4, 128), {"base": 1e6}, "kernel"),
    "smallthinker-q": ((1, 40, 7, 128), {"base": 1.5e6}, "kernel"),
    "smallthinker-k": ((2, 37, 1, 128), {"base": 1.5e6}, "kernel"),
    "ouro-16-heads": ((1, 40, 16, 128), {"base": 1e6}, "kernel"),
    "a-table-and-a-factor": ((1, 40, 2, 128), dict(
        base=5e5, table_scale=YARN["table_scale"], inv_freq=[
            float(f) for f in 5e5 ** (-np.arange(64) / 64.0) / 3.0]),
        "kernel"),
    "laguna-yarn-64-of-128": ((1, 40, 12, 128), dict(
        base=5e5, rotary_dim=64, **YARN), "xla"),
    "qwen3next-64-of-256": ((1, 24, 2, 256), {"base": 1e7, "rotary_dim": 64},
                            "xla"),
    "glm-interleaved-64": ((1, 40, 20, 64), {"base": 1e6,
                                             "layout": "interleaved"}, "xla"),
    "lfm2-heads-of-64": ((1, 40, 8, 64), {"base": 1e6}, "xla"),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_the_op_and_its_grad_op_through_a_program(monkeypatch, case):
    """fluid.layers.rotary_embedding and its grad op under Executor.run with
    the kernel on and off: Out and X@GRAD the same (float32 here, compiled
    as the Executor compiles: within one contraction of a product of
    magnitude |x|, module docstring), the counter under the path the
    predicate names, once, and the grad op on the linearization the forward
    op kept."""
    shape, attrs, path = PROGRAMS[case]
    on, feed, counted, kept = _run_op(monkeypatch, "rope", shape, **attrs)
    assert counted == {path: 1, "xla" if path == "kernel" else "kernel": 0}
    assert kept == 1
    off, _, counted, kept = _run_op(monkeypatch, "0", shape, **attrs)
    assert counted == {"xla": 1, "kernel": 0} and kept == 1
    for name, given in (("y", "x"), ("dx", "ct")):
        assert on[name].shape == shape and np.abs(off[name]).max() > 0
        if path == "xla":
            assert np.array_equal(on[name], off[name]), name
        # one rounding of a product no larger than 2 |x|: 2^-23 of it
        bound = 2.0 ** -22 * np.abs(feed[given]).max()
        assert np.abs(on[name] - off[name]).max() <= bound, name
    # and the rotation is a rotation: a head keeps its norm
    if "table_scale" not in attrs:
        assert np.allclose(np.square(on["y"]).sum(-1),
                           np.square(feed["x"]).sum(-1), rtol=1e-4)


def test_a_training_step_holds_the_kernel_twice(monkeypatch):
    """`ptpu_rotary` once for the forward op and once for the grad op: the
    replay of the forward call that a grad op's jax.vjp would trace is not
    there, the op keeps its linearization. (Whose scope a call lowers
    under: test_device_names.py, on the described v5e.)"""
    from paddle_tpu.core import lowering
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "rope")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[32, 4, 128], dtype="float32")
        x.stop_gradient = False
        pos = fluid.layers.data(name="pos", shape=[32], dtype="int64")
        out = fluid.layers.rotary_embedding(x, pos, base=1e6)
        loss = fluid.layers.reduce_sum(out * out)
        fluid.backward.append_backward(loss)
    rw, ro, outs = lowering.analyze_state(main, ["pos", "x"], [loss.name])
    fn = lowering.build_program_fn(main, ["pos", "x"], [loss.name], rw, ro,
                                   outs)
    jaxpr = jax.make_jaxpr(lambda feed: fn(feed, [], [], 0))(
        [jnp.zeros((2, 32), jnp.int32), jnp.zeros((2, 32, 4, 128))])
    assert str(jaxpr).count("name=ptpu_rotary") == 2
