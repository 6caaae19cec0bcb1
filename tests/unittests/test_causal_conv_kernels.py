"""ops/causal_conv_kernels.py: the convolution's two streaming Pallas passes
(interpreted here, PADDLE_TPU_PALLAS=conv) against the jax.numpy passes the
rule otherwise takes, forward, dx and dw; what crosses a tile's edge in
either direction; the zeros before every sequence; the rule's dispatch, the
fallback and the counter's labels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering, registry
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import causal_conv_kernels as cck
from paddle_tpu.ops import kernel_config
from paddle_tpu.ops.linear_attention_ops import causal_conv_path

T, C = 96, 640          # six tiles of 16 rows, five channel blocks of 128
SMALL = 16 * 128 * 4    # the tile budget that cuts [T, C] so


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _rule(x, w, silu, pallas, monkeypatch):
    """The registered rule's Out, on the path PADDLE_TPU_PALLAS names."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    return registry.get("causal_conv1d").lower(
        None, {"X": [x], "Filter": [w]},
        {"activation": "silu"} if silu else {})["Out"][0]


def _forward_and_grads(x, w, ct, silu, pallas, monkeypatch):
    y, vjp = jax.vjp(lambda x, w: _rule(x, w, silu, pallas, monkeypatch),
                     x, w)
    return (y,) + vjp(ct)


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setitem(kernel_config.DEFAULT_TILES["conv"], "tile_bytes",
                        SMALL)
    assert cck.blocks(T, C, SMALL) == (16, 128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("silu", [True, False], ids=["silu", "linear"])
@pytest.mark.parametrize("width", [4, 2])
@pytest.mark.parametrize("batch", [1, 2])
def test_kernels_against_the_jax_numpy_path(monkeypatch, small_tiles, batch,
                                            width, silu, dtype):
    """y, dx and dw of the two kernels over six tiles a sequence (so the
    forward carry and the backward one are both crossed five times) equal
    those of the K shifted passes: the same float32 products and sums. The
    interpreter's approximate reciprocal is coarser than the chip's, which
    the Newton step squares: 3e-5 here, 1e-7 there (chip_smoke.py)."""
    rng = np.random.RandomState(width + 10 * batch)
    x = jnp.asarray(rng.randn(batch, T, C), dtype)
    w = jnp.asarray(rng.randn(C, width) * 0.5, jnp.float32)
    ct = jnp.asarray(rng.randn(batch, T, C), dtype)
    got = _forward_and_grads(x, w, ct, silu, "conv", monkeypatch)
    want = _forward_and_grads(x, w, ct, silu, "0", monkeypatch)
    assert got[0].dtype == got[1].dtype == dtype and got[2].dtype == w.dtype
    # bf16: one rounding of y and dx, so at most an ulp (2^-8) apart
    tolerance = 1e-4 if dtype == jnp.float32 else 8e-3
    for name, g, v in zip(("y", "dx", "dw"), got, want):
        assert _error(g, v) < (1e-4 if name == "dw" else tolerance), name


def test_a_tiles_edge_is_crossed_in_both_directions(monkeypatch,
                                                    small_tiles):
    """A one-hot x at a tile's last row reaches y in the tile after it (the
    forward kernel's carry), and a one-hot dy at a tile's first row reaches
    dx in the tile before it (the backward kernel's), tap by tap."""
    w = jnp.asarray(np.arange(1, 5, dtype="float32")[None].repeat(C, 0))
    x = np.zeros((1, T, C), "float32")
    x[0, 31] = 1.0                      # the second tile's last row
    y, vjp = jax.vjp(lambda x: _rule(x, w, False, "conv", monkeypatch),
                     jnp.asarray(x))
    # y_t = sum_m w[m] x_(t-3+m): x_31 reaches y_31 .. y_34 by w[3] .. w[0]
    want = np.zeros((T,), "float32")
    want[31:35] = [4, 3, 2, 1]
    np.testing.assert_array_equal(np.asarray(y)[0, :, 7], want)
    dy = np.zeros((1, T, C), "float32")
    dy[0, 48] = 1.0                     # the fourth tile's first row
    dx, = vjp(jnp.asarray(dy))
    want = np.zeros((T,), "float32")
    want[45:49] = [1, 2, 3, 4]          # dx_t = sum_m w[m] dy_(t+3-m)
    np.testing.assert_array_equal(np.asarray(dx)[0, :, 600], want)


@pytest.mark.parametrize("pallas", ["conv", "0"], ids=["kernel", "xla"])
def test_every_sequence_starts_from_zeros(monkeypatch, small_tiles, pallas):
    """Ones under a filter of ones: y_t counts the tokens a tap can see,
    1, 2, 3, then 4, in the second sequence of a batch as in the first (the
    carry is reset a sequence, not a call), and dx counts the outputs a
    token reaches, down to 3, 2, 1 at a sequence's end."""
    x = jnp.ones((2, T, C), jnp.float32)
    w = jnp.ones((C, 4), jnp.float32)
    y, vjp = jax.vjp(lambda x: _rule(x, w, False, pallas, monkeypatch), x)
    dx, = vjp(jnp.ones_like(y))
    count = np.minimum(np.arange(T) + 1, 4).astype("float32")
    for b in range(2):
        np.testing.assert_array_equal(np.asarray(y)[b, :, 129], count)
        np.testing.assert_array_equal(np.asarray(dx)[b, :, 129], count[::-1])


def test_the_sigmoid_stays_finite_far_from_zero(monkeypatch, small_tiles):
    """exp(-z) overflows float32 past z = -88; the kernels' reciprocal and
    its Newton step must not turn that into 0 x inf."""
    x = jnp.full((1, 16, 128), 1.0, jnp.float32).at[0, :, 1].set(-1.0)
    w = jnp.full((128, 2), 100.0, jnp.float32)
    got = _forward_and_grads(x, w, jnp.ones_like(x), True, "conv",
                             monkeypatch)
    want = _forward_and_grads(x, w, jnp.ones_like(x), True, "0", monkeypatch)
    for g, v in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, v, rtol=1e-4, atol=1e-30)


@pytest.mark.parametrize("t,c,tile_bytes,want", [
    (4096, 8192, 1 << 20, (512, 512)),      # the Qwen3-Next cell's
    (4096, 2048, 1 << 20, (512, 512)),
    (4096, 640, 1 << 20, (2048, 128)),      # 640 = 5 x 128
    (4096, 384, 1 << 20, (512, 384)),       # 682 rows fit: 512 divides T
    (48, 128, 1 << 20, (48, 128)),          # no multiple of 32 divides 48
    (4096, 8192, 1 << 10, (16, 512)),       # never under one halo of rows
], ids=str)
def test_the_tile_is_a_budget_in_bytes(t, c, tile_bytes, want):
    """`blocks` turns DEFAULT_TILES["conv"]["tile_bytes"], the float32
    working copy of one tile, into rows and channels that divide the shape,
    whole sublane tiles and whole lanes."""
    block_t, block_c = cck.blocks(t, c, tile_bytes)
    assert (block_t, block_c) == want
    assert t % block_t == 0 and block_t % 16 == 0
    assert c % block_c == 0 and block_c % 128 == 0


def test_refuses_what_its_blocks_do_not_divide():
    x, w = jnp.zeros((2, 9, 6)), jnp.zeros((6, 4))
    assert not cck.applies(9, 128, 4) and not cck.applies(16, 6, 4)
    assert not cck.applies(16, 128, 18) and cck.applies(16, 128, 17)
    with pytest.raises(ValueError, match="multiple of 16"):
        cck.causal_conv1d(x, w)
    with pytest.raises(ValueError, match="w \\[C, K\\]"):
        cck.causal_conv1d(jnp.zeros((1, 16, 128)), jnp.zeros((64, 4)))


def test_the_tile_and_the_switch_live_in_kernel_config(monkeypatch):
    """The rule takes the kernels where pallas_on("conv") (a TPU, or the
    variable) AND the blocks divide the shape; everywhere else the
    jax.numpy passes. No other switch."""
    assert "conv" in kernel_config.KERNEL_OPS
    assert set(kernel_config.DEFAULT_TILES["conv"]) == {"tile_bytes"}
    fits, odd = jnp.zeros((1, 32, 256)), jnp.zeros((2, 9, 6))
    w = jnp.zeros((256, 4))
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert causal_conv_path(fits, w) == "xla"       # the CPU, nothing set
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert causal_conv_path(fits, w) == "kernel"
    assert causal_conv_path(odd, jnp.zeros((6, 4))) == "xla"
    assert causal_conv_path(jnp.zeros((1, 24, 256)), w) == "xla"
    assert causal_conv_path(jnp.zeros((1, 32, 192)),
                            jnp.zeros((192, 4))) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "attn,gdr")
    assert causal_conv_path(fits, w) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "conv")
    assert causal_conv_path(fits, w) == "kernel"
    assert registry.get("causal_conv1d").calls_pallas


# --- the op through a Program ----------------------------------------------

def _conv_layers(**labels):
    return REGISTRY.counter("ptpu_causal_conv_layers_total", "").value(
        **labels)


def _run_op(monkeypatch, pallas, shape, act="silu"):
    """One forward and backward of fluid.layers.causal_conv1d over a fed x:
    ({fetch: value}, the feed, the filter, what the counter gained under
    each path's labels)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", pallas)
    rng = np.random.RandomState(2)
    feed = {"x": rng.randn(*shape).astype("float32"),
            "ct": rng.randn(*shape).astype("float32")}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=list(shape[1:]),
                              dtype="float32")
        x.stop_gradient = False
        out = fluid.layers.causal_conv1d(
            x, 4, act=act, param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(0.0, 0.5)))
        ct = fluid.layers.data(name="ct", shape=list(shape[1:]),
                               dtype="float32")
        loss = fluid.layers.reduce_sum(out * ct)
        fluid.backward.append_backward(loss)
        w, = main.global_block().all_parameters()
    labels = dict(width="4", channels=str(shape[2]), activation=act or "none")
    before = {p: _conv_layers(path=p, **labels) for p in ("kernel", "xla")}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed,
                      fetch_list=[out.name, "x@GRAD", w.name + "@GRAD"])
        weight = np.asarray(scope.get(w.name))
    counted = {p: _conv_layers(path=p, **labels) - before[p] for p in before}
    return dict(zip(["y", "dx", "dw"], got)), feed, weight, counted


def _equation(x, w, ct, silu):
    """y_t = act(sum_m w[:, m] x_(t-3+m)) and its gradients by jax."""
    def f(x, w):
        xp = jnp.pad(x, [(0, 0), (3, 0), (0, 0)])
        y = sum(xp[:, m:m + x.shape[1]] * w[:, m] for m in range(4))
        return jax.nn.silu(y) if silu else y
    y, vjp = jax.vjp(f, jnp.asarray(x), jnp.asarray(w))
    return (y,) + vjp(jnp.asarray(ct))


@pytest.mark.parametrize("pallas,shape,act,path", [
    ("conv", (2, 64, 256), "silu", "kernel"),
    ("conv", (2, 64, 256), None, "kernel"),
    ("conv", (2, 9, 6), "silu", "xla"),         # the blocks do not divide it
    ("0", (2, 64, 256), "silu", "xla"),
], ids=["kernel", "kernel_linear", "fallback", "off"])
def test_the_op_and_its_grad_op_through_a_program(monkeypatch, pallas, shape,
                                                  act, path):
    """fluid.layers.causal_conv1d under Executor.run, on the path the rule
    decides, against the equation and jax.grad of it; the counter says which
    path was lowered, once (the grad op calls the linearization the forward
    op kept), under the taps, channels and activation it saw."""
    got, feed, w, counted = _run_op(monkeypatch, pallas, shape, act)
    want = _equation(feed["x"], w, feed["ct"], act == "silu")
    assert counted == {path: 1, "xla" if path == "kernel" else "kernel": 0}
    assert w.shape == (shape[2], 4)
    for name, v in zip(("y", "dx", "dw"), want):
        assert _error(got[name], v) < 1e-4, name


def test_the_kernels_lower_under_the_ops_scopes(monkeypatch):
    """ptpu_causal_conv1d_fwd under the forward op, once;
    ptpu_causal_conv1d_bwd under the grad op; neither wrapped by a
    transform's name (`jvp_ptpu_..._`). Read off the compiled step's
    op_names: since PR 60 a kernel's call is a jax.jit of its own, which
    lowers as one function whose locations start at the kernel's name, and
    it is XLA's inlining that writes a call site's scope before them."""
    import re
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "conv")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[32, 128], dtype="float32")
        x.stop_gradient = False
        loss = fluid.layers.mean(fluid.layers.causal_conv1d(x, 4,
                                                            act="silu"))
        fluid.backward.append_backward(loss)
    fetch = [loss.name, "x@GRAD"]
    rw, ro, out = lowering.analyze_state(main, ["x"], fetch)
    fn = lowering.build_program_fn(main, ["x"], fetch, rw, ro, out)
    assert (len(rw), len(ro)) == (0, 1)     # the filter, read only
    text = jax.jit(lambda x, w: fn([x], [], [w], 0)).lower(
        np.zeros((2, 32, 128), "float32"),
        np.zeros((128, 4), "float32")).compile().as_text()
    under = {}
    for path in set(re.findall(r'op_name="([^"]*)"', text)):
        if path.startswith("ptpu_"):
            continue        # a reduction's own adder: no call, none inlined
        for part in path.split("/"):
            if "ptpu_" in part:
                under.setdefault(part, set()).add(
                    lowering.parse_op_scope(path)[0])
    assert under == {"ptpu_causal_conv1d_fwd": {"causal_conv1d"},
                     "ptpu_causal_conv1d_bwd": {"causal_conv1d_grad"}}
