"""Fused LSTM / sequence pallas kernels vs the unfused lax.scan and
where-mask paths (interpret mode on CPU — the same kernel code that runs
compiled on TPU).

The dispatch contract under test (ops/sequence_ops.py + ARCHITECTURE.md
§25): with PADDLE_TPU_PALLAS enabling 'lstm'/'seq', dynamic_lstm /
dynamic_lstmp / sequence_softmax / sequence_pool(SUM|AVERAGE|SQRT) run
the fused kernels; fp32 forward numerics are BIT-EXACT vs the unfused
paths on CPU interpret mode (same primitive sequence either way), and
the custom_vjp backward matches jax.grad of the unfused scan. Ragged
@SEQLEN batches (incl. length-1 rows) ride every case.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.ops import pallas_kernels as pk

rng = np.random.RandomState(42)


def _scan_lstm(x, w, b, h0, c0, xlen, reverse=False):
    """The unfused sequence_ops._lstm default path, extracted."""
    t = x.shape[1]
    m = (jnp.arange(t)[None, :]
         < jnp.asarray(xlen)[:, None]).astype(jnp.float32)
    xs = jnp.swapaxes(x, 0, 1)
    ms = m.T[:, :, None]
    if reverse:
        xs, ms = xs[::-1], ms[::-1]

    def step(carry, inp):
        h_prev, c_prev = carry
        xt, mt = inp
        gates = xt + h_prev @ w + b
        gc, gi, gf, go = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(gi)
        f = jax.nn.sigmoid(gf)
        c_new = f * c_prev + i * jnp.tanh(gc)
        o = jax.nn.sigmoid(go)
        h_new = o * jnp.tanh(c_new)
        h = mt * h_new + (1 - mt) * h_prev
        c = mt * c_new + (1 - mt) * c_prev
        return (h, c), (h, c)

    _, (hs, cs) = jax.lax.scan(step, (h0, c0), (xs, ms))
    if reverse:
        hs, cs = hs[::-1], cs[::-1]
    return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d,block_b,reverse", [
    (3, 7, 5, 0, False),      # whole-batch block, odd dims
    (3, 7, 5, 0, True),       # reverse
    (9, 4, 16, 8, False),     # batch spills into a second block
    (2, 9, 3, 32, False),     # block larger than batch
])
def test_fused_lstm_bit_exact_vs_scan(b, t, d, block_b, reverse):
    x = (rng.randn(b, t, 4 * d) * 0.4).astype("float32")
    w = (rng.randn(d, 4 * d) * 0.3).astype("float32")
    bias = (rng.randn(4 * d) * 0.1).astype("float32")
    h0 = (rng.randn(b, d) * 0.2).astype("float32")
    c0 = (rng.randn(b, d) * 0.2).astype("float32")
    # ragged lengths incl. a length-1 row and a full row
    lens = rng.randint(1, t + 1, size=b).astype("int32")
    lens[0], lens[-1] = t, 1
    hf, cf = pk.fused_lstm(x, w, bias, h0, c0, lens, reverse=reverse,
                           block_b=block_b)
    hr, cr = _scan_lstm(x, w, bias, h0, c0, lens, reverse=reverse)
    # fp32 forward is BIT-exact on CPU interpret mode: the kernel body
    # is the same primitive sequence as the scan step
    assert np.array_equal(np.asarray(hf), np.asarray(hr))
    assert np.array_equal(np.asarray(cf), np.asarray(cr))


def test_fused_lstm_backward_matches_scan():
    b, t, d = 4, 6, 5
    x = (rng.randn(b, t, 4 * d) * 0.4).astype("float32")
    w = (rng.randn(d, 4 * d) * 0.3).astype("float32")
    bias = (rng.randn(4 * d) * 0.1).astype("float32")
    h0 = (rng.randn(b, d) * 0.2).astype("float32")
    c0 = (rng.randn(b, d) * 0.2).astype("float32")
    lens = np.asarray([6, 3, 1, 5], "int32")

    def loss_fused(x, w, bias, h0, c0):
        h, c = pk.fused_lstm(x, w, bias, h0, c0, lens)
        return jnp.sum(h ** 2) + jnp.sum(c[:, -1] ** 2)

    def loss_scan(x, w, bias, h0, c0):
        h, c = _scan_lstm(x, w, bias, h0, c0, lens)
        return jnp.sum(h ** 2) + jnp.sum(c[:, -1] ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, w, bias, h0, c0)
    gs = jax.grad(loss_scan, argnums=(0, 1, 2, 3, 4))(x, w, bias, h0, c0)
    for name, a, b_ in zip("x w bias h0 c0".split(), gf, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_fused_lstm_padding_steps_get_zero_grad():
    """Rows' steps past their @SEQLEN must not leak gradient into x."""
    b, t, d = 3, 8, 4
    x = (rng.randn(b, t, 4 * d) * 0.4).astype("float32")
    w = (rng.randn(d, 4 * d) * 0.3).astype("float32")
    bias = np.zeros(4 * d, "float32")
    lens = np.asarray([8, 4, 2], "int32")

    def loss(x):
        h, _ = pk.fused_lstm(x, w, bias, None, None, lens)
        return jnp.sum(h ** 2)

    g = np.asarray(jax.grad(loss)(x))
    assert np.abs(g[1, 4:]).max() == 0.0
    assert np.abs(g[2, 2:]).max() == 0.0
    assert np.abs(g[0]).max() > 0.0


def test_masked_softmax_bit_exact_and_grads():
    b, t = 6, 11
    x = (rng.randn(b, t) * 2).astype("float32")
    lens = np.asarray([11, 7, 1, 3, 11, 5], "int32")
    m = (np.arange(t)[None, :] < lens[:, None]).astype("float32")
    ref = np.asarray(
        jax.nn.softmax(jnp.where(m > 0, x, -1e30), axis=1) * m)
    got = np.asarray(pk.masked_softmax(x, lens, block_n=8))
    assert np.array_equal(got, ref)

    g1 = jax.grad(lambda x: jnp.sum(pk.masked_softmax(x, lens) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(
        (jax.nn.softmax(jnp.where(m > 0, x, -1e30), axis=1) * m) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("ptype", ["SUM", "AVERAGE", "SQRT"])
def test_masked_pool_matches_dense_and_grads(ptype):
    b, t, f = 5, 9, 4
    x = rng.randn(b, t, f).astype("float32")
    lens = np.asarray([9, 5, 1, 3, 9], "int32")
    m = (np.arange(t)[None, :] < lens[:, None]).astype("float32")[..., None]
    denom = np.maximum(lens.astype("float32"), 1.0)[:, None]
    ref = (x * m).sum(1)
    if ptype == "AVERAGE":
        ref = ref / denom
    elif ptype == "SQRT":
        ref = ref / np.sqrt(denom)
    got = np.asarray(pk.masked_pool(x, lens, ptype=ptype))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def loss_f(x):
        return jnp.sum(pk.masked_pool(x, lens, ptype=ptype) ** 2)

    def loss_d(x):
        s = jnp.sum(x * m, axis=1)
        if ptype == "AVERAGE":
            s = s / denom
        elif ptype == "SQRT":
            s = s / np.sqrt(denom)
        return jnp.sum(s ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(loss_f)(x)),
                               np.asarray(jax.grad(loss_d)(x)),
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# op level through the Executor: PADDLE_TPU_PALLAS allowlist flips the path
# ---------------------------------------------------------------------------

def _run_lstm_program(flag, seqs, w, b, monkeypatch, d, proj_size=None,
                      reverse=False):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7  # identical inits per run
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4 * d], dtype="float32",
                              lod_level=1)
        x.stop_gradient = False
        kw = dict(
            use_peepholes=False, is_reverse=reverse,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.NumpyArrayInitializer(w)),
            bias_attr=fluid.ParamAttr(
                initializer=fluid.initializer.NumpyArrayInitializer(
                    b.reshape(1, -1))))
        if proj_size is None:
            hidden, _ = fluid.layers.dynamic_lstm(input=x, size=4 * d,
                                                  **kw)
        else:
            # both weights keep the seeded default init (deterministic
            # across the two builds; an explicit param_attr would apply
            # to recurrent AND proj weights, whose shapes differ)
            hidden, _ = fluid.layers.dynamic_lstmp(
                input=x, size=4 * d, proj_size=proj_size,
                proj_activation="tanh", use_peepholes=False,
                is_reverse=reverse)
        loss = fluid.layers.mean(fluid.layers.square(hidden))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return exe.run(main, feed={"x": LoDTensor.from_sequences(seqs)},
                       fetch_list=[hidden, loss, "x@GRAD"])


@pytest.mark.parametrize("reverse", [False, True],
                         ids=["forward", "reverse"])
def test_dynamic_lstm_fused_path_matches_scan_path(monkeypatch, reverse):
    """The whole vertical: layers.dynamic_lstm -> lstm op -> fused
    kernel under PADDLE_TPU_PALLAS=lstm vs the scan path under =0, on a
    ragged LoD batch, forward AND executor backward."""
    d = 4
    seqs = [(rng.randn(n, 4 * d) * 0.4).astype("float32")
            for n in (6, 3, 1, 5)]
    w = (rng.randn(d, 4 * d) * 0.3).astype("float32")
    b = (rng.randn(4 * d) * 0.1).astype("float32")
    fused = _run_lstm_program("lstm", seqs, w, b, monkeypatch, d,
                              reverse=reverse)
    dense = _run_lstm_program("0", seqs, w, b, monkeypatch, d,
                              reverse=reverse)
    # forward bit-exact; grads at fp32 rounding
    assert np.array_equal(np.asarray(fused[0]), np.asarray(dense[0]))
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(dense[1]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fused[2]), np.asarray(dense[2]),
                               rtol=1e-4, atol=1e-6)


def test_dynamic_lstmp_fused_path_matches_scan_path(monkeypatch):
    d, p = 5, 3
    seqs = [(rng.randn(n, 4 * d) * 0.4).astype("float32")
            for n in (5, 2, 4)]
    w = (rng.randn(p, 4 * d) * 0.3).astype("float32")
    b = (rng.randn(4 * d) * 0.1).astype("float32")
    fused = _run_lstm_program("lstm", seqs, w, b, monkeypatch, d,
                              proj_size=p)
    dense = _run_lstm_program("0", seqs, w, b, monkeypatch, d,
                              proj_size=p)
    # f32 forward within 2 ulp of the output's scale: the kernel body and
    # the scan step are the same primitives, but XLA fuses the
    # projection's tanh(h @ w_proj) differently in the two programs
    # (1 ulp at the largest magnitude seen on jax 0.9.0)
    want = np.asarray(dense[0])
    np.testing.assert_allclose(
        np.asarray(fused[0]), want, rtol=0,
        atol=2 * np.spacing(np.abs(want).max()))
    np.testing.assert_allclose(np.asarray(fused[2]), np.asarray(dense[2]),
                               rtol=1e-4, atol=1e-6)


def test_lstm_nondefault_activations_fall_back_to_scan(monkeypatch):
    """The fused kernel owns only the default-activation, no-peephole
    config; a relu-gate program under PADDLE_TPU_PALLAS=lstm must take
    the scan path (spy: the kernel is never entered)."""
    calls = []
    real = pk.fused_lstm
    monkeypatch.setattr(pk, "fused_lstm",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "lstm")
    d = 3
    seqs = [(rng.randn(4, 4 * d) * 0.3).astype("float32")]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4 * d], dtype="float32",
                              lod_level=1)
        hidden, _ = fluid.layers.dynamic_lstm(
            input=x, size=4 * d, use_peepholes=False,
            candidate_activation="relu")
        h2, _ = fluid.layers.dynamic_lstm(input=x, size=4 * d,
                                          use_peepholes=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    # build-time shape inference also evaluates the lowering rules
    # (dual-sentinel eval_shape) — only count the real run's trace
    calls.clear()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": LoDTensor.from_sequences(seqs)},
                fetch_list=[hidden, h2])
    # exactly the default-config op entered the kernel, not the relu one
    assert len(calls) == 1


def _run_seq_program(flag, build_out, seqs, monkeypatch, feat):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32",
                              lod_level=1)
        x.stop_gradient = False
        out = build_out(x)
        loss = fluid.layers.mean(fluid.layers.square(out))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return exe.run(main, feed={"x": LoDTensor.from_sequences(seqs)},
                       fetch_list=[out, "x@GRAD"])


def test_sequence_softmax_fused_path_matches_dense(monkeypatch):
    seqs = [(rng.randn(n, 1) * 2).astype("float32") for n in (7, 1, 4)]
    build = lambda x: fluid.layers.sequence_softmax(input=x)
    fused = _run_seq_program("seq", build, seqs, monkeypatch, feat=1)
    dense = _run_seq_program("0", build, seqs, monkeypatch, feat=1)
    assert np.array_equal(np.asarray(fused[0]), np.asarray(dense[0]))
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(dense[1]),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("ptype", ["sum", "average", "max"])
def test_sequence_pool_fused_path_matches_dense(monkeypatch, ptype):
    """SUM/AVERAGE ride the fused kernel (SQRT shares their code path
    and is covered kernel-level above); MAX must still work — it keeps
    the dense path under the same flag."""
    seqs = [(rng.randn(n, 6) * 1.5).astype("float32") for n in (5, 1, 8)]
    build = lambda x: fluid.layers.sequence_pool(input=x, pool_type=ptype)
    fused = _run_seq_program("seq", build, seqs, monkeypatch, feat=6)
    dense = _run_seq_program("0", build, seqs, monkeypatch, feat=6)
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(dense[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(dense[1]),
                               rtol=1e-5, atol=1e-7)
