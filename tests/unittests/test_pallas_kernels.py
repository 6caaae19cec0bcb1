"""Pallas fused kernels vs dense references (interpret mode on CPU — the
same kernel code path that runs compiled on TPU)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel.ring_attention import attention_reference


def _qkv(rng, b=2, t=24, h=3, d=16):
    mk = lambda: rng.randn(b, t, h, d).astype("float32") * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("t", [16, 24, 50])
def test_flash_attention_matches_reference(causal, t):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng, t=t)
    out = pk.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_mismatched_block_sizes():
    # block_q != block_k with neither dividing the other: T must pad to the
    # lcm so no tail k block is dropped and every q row is written
    rng = np.random.RandomState(7)
    q, k, v = _qkv(rng, t=32)
    out = pk.flash_attention(q, k, v, causal=True, block_q=16, block_k=24)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_kv_len_masks_padded_keys():
    """Rows attend only to their first kv_len keys — must equal dense
    attention computed on the truncated sequences."""
    rng = np.random.RandomState(8)
    b, t, h, d = 3, 20, 2, 8
    q, k, v = _qkv(rng, b=b, t=t, h=h, d=d)
    lens = np.asarray([20, 13, 5], dtype="int32")
    out = pk.flash_attention(q, k, v, kv_len=lens, block_q=8, block_k=8)
    for i, n in enumerate(lens):
        ref = attention_reference(q[i:i + 1], k[i:i + 1, :n],
                                  v[i:i + 1, :n])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="row %d len %d" % (i, n))
    # grads w.r.t. padded keys must be exactly zero
    def loss(k):
        return jnp.sum(pk.flash_attention(q, k, v, kv_len=lens,
                                          block_q=8, block_k=8) ** 2)
    gk = np.asarray(jax.grad(loss)(k))
    assert np.abs(gk[1, 13:]).max() == 0.0
    assert np.abs(gk[2, 5:]).max() == 0.0
    assert np.abs(gk[0]).max() > 0.0


def test_flash_attention_grads_match_reference():
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, b=1, t=20, h=2, d=8)
    tgt = rng.randn(*q.shape).astype("float32")

    def loss_flash(q, k, v):
        o = pk.flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        return jnp.mean((o - tgt) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean((attention_reference(q, k, v, causal=True)
                         - tgt) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_attention_under_jit():
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, t=16)
    f = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v, block_q=8,
                                                   block_k=8))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(attention_reference(q, k, v)), rtol=2e-4, atol=2e-5)


def test_fused_attention_layer_through_executor():
    import paddle_tpu as fluid
    rng = np.random.RandomState(5)
    b, t, h, d = 2, 12, 2, 8
    qn, kn, vn = (rng.randn(b, t, h, d).astype("float32") * 0.5
                  for _ in range(3))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[t, h, d], dtype="float32")
        k = fluid.layers.data(name="k", shape=[t, h, d], dtype="float32")
        v = fluid.layers.data(name="v", shape=[t, h, d], dtype="float32")
        q.stop_gradient = False  # data vars default to stop_gradient=True
        out = fluid.layers.fused_attention(q, k, v, causal=True)
        loss = fluid.layers.mean(fluid.layers.square(out))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        got, gq = exe.run(main, feed={"q": qn, "k": kn, "v": vn},
                          fetch_list=[out, "q@GRAD"])
    ref = attention_reference(qn, kn, vn, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    def loss_ref(q):
        o = attention_reference(q, kn, vn, causal=True)
        return jnp.mean(jnp.square(o))

    np.testing.assert_allclose(np.asarray(gq),
                               np.asarray(jax.grad(loss_ref)(qn)),
                               rtol=2e-3, atol=2e-4)


def test_fused_attention_kv_len_through_executor(monkeypatch):
    """Layer-level KVLen plumbing: kv_len auto-resolved from a sequence
    feed's lengths companion, through Executor + append_backward —
    through the PALLAS KERNEL (min_seq=0 forces it; the per-shape
    dispatch would otherwise route this tiny T to the dense path and
    the test would stop covering the kernel's KVLen/custom_vjp)."""
    import paddle_tpu as fluid
    from paddle_tpu.ops import kernel_config
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    # two blocks over the longest row, so that block skipping is on the path
    monkeypatch.setitem(kernel_config.DEFAULT_TILES, "attn",
                        {"block_q": 8, "block_k": 8})
    rng = np.random.RandomState(12)
    H, D = 2, 8
    seqs = [rng.randn(n, H * D).astype("float32") * 0.5 for n in (9, 5, 2)]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        seq = fluid.layers.data(name="seq", shape=[H * D], dtype="float32",
                                lod_level=1)
        seq.stop_gradient = False
        x = fluid.layers.reshape(seq, shape=[0, -1, H, D])
        # reshape drops the lengths companion, so pass kv_len explicitly
        kv = seq.block.var_recursive(seq.seq_len_var)
        att = fluid.layers.fused_attention(x, x, x, kv_len=kv)
        loss = fluid.layers.mean(fluid.layers.square(att))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        a, g = exe.run(main,
                       feed={"seq": fluid.LoDTensor.from_sequences(seqs)},
                       fetch_list=[att, "seq@GRAD"])
    a = np.asarray(a)
    # each row must equal dense attention over its true length only
    for i, s in enumerate(seqs):
        n = len(s)
        xi = s.reshape(1, n, H, D)
        ref = attention_reference(xi, xi, xi)
        np.testing.assert_allclose(a[i, :n], np.asarray(ref)[0],
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="row %d" % i)
    # grads flow through the executor backward (padded-KEY zero-grad is
    # asserted at kernel level; here the loss also covers padded QUERY
    # rows, whose grads are legitimately nonzero)
    g = np.asarray(g)
    assert np.isfinite(g).all() and np.abs(g[0]).max() > 0


def test_softmax_xent_pallas_path_through_executor(monkeypatch):
    """PADDLE_TPU_PALLAS=1 routes the softmax_with_cross_entropy op through
    the fused kernel; results and grads must match the dense path."""
    import paddle_tpu as fluid
    rng = np.random.RandomState(6)
    x = rng.randn(6, 10).astype("float32")
    y = rng.randint(0, 10, (6, 1)).astype("int64")

    def run(flag):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            xv = fluid.layers.data(name="x", shape=[10], dtype="float32")
            yv = fluid.layers.data(name="y", shape=[1], dtype="int64")
            xv.stop_gradient = False
            loss = fluid.layers.softmax_with_cross_entropy(logits=xv,
                                                           label=yv)
            avg = fluid.layers.mean(loss)
            fluid.append_backward(avg)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            return exe.run(main, feed={"x": x, "y": y},
                           fetch_list=[avg, "x@GRAD"])

    fused = run("1")
    dense = run("0")
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(dense[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fused[1]), np.asarray(dense[1]),
                               rtol=1e-4, atol=1e-6)


def test_fused_layer_norm_matches_dense():
    rng = np.random.RandomState(9)
    n, d = 11, 24
    x = rng.randn(n, d).astype("float32") * 2 + 1
    scale = (rng.rand(d).astype("float32") + 0.5)
    bias = rng.randn(d).astype("float32")
    y, mean, var = pk.layer_norm(x, scale, bias, eps=1e-5)
    mu = x.mean(-1, keepdims=True)
    v = x.var(-1)
    expect = (x - mu) / np.sqrt(v[:, None] + 1e-5) * scale + bias
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mean), mu[:, 0], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), v, rtol=1e-4)


def test_fused_layer_norm_grads_match_dense():
    rng = np.random.RandomState(10)
    n, d = 6, 16
    x = rng.randn(n, d).astype("float32")
    scale = rng.rand(d).astype("float32") + 0.5
    bias = rng.randn(d).astype("float32")
    tgt = rng.randn(n, d).astype("float32")

    def loss_fused(x, s, b):
        y, _, _ = pk.layer_norm(x, s, b)
        return jnp.mean((y - tgt) ** 2)

    def loss_dense(x, s, b):
        mu = jnp.mean(x, -1, keepdims=True)
        v = jnp.var(x, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(v + 1e-5) * s + b
        return jnp.mean((y - tgt) ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("n,d,dtype,tile_bytes,tiles,tol", [
    # the table's own budget: 512 rows a tile at D=512
    (1100, 512, "float32", None, 3, 2e-5),
    # smaller budgets, so that a small N is several tiles too
    (50, 24, "float32", 1536, 4, 2e-5),
    (1000, 128, "float32", 250 * 512, 5, 2e-5),        # 200 rows divide N
    (70, 32, "bfloat16", 4096, 3, 2e-2),
], ids=["n1100_d512", "n50_d24", "n1000_d128_whole", "n70_d32_bf16"])
def test_fused_layer_norm_tiles_match_dense(monkeypatch, n, d, dtype,
                                            tile_bytes, tiles, tol):
    """Several tiles of the byte budget and (but for one case) a padded
    tail: y and all three gradients agree with the dense float32 math on
    the same inputs, under a random cotangent, and no row of the pad
    leaks into dscale or dbias."""
    from paddle_tpu.ops import kernel_config as kc
    if tile_bytes is not None:
        monkeypatch.setitem(kc.DEFAULT_TILES, "ln",
                            {"tile_bytes": tile_bytes})
    rows = pk._ln_block_rows(n, d, jnp.dtype(dtype),
                             kc.DEFAULT_TILES["ln"]["tile_bytes"])
    assert -(-n // rows) == tiles
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(n, d).astype("float32") * 2 + 1, dtype)
    scale = jnp.asarray(rng.rand(d).astype("float32") + 0.5)
    bias = jnp.asarray(rng.randn(d).astype("float32"))
    g = jnp.asarray(rng.randn(n, d).astype("float32"), dtype)

    def fused(x, s, b):
        return pk.layer_norm(x, s, b)[0]

    def dense(x, s, b):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        v = jnp.var(xf, -1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(v + 1e-5) * s + b).astype(x.dtype)

    y1, vjp1 = jax.vjp(fused, x, scale, bias)
    y2, vjp2 = jax.vjp(dense, x, scale, bias)
    assert y1.dtype == x.dtype and y1.shape == (n, d)
    for a, b in zip((y1,) + vjp1(g), (y2,) + vjp2(g)):
        assert a.dtype == b.dtype
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-6)


def test_layer_norm_op_pallas_path_matches_dense(monkeypatch):
    import paddle_tpu as fluid
    rng = np.random.RandomState(11)
    x = rng.randn(5, 3, 8).astype("float32")

    def run(flag):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            xv = fluid.layers.data(name="x", shape=[3, 8], dtype="float32")
            xv.stop_gradient = False
            y = fluid.layers.layer_norm(xv, begin_norm_axis=2)
            avg = fluid.layers.mean(fluid.layers.square(y))
            fluid.append_backward(avg)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            return exe.run(main, feed={"x": x},
                           fetch_list=[y, avg, "x@GRAD"])

    fused = run("1")
    dense = run("0")
    for a, b in zip(fused, dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_softmax_xent_matches_dense():
    rng = np.random.RandomState(3)
    n, vsz = 13, 37
    logits = rng.randn(n, vsz).astype("float32") * 2.0
    labels = rng.randint(0, vsz, (n,)).astype("int64")
    loss = pk.softmax_xent(logits, labels)
    lp = jax.nn.log_softmax(logits, axis=-1)
    expect = -np.asarray(lp)[np.arange(n), labels].reshape(n, 1)
    np.testing.assert_allclose(np.asarray(loss), expect, rtol=1e-5,
                               atol=1e-6)


def test_softmax_xent_grad_matches_dense():
    rng = np.random.RandomState(4)
    n, vsz = 6, 19
    logits = rng.randn(n, vsz).astype("float32")
    labels = rng.randint(0, vsz, (n,)).astype("int64")

    def loss_fused(x):
        return jnp.mean(pk.softmax_xent(x, labels))

    def loss_dense(x):
        lp = jax.nn.log_softmax(x, axis=-1)
        return jnp.mean(-lp[jnp.arange(n), labels])

    g1 = jax.grad(loss_fused)(logits)
    g2 = jax.grad(loss_dense)(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("t,bq,bk", [
    (100, 32, 64), (100, 64, 32), (33, 32, 32), (7, 8, 8),
    (129, 64, 64), (65, 128, 128),
])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_attention_block_grid(t, bq, bk, causal):
    """Block-size x ragged-T matrix: every (block_q, block_k) index-math
    combination must match dense, incl. T smaller than one block, T one
    past a block boundary, and asymmetric q/k tiles both ways."""
    rng = np.random.RandomState(t * 7 + bq)
    q, k, v = _qkv(rng, t=t, h=2, d=8)
    out = pk.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("t,bq,bk", [(50, 16, 32), (33, 32, 16)])
def test_flash_attention_grads_block_grid(t, bq, bk):
    """Flash backward across uneven block tilings vs jax.grad of dense."""
    rng = np.random.RandomState(t + bq)
    q, k, v = _qkv(rng, t=t, h=2, d=8)

    def loss_flash(q, k, v):
        o = pk.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        return jnp.sum(o ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_flash_attention_kv_len_block_boundaries():
    """kv_len landing exactly on, one before, and one after a block
    boundary — the block-skip fast path must not drop a partial block."""
    rng = np.random.RandomState(11)
    q, k, v = _qkv(rng, b=4, t=64, h=2, d=8)
    lens = np.array([32, 31, 33, 64], "int32")  # on/under/over boundary
    out = pk.flash_attention(q, k, v, kv_len=jnp.asarray(lens),
                             block_q=32, block_k=32)
    ref = attention_reference(q, k, v, kv_len=jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (16, 16)])
def test_flash_attention_kv_len_grads_block_grid(bq, bk, causal):
    """Gradients where both frontiers cross blocks: key lengths on, before
    and after a block boundary (and one row of a single key), with and
    without the causal diagonal, on uneven tilings."""
    rng = np.random.RandomState(bq * 3 + bk)
    q, k, v = _qkv(rng, b=5, t=64, h=2, d=8)
    lens = jnp.asarray([32, 31, 33, 64, 1], "int32")

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(loss(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, kv_len=lens, block_q=bq, block_k=bk)),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=causal, kv_len=lens)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


# ---------------------------------------------------------------------------
# bf16 inputs: the kernels' dots take bf16 operands and accumulate in float32
# ---------------------------------------------------------------------------

# block_q, block_k; None is the default table's pair
_BF16_BLOCKS = {"128x128": (128, 128), "256x512": (256, 512),
                "default": (None, None)}


@functools.lru_cache(maxsize=2)     # the four cases of one key run in a row
def _bf16_flash_and_reference(mask, d, blocks):
    """(flash, reference) as (out, dq, dk, dv) in float32: the kernels on
    bf16 q, k, v and a bf16 cotangent; dense attention in float32 on the
    same rounded values."""
    rng = np.random.RandomState(d + len(mask))
    b, t, h = 2, 1024, 1
    q, k, v, g = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)
                  for _ in range(4))
    kw = {"causal": mask == "causal"}
    if mask == "kv_len":
        kw["kv_len"] = jnp.asarray([t - 200, 130], jnp.int32)
    bq, bk = _BF16_BLOCKS[blocks]
    out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
        q, k, v, block_q=bq, block_k=bk, **kw), q, k, v)
    assert out.dtype == jnp.bfloat16
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, ref_vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, **kw), *f32)
    return (tuple(np.asarray(x.astype(jnp.float32))
                  for x in (out,) + vjp(g)),
            tuple(np.asarray(x)
                  for x in (ref,) + ref_vjp(g.astype(jnp.float32))))


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("blocks", sorted(_BF16_BLOCKS))
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mask", ["full", "causal", "kv_len"])
def test_flash_attention_bf16_matches_float32_reference(mask, d, blocks,
                                                        which):
    """What bf16 operands cost in accuracy, as the largest error over the
    largest reference value. q @ k.T and dO @ v.T are exact products summed
    in float32, so the only roundings the kernels add to the float32
    reference are of p and ds to bf16 where they enter a dot and of each
    result to bf16: up to 2**-8 = 0.39 % of a value each, and the sums
    over keys average the first down. Measured 0.18-0.49 %; 1 % holds a
    second rounding of an operand, a missing mask or a wrong block out."""
    got, want = _bf16_flash_and_reference(mask, d, blocks)
    i = ["out", "dq", "dk", "dv"].index(which)
    err = np.abs(got[i] - want[i]).max() / np.abs(want[i]).max()
    assert err <= 1e-2, "%s off by %.2f %% of its largest value" % (
        which, 100 * err)


def _kernel_dot_operands(dtype):
    """{kernel name: [(lhs dtype, rhs dtype) of every dot_general in its
    body]} for the forward and both backward kernels traced on `dtype`."""
    x = jnp.zeros((1, 64, 2, 16), dtype)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: pk.flash_attention(*a, causal=True, block_q=16,
                                      block_k=16), q, k, v)[1](q))(x, x, x)
    found = {}

    def walk(j, kernel):
        for e in j.eqns:
            if e.primitive.name == "dot_general" and kernel:
                found.setdefault(kernel, []).append(
                    tuple(str(a.aval.dtype) for a in e.invars))
            inside = e.params["name"] if e.primitive.name == "pallas_call" \
                else kernel
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, inside)
    walk(jaxpr.jaxpr, None)
    return found


@pytest.mark.parametrize("kernel,n_dots", [
    ("ptpu_flash_fwd", 2), ("ptpu_flash_bwd_dkdv", 4),
    ("ptpu_flash_bwd_dq", 3)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_kernel_dots_take_the_input_dtype(dtype, kernel, n_dots):
    """No cast in front of the MXU: with bf16 inputs no dot in a kernel
    body has a float32 operand, with float32 inputs every one has."""
    dots = _kernel_dot_operands(dtype)[kernel]
    assert len(dots) == n_dots
    assert all(pair == (dtype, dtype) for pair in dots), dots
