"""lookup_table's backward (ops/embedding_grad.py): the dense [V, D]
gradient of the table against `one_hot(ids).T @ g` in float64, whatever the
ids repeat like, by XLA's scatter and by the kernel (interpreted here: the
kernels off and on);
padding_idx; the shapes ids come in; a tied head through `causal_lm`; the
table sharded over its rows on the CPU mesh; and what
`ptpu_embedding_layers_total` says at two benchmark cells' shapes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import embedding_grad

N, V = 512, 1000                # V is no multiple of 128
WIDTHS = (512, 2048, 2560)


@pytest.fixture(params=["kernels_off", "kernels_on"])
def kernels(request, monkeypatch):
    """Off is the CPU's default; on is PADDLE_TPU_PALLAS=emb, under which
    the rule takes the kernel where it would on a TPU, interpreted."""
    if request.param == "kernels_on":
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "emb")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    return request.param == "kernels_on"


def _ids(kind, n=N, v=V, seed=0):
    rng = np.random.RandomState(seed)
    if kind == "uniform":
        return rng.randint(0, v, n)
    if kind == "zipf":              # exponent 1: the commonest word a tenth
        p = 1.0 / np.arange(1, v + 1)
        return rng.permutation(v)[rng.choice(v, n, p=p / p.sum())]
    return np.full(n, v // 3)       # one id n times


def _cotangent(n, width, dtype, seed=1):
    g = np.random.RandomState(seed).randn(n, width).astype(np.float32)
    # a bfloat16 cotangent reaches the rule as float32, as _lower_grad_of
    # casts it to the forward output's dtype
    return np.asarray(jnp.asarray(g, dtype).astype(jnp.float32))


def _rule_grad(w, ids, g, padding_idx=-1):
    """d W of the registered rule, as a grad op takes it: jax.vjp."""
    rule = registry.get("lookup_table").lower

    def out(w):
        return rule(registry.AbstractCtx(),
                    {"W": [w], "Ids": [jnp.asarray(ids)]},
                    {"padding_idx": padding_idx})["Out"][0]
    y, vjp = jax.vjp(out, jnp.asarray(w))
    return y, vjp(jnp.asarray(g).reshape(y.shape))[0]


def _dense_reference(ids, g, v):
    onehot = np.zeros((v, ids.size))
    onehot[ids.reshape(-1), np.arange(ids.size)] = 1.0
    return onehot @ g.reshape(ids.size, -1).astype(np.float64)


def _error(got, want):
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("kind", ["uniform", "zipf", "one"])
def test_dense_gradient_against_one_hot_in_float64(kind, width, dtype,
                                                   kernels):
    ids = _ids(kind)
    g = _cotangent(N, width, dtype)
    w = np.random.RandomState(2).randn(V, width).astype(np.float32)
    y, dw = _rule_grad(w, ids[:, None], g)
    np.testing.assert_array_equal(np.asarray(y), w[ids])
    assert dw.shape == (V, width) and dw.dtype == jnp.float32
    assert embedding_grad.grad_form(N, width) \
        == ("kernel" if kernels and width >= 2048 else "scatter")
    # float32 sums of up to N terms, in another order than float64's
    assert _error(dw, _dense_reference(ids, g, V)) < 2e-6
    # and the very adds of jax's own transpose of the gather, in its order
    own = jax.vjp(lambda w: jnp.take(w, jnp.asarray(ids), axis=0),
                  jnp.asarray(w))[1](jnp.asarray(g))[0]
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(own))


@pytest.mark.parametrize("shape", [(N, 1), (4, N // 4), (4, N // 4, 1)])
def test_ids_as_a_column_and_as_a_batch_of_sequences(shape, kernels):
    ids = _ids("zipf").reshape(shape)
    g = _cotangent(N, 2560, "float32")
    w = np.random.RandomState(2).randn(V, 2560).astype(np.float32)
    y, dw = _rule_grad(w, ids, g)
    lead = shape[:-1] if shape[-1] == 1 else shape
    assert y.shape == lead + (2560,)
    assert _error(dw, _dense_reference(ids, g, V)) < 2e-6


@pytest.mark.parametrize("width", [512, 2560])
def test_padding_idx_has_no_gradient(width, kernels):
    # (512 is XLA's whatever is on; 2560 the kernel's where it is)
    ids = _ids("zipf")
    pad = int(np.bincount(ids).argmax())        # the commonest id
    g = _cotangent(N, width, "float32")
    w = np.random.RandomState(2).randn(V, width).astype(np.float32)
    y, dw = _rule_grad(w, ids[:, None], g, padding_idx=pad)
    assert not np.asarray(y)[ids == pad].any()
    assert not np.asarray(dw)[pad].any()
    want = _dense_reference(ids, g, V)
    want[pad] = 0.0
    assert _error(dw, want) < 2e-6


def test_ids_out_of_range_are_dropped_and_negative_ones_wrap(kernels):
    """As jnp.take and its transpose have it: id -1 is the last row, an id
    past the table reads NaN and gives no gradient."""
    ids = np.array([3, -1, V, 3, -V - 1, V - 1])
    g = _cotangent(ids.size, 2560, "float32")
    w = np.random.RandomState(2).randn(V, 2560).astype(np.float32)
    y, dw = _rule_grad(w, ids[:, None], g)
    assert np.isnan(np.asarray(y)[[2, 4]]).all()
    want = np.zeros((V, 2560))
    want[3] = g[0].astype(np.float64) + g[3]
    want[V - 1] = g[1].astype(np.float64) + g[5]
    assert _error(dw, want) < 1e-6


@pytest.mark.parametrize("width,form", [
    (2048, "kernel"), (2560, "kernel"), (7168, "kernel"),
    (512, "scatter"), (1920, "scatter"),    # a row under 8 KiB: XLA's
    (32, "scatter"), (2600, "scatter")])    # no whole lane tiles: XLA's
def test_who_builds_the_gradient(width, form, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "emb")
    assert embedding_grad.grad_form(8192, width) == form
    assert embedding_grad.grad_form(1 << 17, width) == form
    # ids that do not fit the scalar memory the kernel prefetches them into
    assert embedding_grad.grad_form((1 << 17) + 1, width) == "scatter"
    assert embedding_grad.grad_form(8192, width, mesh=object()) == "scatter"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "0")
    assert embedding_grad.grad_form(8192, width) == "scatter"
    monkeypatch.delenv("PADDLE_TPU_PALLAS")     # the CPU's default: off
    assert embedding_grad.grad_form(8192, width) == "scatter"


@pytest.mark.parametrize("vocab,width,block_rows", [
    (1000, 2560, None),             # the budget's block: the whole table
    (1000, 2560, 24),               # 42 blocks, the last one 16 rows
    (77, 128, 16),                  # a vocabulary of no whole sublane tiles
    (200, 256, 200)])
def test_the_kernel_by_block_sizes(vocab, width, block_rows):
    """Rows that are not whole chunks, blocks no id falls in, a run that
    crosses chunks and ids the table does not have."""
    rng = np.random.RandomState(3)
    ids = np.concatenate([rng.randint(0, vocab // 2, 70),
                          np.full(41, vocab - 1), [-1, vocab, -vocab - 1]])
    g = rng.randn(ids.size, width).astype(np.float32)
    got = embedding_grad.dense_grad(jnp.asarray(ids, jnp.int32),
                                    jnp.asarray(g), vocab,
                                    block_rows=block_rows, interpret=True)
    want = jnp.zeros((vocab, width), jnp.float32).at[jnp.asarray(ids)].add(
        jnp.asarray(g))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_bfloat16_table_is_summed_in_float32(kernels):
    ids = _ids("one")
    g = np.full((N, 2560), 1.0 + 2.0 ** -7, np.float32)   # exact in bf16
    w = jnp.zeros((V, 2560), jnp.bfloat16)
    _, vjp = jax.vjp(lambda w: embedding_grad.take_rows(
        w, jnp.asarray(ids, jnp.int32), embedding_grad.grad_form(N, 2560)), w)
    dw = vjp(jnp.asarray(g, jnp.bfloat16))[0]
    assert dw.dtype == jnp.bfloat16
    # 512 terms of 1.0078125: a bfloat16 running sum stalls at 256
    assert float(dw[V // 3, 0]) == float(jnp.asarray(N * (1.0 + 2.0 ** -7),
                                                     jnp.bfloat16))


# --- through Programs ---------------------------------------------------------

def _embedding_program(vocab, width, ids_shape, padding_idx=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=list(ids_shape),
                                dtype="int64", append_batch_size=False)
        g = fluid.layers.data(name="g", shape=[ids_shape[0], width],
                              dtype="float32", append_batch_size=False)
        emb = fluid.layers.embedding(
            ids, size=[vocab, width], padding_idx=padding_idx,
            param_attr=fluid.ParamAttr(name="table"))
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(emb, g))
        fluid.backward.append_backward(loss)
    return main, startup


def _embedding_counts():
    return {tuple(sorted(dict(key).items())): n for key, n in
            REGISTRY.counter("ptpu_embedding_layers_total", "").samples()}


# (rows, vocabulary, width): the SmallThinker cell's lookup and one of the
# base transformer's at T=256, the two sides of the rule
@pytest.mark.parametrize("rows,vocab,width,form", [
    (8192, 37984, 2560, "kernel"), (16384, 32000, 512, "scatter")])
def test_the_counter_names_the_form_at_a_cells_shape(rows, vocab, width,
                                                     form, kernels):
    """Lowered abstractly (jax.eval_shape): the counter is booked where the
    forward op lowers, once a lookup, and the grad op books nothing."""
    from paddle_tpu.core import lowering
    main, _ = _embedding_program(vocab, width, (rows, 1))
    state_rw, state_ro, state_out = lowering.analyze_state(
        main, ["ids", "g"], ["table@GRAD"])
    fn = lowering.build_program_fn(main, ["ids", "g"], ["table@GRAD"],
                                   state_rw, state_ro, state_out)
    sds = jax.ShapeDtypeStruct
    shapes = {"ids": sds((rows, 1), jnp.int64),
              "g": sds((rows, width), jnp.float32),
              "table": sds((vocab, width), jnp.float32)}
    before = _embedding_counts()
    out = jax.eval_shape(
        fn, [shapes["ids"], shapes["g"]], [shapes[n] for n in state_rw],
        [shapes[n] for n in state_ro], sds((), jnp.uint32))
    assert out[0][0].shape == (vocab, width)
    assert out[0][0].dtype == jnp.float32
    after = _embedding_counts()
    counted = {k: after[k] - before.get(k, 0) for k in after
               if after[k] != before.get(k, 0)}
    form = form if kernels else "scatter"
    assert counted == {(("grad", form), ("rows", str(rows)),
                        ("vocab", str(vocab)), ("width", str(width))): 1}
    assert "gradient by %s" % form in "\n".join(
        fluid.profiler._embedding_lines())


def test_an_executor_step_gives_the_tables_gradient(kernels):
    ids = _ids("zipf")
    g = _cotangent(N, 2560, "float32")
    main, startup = _embedding_program(V, 2560, (N, 1), padding_idx=7)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        dw, = exe.run(main, feed={"ids": ids[:, None], "g": g},
                      fetch_list=["table@GRAD"])
    want = _dense_reference(ids, g, V)
    want[7] = 0.0
    assert _error(dw, want) < 2e-6


# a dense one-layer model whose head is the embedding's own parameter, at
# the narrowest row the kernel takes and toy sizes elsewhere
TIED = dict(
    vocab_size=72, hidden_size=2048, num_hidden_layers=1,
    num_attention_heads=2, num_key_value_heads=1, head_dim=8,
    intermediate_size=16, rms_norm_eps=1e-5, rope_theta=1e4,
    tie_word_embeddings=True, initializer_range=0.02,
    embedding_initializer_range=0.05)
T = 24


def test_a_tied_heads_gradient_is_the_sum_of_lookup_and_head(kernels):
    """The lookup's dense gradient and the head's matmul land in one
    gradient variable through `sum`."""
    assert embedding_grad.grad_form(2 * T, TIED["hidden_size"]) \
        == ("kernel" if kernels else "scatter")
    tok = np.random.RandomState(0).randint(0, TIED["vocab_size"],
                                           (2, T + 1))
    tok[0, :12] = 5                             # a run of one word
    feed = {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(T), (2, T)).copy(),
            "labels": tok[:, 1:, None]}
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss = causal_lm.build_train(TIED, T)[0]
    params = main.global_block().all_parameters()
    assert "head" not in [p.name for p in params]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        got = exe.run(main, feed=feed, fetch_list=[loss, "embedding@GRAD"])
    (want_loss, _), grads = reference.loss_and_grads(
        TIED, weights, *(jnp.asarray(feed[k])
                         for k in ("ids", "pos", "labels")))
    assert abs(float(np.ravel(got[0])[0]) - float(want_loss)) < 1e-5
    want = np.asarray(grads[0], np.float64)
    assert _error(got[1], want) < 2e-4          # test_causal_lm_lfm2's


# --- the table sharded over its rows ------------------------------------------

@pytest.mark.parametrize("width", [512, 2560])
def test_a_vocabulary_parallel_table_still_partitions(width, kernels):
    """W under P(tp, None), as parallel/plan.py places an embedding: under
    a mesh the rule keeps XLA's scatter whatever else is on, its forward and
    backward compile for the mesh and give the single device's gradient,
    sharded like the table."""
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs the conftest's virtual CPU devices")
    mesh = Mesh(np.array(devices[:4]), ("tp",))
    rows = NamedSharding(mesh, P("tp", None))
    whole = NamedSharding(mesh, P())
    ids = _ids("zipf")
    g = _cotangent(N, width, "float32")
    w = np.random.RandomState(2).randn(V, width).astype(np.float32)
    rule = registry.get("lookup_table").lower

    class Ctx(object):
        pass
    ctx = Ctx()
    ctx.mesh = mesh
    assert embedding_grad.grad_form(N, width, mesh) == "scatter"

    def grad(w, ids, g):
        y, vjp = jax.vjp(lambda w: rule(
            ctx, {"W": [w], "Ids": [ids]}, {"padding_idx": -1})["Out"][0], w)
        return y, vjp(g)[0]

    y, dw = jax.jit(grad, in_shardings=(rows, whole, whole),
                    out_shardings=(whole, rows))(
        jnp.asarray(w), jnp.asarray(ids[:, None], jnp.int32), jnp.asarray(g))
    assert dw.sharding.is_equivalent_to(rows, 2)
    np.testing.assert_array_equal(np.asarray(y), w[ids])
    assert _error(dw, _dense_reference(ids, g, V)) < 2e-6
