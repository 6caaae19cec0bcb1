"""The repo's one way to recompute: `StaticRNN(steps=K, recompute=True)`, an
`rnn_scan` op whose body runs under jax.checkpoint (ops/control_ops.py
_recomputing). Small float32 programs on the CPU, each built twice, the
loop recomputing its body and keeping it (`recompute=False`, the reference
side), and held to each other after three optimizer steps: the loss of
every step and every parameter. Dropout in the body (the replay must draw
the forward's masks), a top-level While beside the loop, the data-parallel
ParallelExecutor, mixed precision, K steps in one dispatch; and the
recomputing step's backward scan really holds the body's matmuls a second
time.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.observability.registry import REGISTRY

WIDTH, BATCH = 16, 16


def _build(recompute, trips=2, seed=0, with_while=False, amp=False):
    """(main, startup, loss, the While's sum or None): x -> fc -> a loop of
    `trips` trips over one residual block with dropout in it (weights the
    body closes over) -> the last trip's state -> fc -> squared error."""
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=WIDTH, act="tanh")
        total = None
        if with_while:
            # reads `h` from the enclosing scope: an implicit read that the
            # While op's input list does not carry
            i = layers.zeros(shape=[1], dtype="int32")
            i.stop_gradient = True
            n = layers.fill_constant(shape=[1], dtype="int32", value=3)
            total = layers.zeros(shape=[1], dtype="float32")
            total.stop_gradient = True
            cond = layers.less_than(x=i, y=n)
            loop = layers.While(cond=cond)
            with loop.block():
                layers.sums(input=[total, layers.reduce_sum(h)], out=total)
                layers.less_than(x=layers.increment(i), y=n, cond=cond)
        rnn = layers.StaticRNN(steps=trips, recompute=recompute)
        with rnn.step():
            state = rnn.memory(init=h)
            a = layers.fc(input=state, size=WIDTH, act="relu",
                          param_attr=fluid.ParamAttr(name="body.w1"),
                          bias_attr=fluid.ParamAttr(name="body.b1"))
            a = layers.dropout(a, dropout_prob=0.3, seed=seed)
            a = layers.fc(input=a, size=WIDTH,
                          param_attr=fluid.ParamAttr(name="body.w2"),
                          bias_attr=fluid.ParamAttr(name="body.b2"))
            new = layers.tanh(state + a)
            rnn.update_memory(state, new)
            rnn.output(new)
        last = layers.reshape(layers.split(rnn(), trips, dim=1)[-1],
                              shape=[-1, WIDTH])
        pred = layers.fc(input=last, size=1)
        loss = layers.mean(x=layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    if amp:
        main.enable_mixed_precision()
    return main, startup, loss, total


def _batches(n=3):
    r = np.random.RandomState(2)
    return [{"x": r.rand(BATCH, 8).astype("f"),
             "y": r.rand(BATCH, 1).astype("f")} for _ in range(n)]


def _train(recompute, parallel=False, steps=1, init=None, **build):
    """({"loss": a step's, "sum": the While's, parameter: value}, the values
    the startup program drew) after three calls of `steps` optimizer steps."""
    main, startup, loss, total = _build(recompute, **build)
    fetch = [loss] + ([total] if total is not None else [])
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if init is None:
            init = {n: np.asarray(scope.get(n)) for n in scope.names()}
        for n, v in init.items():
            scope.set(n, v)
        scope._rng_counter = 0
        if parallel:
            pexe = fluid.ParallelExecutor(main_program=main,
                                          loss_name=loss.name)
            assert pexe.device_count == 8

            def run(feed):
                return pexe.run(fetch_list=fetch, feed=feed)
        else:
            def run(feed):
                return exe.run(main, feed=feed, fetch_list=fetch, steps=steps)
        rows = [[np.ravel(v) for v in run(feed)] for feed in _batches()]
        found = {p.name: np.asarray(scope.get(p.name))
                 for p in main.global_block().all_parameters()}
    found["loss"] = np.concatenate([row[0] for row in rows])
    found["sum"] = np.array([row[1:] for row in rows])
    return found, init


def _same(got, want, rtol, atol):
    assert set(got) == set(want) and len(got) == 8 + 2   # 8 parameters
    assert np.isfinite(want["loss"]).all() and want["loss"][0] > 0
    for name in sorted(want):
        np.testing.assert_allclose(got[name], want[name], rtol=rtol,
                                   atol=atol, err_msg=name)


# --- (a) dropout in the body: the replay draws the forward's masks ----------

@pytest.mark.parametrize("trips,seed", [(2, 0), (4, 0), (2, 11)],
                         ids=["k2", "k4", "k2_op_seed"])
def test_recomputing_loop_with_dropout_trains_as_the_kept_one(trips, seed):
    """A wrong mask on the replay moves the trajectory by whole percents,
    not by a rounding: the two programs differ only in what the backward
    scan reads (kept) or runs again (replayed)."""
    kept, init = _train(False, trips=trips, seed=seed)
    got, _ = _train(True, init=init, trips=trips, seed=seed)
    _same(got, kept, rtol=2e-6, atol=1e-7)
    # ... and the masks matter: under other masks the losses are others
    assert abs(kept["loss"][0] - _train(
        False, init=init, trips=trips, seed=seed + 1)[0]["loss"][0]) > 1e-6


# --- (b) a top-level While beside the loop -----------------------------------

def test_recomputing_loop_beside_a_top_level_while_matches_kept():
    """A While reads enclosing variables through a copy of the env that its
    input list does not name; the loop op beside it recomputes all the
    same, and the While's sum is the same sum."""
    kept, init = _train(False, with_while=True)
    got, _ = _train(True, init=init, with_while=True)
    assert np.ravel(kept["sum"]).size == 3 and np.all(
        np.ravel(kept["sum"]) != 0)
    _same(got, kept, rtol=2e-6, atol=1e-7)


# --- (c) data-parallel on the eight CPU devices ------------------------------

@pytest.mark.parametrize("amp", [False, True], ids=["fp32", "amp"])
def test_recomputing_loop_under_parallel_executor_matches_single(amp):
    """The recomputing program, batch 16 over eight devices, against the
    same program on one Executor: the checkpointed scan shards like any
    other op. bfloat16 matmuls round by the shard, so the mixed-precision
    case is close, not equal."""
    single, init = _train(True, amp=amp)
    par, _ = _train(True, parallel=True, init=init, amp=amp)
    if amp:
        _same(par, single, rtol=3e-2, atol=3e-3)
    else:
        _same(par, single, rtol=2e-5, atol=1e-6)


# --- (d) the recomputing step runs the body's matmuls again -----------------

CHECKPOINT = ("checkpoint", "remat", "remat2")     # jax.checkpoint's equation


def _count(jaxpr, names, inside=()):
    """How many equations of `names` `jaxpr` holds, sub-jaxprs included,
    under an equation of `inside` only where that is given."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names and not inside
        for inner in jax.core.jaxprs_in_params(eqn.params):
            n += _count(inner, names,
                        () if eqn.primitive.name in inside else inside)
    return n


def test_recomputing_step_holds_more_matmuls_in_its_backward_scan():
    """The backward scan of the recomputing step holds the body's two
    matmuls beside their four transposes, under jax.checkpoint's equation;
    the kept step's holds the four. (Whole jaxprs do not tell the two
    apart: the kept loop's grad op replays the forward scan under jax.vjp,
    a copy that XLA merges with the forward op's and that the recomputing
    loop, which keeps its linearization, does not write.)"""
    def dots_a_scan(recompute):
        main, startup, loss, _ = _build(recompute)
        rw, ro, out = lowering.analyze_state(main, ["x", "y"], [loss.name])
        fn = lowering.build_program_fn(main, ["x", "y"], [loss.name], rw, ro,
                                       out)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            vals = {n: np.asarray(scope.get(n)) for n in set(rw) | set(ro)}
        feed = _batches(1)[0]
        jaxpr = jax.make_jaxpr(lambda f, a, b: fn(f, a, b, 0))(
            [feed["x"], feed["y"]], [vals[n] for n in rw],
            [vals[n] for n in ro]).jaxpr
        scans = [eqn.params["jaxpr"].jaxpr for eqn in jaxpr.eqns
                 if eqn.primitive.name == "scan"]
        return (sorted(_count(scan, ("dot_general",)) for scan in scans),
                _count(jaxpr, ("dot_general",), inside=CHECKPOINT),
                _count(jaxpr, CHECKPOINT))

    # forward op, the grad op's replay of it, the backward scan
    assert dots_a_scan(False) == ([2, 2, 4], 0, 0)
    # forward op, the backward scan: all six under the one checkpoint
    assert dots_a_scan(True) == ([2, 6], 6, 1)


# --- (e) K steps in one dispatch ---------------------------------------------

@pytest.mark.parametrize("unroll", ["0", "1"], ids=["scanned", "unrolled"])
def test_recomputing_loop_in_a_multi_step_dispatch(unroll, monkeypatch):
    """Executor.run(steps=2): the loop op inside the K-step scan (a scan
    in a scan) and inside its unrolled form, three calls of two steps."""
    monkeypatch.setenv("FLAGS_multistep_unroll", unroll)
    kept, init = _train(False, steps=2)
    got, _ = _train(True, steps=2, init=init)
    assert kept["loss"].shape == (6,)
    _same(got, kept, rtol=2e-6, atol=1e-7)


# --- (f) what the program's counter says -------------------------------------

def test_counter_books_the_body_forward_and_replayed_a_trip():
    """ptpu_remat_ops_total: the body's two `mul`s forward and replayed, a
    trip each, and the block's other two forward; a program that keeps its
    activations books nothing."""
    def counted():
        return [REGISTRY.counter("ptpu_remat_ops_total").value(
            kind=kind, op="mul") for kind in ("forward", "replayed")]

    def lower(recompute, trips):
        before = counted()
        main, startup, loss, _ = _build(recompute, trips=trips)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=_batches(1)[0], fetch_list=[loss])
        return [after - was for after, was in zip(counted(), before)]

    assert lower(False, 4) == [0, 0]
    assert lower(True, 4) == [2 * 4 + 2, 2 * 4]


def test_the_memory_transpiler_is_the_references_two_names():
    """Nothing there switches recomputation on: a name that did nothing
    would tell a caller that their program recomputes."""
    from paddle_tpu import memory_optimization_transpiler as transpiler
    assert transpiler.__all__ == ["memory_optimize", "release_memory"]
    assert not [n for n in vars(transpiler) if "remat" in n.lower()]
