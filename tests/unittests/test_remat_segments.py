"""Segment-level rematerialization (core/lowering._lower_block_remat).

The reference has no remat counterpart (its memory optimizer reuses
buffers); this is the TPU-native activation-checkpointing lever
(SURVEY §2 aux). Checks: (1) numerics are IDENTICAL with remat on/off —
including through dropout, which proves the recompute replays the
forward's exact counter-derived RNG keys; (2) the lowered jaxpr really
contains duplicated forward compute behind optimization_barrier (i.e.
the flag does something); (3) training convergence is unaffected.
"""
import numpy as np

import jax
import paddle_tpu as fluid
from paddle_tpu.core import lowering

rng = np.random.RandomState(5)


def _conv_net():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 12, 12],
                                dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
        h = img
        for _ in range(3):  # enough forward ops to cross the remat gate
            h = fluid.layers.conv2d(input=h, num_filters=6, filter_size=3,
                                    padding=1, act="relu")
            h = fluid.layers.batch_norm(input=h)
        h = fluid.layers.dropout(h, dropout_prob=0.3, seed=11)
        pred = fluid.layers.fc(input=h, size=5, act="softmax")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=pred, label=lab))
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9) \
            .minimize(loss)
    return main, startup, loss


def _train(remat, steps=4):
    main, startup, loss = _conv_net()
    if remat:
        fluid.memory_optimization_transpiler.enable_rematerialization(main)
    r = np.random.RandomState(2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    out = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(steps):
            xs = r.rand(8, 1, 12, 12).astype("f")
            ys = r.randint(0, 5, (8, 1)).astype("int64")
            l, = exe.run(main, feed={"img": xs, "lab": ys},
                         fetch_list=[loss])
            out.append(float(np.ravel(l)[0]))
    return out


def test_remat_numerics_identical_incl_dropout():
    base = _train(False)
    remat = _train(True)
    # same program, same seeds: remat must not change the training
    # trajectory (dropout masks replay via counter-derived keys). On
    # XLA:CPU the optimization_barrier changes which ops fuse, so the
    # replayed segment can round differently by ~1 ulp (measured 4.8e-7
    # on O(1) losses — PR 8 triage; failing at rtol=0 since seed). The
    # RNG-replay claim this test exists for survives at 1-ulp tolerance:
    # a wrong dropout mask diverges the trajectory by whole percents,
    # not 1e-7. Bit-exactness stays asserted off-CPU (TPU keeps fusion
    # decisions stable across the barrier) and under
    # PTPU_STRICT_REMAT_BITS=1.
    import os

    import jax
    strict = (jax.default_backend() != "cpu"
              or os.environ.get("PTPU_STRICT_REMAT_BITS") == "1")
    if strict:
        np.testing.assert_allclose(base, remat, rtol=0, atol=0)
    else:
        np.testing.assert_allclose(base, remat, rtol=3e-7, atol=1e-6)
    assert np.isfinite(base).all()


def test_remat_duplicates_forward_compute():
    """The jaxpr with remat on must hold more conv ops than without
    (backward-side segment replays) plus optimization_barrier guards."""

    def jaxpr_for(remat):
        main, startup, loss = _conv_net()
        if remat:
            fluid.memory_optimization_transpiler \
                .enable_rematerialization(main)
        feed_names = ["img", "lab"]
        state_rw, state_ro, state_out = lowering.analyze_state(
            main, feed_names, [loss.name])
        # state vars need concrete arrays: pull shapes via the startup
        # program on a real executor
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            vals = {n: np.asarray(scope.find_var(n).get_tensor()) for n in
                    set(state_rw) | set(state_ro)}
            fn = lowering.build_program_fn(
                main, feed_names, [loss.name], state_rw, state_ro,
                state_out)
            xs = np.zeros((8, 1, 12, 12), "float32")
            ys = np.zeros((8, 1), "int64")
            return jax.make_jaxpr(
                lambda f, rw, ro: fn(f, rw, ro, 0))(
                    [xs, ys], [vals[n] for n in state_rw],
                    [vals[n] for n in state_ro])

    def count(jaxpr, prim_sub):
        n = 0
        for eqn in jaxpr.jaxpr.eqns:
            if prim_sub in eqn.primitive.name:
                n += 1
        return n

    base = jaxpr_for(False)
    remat = jaxpr_for(True)
    assert count(remat, "conv") > count(base, "conv")
    assert count(remat, "optimization_barrier") > 0
    assert count(base, "optimization_barrier") == 0


def test_remat_counts_forward_and_replayed_ops():
    """ptpu_remat_ops_total: every forward op of a recomputing program once
    (`forward`) and, once more, each op of a segment the backward pass
    replayed; a program that keeps its activations counts nothing."""
    from paddle_tpu.observability.registry import REGISTRY

    def counted(kind, op="conv2d"):
        return REGISTRY.counter("ptpu_remat_ops_total", "").value(
            kind=kind, op=op)

    before = counted("forward"), counted("replayed")
    _train(False, steps=1)
    assert (counted("forward"), counted("replayed")) == before
    _train(True, steps=1)
    assert counted("forward") - before[0] == 3
    assert 1 <= counted("replayed") - before[1] <= 3


def test_remat_with_top_level_while_matches_base():
    """While/conditional_block read enclosing vars via env copies that are
    not op inputs — remat must treat them as barriers, not replay them."""

    def build_and_train(remat):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(input=x, size=6, act="relu")
            h = fluid.layers.fc(input=h, size=6, act="relu")
            h = fluid.layers.fc(input=h, size=6, act="relu")
            # a While accumulating h-sums; reads `h` from enclosing scope
            # (an implicit read the While op's input list does not carry)
            i = fluid.layers.zeros(shape=[1], dtype="int32")
            i.stop_gradient = True
            n = fluid.layers.fill_constant(shape=[1], dtype="int32", value=3)
            s0 = fluid.layers.zeros(shape=[1], dtype="float32")
            s0.stop_gradient = True
            cond = fluid.layers.less_than(x=i, y=n)
            w = fluid.layers.While(cond=cond)
            with w.block():
                fluid.layers.sums(
                    input=[s0, fluid.layers.reduce_sum(h)], out=s0)
                i2 = fluid.layers.increment(i)
                fluid.layers.less_than(x=i2, y=n, cond=cond)
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        if remat:
            fluid.memory_optimization_transpiler \
                .enable_rematerialization(main)
        r = np.random.RandomState(7)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        out = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(3):
                xs = r.rand(8, 6).astype("f")
                ys = r.rand(8, 1).astype("f")
                l, s = exe.run(main, feed={"x": xs, "y": ys},
                               fetch_list=[loss, s0])
                out.append(float(np.ravel(l)[0]))
                out.append(float(np.ravel(s)[0]))
        return out

    np.testing.assert_allclose(build_and_train(False), build_and_train(True),
                               rtol=0, atol=0)


def test_remat_under_parallel_executor_matches_single():
    """Segment remat must compose with GSPMD: an 8-device data-parallel
    run of a remat-enabled conv program matches the remat-enabled
    single-device run exactly (barrier'd segment replays shard like any
    other op)."""
    import paddle_tpu as pfluid

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[1, 10, 10],
                                    dtype="float32")
            lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
            h = img
            for _ in range(3):
                h = fluid.layers.conv2d(input=h, num_filters=4,
                                        filter_size=3, padding=1,
                                        act="relu")
            pred = fluid.layers.fc(input=h, size=4, act="softmax")
            loss = fluid.layers.mean(
                x=fluid.layers.cross_entropy(input=pred, label=lab))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        fluid.memory_optimization_transpiler.enable_rematerialization(main)
        return main, startup, loss

    rng = np.random.RandomState(8)
    xs = rng.rand(16, 1, 10, 10).astype("float32")
    ys = rng.randint(0, 4, (16, 1)).astype("int64")

    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup)
        init = {n: np.asarray(s1.get(n)) for n in s1.names()}
        single = [float(np.ravel(exe.run(main, feed={"img": xs, "lab": ys},
                                         fetch_list=[loss])[0])[0])
                  for _ in range(3)]

    main2, startup2, loss2 = build()
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2)
        for n, v in init.items():
            s2.set(n, v)
        s2._rng_counter = 0
        pexe = pfluid.ParallelExecutor(main_program=main2,
                                       loss_name=loss2.name)
        par = [float(np.ravel(pexe.run(fetch_list=[loss2],
                                       feed={"img": xs, "lab": ys})[0])[0])
               for _ in range(3)]
    np.testing.assert_allclose(single, par, rtol=1e-5, atol=1e-6)


def test_remat_with_mixed_precision_matches_base():
    """The bench remat configs run bf16 AMP — segment replays must apply
    the same AMP casts as the original forward. Unlike fp32 (bit-exact,
    test above), bf16 trajectories are only CLOSE: the replayed segment
    may fuse differently under XLA, so bf16 intermediate rounding can
    differ (the same property jax.checkpoint has in low precision).
    Step 1 must still match closely and the drift stay bf16-sized."""

    def train(remat):
        main, startup, loss = _conv_net()
        main.enable_mixed_precision()
        if remat:
            fluid.memory_optimization_transpiler \
                .enable_rematerialization(main)
        r = np.random.RandomState(12)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        out = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(3):
                xs = r.rand(8, 1, 12, 12).astype("f")
                ys = r.randint(0, 5, (8, 1)).astype("int64")
                l, = exe.run(main, feed={"img": xs, "lab": ys},
                             fetch_list=[loss])
                out.append(float(np.ravel(l)[0]))
        return out

    base = train(False)
    remat = train(True)
    np.testing.assert_allclose(base, remat, rtol=5e-3, atol=1e-3)
    assert np.isfinite(base).all()


def test_segment_len_flag_controls_barrier_count(monkeypatch):
    """FLAGS_remat_segment_len is the round-5 compile-cost tuning knob:
    longer segments -> fewer optimization barriers in the emitted graph
    (the CPU compile probe measured 22/13/4 barriers for seg 8/sqrt/44
    on ResNet-50; this pins the mechanism on the small conv net).
    Numerics stay identical across segment lengths."""

    def barriers_and_loss(seg_len):
        if seg_len:
            monkeypatch.setenv("FLAGS_remat_segment_len", str(seg_len))
        else:
            monkeypatch.delenv("FLAGS_remat_segment_len", raising=False)
        main, startup, loss = _conv_net()
        fluid.memory_optimization_transpiler.enable_rematerialization(main)
        feed_names = ["img", "lab"]
        state_rw, state_ro, state_out = lowering.analyze_state(
            main, feed_names, [loss.name])
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            vals = {n: np.asarray(scope.find_var(n).get_tensor())
                    for n in set(state_rw) | set(state_ro)}
            fn = lowering.build_program_fn(
                main, feed_names, [loss.name], state_rw, state_ro,
                state_out)
        local = np.random.RandomState(77)   # same data for every call
        xs = local.rand(8, 1, 12, 12).astype("float32")
        ys = local.randint(0, 5, (8, 1)).astype("int64")
        args = ([xs, ys], [vals[n] for n in state_rw],
                [vals[n] for n in state_ro])
        jaxpr = jax.make_jaxpr(lambda f, rw, ro: fn(f, rw, ro, 0))(*args)
        n_bar = sum(1 for eqn in jaxpr.jaxpr.eqns
                    if "optimization_barrier" in eqn.primitive.name)
        out = jax.jit(lambda f, rw, ro: fn(f, rw, ro, 0))(*args)
        loss_val = float(np.asarray(out[0][0]).ravel()[0])
        return n_bar, loss_val

    few_bar, few_loss = barriers_and_loss(64)   # one huge segment
    many_bar, many_loss = barriers_and_loss(4)  # minimum segment length
    assert many_bar > few_bar, (many_bar, few_bar)
    np.testing.assert_allclose(few_loss, many_loss, rtol=1e-6)
