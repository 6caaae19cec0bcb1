"""Profiler event table (sorted_key contract) + layers.data batch-dim parity
+ v2 layer shim details."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler


def _run_small_program(n_steps=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(n_steps):
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[y])


def test_profiler_records_per_entry_stats(capsys):
    profiler.reset_profiler()
    with profiler.profiler(sorted_key="total"):
        _run_small_program(n_steps=4)
    out = capsys.readouterr().out
    assert "Calls" in out and "Compile(s)" in out
    report = profiler.profile_report(sorted_key="calls")
    # the training program entry ran 4 times; startup ran once each
    # 11 numeric columns after the (possibly space-containing) tag; the
    # "compile cache:" / "host syncs:" / "embedding:" / "softmax_xent:" /
    # "recompute:" / "kernel bodies traced" / "device time by fluid op:"
    # footers are summaries, not rows
    # (the last four a kind of lookup_table, of softmax_with_cross_entropy,
    # a program that recomputes and a kernel entry that this process has
    # lowered or traced), and the
    # "Lowering(s) by op type" block after them is its own table
    entries = report[:report.index("Lowering(s) by op type")]
    counts = sorted(int(line.split()[-11]) for line in
                    entries.splitlines()[1:]
                    if not line.startswith(("compile cache:", "host syncs:",
                                            "embedding:", "softmax_xent:",
                                            "recompute:",
                                            "kernel bodies traced",
                                            "device time by fluid op:")))
    assert counts[-1] == 4, report
    with pytest.raises(ValueError, match="sorted_key"):
        profiler.profile_report(sorted_key="bogus")
    with pytest.raises(ValueError, match="sorted_key"):
        # invalid key fails BEFORE the workload runs, not in the finally
        with profiler.profiler(sorted_key="avg"):
            raise AssertionError("body must not run")
    profiler.reset_profiler()
    assert profiler.profile_report().count("\n") == 0  # header only


def test_profiler_records_parallel_executor_runs():
    profiler.reset_profiler()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        c = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(c)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = fluid.ParallelExecutor(main_program=main, loss_name=c.name)
        with profiler.profiler():
            for _ in range(3):
                pexe.run(feed={"x": np.ones((8, 4), "f"),
                               "y": np.ones((8, 1), "f")},
                         fetch_list=[c])
    report = profiler.profile_report(sorted_key="calls")
    assert "pexe_program" in report
    profiler.reset_profiler()


def test_data_batch_dim_reference_semantics():
    """Parity: reference layers/io.py:67-75 — None becomes -1 and, like any
    explicit negative dim, disables batch-dim prepending."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        plain = fluid.layers.data(name="a", shape=[3, 4], dtype="float32")
        with_none = fluid.layers.data(name="b", shape=[None, 4],
                                      dtype="float32")
        with_neg = fluid.layers.data(name="c", shape=[3, -1],
                                     dtype="float32")
        no_batch = fluid.layers.data(name="d", shape=[3, 4],
                                     dtype="float32",
                                     append_batch_size=False)
    assert tuple(plain.shape) == (-1, 3, 4)
    assert tuple(with_none.shape) == (-1, 4)   # no second batch dim
    assert tuple(with_neg.shape) == (3, -1)
    assert tuple(no_batch.shape) == (3, 4)


def test_send_recv_layer_markers():
    """layers.Send/Recv (reference layers/io.py:179,207): placement markers
    that round-trip through the executor as no-ops over device-resident
    sharded state."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        out = fluid.layers.fc(input=x, size=2,
                              param_attr=fluid.ParamAttr(name="sr_w"))
        g = main.global_block()
        fluid.layers.Send("ps0:6174,ps1:6174", [g.var("sr_w")])
        fluid.layers.Recv("ps0:6174,ps1:6174", [g.var("sr_w")])
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        got, = exe.run(main, feed={"x": np.ones((3, 4), "float32")},
                       fetch_list=[out])
    assert np.asarray(got).shape == (3, 2)
    types = [op.type for op in main.global_block().ops]
    assert "send" in types and "recv" in types


def test_v2_fc_name_passthrough():
    import paddle_tpu.v2 as paddle
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = paddle.layer.data(name="x",
                              type=paddle.data_type.dense_vector(4))
        out = paddle.layer.fc(input=x, size=2, name="my_fc")
    assert "my_fc" in out.name


def test_v2_embedding_requires_integer_data_type():
    import paddle_tpu.v2 as paddle
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        dense = paddle.layer.data(name="x",
                                  type=paddle.data_type.dense_vector(4))
        with pytest.raises(ValueError, match="integer_value"):
            paddle.layer.embedding(input=dense, size=8)