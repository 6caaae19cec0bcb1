"""Aux subsystems: evaluators, WeightedAverage, debugger printer,
memory_optimize liveness.

Parity: reference tests/unittests/{test_fluid_evaluator-era usage,
test_memory_optimization_transpiler.py, debuger usage}.
"""
import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.lod import LoDTensor


def _mlp_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=pred, label=label))
        acc_eval = fluid.evaluator.Accuracy(input=pred, label=label)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, pred, loss, acc_eval


def test_accuracy_evaluator_accumulates():
    main, startup, pred, loss, acc_eval = _mlp_program()
    rng = np.random.RandomState(0)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        acc_eval.reset(exe)
        seen, correct_manual = 0, None
        for i in range(5):
            xs = rng.rand(16, 8).astype("f")
            ys = rng.randint(0, 4, (16, 1)).astype("int64")
            exe.run(main, feed={"x": xs, "label": ys},
                    fetch_list=[loss])
            seen += 16
        acc = acc_eval.eval(exe)
        assert 0.0 <= float(acc[0]) <= 1.0
        # states really accumulated across the 5 batches
        total = scope.find_var(acc_eval.total.name).get_tensor()
        assert int(np.ravel(total)[0]) == seen
        # reset zeroes the states
        acc_eval.reset(exe)
        total = scope.find_var(acc_eval.total.name).get_tensor()
        assert int(np.ravel(total)[0]) == 0


def test_edit_distance_evaluator():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        hyp = fluid.layers.data(name="hyp", shape=[1], dtype="int64",
                                lod_level=1)
        ref = fluid.layers.data(name="ref", shape=[1], dtype="int64",
                                lod_level=1)
        ed_eval = fluid.evaluator.EditDistance(input=hyp, label=ref)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ed_eval.reset(exe)
        h = [np.array([[1], [2], [3]], "int64"), np.array([[4]], "int64")]
        r = [np.array([[1], [2], [9]], "int64"), np.array([[4]], "int64")]
        exe.run(main, feed={"hyp": LoDTensor.from_sequences(h),
                            "ref": LoDTensor.from_sequences(r)},
                fetch_list=[ed_eval.metrics[0]])
        dist, inst_err = ed_eval.eval(exe)
    # seq0: 1 sub / len 3; seq1 exact -> avg = (1/3 + 0)/2
    np.testing.assert_allclose(dist[0], (1 / 3) / 2, rtol=1e-5)
    np.testing.assert_allclose(inst_err[0], 0.5, rtol=1e-6)


def test_weighted_average():
    wa = fluid.WeightedAverage()
    wa.add(1.0, 1)
    wa.add(3.0, 3)
    np.testing.assert_allclose(wa.eval(), 10.0 / 4)
    wa.reset()
    wa.add(2.0, 5)
    np.testing.assert_allclose(wa.eval(), 2.0)


def test_detection_map_metric():
    m = fluid.metrics.DetectionMAP(overlap_threshold=0.5)
    # one image, one gt of class 1, one perfect det + one false positive
    nmsed = np.array([[[1, 0.9, 0.1, 0.1, 0.5, 0.5],
                       [1, 0.8, 0.6, 0.6, 0.9, 0.9]]], "f")
    m.update(nmsed, [2], [np.array([[0.1, 0.1, 0.5, 0.5]])],
             [np.array([1])])
    ap = m.eval()
    # P-R: [1/1, 1/2] at recalls [1, 1] -> integral AP = 1.0
    np.testing.assert_allclose(ap, 1.0, rtol=1e-6)
    # miss the gt entirely -> AP 0
    m.reset()
    m.update(nmsed, [1], [np.array([[0.6, 0.1, 0.9, 0.4]])],
             [np.array([1])])
    assert m.eval() == 0.0


def test_debugger_printer_and_graphviz(tmp_path):
    main, startup, pred, loss, _ = _mlp_program()
    code = fluid.debuger.pprint_program_codes(main)
    assert "mul" in code and "softmax" in code and "block_0" in code
    dot = fluid.debuger.draw_block_graphviz(
        main.global_block(), path=str(tmp_path / "g.dot"))
    text = open(dot).read()
    assert "digraph G" in text and "mul" in text


def test_memory_optimize_report_and_release_memory():
    main, startup, pred, loss, _ = _mlp_program()
    report = fluid.memory_optimize(main)
    assert isinstance(report, list)
    assert fluid.release_memory(main) is main


def test_fetch_param_from_startup_program():
    """Fetching a var the program itself writes must not demand prior
    scope initialization (regression: fetch-as-read ordering)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=2)
    w_name = main.global_block().all_parameters()[0].name
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        w, = exe.run(startup, fetch_list=[w_name])
    assert np.asarray(w).shape == (4, 2)


def test_unique_name_generate_switch_guard():
    """Parity with the reference's test_unique_name.py: generate()
    produces distinct monotonically-suffixed names per key, switch()
    swaps the generator state, and guard() restores it."""
    from paddle_tpu import unique_name
    with unique_name.guard():
        a0 = unique_name.generate("fc")
        a1 = unique_name.generate("fc")
        b0 = unique_name.generate("conv")
        assert a0 != a1 and a0.startswith("fc") and b0.startswith("conv")
        old = unique_name.switch()          # fresh generator
        f0 = unique_name.generate("fc")
        assert f0 == a0                     # counters restarted
        unique_name.switch(old)             # back to the first generator
        a2 = unique_name.generate("fc")
        assert a2 not in (a0, a1)
    with unique_name.guard():
        assert unique_name.generate("fc") == a0  # guard isolates state


def test_default_scope_funcs_stack_and_lookup():
    """Parity with the reference's test_default_scope_funcs.py: the
    thread-local scope stack, ancestor lookup, and scoped_function."""
    from paddle_tpu import default_scope_funcs as dsf
    base = dsf.get_cur_scope()
    dsf.var("outer_v")
    dsf.enter_local_scope()
    try:
        assert dsf.get_cur_scope() is not base
        assert dsf.find_var("outer_v") is not None   # ancestor lookup
        dsf.var("inner_v")
        assert dsf.find_var("inner_v") is not None
    finally:
        dsf.leave_local_scope()
    assert dsf.get_cur_scope() is base
    assert dsf.find_var("outer_v") is not None

    seen = {}
    def body():
        dsf.var("scoped_v")
        seen["inside"] = dsf.find_var("scoped_v") is not None
    dsf.scoped_function(body)
    assert seen["inside"]
