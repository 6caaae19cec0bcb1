"""Every donated state buffer goes to its own variable's new value.

The executors donate state_rw to the jitted step (lowering.jit_step). jax
knows no names: walking the step's flattened results in order, it gives each
the first free donated argument of its shape and dtype, and writes the pair
into the lowered module as `tf.aliasing_output` on the argument. A result
that takes another variable's buffer makes XLA copy: the new value must be
kept out of the way of a buffer that is still being read. So the order of
the results is a contract (lowering.analyze_state, lowering.jit_step), and
this file reads what jax wrote:

  * every case's lowered step, as the executor built it, aliases each
    state_rw argument to the result of its own name, and
    lowering.donation_pairing (the rule as the program's counter applies it)
    says what the lowered text says, so it cannot drift from jax unseen;
  * the order of results changes no arithmetic and the write-back goes by
    name: five steps through the Executor leave every persistable and the
    loss bit for bit what the step in the orders before PR 56 (the
    arguments by first read, the results by first write, the fetches ahead
    of the state) leaves in the same process, and at
    rounding what that tree itself left, by name, on the machine where
    recorded_donation_pairing.json was made (sums, not bits: XLA:CPU's bits
    follow the host's vector unit, and a recording must hold elsewhere).

`python tests/unittests/test_donation_pairing.py <file>` writes the recording
with whatever tree PYTHONPATH names.
"""
import itertools
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
RECORDING = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "recorded_donation_pairing.json")
COUNTER = "ptpu_donated_state_buffers_total"


# ---------------------------------------------------------- the programs --
def _fc_stack(fluid, extra=None):
    """Four fc layers under Adam: three [16, 16] weights and three [16]
    biases, each with two moments, so every shape class has several
    members; the last bias is [1] float32, the loss's shape and dtype."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for _ in range(3):
            h = fluid.layers.fc(input=h, size=16, act="tanh")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fetches = [loss]
        if extra == "write_only":
            # a persistable of a weight's shape that the step writes and
            # never reads: it has no buffer of its own to take
            kept = fluid.layers.create_global_var(
                shape=[16, 16], value=0.0, dtype="float32",
                persistable=True, name="kept_activation_product")
            fluid.layers.assign(
                fluid.layers.matmul(h, h, transpose_x=True), output=kept)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        if extra == "fetch":
            # a weight's gradient: the shape and dtype of three weights
            # and six moments
            fetches.append(main.global_block().var("fc_1.w_0@GRAD"))
    rng = np.random.RandomState(11)
    feed = {"x": rng.rand(8, 16).astype("float32"),
            "y": rng.rand(8, 1).astype("float32")}
    return main, startup, feed, fetches


def _tiny_glm(fluid):
    """The GLM-4.7-Flash-shaped toy of the benchmark's rehearsal, built as
    test_benchmark_cells.py builds a cell."""
    import jax
    from benchmark import manifest
    cell = manifest.load_cell(
        os.path.join(REPO, "benchmark", "tests", "tiny_glm_4_7_flash",
                     "manifest.json"), "tiny_glm_4_7_flash_t64")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fetches = cell.config_module.build(fluid, cell.config, cell.traffic)
    batch = cell.config_module.make_batch(cell.config, cell.traffic,
                                          jax.random.key(5))
    feed = {n: np.asarray(v) for n, v in batch.items()}
    return main, startup, feed, [fetches["loss"]]


# ------------------------------------------------- what jax wrote, by name --
def _lowered_pairs(lowered, state_rw, state_out, state_at=0):
    """{state_rw name: "own" | "other" | "none"} as `tf.aliasing_output` in
    @main's signature has it. jax names each argument's place in the call
    (`loc("state_rw_vals[3]")`) and each result's in the return
    (`jax.result_info = "result[0][3]"`: the step returns the state first,
    `state_at` 0; the step before PR 56 returned it second)."""
    text = lowered.as_text(debug_info=True)
    line = next(l for l in text.splitlines() if "func.func public @main(" in l)
    args, results = line.split(") -> (", 1)
    result_names = []
    for info in re.findall(r'jax\.result_info = "([^"]*)"', results):
        m = re.fullmatch(r"result\[%d\]\[(\d+)\]" % state_at, info)
        result_names.append(state_out[int(m.group(1))] if m else None)
    pairs = {}
    for chunk in re.split(r"(?=%arg\d+: )", args):
        where = re.search(r'loc\("state_rw_vals\[(\d+)\]"\)', chunk)
        if not where:
            continue
        name = state_rw[int(where.group(1))]
        alias = re.search(r"tf\.aliasing_output = (\d+)", chunk)
        if alias is None:
            pairs[name] = "none"
        else:
            taker = result_names[int(alias.group(1))]
            pairs[name] = "own" if taker == name else "other"
    return pairs


def _helper_pairs(lowered, state_rw, state_out, state_at=0):
    """The same, as lowering.donation_pairing computes it from the lowered
    step's own argument and result types."""
    import jax
    from paddle_tpu.core import lowering
    (_, rw_info, _, _), _ = lowered.args_info
    results = []
    for at, part in enumerate(lowered.out_info):
        results += _typed(state_out, part) if at == state_at else _typed(
            itertools.repeat(None), jax.tree_util.tree_leaves(part))
    return lowering.donation_pairing(_typed(state_rw, rw_info), results)


def _typed(names, infos):
    return [(n, i.shape, i.dtype) for n, i in zip(names, infos)]


def _counter():
    from paddle_tpu.observability.registry import REGISTRY
    c = REGISTRY.counter(COUNTER)
    return {how: c.value(paired=how) for how in ("own", "other", "none")}


def _run_and_lower(fluid, build, executor, steps, monkeypatch):
    """One call through the executor; (lowered step, state_rw, state_out,
    what the counter booked for the compile)."""
    import jax
    if steps > 1:
        monkeypatch.setenv("FLAGS_multistep_unroll",
                           "1" if executor == "unrolled" else "0")
    main, startup, feed, fetches = build(fluid)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        before = _counter()
        kw = {"steps": steps} if steps > 1 else {}
        if executor == "parallel":
            runner = fluid.ParallelExecutor(main_program=main,
                                            loss_name=fetches[0].name)
            runner.run([v.name for v in fetches], feed=feed, **kw)
        else:
            runner = exe
            exe.run(main, feed=feed, fetch_list=fetches, **kw)
        booked = {how: n - before[how] for how, n in _counter().items()}
        (jitted, rw, ro, out), = [
            entry for key, entry in runner._cache.items()
            if key[0] == main._uid]

        def avals(names):
            return [jax.ShapeDtypeStruct(np.shape(scope.get(n)),
                                         scope.get(n).dtype) for n in names]
        lowered = jitted.lower(
            [jax.ShapeDtypeStruct(feed[n].shape, feed[n].dtype)
             for n in sorted(feed)],
            avals(rw), avals(ro), jax.ShapeDtypeStruct((), np.uint32))
    return lowered, rw, out, booked


CASES = {
    "a_fc_adam_executor": (_fc_stack, "executor", 1),
    "b_fc_adam_steps4_scanned": (_fc_stack, "scanned", 4),
    "b_fc_adam_steps4_unrolled": (_fc_stack, "unrolled", 4),
    "c_fc_adam_parallel_executor": (_fc_stack, "parallel", 1),
    "c_fc_adam_parallel_executor_steps4": (_fc_stack, "parallel", 4),
    "d_fetch_of_a_weights_shape": (
        lambda fluid: _fc_stack(fluid, "fetch"), "executor", 1),
    "e_write_only_of_a_weights_shape": (
        lambda fluid: _fc_stack(fluid, "write_only"), "executor", 1),
    "f_tiny_glm_4_7_flash": (_tiny_glm, "executor", 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_donated_buffer_goes_to_its_own_new_value(case, monkeypatch):
    import paddle_tpu as fluid
    build, executor, steps = CASES[case]
    lowered, rw, out, booked = _run_and_lower(fluid, build, executor, steps,
                                              monkeypatch)
    assert len(rw) >= 26 and list(out[:len(rw)]) == list(rw)
    if case.startswith("e_"):
        assert "kept_activation_product" in out[len(rw):]
    written = _lowered_pairs(lowered, rw, out)
    assert sorted(written) == sorted(rw)
    assert {n: how for n, how in written.items() if how != "own"} == {}
    assert _helper_pairs(lowered, rw, out) == written
    assert booked == {"own": len(rw), "other": 0, "none": 0}


# ------------------------------------- the rule itself, on hand-made lists --
F32 = "float32"


@pytest.mark.parametrize("donated,results,want", [
    # results in the arguments' order: each takes its own
    ([("w", (4, 4), F32), ("m", (4, 4), F32)],
     [("w", (4, 4), F32), ("m", (4, 4), F32)],
     {"w": "own", "m": "own"}),
    # arguments by first read, results by first write, as before PR 56: a
    # swap inside the class
    ([("w0", (4, 4), F32), ("w1", (4, 4), F32), ("m0", (4, 4), F32)],
     [("w0", (4, 4), F32), ("m0", (4, 4), F32), ("w1", (4, 4), F32)],
     {"w0": "own", "w1": "other", "m0": "other"}),
    # a fetch ahead of the state takes the bias's buffer
    ([("b", (1,), F32)], [(None, (1,), F32), ("b", (1,), F32)],
     {"b": "other"}),
    # behind the state it finds none left
    ([("b", (1,), F32)], [("b", (1,), F32), (None, (1,), F32)],
     {"b": "own"}),
    # a new value of another dtype cannot alias; it shifts its new class
    ([("c", (1,), "int32"), ("b", (1,), F32)],
     [("c", (1,), F32), ("b", (1,), F32)],
     {"c": "none", "b": "other"}),
    # a dtype is compared as a dtype, however it is spelled
    ([("h", (2,), "bfloat16")], [("h", (2,), np.dtype("float16"))],
     {"h": "none"}),
], ids=["in_order", "first_write_order", "fetch_first", "fetch_last",
        "dtype_changed", "dtype_differs"])
def test_donation_pairing_is_first_come_first_served_by_class(
        donated, results, want):
    from paddle_tpu.core import lowering
    assert lowering.donation_pairing(donated, results) == want


def test_the_order_before_pr_56_mispairs_and_the_rule_says_so():
    """The parent's step (arguments by first read, results by first write,
    fetches ahead of the state), lowered here: jax aliases 9 of the 26
    donated buffers of the fc stack to their own new value, and
    donation_pairing says the same."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core import lowering
    main, startup, feed, fetches = _fc_stack(fluid)
    names = sorted(feed)
    rw, ro, out = _orders_before_pr_56(main, names, [fetches[0].name])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        lowered = _jit_before_pr_56(
            main, names, [fetches[0].name], rw, ro, out).lower(
            [feed[n] for n in names], [scope.get(n) for n in rw],
            [scope.get(n) for n in ro], np.uint32(0))
    written = _lowered_pairs(lowered, rw, out, state_at=1)
    assert (list(written.values()).count("own"), len(written)) == (9, 26)
    assert _helper_pairs(lowered, rw, out, state_at=1) == written


# ----------------------------------------------- the numbers did not change --
def _orders_before_pr_56(program, feed_names, fetch_names):
    """(state_rw, state_ro, state_out) as analyze_state returned them before
    PR 56: state_rw in order of first read, state_out of first write."""
    from paddle_tpu.core import lowering
    rw, ro, out = lowering.analyze_state(program, feed_names, fetch_names)
    first_read, first_write = [], []
    for op in lowering._all_ops(program):
        for n in op.all_input_vars():
            if n in rw and n not in first_read:
                first_read.append(n)
        for n in op.all_output_vars():
            if n in out and n not in first_write:
                first_write.append(n)
    assert sorted(first_read) == sorted(rw) and sorted(first_write) == sorted(
        out)
    return first_read, ro, first_write


def _jit_before_pr_56(program, feed_names, fetch_names, rw, ro, out):
    """The step as the parent's executor jitted it: (fetches, new_state,
    errors), the fetches ahead of the state, state_rw donated."""
    import jax
    from paddle_tpu.core import lowering
    fn = lowering.build_program_fn(program, feed_names, fetch_names, rw, ro,
                                   out, collect_errors=True)

    def fetches_first(feed_vals, state_rw_vals, state_ro_vals, seed):
        new_state, fetches, errors = fn(feed_vals, state_rw_vals,
                                        state_ro_vals, seed)
        return fetches, new_state, errors
    return jax.jit(fetches_first, donate_argnums=(1,))


def _five_steps(fluid, build):
    """({persistable name: value}, the five losses) after five steps through
    the Executor, and the scope's values and seed cursor before the first."""
    main, startup, feed, fetches = build(fluid)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        names = sorted(v.name for v in main.list_vars() if v.persistable
                       and scope.get(v.name) is not None)
        start = {n: np.array(scope.get(n)) for n in names}
        seed = scope.seed_state()
        losses = [np.asarray(exe.run(main, feed=feed,
                                     fetch_list=fetches[:1])[0])
                  for _ in range(5)]
        state = {n: np.asarray(scope.get(n)) for n in names}
    return (main, feed, fetches[0].name), start, seed, state, losses


def _five_steps_in_the_old_order(program, feed, loss_name, start, seed):
    """The same five steps by hand, as the parent's executor ran them:
    state_rw in order of first read, state_out of first write, (fetches,
    new_state, errors) out of the jit, state_rw donated, the write-back by
    name."""
    import jax
    from paddle_tpu.core import lowering
    names = sorted(feed)
    rw, ro, out = _orders_before_pr_56(program, names, [loss_name])
    assert out[:len(rw)] != rw, "the old order is the new one: no test"
    jitted = _jit_before_pr_56(program, names, [loss_name], rw, ro, out)
    state, losses = dict(start), []
    for i in range(5):
        fetched, new_state, _ = jitted(
            [feed[n] for n in names],
            [jax.numpy.array(state[n]) for n in rw],
            [state[n] for n in ro], np.uint32(seed + 1 + i))
        state.update(zip(out, map(np.asarray, new_state)))
        losses.append(np.asarray(fetched[0]))
    return state, losses


def _bits(x):
    x = np.ascontiguousarray(x)
    return x.view(np.uint8).tobytes(), x.shape, str(x.dtype)


NUMBERS = {"fc_adam": _fc_stack, "tiny_glm_4_7_flash": _tiny_glm}


@pytest.mark.parametrize("which", sorted(NUMBERS))
def test_five_steps_leave_the_numbers_of_the_old_order(which):
    import paddle_tpu as fluid
    (main, feed, loss_name), start, seed, state, losses = _five_steps(
        fluid, NUMBERS[which])
    old_state, old_losses = _five_steps_in_the_old_order(
        main, feed, loss_name, start, seed)
    assert not np.array_equal(losses[0], losses[4]), "nothing trained"
    assert sorted(state) == sorted(old_state)
    moved = [n for n in state if _bits(state[n]) != _bits(start[n])]
    assert len(moved) >= 26
    assert [n for n in state if _bits(state[n]) != _bits(old_state[n])] == []
    assert [_bits(l) for l in losses] == [_bits(l) for l in old_losses]

    # what the tree before PR 56 left, by name
    with open(RECORDING) as f:
        rec = json.load(f)[which]
    now = _summary(state, losses)
    assert sorted(now) == sorted(rec)
    np.testing.assert_allclose(now["__losses__"], rec["__losses__"],
                               rtol=1e-3)
    for n in state:
        assert (now[n]["shape"], now[n]["dtype"]) == (
            rec[n]["shape"], rec[n]["dtype"]), n
        scale = rec[n]["abs_sum"]
        np.testing.assert_allclose(now[n]["abs_sum"], scale, rtol=1e-3,
                                   err_msg=n)
        np.testing.assert_allclose(now[n]["sum"], rec[n]["sum"], rtol=0,
                                   atol=1e-3 * scale + 1e-9, err_msg=n)


def _summary(state, losses):
    """What is recorded of a run: the losses, and of each persistable its
    shape, dtype, sum and sum of magnitudes (float64)."""
    out = {"__losses__": [float(l) for l in np.ravel(losses)]}
    for n, v in state.items():
        v64 = np.asarray(v, np.float64)
        out[n] = {"shape": list(v.shape), "dtype": str(v.dtype),
                  "sum": float(v64.sum()), "abs_sum": float(abs(v64).sum())}
    return out


if __name__ == "__main__":
    import paddle_tpu as fluid
    recording = {}
    for which, build in sorted(NUMBERS.items()):
        _, _, _, state, losses = _five_steps(fluid, build)
        recording[which] = _summary(state, losses)
        print(which, len(state), "persistables", np.ravel(losses))
    with open(sys.argv[1], "w") as f:
        json.dump(recording, f, indent=0, sort_keys=True)
