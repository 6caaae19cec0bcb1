"""Device time carries the program's own names: a named scope a fluid op
(core/lowering.op_scope / parse_op_scope), a name a Pallas kernel
(ops/pallas_kernels.KERNEL_NAMES), and the profiler's per-op table of a
device trace (profiler.device_op_table)."""
import ast
import collections
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import lowering
from paddle_tpu.ops import pallas_kernels


# --- a scope a fluid op ----------------------------------------------------

def _program(amp=False, loop=False):
    """`loop`: two trips of a recomputing loop op (its body an fc over
    weights it closes over) between the two layers."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        if loop:
            rnn = fluid.layers.StaticRNN(steps=2, recompute=True)
            with rnn.step():
                state = rnn.memory(init=h)
                new = fluid.layers.fc(input=state, size=8, act="relu")
                rnn.update_memory(state, new)
                rnn.output(new)
            h = fluid.layers.reduce_sum(rnn(), dim=1)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    if amp:
        main.enable_mixed_precision()
    return main, startup, loss


def _lowered_op_names(main, startup, loss, steps, unroll, whole=False):
    """The op_name paths of the lowered step (its MLIR locations); with
    `whole`, those of the compiled HLO beside them."""
    feeds = ["x", "y"]
    rw, ro, out = lowering.analyze_state(main, feeds, [loss.name])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        vals = {n: np.asarray(scope.find_var(n).get_tensor())
                for n in set(rw) | set(ro)}
    if steps > 1:
        fn = lowering.lower_multi_step(main, feeds, [loss.name], rw, ro, out,
                                       steps, unroll=unroll)
    else:
        fn = lowering.build_program_fn(main, feeds, [loss.name], rw, ro, out)
    lowered = jax.jit(lambda f, a, b: fn(f, a, b, 0)).lower(
        [np.zeros((4, 13), "float32"), np.zeros((4, 1), "float32")],
        [vals[n] for n in rw], [vals[n] for n in ro])
    names = set(re.findall(r'loc\("([^"]*)"',
                           lowered.as_text(debug_info=True)))
    if whole or steps > 1 and not unroll:  # a scan's body is a call of its own
        names |= set(re.findall(    # there; the compiled HLO has whole paths
            r'op_name="([^"]*)"', lowered.compile().as_text()))
    return names


@pytest.mark.parametrize("amp,loop,steps,unroll", [
    (False, False, 1, False), (True, False, 1, False),
    (False, True, 1, False), (False, False, 2, False),
    (False, False, 2, True), (True, True, 2, False)],
    ids=["plain", "amp", "loop_recompute", "steps2_scan", "steps2_unrolled",
         "amp_loop_recompute_steps2"])
def test_every_fluid_op_of_the_block_lowers_under_its_scope(
        amp, loop, steps, unroll):
    main, startup, loss = _program(amp, loop)
    names = _lowered_op_names(main, startup, loss, steps, unroll, whole=loop)
    got = {lowering.parse_op_scope(n) for n in names} - {None}
    ops = [op for block in main.blocks for op in block.ops]
    want = {}
    for op in ops:
        op_type = (op.attrs["fwd_type"] + "_grad" if op.type == "grad_of"
                   else op.type)
        instance = next(n for v in op.outputs.values() for n in v if n)
        want[(op_type, instance)] = op
    assert {t for t, _ in want} >= {"mul", "mul_grad", "momentum", "mean",
                                    "relu_grad", "fill_constant"}
    assert set(want) == got
    if steps > 1 and not unroll:
        assert any("while/body" in n and lowering.parse_op_scope(n)
                   for n in names)
    if loop:
        # the body's ops, replayed inside the backward scan, each under its
        # own scope inside the loop's grad op's
        body = {lowering.op_scope(op) for op in main.blocks[1].ops}
        assert len(body) == 3 and all(any(
            "/op:rnn_scan_grad/" in n and "/while/body/" in n
            and "/checkpoint/rematted_computation/%s/" % scope in n
            for n in names) for scope in body)


@pytest.mark.parametrize("path,want", [
    ("jit(fn)/op:mul/fc_0.tmp_0/dot_general", ("mul", "fc_0.tmp_0")),
    ("jit(fn)/jit(main)/transpose(jvp(op:scale/tmp_3))/mul",
     ("scale", "tmp_3")),
    ("jit(fn)/op:mul_grad/fc_0.w_0~GRAD/transpose(jvp())/dot_general",
     ("mul_grad", "fc_0.w_0@GRAD")),
    ("jit(fn)/op:relu_grad/a~GRAD/transpose(op:relu_grad/a~GRAD)/jvp()/"
     "select_n", ("relu_grad", "a@GRAD")),
    ("jit(fn)/op:conv2d_grad/w~GRAD/transpose(jvp(op:conv2d_grad/w~GRAD))/"
     "jvp()/checkpoint/rematted_computation/conv_general_dilated",
     ("conv2d_grad", "w@GRAD")),
    ("jit(fn)/while/body/op:sum/x~GRAD/add_any", ("sum", "x@GRAD")),
    ("jit(fn)/op:while/out_1/while/body/op:transpose/tmp_3/transpose",
     ("transpose", "tmp_3")),
    ("jit(fn)/op:mul_grad/a~GRAD/op:reshape/tmp_1/reshape",
     ("reshape", "tmp_1")),
    # a grad op on the linearization its forward op kept: what it transposes
    # was traced under the forward op's scope, and is the grad op's work
    ("jit(fn)/op:layer_norm_grad/x~GRAD/transpose(jvp(op:layer_norm/"
     "ln_0.tmp_2))/mul", ("layer_norm_grad", "x@GRAD")),
    ("jit(fn)/op:fused_attention_grad/q~GRAD/transpose(op:fused_attention/"
     "attn_0.tmp_0)/jvp(op:fused_attention/attn_0.tmp_0)/ptpu_flash_bwd_dkdv/"
     "pallas_call", ("fused_attention_grad", "q@GRAD")),
    ("jit(fn)/while/body/op:softmax_with_cross_entropy_grad/fc~GRAD/"
     "transpose(jvp(op:softmax_with_cross_entropy/loss))/sub",
     ("softmax_with_cross_entropy_grad", "fc@GRAD")),
    # ... and what is not: the forward op itself, a forward op that a
    # replay lowers again inside a grad op, another op's scope
    ("jit(fn)/op:layer_norm/ln_0.tmp_2/jvp(op:layer_norm/ln_0.tmp_2)/"
     "ptpu_layer_norm_fwd/pallas_call", ("layer_norm", "ln_0.tmp_2")),
    ("jit(fn)/op:relu_grad/a~GRAD/op:relu/a/max", ("relu", "a")),
    ("jit(fn)/op:rnn_scan_grad/h~GRAD/transpose(jvp(op:rnn_scan_grad/"
     "h~GRAD))/while/body/op:mul/tmp_1/dot_general", ("mul", "tmp_1")),
    ("jit(fn)/transpose(jvp(mul.4))/dot_general", None),
    ("jit(fn)/transpose/scale/sum/top:k", None),
    ("", None)])
def test_parse_op_scope_finds_the_fluid_op_of_a_path(path, want):
    assert lowering.parse_op_scope(path) == want


@pytest.mark.parametrize("op_type,attrs,outputs,want", [
    ("conv2d", {}, {"Output": ["conv2d_0.tmp_0"]},
     ("conv2d", "conv2d_0.tmp_0")),
    ("grad_of", {"fwd_type": "conv2d"},
     {"InGrad::Input": [""], "InGrad::Filter": ["res2a_branch2a.w_0@GRAD"]},
     ("conv2d_grad", "res2a_branch2a.w_0@GRAD")),
    ("while", {}, {"Out": ["x@GRAD@RENAME@0"]},
     ("while", "x@GRAD@RENAME@0")),
    ("print", {}, {}, ("print", "-"))])
def test_op_scope_and_parse_op_scope_are_inverse(op_type, attrs, outputs,
                                                 want):
    op = types.SimpleNamespace(type=op_type, attrs=attrs, outputs=outputs)
    name = lowering.op_scope(op)
    assert name.startswith(lowering.SCOPE_MARK) and "@" not in name
    for path in (name, "jit(fn)/%s/add" % name,
                 "jit(fn)/transpose(jvp(%s))/mul" % name,
                 "jit(fn)/while/body/%s/checkpoint/rematted_computation/dot"
                 % name):
        assert lowering.parse_op_scope(path) == want


def test_an_at_sign_would_cut_the_op_name_short():
    """Why op_scope writes '~' for '@': XLA keeps an op_name up to its
    first '@' only, and the rest of the path would go with it."""
    def f(x):
        with jax.named_scope("op:scale/x@GRAD"):
            return x * 2.0
    text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
    assert "op:scale/x~GRAD" not in text and "x@GRAD" not in text
    assert 'op_name="jit(f)/op:scale/x"' in text


# --- a name a kernel -------------------------------------------------------

def test_every_pallas_call_takes_its_name_from_kernel_names():
    from paddle_tpu.ops import causal_conv_kernels, embedding_grad, \
        expert_gmm, gated_delta_kernels, kda_kernels, mhc_kernels, \
        rms_norm_kernels, rotary_kernels, selective_scan_kernels, ssd_kernels
    names = []
    for module in (pallas_kernels, gated_delta_kernels, causal_conv_kernels,
                   embedding_grad, mhc_kernels, expert_gmm,
                   selective_scan_kernels, ssd_kernels, rotary_kernels,
                   kda_kernels, rms_norm_kernels):
        with open(module.__file__) as f:
            tree = ast.parse(f.read())
        # mhc_kernels' pallas_calls sit in two helpers that are handed the
        # name, and expert_gmm's forward and d rows in one: there the
        # literal is the helper's second argument
        helpers = {mhc_kernels: {"_call", "_coeffs_call"},
                   expert_gmm: {"_rows_call"}}.get(module, set())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and node.func.id in helpers:
                assert isinstance(node.args[1], ast.Constant), \
                    "%s at line %d is given no literal name" \
                    % (node.func.id, node.lineno)
                names.append(node.args[1].value)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                if helpers and isinstance(kw.get("name"), ast.Name):
                    continue
                assert isinstance(kw.get("name"), ast.Constant), \
                    "pallas_call at line %d has no literal name=" \
                    % node.lineno
                names.append(kw["name"].value)
    assert sorted(names) == sorted(pallas_kernels.KERNEL_NAMES)
    assert pallas_kernels.EXPERT_MATMUL_KERNELS == expert_gmm.KERNELS
    assert pallas_kernels.SELECTIVE_SCAN_KERNELS \
        == pallas_kernels.KERNEL_NAMES[-10:-8]
    assert pallas_kernels.SSD_KERNELS == pallas_kernels.KERNEL_NAMES[-8:-6]
    # PR 65's two, named at the module's end: the forward walk with the
    # unit in it is an expert matmul, the buffer nothing wrote is not
    assert pallas_kernels.KERNEL_NAMES[-6:-4] == (
        "ptpu_expert_gmm_unit_fwd", "ptpu_expert_rows_unwritten")
    # PR 70's, and behind it PR 71's two and PR 72's one, at the module's
    # end too
    assert pallas_kernels.KERNEL_NAMES[-4] == "ptpu_rotary"
    assert pallas_kernels.KERNEL_NAMES[-3:-1] == pallas_kernels.KDA_KERNELS
    assert pallas_kernels.KERNEL_NAMES[-1] == "ptpu_rms_norm_bwd"
    assert len(set(names)) == len(names) == 35
    for a in names:         # a reader matching `<name>` or `<name>.<n>`
        for b in names:     # never counts one kernel under another
            assert a == b or not (b + ".").startswith(a + ".")
            assert a == b or not b.startswith(a) or b[len(a)] == "_"


def _kernel_program():
    """flash attention, layer_norm and softmax_xent in one training step."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16, 2, 8], dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
        q = fluid.layers.reshape(
            fluid.layers.fc(input=x, size=16, num_flatten_dims=2),
            shape=[-1, 16, 2, 8])
        a = fluid.layers.fused_attention(q, q, q, causal=True)
        h = fluid.layers.layer_norm(fluid.layers.reshape(a, shape=[-1, 256]))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=h, size=32), lab))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _kernel_program_args(main, startup, loss, shard=None,
                         x_shape=(4, 16, 2, 8)):
    """(fn, args) of the step on a batch `x_shape`; `shard` turns the
    arguments into shapes on a described device."""
    feeds = ["x", "lab"]
    rw, ro, out = lowering.analyze_state(main, feeds, [loss.name])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        vals = {n: np.asarray(scope.find_var(n).get_tensor())
                for n in set(rw) | set(ro)}
    fn = lowering.build_program_fn(main, feeds, [loss.name], rw, ro, out)
    args = ([np.zeros(x_shape, "float32"),
             np.zeros((x_shape[0], 1), "int32")],
            [vals[n] for n in rw], [vals[n] for n in ro])
    if shard is not None:
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard),
            args)
    return (lambda f, a, b: fn(f, a, b, 0)), args


def test_no_transform_wraps_a_kernels_name_scope(monkeypatch):
    """pallas_call opens a scope named after the kernel, and XLA names the
    Mosaic call's instruction by the path's last scope as jax renders it:
    `jvp(ptpu_layer_norm_fwd)` would become `jvp_ptpu_layer_norm_fwd_`. The
    lowering op's scope inside the differentiated function keeps every
    kernel's scope bare. A forward kernel runs once, under its forward op
    (which keeps the linearization); the backward kernels under the grad
    op that calls it. Read off the compiled step's op_names: since PR 60 a
    kernel's call is a jax.jit of its own, which lowers as one function
    whose locations start at the kernel's name, and it is XLA's inlining
    that writes a call site's scope before them."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")    # interpreted, off a TPU
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    fn, args = _kernel_program_args(*_kernel_program())
    text = jax.jit(fn).lower(*args).compile().as_text()
    under = {}      # kernel -> the fluid op types it lowered under
    for path in set(re.findall(r'op_name="([^"]*)"', text)):
        if path.startswith("ptpu_"):
            continue        # a reduction's own adder: no call, none inlined
        for part in path.split("/"):
            if "ptpu_" in part:
                assert part in pallas_kernels.KERNEL_NAMES, path
                under.setdefault(part, set()).add(
                    lowering.parse_op_scope(path)[0])
    assert under == {
        "ptpu_flash_fwd": {"fused_attention"},
        "ptpu_flash_bwd_dkdv": {"fused_attention_grad"},
        "ptpu_flash_bwd_dq": {"fused_attention_grad"},
        "ptpu_layer_norm_fwd": {"layer_norm"},
        "ptpu_softmax_xent_fwd": {"softmax_with_cross_entropy"}}


# --- the profiler's table of a device trace --------------------------------

class _Ev(object):
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line(object):
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane(object):
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


_MOSAIC = ('%%%s = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %%p.1), '
           'custom_call_target="tpu_custom_call", operand_layout_constrain'
           'ts={f32[8,128]{1,0}}')


def _planes():
    """(planes, op_names). One device: a `while` of 100 us whose body holds
    a forward fusion (30) and a backward fusion (20); a named Mosaic call
    under a forward op (40) and the same kernel under its grad op (10,
    twice); a copy XLA added, with no op_name (5); an all-reduce GSPMD put
    under a fluid op (8) and one with no op_name (2); an async line and a
    host plane the table must not read."""
    body = "jit(fn)/op:while/out/while/body/"
    texts = {
        "while": "%while.3 = (s32[], f32[4]) while(%tuple.1), body=%b, "
                 "condition=%c",
        "fwd": "%fusion.7 = f32[4]{0} fusion(%p.2), kind=kOutput, calls=%fc",
        "bwd": "%fusion.9 = f32[4]{0} fusion(%p.3), kind=kLoop, calls=%fd",
        "ln": _MOSAIC % "ptpu_layer_norm_fwd.2",
        "ln_again": _MOSAIC % "ptpu_layer_norm_fwd.5",
        "copy": "%copy.11 = f32[4]{0} copy(f32[4]{0} %p.9)",
        "sum": "%all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %p.5), "
               "replica_groups={{0,1}}, to_apply=%add",
        "sum_xla": "%all-reduce-done.1 = f32[4]{0} all-reduce-done(%s.1)"}
    op_names = {
        texts["while"]: "jit(fn)/op:while/out/while:",
        texts["fwd"]: body + "op:mul/fc_0.tmp_0/dot_general:",
        texts["bwd"]: body + "op:mul_grad/fc_0.w_0~GRAD/transpose(jvp(op:mul_"
                             "grad/fc_0.w_0~GRAD))/dot_general:",
        texts["sum"]: "jit(fn)/op:batch_norm_grad/x~GRAD/transpose(jvp(op:"
                      "batch_norm_grad/x~GRAD))/reduce_sum:",
        texts["ln"]: "jit(fn)/op:layer_norm/ln_0.tmp_2/ptpu_layer_norm_fwd/"
                     "pallas_call:",
        texts["ln_again"]: "jit(fn)/op:layer_norm_grad/x~GRAD/jvp(op:layer_"
                           "norm_grad/x~GRAD)/ptpu_layer_norm_fwd/pallas_call:"}
    ops = [_Ev(texts["while"], 0, 100), _Ev(texts["fwd"], 10, 30),
           _Ev(texts["bwd"], 50, 20), _Ev(texts["ln"], 100, 40),
           _Ev(texts["ln_again"], 150, 10), _Ev(texts["ln_again"], 170, 10),
           _Ev(texts["copy"], 200, 5), _Ev("%zero = f32[] constant(0)", 210, 0),
           _Ev(texts["sum"], 220, 8), _Ev(texts["sum_xla"], 230, 2)]
    planes = [
        _Plane("/device:TPU:0", [
            _Line("XLA Ops", ops),
            _Line("Async XLA Ops", [_Ev("%all-reduce-start.1 = f32[4]{0} "
                                        "all-reduce-start(%p)", 0, 500)])]),
        _Plane("/host:CPU", [_Line("python", [_Ev("bench/run_call", 0,
                                                  900)])])]
    return planes, op_names


def _looped_planes():
    """(planes, op_names). A loop op of 2 passes run twice (two steps). The
    forward `while` of a step holds a matmul once a trip (10, then 12) and,
    in a loop of its own, an inner fusion twice a trip (1 each); the grad
    op's `while` holds the replayed matmul and its gradient once a trip (8
    + 20, then 6 + 20); a head's matmul runs under no pass (30)."""
    fwd = "jit(fn)/pass:1-2/op:rnn_scan/rnn.out_0/while/body/"
    bwd = "jit(fn)/pass:1-2/op:rnn_scan_grad/x~GRAD/transpose(jvp(pass:1-2/" \
        "op:rnn_scan/rnn.out_0))/while/body/"
    texts = {
        "loop": "%while.1 = (s32[], f32[4]) while(%t.1), body=%b, "
                "condition=%c",
        "loop_grad": "%while.2 = (s32[], f32[4]) while(%t.2), body=%b2, "
                     "condition=%c2",
        "dot": "%fusion.1 = f32[4]{0} fusion(%p.1), kind=kOutput, calls=%f1",
        "inner": "%fusion.2 = f32[4]{0} fusion(%p.2), kind=kLoop, calls=%f2",
        "replay": "%fusion.3 = f32[4]{0} fusion(%p.3), kind=kOutput, "
                  "calls=%f3",
        "dot_grad": "%fusion.4 = f32[4]{0} fusion(%p.4), kind=kOutput, "
                    "calls=%f4",
        "head": "%fusion.5 = f32[4]{0} fusion(%p.5), kind=kOutput, calls=%f5"}
    op_names = {
        texts["loop"]: "jit(fn)/pass:1-2/op:rnn_scan/rnn.out_0/while:",
        texts["loop_grad"]: "jit(fn)/pass:1-2/op:rnn_scan_grad/x~GRAD/"
                            "transpose(jvp(pass:1-2/op:rnn_scan/rnn.out_0))/"
                            "while:",
        texts["dot"]: fwd + "op:mul/fc_0.tmp_0/dot_general:",
        texts["inner"]: fwd + "op:rms_norm/n.tmp_0/while/body/mul:",
        texts["replay"]: bwd + "checkpoint/op:mul/fc_0.tmp_0/dot_general:",
        texts["dot_grad"]: bwd + "op:mul/fc_0.tmp_0/transpose/dot_general:",
        texts["head"]: "jit(fn)/op:mul/fc_9.tmp_0/dot_general:"}
    ops = []
    for step in (0, 1000):
        ops += [_Ev(texts["loop"], step, 100),
                _Ev(texts["dot"], step + 1, 10),
                _Ev(texts["inner"], step + 20, 1),
                _Ev(texts["inner"], step + 22, 1),
                _Ev(texts["dot"], step + 50, 12),
                _Ev(texts["inner"], step + 70, 1),
                _Ev(texts["inner"], step + 72, 1),
                _Ev(texts["head"], step + 100, 30),
                _Ev(texts["loop_grad"], step + 200, 100),
                _Ev(texts["replay"], step + 201, 8),
                _Ev(texts["dot_grad"], step + 210, 20),
                _Ev(texts["replay"], step + 250, 6),
                _Ev(texts["dot_grad"], step + 260, 20)]
    return [_Plane("/device:TPU:0", [_Line("XLA Ops", ops)])], op_names


def test_device_pass_table_deals_a_loops_trips_out_to_its_passes():
    """The k-th run of an instruction under one run of the loop is trip k;
    the backward loop walks the passes from the last to the first."""
    table = profiler.device_pass_table(*_looped_planes())
    rows = {r["pass"]: r for r in table["rows"]}
    assert list(rows) == ["1", "1-2", "2", "outside"]
    assert table["busy_self_ms"] == pytest.approx(2 * 230e-6)
    # pass 1: forward 10 + 2 x 1; backward the second trip's 6 + 20
    assert rows["1"]["fwd_ms"] == pytest.approx(2 * 12e-6)
    assert rows["1"]["bwd_ms"] == pytest.approx(2 * 26e-6)
    assert rows["2"]["fwd_ms"] == pytest.approx(2 * 14e-6)
    assert rows["2"]["bwd_ms"] == pytest.approx(2 * 28e-6)
    assert rows["1"]["events"] == rows["2"]["events"] == 2 * 5
    # the two `while`s' own time is the loop's and no trip's; the head is
    # outside every pass
    assert rows["1-2"]["total_ms"] == pytest.approx(2 * 120e-6)
    assert rows["outside"]["total_ms"] == pytest.approx(2 * 30e-6)
    assert sum(r["share"] for r in table["rows"]) == pytest.approx(100.0)
    text = profiler.render_pass_table(table)
    assert text.splitlines()[0].split() == [
        "Pass", "Events", "Forward(ms)", "Backward(ms)", "Total(ms)", "Busy%"]
    assert len(text.splitlines()) == 6


def test_device_pass_table_is_empty_without_a_loop_op():
    table = profiler.device_pass_table(*_planes())
    assert table["rows"] == [] and profiler.render_pass_table(table) == ""
    assert table["busy_self_ms"] == pytest.approx(175e-6)


def test_device_op_table_sums_to_the_busy_self_time():
    table = profiler.device_op_table(*_planes())
    rows = {(r["name"], r["kernel"]): r for r in table["rows"]}
    assert table["planes"] == 1
    assert table["busy_self_ms"] == pytest.approx(175e-6)
    assert sum(r["total_ms"] for r in table["rows"]) == pytest.approx(
        table["busy_self_ms"])
    assert sum(r["share"] for r in table["rows"]) == pytest.approx(100.0)
    # the while keeps what its body did not use; the innermost scope owns
    assert rows[("while", "")]["total_ms"] == pytest.approx(50e-6)
    assert rows[("mul", "")]["pass"] == "fwd"
    assert rows[("mul_grad", "")]["pass"] == "bwd"
    assert rows[("mul_grad", "")]["total_ms"] == pytest.approx(20e-6)
    # one kernel under two fluid ops: a row each
    assert rows[("layer_norm", "ptpu_layer_norm_fwd")]["events"] == 1
    twice = rows[("layer_norm_grad", "ptpu_layer_norm_fwd")]
    assert (twice["events"], twice["pass"]) == (2, "bwd")
    assert twice["total_ms"] == pytest.approx(20e-6)
    assert twice["ave_ms"] == twice["max_ms"] == twice["min_ms"]
    # no scope: listed under its own name, never dropped
    copy = rows[("copy", "")]
    assert (copy["scoped"], copy["pass"]) == (False, "-")
    # a collective is set apart from its fluid op's own arithmetic
    reduce = rows[("batch_norm_grad", "all-reduce")]
    assert (reduce["scoped"], reduce["pass"], reduce["events"]) == (
        True, "bwd", 1)
    assert rows[("all-reduce-done", "all-reduce-done")]["scoped"] is False
    assert table["scoped_ms"] == pytest.approx(168e-6)


def test_device_op_table_by_instance_and_over_planes():
    planes, op_names = _planes()
    planes.insert(1, _Plane("/device:TPU:1", planes[0].lines))
    table = profiler.device_op_table(planes, op_names, by="instance")
    rows = {(r["name"], r["kernel"]): r for r in table["rows"]}
    assert table["planes"] == 2
    assert table["busy_self_ms"] == pytest.approx(175e-6)   # a device
    assert rows[("mul_grad/fc_0.w_0@GRAD", "")]["events"] == 2
    assert rows[("mul_grad/fc_0.w_0@GRAD", "")]["total_ms"] == \
        pytest.approx(20e-6)
    assert ("copy.11", "") in rows
    with pytest.raises(ValueError):
        profiler.device_op_table(planes, op_names, by="layer")


@pytest.mark.parametrize("sorted_key", [None, "calls", "total", "max",
                                        "min", "ave"])
def test_render_device_ops_sorts_by_every_sorted_key(sorted_key):
    table = profiler.device_op_table(*_planes())
    text = profiler.render_device_ops(table, sorted_key)
    order = [ln.split()[0] for ln in text.splitlines()[1:-1]]
    field = {None: "total_ms", "calls": "events", "total": "total_ms",
             "max": "max_ms", "min": "min_ms", "ave": "ave_ms"}[sorted_key]
    values = [next(r[field] for r in table["rows"]
                   if r["name"] == name and
                   (r["kernel"] or "-") == ln.split()[1])
              for name, ln in zip(order, text.splitlines()[1:-1])]
    assert len(values) == 8 and values == sorted(values, reverse=True)
    assert "root instruction's scope" in text.splitlines()[-1]
    cut = profiler.render_device_ops(table, sorted_key, limit=2)
    assert "(6 more rows)" in cut and len(cut.splitlines()) == 5
    with pytest.raises(ValueError):
        profiler.render_device_ops(table, "bogus")


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A protobuf message: (number, int) a varint, (number, bytes or str)
    length-delimited."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_read_op_names_takes_tf_op_from_the_events_metadata(tmp_path):
    """An .xplane.pb written by hand, field numbers as in xplane.proto: the
    op_name is the stat `tf_op` of an XEventMetadata on a device plane."""
    def plane(name, tf_op_id):
        long_text = "%fusion.1 = f32[4]{0} fusion(%p), kind=kLoop " + "x" * 300
        return _msg(
            (1, 7), (2, name),
            (5, _msg((1, 3), (2, _msg((1, 3), (2, "flops"))))),
            (5, _msg((1, tf_op_id), (2, _msg((1, tf_op_id), (2, "tf_op"))))),
            (4, _msg((1, 1), (2, _msg(
                (1, 1), (2, long_text), (4, "fusion"),
                (5, _msg((1, 3), (3, 1 << 40))),
                (5, _msg((1, tf_op_id),
                         (5, "jit(fn)/op:mul/fc_0.tmp_0/dot_general:"))))))),
            (4, _msg((1, 2), (2, _msg(      # XLA's own: no op_name
                (1, 2), (2, "%copy.2 = f32[4]{0} copy(%p)"),
                (5, _msg((1, 3), (3, 0))))))),
            (3, _msg((1, 1), (2, "XLA Ops"),
                     (4, _msg((1, 1), (2, 1000), (3, 5000))))))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_msg((1, plane("/device:TPU:0", 26)),
                          (1, plane("/host:CPU", 26)),
                          (2, "an error string")))
    got = profiler.read_op_names(path.read_bytes())
    assert list(got.values()) == ["jit(fn)/op:mul/fc_0.tmp_0/dot_general:"]
    assert next(iter(got)).startswith("%fusion.1 = ")
    # and the whole way, through ProfileData: one event, under `mul`
    table = profiler.device_op_table_from(str(path))
    assert [(r["name"], r["events"], r["pass"]) for r in table["rows"]] == \
        [("mul", 1, "fwd")]
    assert table["busy_self_ms"] == pytest.approx(5e-6)


def test_the_table_is_empty_and_harmless_off_the_chip(tmp_path, capsys):
    main, startup, loss = _program()
    scope = fluid.Scope()
    profiler.reset_profiler()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((4, 13), "float32"),
                "y": np.ones((4, 1), "float32")}
        with profiler.profiler(profile_path=str(tmp_path)):
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
    out = capsys.readouterr().out
    assert "no TPU device plane" in out
    assert profiler.find_xplane(str(tmp_path)) is not None
    snap = profiler.profile_report(json=True)
    assert snap["device_ops"]["rows"] == []
    assert snap["device_ops"]["busy_self_ms"] == 0.0
    # the executors report no idle: their rows read "-" in both columns
    row = next(ln for ln in profiler.profile_report().splitlines()
               if ln.startswith("program_"))
    assert row.split()[-2:] == ["-", "-"]
    assert all(e["gaps"] == 0 for e in snap["entries"].values())
    # an empty directory is no error either
    empty = profiler.device_op_table_from(str(tmp_path / "nothing"))
    assert empty["rows"] == []
    profiler.main([str(tmp_path), "--sorted-key", "total", "--by",
                   "instance"])
    assert "no TPU device plane" in capsys.readouterr().out
    profiler.reset_profiler()
    assert profiler.profile_report(json=True)["device_ops"] is None


# --- the names on a v5e, compiled here without the chip --------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this machine
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile_uncached(fn, *args):
    """Compiled for the described chip with the persistent cache off: an
    executable for a device that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_mosaic_calls_are_named_on_a_described_v5e(one_chip, monkeypatch):
    """A training step with layer_norm, softmax_xent and flash attention,
    lowered by build_program_fn and compiled for a TPU: five Mosaic calls
    (each forward kernel once, dK/dV, dQ), every one named from
    KERNEL_NAMES and none by the name stack (`fn`, `jvp__`,
    `transpose_jvp___`, `jvp_ptpu_layer_norm_fwd_`); the forward kernels
    under their forward op, the backward kernels under the grad op."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    # the rules ask where the step will run; here it is only described
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_kernels, "dispatch_platform", lambda: "tpu")
    fn, args = _kernel_program_args(*_kernel_program(), shard=one_chip)
    text = _compile_uncached(fn, *args).as_text()
    calls = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)
    assert len(calls) == len(re.findall(
        r'custom_call_target="tpu_custom_call"', text)) == 5
    under = {(name.rpartition(".")[0] if name.rpartition(".")[2].isdigit()
              else name): lowering.parse_op_scope(op_name)[0]
             for name, op_name in calls}
    assert under == {
        "ptpu_flash_fwd": "fused_attention",
        "ptpu_flash_bwd_dkdv": "fused_attention_grad",
        "ptpu_flash_bwd_dq": "fused_attention_grad",
        "ptpu_layer_norm_fwd": "layer_norm",
        "ptpu_softmax_xent_fwd": "softmax_with_cross_entropy"}
    assert not re.search(r"%(fn|jvp_|transpose_jvp_)[\w.]* = ", text)
    scoped = {lowering.parse_op_scope(m)[0]
              for m in re.findall(r'op_name="([^"]*)"', text)
              if lowering.parse_op_scope(m)}
    assert {"layer_norm", "layer_norm_grad", "fused_attention_grad",
            "softmax_with_cross_entropy_grad", "mul_grad"} <= scoped


def test_the_loss_reads_the_logits_as_they_come_on_a_described_v5e(
        one_chip, monkeypatch):
    """An AMP training step whose head feeds softmax_with_cross_entropy,
    compiled for a TPU (PR 44): one `ptpu_softmax_xent_fwd`, on the bf16
    logits the head's matmul wrote, and no second kernel (one that wrote
    dlogits lost to XLA's fusing it into the two gradient matmuls); no
    float32 [N, V] array is a result of any instruction of the step (the
    parent's forward fusion wrote one beside the bf16 logits, for the loss
    alone), and no instruction comes from a `log_softmax` (the dense
    Softmax nobody reads was a result of the differentiated function, and
    XLA kept its sum and that sum's transpose)."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_kernels, "dispatch_platform", lambda: "tpu")
    n, d, v = 64, 256, 1000
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[d], dtype="float32")
        lab = fluid.layers.data(name="lab", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=d, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=h, size=v, bias_attr=False), lab))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main.enable_mixed_precision()
    fn, args = _kernel_program_args(main, startup, loss, shard=one_chip,
                                    x_shape=(n, d))
    text = _compile_uncached(fn, *args).as_text()
    calls = {m.group(1): m.group(2) for m in re.finditer(
        r"%?(ptpu_[a-z_]+)[\w.]* = ([^\n]*custom_call_target="
        r'"tpu_custom_call"[^\n]*)', text)}
    assert sorted(calls) == ["ptpu_softmax_xent_fwd"]
    logits = "bf16[%d,%d]" % (n, v)     # the first operand, as constrained
    assert "operand_layout_constraints={%s" % logits in \
        calls["ptpu_softmax_xent_fwd"]
    entry = text[text.index("ENTRY"):]
    assert "f32[%d,%d]" % (n, v) not in re.sub(r"\(.*", "", "\n".join(
        line.split(" = ", 1)[1] for line in entry.splitlines()
        if " = " in line))
    assert "log_softmax" not in text


@pytest.mark.parametrize("n,d,dtype", [
    (16384, 512, jnp.float32), (16384, 1024, jnp.float32),
    (4096, 2048, jnp.float32), (1024, 8192, jnp.float32),
    (16384, 512, jnp.bfloat16), (1024, 8192, jnp.bfloat16),
    (3000, 520, jnp.float32),
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_layer_norm_tile_fits_the_default_vmem_limit(one_chip, n, d, dtype):
    """The one budget of DEFAULT_TILES["ln"] gives a tile Mosaic takes at
    its default scoped-VMEM limit at every width, the cells' D=512 and the
    D=2048 and D=8192 no cell runs (PERF.md section 6, PR 30: 4 MiB of
    float32 tile is refused at all three, by 4 to 64 KiB)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_uncached(
        lambda x, s, b: pallas_kernels._ln_fwd_call(x, s, b, 1e-5, None,
                                                    False),
        sds((n, d), dtype), sds((d,), jnp.float32),
        sds((d,), jnp.float32)).as_text()
    assert len(re.findall(r'%?ptpu_layer_norm_fwd[\w.]* = [^\n]*'
                          r'custom_call_target="tpu_custom_call"',
                          text)) == 1


def _routed_ffn_step(held, sds):
    """routed_ffn forward and backward at top-6 of 16, `held` of them here,
    and the shapes it is lowered for."""
    from paddle_tpu.parallel import moe

    def step(x, a, router, wg, wu, wd, g):
        out, vjp = jax.vjp(
            lambda *p: moe.routed_ffn(
                p[0], router, *p[2:], top_k=6, norm_topk_prob=True,
                expert_dtype=jnp.bfloat16, router_x=p[1],
                activation="relu")[0], x, a, wg, wu, wd)
        return out, vjp(g)

    n, d, f = 1024, 256, 128
    return step, (
        sds((n, d), jnp.bfloat16), sds((n, d), jnp.float32),
        sds((d, 16), jnp.float32), sds((held, d, f), jnp.float32),
        sds((held, d, f), jnp.float32), sds((held, f, d), jnp.float32),
        sds((n, d), jnp.bfloat16))


@pytest.mark.parametrize("held", [4, 16], ids=["a_share", "every_expert"])
def test_routed_ffn_moves_its_rows_without_a_relayout(one_chip, held):
    """routed_ffn forward and backward at top-6 of 16, compiled for a TPU:
    no reshape, copy, transpose or convert over the row buffer ([6144, 256],
    [6, 1024, 256]; PR 31's order made three relayouts of it a layer and
    PERF.md section 6, PR 32, has what they cost). Where a share of the
    experts is held every pass over the buffer is a loop over the held tiles
    (PR 32: the two expert-side gathers; PR 40: the two token-side sums,
    each a gather and a one-hot matmul a trip, and `_gated`'s transpose), no
    gather gives the whole row buffer, no `add` runs over it (the two
    matmuls' d rows are added where the token-side sum reads them) and only
    `_held_rows` fills one with zeros; where every expert is held there is
    no loop. The nine grouped matmuls are the same calls either way."""
    step, shapes = _routed_ffn_step(
        held, lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=one_chip))
    text = _compile_uncached(step, *shapes).as_text()
    # min(top_k, held) x N rows: a token's choices are distinct experts, so
    # a share of 4 holds at most 4 of its 6 (PR 61)
    buffer = r"= \w+\[(?:%d,256|6,1024,256|1024,6,256)\]\S* " \
        % (min(6, held) * 1024)
    moved = re.findall(     # in the entry computation: a gather's fusion
        buffer + r"(reshape|copy|transpose|convert)\(",     # has its own
        text[text.index("ENTRY"):])
    assert moved == []
    whole = collections.Counter(re.findall(buffer + r"(gather|add|copy|"
                                           r"broadcast)\(", text))
    assert whole == ({"broadcast": 1} if held < 16 else
                     {"gather": 4, "add": 1, "broadcast": 1})
    assert len(re.findall(r"%ragged-dot-none[\w.]* = ", text)) == 9
    assert len(re.findall(r" while\(", text)) == (5 if held < 16 else 0)


def test_routed_ffn_holding_every_expert_lowers_as_the_parent_did():
    """Where every expert is held (`rows_moved` says "all": the OLMoE cell)
    PR 40 changed nothing, and the StableHLO of the step above was pinned
    byte for byte to PR 39's up to PR 62. PR 63 changed what moves the
    scalars and nothing else: the four gathers left each move a whole row
    (the two scalar gathers of `_combine_bwd` and both scatters, into `rank`
    and of the weights' gradients, are gone), four sorts stand where one
    did (the argsort, `rank` out of `order`, the weights by sorted row and
    their gradients back), and there is still no loop."""
    step, shapes = _routed_ffn_step(16, jax.ShapeDtypeStruct)
    text = jax.jit(step).lower(*shapes).as_text()
    assert re.findall(r'"stablehlo\.gather"\(.*?slice_sizes = array<i64: '
                      r'([0-9, ]+)>', text) == ["1, 256"] * 4
    assert len(re.findall(r'"stablehlo\.sort"\(', text)) == 4
    assert not re.search(r"stablehlo\.(scatter|while)\b", text)


def test_gated_delta_kernels_at_the_cells_shapes_on_a_described_v5e(
        one_chip, monkeypatch):
    """fluid.layers.gated_delta_rule and its grad op at the Qwen3-Next
    cell's shapes (2 x 4096 tokens, 16 key heads on 32 value heads of 128,
    bf16 operands), lowered by build_program_fn and compiled for a TPU:
    Mosaic takes both kernels at the default chunk and heads a step (their
    transposed dots, a [64, 64] tile, 512 KiB of state scratch), the
    forward kernel runs under the forward op and once more, for the states,
    under the grad op beside the reverse kernel, and each instruction is
    named from KERNEL_NAMES."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    b, t, hk, hv, d = 2, 4096, 16, 32, 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        main.enable_mixed_precision()
        feeds = [fluid.layers.data(name=n, shape=list(s), dtype="float32")
                 for n, s in (("q", (t, hk, d)), ("k", (t, hk, d)),
                              ("v", (t, hv, d)), ("g", (t, hv)),
                              ("beta", (t, hv)))]
        for var in feeds:
            var.stop_gradient = False
        loss = fluid.layers.mean(fluid.layers.gated_delta_rule(*feeds))
        fluid.backward.append_backward(loss)
    names = ["q", "k", "v", "g", "beta"]
    fetch = [loss.name] + [n + "@GRAD" for n in names]
    rw, ro, out = lowering.analyze_state(main, names, fetch)
    fn = lowering.build_program_fn(main, names, fetch, rw, ro, out)
    args = [jax.ShapeDtypeStruct((b,) + tuple(v.shape[1:]),
                                 jnp.bfloat16 if v.name in "qkv"
                                 else jnp.float32, sharding=one_chip)
            for v in feeds]
    text = _compile_uncached(lambda *a: fn(list(a), [], [], 0),
                             *args).as_text()
    calls = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)
    under = sorted(
        (name.rpartition(".")[0] if name.rpartition(".")[2].isdigit()
         else name, lowering.parse_op_scope(op_name)[0])
        for name, op_name in calls)
    assert under == [
        ("ptpu_gated_delta_bwd", "gated_delta_rule_grad"),
        ("ptpu_gated_delta_fwd", "gated_delta_rule"),
        ("ptpu_gated_delta_fwd", "gated_delta_rule_grad")]


@pytest.mark.parametrize("batch,t,c,dtype", [
    (1, 4096, 8192, jnp.bfloat16),      # the Qwen3-Next cell's three layers
    (2, 4096, 2048, jnp.bfloat16), (1, 4096, 8192, jnp.float32),
    (2, 48, 640, jnp.float32),          # one tile of 48 rows, blocks of 128
], ids=lambda v: getattr(v, "__name__", None) or str(v))
def test_causal_conv_kernels_on_a_described_v5e(one_chip, monkeypatch, batch,
                                                t, c, dtype):
    """fluid.layers.causal_conv1d and its grad op, lowered by
    build_program_fn and compiled for a TPU: Mosaic takes both kernels at
    the tile DEFAULT_TILES["conv"] gives (the rolls of a 48-row window, the
    backward pass's two float32 scratches and three double-buffered tiles
    inside the default scoped-VMEM limit), the forward kernel under the
    forward op and the backward one under the grad op, each instruction
    named from KERNEL_NAMES; and nothing converts, pads or transposes the
    [B, T, C] operands on the way in or out."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[t, c], dtype="float32")
        x.stop_gradient = False
        out = fluid.layers.causal_conv1d(x, 4, act="silu")
        ct = fluid.layers.data(name="ct", shape=[t, c], dtype="float32")
        fluid.backward.append_backward(fluid.layers.reduce_sum(out * ct))
        w, = main.global_block().all_parameters()
    fetch = [out.name, "x@GRAD", w.name + "@GRAD"]
    rw, ro, outs = lowering.analyze_state(main, ["x", "ct"], fetch)
    fn = lowering.build_program_fn(main, ["x", "ct"], fetch, rw, ro, outs)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_uncached(
        lambda x, ct, w: fn([x, ct], [], [w], 0), sds((batch, t, c), dtype),
        sds((batch, t, c), dtype), sds((c, 4), jnp.float32)).as_text()
    calls = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)
    under = sorted(
        (name.rpartition(".")[0] if name.rpartition(".")[2].isdigit()
         else name, lowering.parse_op_scope(op_name)[0])
        for name, op_name in calls)
    assert under == [("ptpu_causal_conv1d_bwd", "causal_conv1d_grad"),
                     ("ptpu_causal_conv1d_fwd", "causal_conv1d")]
    whole = r"\[%d,%d,%d\]" % (batch, t, c)
    moved = re.findall(r"= \w+%s\S* (copy|transpose|pad|convert)\(" % whole,
                       text[text.index("ENTRY"):])
    assert moved == []


# --- the flash kernels, heads indexed in place (PR 38) -----------------------

# a cell's flash shape: (B, T, Hq, Hkv, D), the window, heads a lane block
_FLASH_CELLS = {
    "transformer_t2048": ((8, 2048, 8, 8, 64), None, "2"),
    "olmoe_t4096": ((4, 4096, 16, 16, 128), None, "1"),
    "ouro_t4096": ((1, 4096, 16, 16, 128), None, "1"),
    "smallthinker_t8192": ((1, 8192, 7, 1, 128), 4096, "1"),
    "qwen3_next_t4096": ((1, 4096, 16, 2, 256), None, "1"),
}


def _attention_counts():
    """{heads_a_block: flash layers counted so far}."""
    from paddle_tpu.observability.registry import REGISTRY
    counts = {}
    for key, n in REGISTRY.counter("ptpu_attention_layers_total",
                                   "").samples():
        labels = dict(key)
        if labels["path"] == "flash":
            counts[labels["heads_a_block"]] = counts.get(
                labels["heads_a_block"], 0) + n
    return counts


def _counted_since(before):
    after = _attention_counts()
    return {key: after[key] - before.get(key, 0) for key in after
            if after[key] != before.get(key, 0)}


@pytest.mark.parametrize("cell", sorted(_FLASH_CELLS))
def test_flash_kernels_index_heads_in_place_on_a_described_v5e(
        one_chip, monkeypatch, cell):
    """fluid.layers.fused_attention and its grad op at a cell's shape,
    lowered by build_program_fn and compiled for a TPU: Mosaic takes the
    three kernels with a head's blocks indexed in [B, T, H*D] (the XLU
    transposes that turn the statistics' rows into columns, the lane masks
    of two heads a block, one buffer for the pinned pair where two do not
    fit: Qwen3-Next's dK/dV), the counter says how many heads a block, and
    no transpose or pad of an operand is left in the compiled step."""
    from paddle_tpu.ops import kernel_config
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_kernels, "dispatch_platform", lambda: "tpu")
    (b, t, h, hkv, d), window, heads = _FLASH_CELLS[cell]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        main.enable_mixed_precision()
        q, g = (fluid.layers.data(name=n, shape=[t, h * d], dtype="float32")
                for n in "qg")
        k, v = (fluid.layers.data(name=n, shape=[t, hkv * d],
                                  dtype="float32") for n in "kv")
        for var in (q, k, v):
            var.stop_gradient = False
        # heads by a reshape of a [B, T, H*D] stream, as both builders do
        out = fluid.layers.fused_attention(
            *(fluid.layers.reshape(x, shape=[0, -1, n, d])
              for x, n in ((q, h), (k, hkv), (v, hkv))),
            causal=True, window=window)
        out = fluid.layers.reshape(out, shape=[0, -1, h * d])
        fluid.backward.append_backward(fluid.layers.reduce_sum(out * g))
    names = ["q", "k", "v", "g"]
    fetch = [out.name] + [n + "@GRAD" for n in "qkv"]
    rw, ro, outs = lowering.analyze_state(main, names, fetch)
    fn = lowering.build_program_fn(main, names, fetch, rw, ro, outs)
    args = [jax.ShapeDtypeStruct((b, t, (h if n in "qg" else hkv) * d),
                                 jnp.bfloat16, sharding=one_chip)
            for n in names]
    before = _attention_counts()
    text = _compile_uncached(lambda *a: fn(list(a), [], [], 0),
                             *args).as_text()
    assert _counted_since(before) == {heads: 1}
    kernels = sorted(re.sub(r"\.\d+$", "", name) for name in re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text))
    assert kernels == ["ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq",
                       "ptpu_flash_fwd"]
    big = b * t * hkv * d           # the smallest of the op's operands
    for shape in re.findall(r"= \w+\[([\d,]+)\]\S* (?:transpose|pad)\(",
                            text):
        assert np.prod([int(n) for n in shape.split(",")]) < big, shape


# (B, T, Hq, Hkv, D, the latent form's rotary width): the two cells' flash
# shapes `_FLASH_CELLS` does not have, a row a head through `_to_bh` and the
# latent form with its pinned rotary key
_FLASH_FORWARD_CELLS = {
    "lfm2_t8192": (1, 8192, 32, 8, 64, 0),
    "xing4_0_t4096": (1, 4096, 32, 32, 128, 64),
}


@pytest.mark.parametrize("cell", sorted(_FLASH_FORWARD_CELLS))
def test_flash_kernels_fit_vmem_at_the_other_cells_shapes(one_chip, cell):
    """The three kernels at the default 512 x 512 blocks where the forward
    pins the most beside its tiles: the row sums' lane partials and the
    output's accumulator are VMEM scratch (PR 46), and no limit is raised
    for them."""
    b, t, h, hkv, d, dr = _FLASH_FORWARD_CELLS[cell]

    def forward(q, k, v, *rope):
        return pallas_kernels.flash_attention(
            q, k, v, causal=True, interpret=False,
            **dict(zip(("q_rope", "k_rope"), rope)))

    def both(*operands):
        out, vjp = jax.vjp(forward, *operands)
        return (out,) + vjp(out)
    args = [jax.ShapeDtypeStruct((b, t, n, w), jnp.bfloat16,
                                 sharding=one_chip)
            for n, w in ((h, d), (hkv, d), (hkv, d))
            + (((h, dr), (1, dr)) if dr else ())]
    text = _compile_uncached(both, *args).as_text()
    # with no op scope around it jax names the call under vjp
    # jvp_ptpu_flash_fwd_
    kernels = sorted(re.search(r"ptpu_flash_[a-z_]*[a-z]", name).group(0)
                     for name in re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text))
    assert kernels == ["ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq",
                       "ptpu_flash_fwd"]
    assert "vmem_limit" not in text


@pytest.mark.parametrize("model,heads", [("transformer", "2"),
                                         ("causal_lm", "1")])
def test_the_attention_counter_says_how_many_heads_a_block(monkeypatch,
                                                           model, heads):
    """Lowering the two model builders at the cells' head shapes (8 heads
    of 64: the transformer; 16 of 128: OLMoE and Ouro) counts every flash
    layer under heads_a_block "2" and "1", by the function the kernels'
    wrapper asks (pallas_kernels.heads_a_block)."""
    from paddle_tpu.models import causal_lm, transformer
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")    # interpreted, off a TPU
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    t = 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if model == "transformer":
            transformer.transformer(
                64, 64, t, n_layer=1, n_head=8, d_key=64, d_value=64,
                d_model=512, d_inner_hid=64, dropout_rate=0.0,
                use_fused_attention=True)
            layers = 3              # encoder, decoder self and cross
        else:
            causal_lm.causal_lm(dict(
                hidden_size=2048, head_dim=128, num_attention_heads=16,
                num_key_value_heads=16, num_hidden_layers=2, vocab_size=64,
                intermediate_size=64, rms_norm_eps=1e-6, rope_theta=1e4,
                tie_word_embeddings=False, hidden_act="silu"), t)
            layers = 2
    feeds = [v.name for v in main.global_block().vars.values()
             if getattr(v, "is_data", False)]
    s_rw, s_ro, s_out = lowering.analyze_state(startup, [])
    state = dict(zip(s_out, jax.eval_shape(
        lambda: lowering.build_program_fn(startup, [], [], s_rw, s_ro, s_out)(
            [], [], [], np.uint32(0)))[1]))
    rw, ro, out = lowering.analyze_state(main, feeds, [])
    fn = lowering.build_program_fn(main, feeds, [], rw, ro, out)
    block = main.global_block()
    shapes = [jax.ShapeDtypeStruct(
        (2,) + tuple(block.var(n).shape[1:]),
        np.dtype(block.var(n).dtype) if np.dtype(block.var(n).dtype)
        != np.int64 else np.int32) for n in feeds]
    before = _attention_counts()
    jax.eval_shape(fn, shapes, [state[n] for n in rw],
                   [state[n] for n in ro], np.uint32(0))
    assert _counted_since(before) == {heads: layers}


# --- the embedding's backward (PR 41) ----------------------------------------

# (rows, vocabulary, width) of a token cell's lookup
_LOOKUP_CELLS = {
    "smallthinker_t8192": (8192, 37984, 2560),
    "olmoe_t4096": (16384, 50304, 2048),
    "lfm2_t8192": (8192, 16384, 2048),
}


@pytest.mark.parametrize("cell", sorted(_LOOKUP_CELLS))
def test_the_embeddings_backward_on_a_described_v5e(one_chip, monkeypatch,
                                                    cell):
    """fluid.layers.embedding and its grad op at a cell's shape, lowered by
    build_program_fn and compiled for a TPU: Mosaic takes the kernel at the
    block DEFAULT_TILES["emb"] gives (the row adds at a dynamic sublane, the
    ids in SMEM, two 4 MiB blocks in flight inside the default scoped-VMEM
    limit), named from KERNEL_NAMES under the grad op, and no scatter is
    left in the compiled step."""
    from paddle_tpu.ops import kernel_config
    rows, vocab, width = _LOOKUP_CELLS[cell]
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[rows, 1], dtype="int64",
                                append_batch_size=False)
        ct = fluid.layers.data(name="ct", shape=[rows, width],
                               dtype="float32", append_batch_size=False)
        emb = fluid.layers.embedding(
            ids, size=[vocab, width], param_attr=fluid.ParamAttr("table"))
        fluid.backward.append_backward(fluid.layers.reduce_sum(emb * ct))
    fetch = ["table@GRAD"]
    rw, ro, outs = lowering.analyze_state(main, ["ids", "ct"], fetch)
    fn = lowering.build_program_fn(main, ["ids", "ct"], fetch, rw, ro, outs)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_uncached(
        lambda ids, ct, w: fn([ids, ct], [], [w], 0),
        sds((rows, 1), jnp.int32), sds((rows, width), jnp.float32),
        sds((vocab, width), jnp.float32)).as_text()
    calls = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text)
    assert [(name.rpartition(".")[0] if name.rpartition(".")[2].isdigit()
             else name, lowering.parse_op_scope(op_name)[0])
            for name, op_name in calls] \
        == [("ptpu_embedding_grad", "lookup_table_grad")]
    assert " scatter(" not in text


# --- the routed experts' grouped matmuls (PR 50) ----------------------------

# rows, hidden, the experts' width, experts held: SmallThinker's (a share of
# the rows in a group) and Xing4.0's (the widest matrix a cell has: 7 MiB)
_EXPERT_CELLS = {"smallthinker": (49152, 2560, 768, 16),
                 "xing4_0": (16384, 3584, 1024, 8)}


@pytest.mark.parametrize("cell", sorted(_EXPERT_CELLS))
def test_expert_gmm_kernels_on_a_described_v5e(one_chip, monkeypatch, cell):
    """routed_ffn's nine matmuls at a cell's widths, compiled for a TPU: the
    route is the kernels', Mosaic takes all three at both shapes (an
    expert's matrix, its second buffer and d weights' float32 accumulator
    inside the VMEM the kernels ask for), each is named from KERNEL_NAMES,
    and no `ragged-dot` is left."""
    from paddle_tpu.ops import kernel_config
    from paddle_tpu.parallel import moe
    rows, d, f, held = _EXPERT_CELLS[cell]
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    assert moe.matmul_route(d, f, jnp.bfloat16) == moe.KERNEL_MATMUL

    def layer(x, w_in, w_out, dy, sizes):
        from paddle_tpu.ops import expert_gmm
        plan = expert_gmm.plan(sizes, rows)
        y, vjp = jax.vjp(
            lambda x, w_in, w_out: moe._grouped_matmul(
                moe._grouped_matmul(x, w_in, sizes, plan), w_out, sizes,
                plan), x, w_in, w_out)
        return y, vjp(dy)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compile_uncached(
        layer, sds((rows, d)), sds((held, d, f)), sds((held, f, d)),
        sds((rows, d)), sds((held,), jnp.int32)).as_text()
    calls = collections.Counter(
        name.rpartition(".")[0] if name.rpartition(".")[2].isdigit()
        else name for name in re.findall(
            r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
            text))
    assert calls == {name: 2
                     for name in pallas_kernels.EXPERT_MATMUL_KERNELS[:3]}
    assert "ragged-dot" not in text


# experts, held, top_k: a share numbered by slot (LFM2's), one numbered by
# held expert and ungated (Nemotron-3-Super's), every expert held (OLMoE's)
_ONE_LAYER = {"by_slot": (32, 8, 4, True), "by_expert": (512, 8, 22, False),
              "all_held": (64, 64, 8, True)}


@pytest.mark.parametrize("case", sorted(_ONE_LAYER))
def test_a_layer_of_experts_on_a_described_v5e(one_chip, monkeypatch, case):
    """One training step of a `moe_ffn` layer at 512 tokens of width 256,
    compiled for a TPU: the forward kernels (the down matmul's, the one
    with the unit in it, and where a share is held the call whose output
    the rows' loop starts from) are Mosaic calls under the forward op and
    NONE under the grad op, which replays the rule and counts on XLA to
    merge the replay (a Mosaic call is whole to XLA; the interpreter's loop
    is cut to the outputs a consumer reads, so only here can the kernel
    with three outputs be seen merged); the transposes are under the grad
    op; and outside the Mosaic calls nothing under either scope has all
    the buffer's rows in its result but a loop's carry: no fill, no pass
    of the unit (PR 65)."""
    from paddle_tpu.ops import kernel_config
    experts, held, top_k, gated = _ONE_LAYER[case]
    tokens, width = 512, 256
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        hidden = fluid.layers.fc(input=x, size=width, bias_attr=False)
        out, _, _, _ = fluid.layers.moe_ffn(
            hidden, experts, width, top_k, norm_topk_prob=True,
            experts_held=held, scoring="sigmoid", expert_bias_attr=True,
            gated=gated, activation="silu" if gated else "relu2")
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rw, ro, outs = lowering.analyze_state(main, ["x"], [loss.name])
    fn = lowering.build_program_fn(main, ["x"], [loss.name], rw, ro, outs)
    block = main.global_block()

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def state(names):
        return [sds(block.var(name).shape) for name in names]
    text = _compile_uncached(
        lambda feed, rw, ro: fn(feed, rw, ro, 0), [sds((tokens, width))],
        state(rw), state(ro)).as_text()
    calls = collections.Counter(
        (re.sub(r"\.\d+$", "", name), lowering.parse_op_scope(op_name)[0])
        for name, op_name in re.findall(
            r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
            r'[^\n]*op_name="([^"]*)"', text))
    fwd, drows, dweights, unit_fwd = pallas_kernels.EXPERT_MATMUL_KERNELS
    want = {(fwd, "moe_ffn"): 1, (unit_fwd, "moe_ffn"): 1,
            (drows, "moe_ffn_grad"): 3 if gated else 2,
            (dweights, "moe_ffn_grad"): 3 if gated else 2}
    if held < experts:
        # the rows' start under the forward op; under the grad op d up's,
        # or the hidden rows' that an ungated layer makes again
        want["ptpu_expert_rows_unwritten", "moe_ffn"] = 1
        want["ptpu_expert_rows_unwritten", "moe_ffn_grad"] = 1
    assert calls == want
    if held == experts:
        return                  # every row is in a group: the passes stay
    rows = tokens * min(held, top_k)
    passes = []
    for line in text.splitlines():
        met = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?\)?) "
                       r"(\w[\w\-]*)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if met and name and "op:moe_ffn" in name.group(1) \
                and "[%d,%d]" % (rows, width) in met.group(1) \
                and met.group(2) not in (
                    "parameter", "get-tuple-element", "tuple", "bitcast",
                    "while", "dynamic-update-slice", "custom-call"):
            passes.append((met.group(2), name.group(1)[-60:]))
    assert passes == []


def test_an_sdar_layers_rotary_on_a_described_v5e(one_chip, monkeypatch):
    """One attention layer at SDAR's geometry (8192 rows, 32 query heads of
    128 on 4, a norm a head, rotary, flash) as a training step compiled for
    a TPU: `ptpu_rotary` is a Mosaic call under `op:rotary_embedding` for q
    and for k and under `op:rotary_embedding_grad` for both, four calls and
    none replayed under the grad op (the forward op keeps its
    linearization), and no convert writes a float32 image of q, which is
    how XLA started its three passes over the jax.numpy lines (PR 70)."""
    from paddle_tpu.ops import kernel_config
    rows, width, heads, kv_heads, head = 8192, 2048, 32, 4, 128
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernel_config, "dispatch_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_kernels, "dispatch_platform", lambda: "tpu")
    main, startup = fluid.Program(), fluid.Program()
    main._amp = True
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[rows, width], dtype="float32")
        pos = fluid.layers.data(name="pos", shape=[rows], dtype="int64")

        def projected(n, turned=True):
            y = fluid.layers.reshape(
                fluid.layers.fc(input=x, size=n * head, num_flatten_dims=2,
                                bias_attr=False), shape=[0, rows, n, head])
            if not turned:
                return y
            return fluid.layers.rotary_embedding(
                fluid.layers.rms_norm(y, begin_norm_axis=3), pos, base=1e6)
        out = fluid.layers.fused_attention(
            projected(heads), projected(kv_heads),
            projected(kv_heads, turned=False), causal=True)
        loss = fluid.layers.mean(fluid.layers.fc(
            input=fluid.layers.reshape(out, shape=[0, rows, heads * head]),
            size=width, num_flatten_dims=2, bias_attr=False))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feeds = ["pos", "x"]
    rw, ro, outs = lowering.analyze_state(main, feeds, [loss.name])
    fn = lowering.build_program_fn(main, feeds, [loss.name], rw, ro, outs)
    block = main.global_block()

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def state(names):
        return [sds(block.var(name).shape) for name in names]
    text = _compile_uncached(
        lambda feed, rw, ro: fn(feed, rw, ro, 0),
        [sds((1, rows), jnp.int32), sds((1, rows, width))],
        state(rw), state(ro)).as_text()
    calls = collections.Counter(
        (re.sub(r"\.\d+$", "", name), lowering.parse_op_scope(op_name)[0])
        for name, op_name in re.findall(
            r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
            r'[^\n]*op_name="([^"]*)"', text))
    assert {k: n for k, n in calls.items() if k[0] == "ptpu_rotary"} == {
        ("ptpu_rotary", "rotary_embedding"): 2,
        ("ptpu_rotary", "rotary_embedding_grad"): 2}
    # the norm a head of q and of k (PR 72): its transpose is a kernel under
    # the grad op, which replays the rule's jax.numpy lines for the forward
    # (`rms_norm` keeps no linearization; what crosses the passes is the
    # op's inputs); no kernel under the forward op
    assert {k: n for k, n in calls.items() if "rms_norm" in k[0]} == {
        ("ptpu_rms_norm_bwd", "rms_norm_grad"): 2}
    images = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= f32\[(1,)?%d,(%d,%d|%d)\][^ ]* convert\("
                           % (rows, heads, head, heads * head), line)
              and "op:rotary_embedding" in line]
    assert images == []
