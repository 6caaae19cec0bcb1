"""Whole-program lowering: Program -> one pure JAX function -> one XLA module.

This replaces the reference's op-by-op interpreter
(paddle/fluid/framework/executor.cc: Executor::RunPreparedContext walks the
BlockDesc and launches a kernel per OpDesc). On TPU the right execution model
is to trace the entire Program once into a single XLA computation: XLA then
fuses elementwise chains into the matmuls/convs, plans memory, and overlaps
collectives — none of which an op-at-a-time interpreter can do.

Gradient ops ("grad_of" appended by core/backward.py) lower via jax.vjp of the
forward op's registered rule. For an op XLA generates, the grad op replays
the rule under jax.vjp: the recomputed forward subexpressions are
deduplicated by XLA CSE, so the backward pass costs the same as hand-written
grad kernels (reference: paddle/fluid/operators/*_grad kernels). A Mosaic
custom call is not deduplicated, so a forward op whose rule can reach a
Pallas kernel (OpDef.calls_pallas) and whose gradient is taken in the same
block lowers under jax.vjp once and keeps its vjp_fn for the grad op
(_linearizations): every Pallas forward kernel runs once a step.

A block has one lowering. Recomputing activations is no pass of this module:
it lives in the loop op that replays its body (`recompute` on an rnn_scan op,
ops/control_ops.py _recomputing), and what this module does for it is count
what runs twice (_count_remat_ops).
"""
import collections
import itertools
import re
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import registry
from .framework import GRAD_SUFFIX
from .utils import find_var as _find_var

# Lowering rules for ops that need access to the full env / program structure
# (control flow with sub-blocks, tensor arrays). Signature:
#   fn(ctx, op, env) -> None   (mutates env)
_SPECIAL = {}


def trace_env_key():
    """Values of every env flag that is read at TRACE time (they shape
    the lowered computation): any jit-program cache over lowered fns must
    include this tuple in its key, or flipping a flag between runs would
    silently serve the other configuration's compiled fn.

    The four members: FLAGS_conv_layout (conv/pool compute layout), the
    flash crossover (kernel_config.flash_min_seq: FLAGS_flash_min_seq,
    else the constant), the raw PADDLE_TPU_PALLAS env string — the RAW
    string, not pallas_on(): that helper also reads the dispatch
    platform, which is fixed per executor (and in the AOT key through the
    device), so the env string alone captures everything that can change
    between runs of one executor — and jax's PRNG formulation. A function
    of the environment and the jax config only: both executors call it on
    every run, and it touches no file. When adding a trace-time flag, add
    its resolved value HERE."""
    import os
    from ..ops.kernel_config import flash_min_seq
    from ..ops.nn_ops import _conv_layout
    return (_conv_layout(), flash_min_seq(),
            os.environ.get("PADDLE_TPU_PALLAS", ""),
            # the PRNG formulation is traced into every random op; the
            # package __init__ pins it partitionable, so this entry's
            # real job is re-keying AOT artifacts serialized under the
            # legacy stream (they would otherwise hit and silently
            # serve the other formulation's masks)
            bool(jax.config.jax_threefry_partitionable))


def register_special(type):
    def deco(fn):
        _SPECIAL[type] = fn
        return fn
    return deco


# --- bf16 mixed precision (Program.enable_mixed_precision) -----------------
# Ops whose MXU contraction runs in bfloat16 under AMP. They return bf16
# outputs, so bf16 propagates through the elementwise/norm chains between
# them without touching any other rule (batch_norm/layer_norm already
# compute statistics in f32 regardless of input dtype). Accumulation:
# mul/matmul request f32 via preferred_element_type; conv relies on the TPU
# MXU's internal f32 accumulate (see ops/nn_ops.py).
_AMP_BF16_OPS = frozenset({
    "conv2d", "depthwise_conv2d", "conv2d_transpose", "mul", "matmul",
    "fused_attention"})
# `moe_ffn` is in neither table: one input feeds its float32 router and its
# bf16 experts, so its rule casts for itself (ops/parallel_ops.py).
# Numerically sensitive ops: force their float inputs back up to f32 so the
# loss/probability path never rounds through bf16. One of them is upcast
# only where it needs to be: `softmax_with_cross_entropy` on its kernel
# path with no dense Softmax (ops/nn_ops.softmax_xent_form) takes bf16
# logits as they are, because the kernel casts a tile to f32 in VMEM, which
# is exact, and a cast here is a float32 [N, V] array in HBM with that one
# reader; its loss and logsumexp are f32 all the same.
_AMP_F32_OPS = frozenset({
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "mean"})


def _amp_cast_ins(ins, dtype, from_dtype):
    def cast(v):
        if hasattr(v, "dtype") and v.dtype == from_dtype:
            return v.astype(dtype)
        return v
    return {slot: [cast(v) for v in vals] for slot, vals in ins.items()}


def _apply_amp(ctx, op_type, ins, attrs):
    if op_type in _AMP_BF16_OPS:
        return _amp_cast_ins(ins, jnp.bfloat16, jnp.float32)
    if op_type in _AMP_F32_OPS:
        if op_type == "softmax_with_cross_entropy":
            from ..ops.nn_ops import softmax_xent_form
            if softmax_xent_form(ctx, ins["Logits"][0], attrs) == (
                    "kernel", False):
                return ins
        return _amp_cast_ins(ins, jnp.float32, jnp.bfloat16)
    return ins


class LowerCtx(object):
    """Per-trace context handed to op lowering rules."""

    def __init__(self, program, base_key=None, is_startup=False, mesh=None):
        self.program = program
        self.base_key = base_key
        self.is_startup = is_startup
        self.is_abstract = False
        self.mesh = mesh
        self.amp = bool(getattr(program, "_amp", False))
        self._op_salt = 0
        self._op_calls = 0
        # the slots, among the lowering op's OpDef.optional_outputs, that
        # nothing reads (_unread_outputs): its rule may leave them out
        self.unread_outputs = frozenset()
        self._read_names = None
        # the names that leave the step (build_program_fn): fetches, state,
        # feeds. _unread_outputs counts them as read.
        self.leaves_step = frozenset()
        # traced iteration counters of enclosing lax.scan/while_loop bodies
        # (pushed by control-flow lowerings) — folded into every key so
        # dropout/random ops inside loops vary per time step.
        self._loop_iters = []
        # rng-only extra salts: folded into keys like _loop_iters but
        # WITHOUT suppressing add_error — for re-lowering the same ops at
        # top trace level (sequential pipeline stages), where assertions
        # can still escape but randomness must differ per replay.
        self._rng_extra = []
        # message -> traced bool flag: in-graph assertions raised host-side
        # after the step (same channel as TensorArray overflow). Sticky OR
        # per message.
        self.op_errors = {}
        # loops that recompute: (trips, kept ops) by sub-block; in one's body?
        self.recomputing_loops, self.in_recomputing_loop = {}, False
        # forward op uid -> (primal outputs, vjp_fn), for the forward ops of
        # the block being lowered whose grad ops call the vjp_fn the forward
        # op kept (see _linearizations); None until the forward op has
        # lowered. lower_block gives every block a dict of its own.
        self.linearized = {}

    def add_error(self, message, flag):
        """Record an in-graph assertion (checkify-style). Only valid at the
        top trace level — flags minted inside lax sub-block traces cannot
        escape them, so callers inside loops are skipped.

        A GUARD_STAT_PREFIX message carries a float STATISTIC, not a
        boolean assertion: it rides the same error channel (so it costs
        zero extra host syncs — the executor peels it off after dispatch)
        but folds with max instead of OR and never trips __any__."""
        if self._loop_iters:
            return
        prev = self.op_errors.get(message)
        if prev is None:
            self.op_errors[message] = flag
        elif is_stat_key(message):
            self.op_errors[message] = jnp.maximum(prev, flag)
        else:
            self.op_errors[message] = prev | flag

    def begin_op(self, salt):
        self._op_salt = salt
        self._op_calls = 0

    def rng(self, salt=0, seed=0):
        """Deterministic key derived from (run seed, op uid, call index within
        the op). Re-lowering the same forward op inside jax.vjp (a grad op's
        replay, a recomputing loop's body) replays the identical key stream,
        so dropout masks / random inits are grad-consistent and XLA CSE
        dedupes what it generated itself (not a Pallas kernel:
        _linearizations).

        A nonzero user `seed` (the op's seed attr — fluid's reproducibility
        contract) pins the key independent of the run counter, so the op
        produces identical randomness on every run of every process."""
        self._op_calls += 1
        base = jax.random.key(seed) if seed else self.base_key
        key = jax.random.fold_in(
            base,
            (self._op_salt * 1000003 + self._op_calls * 97 + salt) & 0x7FFFFFFF)
        for it in self._loop_iters:
            key = jax.random.fold_in(key, it)
        for it in self._rng_extra:
            key = jax.random.fold_in(key, it)
        return key


class EnvReadError(KeyError):
    """Env.read miss: a variable read before anything wrote it.
    Subclasses KeyError so existing broad handlers keep working, while
    lower_op can convert exactly THIS failure (and no other KeyError)
    into a readable annotated RuntimeError."""


class Env(object):
    """Name -> traced value mapping for one lowering pass.

    `constraints` ({name: NamedSharding}, optional) is the ShardingPlan's
    gradient-placement seam: every write/accumulate of a constrained name
    pins the traced value with `lax.with_sharding_constraint`, so GSPMD
    lowers a sharded param's gradient sum as reduce-scatter onto the
    owner's shard instead of a full all-reduce (parallel/plan.py
    grad_constraints; ARCHITECTURE.md §21). Applied per partial
    accumulation too — constraining each contribution keeps the running
    sum on the shard layout throughout the backward."""

    def __init__(self, constraints=None):
        self.values = {}
        self._constraints = constraints or None

    def _constrain(self, name, value):
        if self._constraints is not None and _is_traced_array(value):
            sharding = self._constraints.get(name)
            if sharding is not None:
                return jax.lax.with_sharding_constraint(value, sharding)
        return value

    def read(self, name):
        if name not in self.values:
            raise EnvReadError("variable %r read before it was written; "
                               "is it fed / initialized?" % name)
        return self.values[name]

    def read_opt(self, name):
        return self.values.get(name)

    def write(self, name, value):
        self.values[name] = self._constrain(name, value)

    def accumulate(self, name, value):
        cur = self.read_opt(name)
        self.values[name] = self._constrain(
            name, value if cur is None else cur + value)

    def __contains__(self, name):
        return name in self.values


def lower_block(ctx, block, env):
    from .readers import is_host_io_op
    ops = [op for op in block.ops if not is_host_io_op(op.type)]
    # host io ops are executed host-side by the Executor's io pre-pass
    outer = ctx.linearized
    ctx.linearized = _linearizations(ctx, ops)
    if block.idx == 0 and any(op.attrs.get("recompute") for op in ops):
        # a loop of this block replays its body (ops/control_ops.py counts
        # the body's ops): the forward ops around it are counted here
        _count_remat_ops("forward", [
            op for op in ops[:_first_backward_op(ops)]
            if not op.attrs.get("recompute")])
    try:
        for op in ops:
            lower_op(ctx, op, env)
    finally:
        ctx.linearized = outer


def _linearizations(ctx, ops):
    """{uid: None} for the forward ops among `ops` that lower under jax.vjp
    and keep their linearization, (primal outputs, vjp_fn), for the grad ops
    of the same block: those a `grad_of` op names whose rule can reach a
    Pallas kernel (OpDef.calls_pallas). A grad op differentiates its forward
    rule and so runs the rule's forward again; where XLA generated the
    forward it merges the two, a Mosaic custom call it runs twice, and a
    loop that recomputes its body (`recompute` on an rnn_scan op) as well.
    (INSIDE such a loop the same holds a trip: its checkpoint keeps the
    kernels' outputs, ops/control_ops.py keeps_across_passes.) Every other
    op keeps the replay: nothing would be gained on the device, and every
    program's HLO would change."""
    return {op.attrs["fwd_uid"]: None for op in ops
            if op.type == "grad_of" and "fwd_uid" in op.attrs
            and registry.is_registered(op.attrs["fwd_type"])
            and (registry.get(op.attrs["fwd_type"]).calls_pallas
                 or op.attrs["fwd_attrs"].get("recompute"))}


def _first_backward_op(ops):
    """Index of the first op of the backward region (a grad op, or one that
    writes a gradient), or None where there is none."""
    for i, op in enumerate(ops):
        if op.type == "grad_of" or any(
                n.endswith(GRAD_SUFFIX) for n in op.all_output_vars() if n):
            return i
    return None


def _is_traced_array(v):
    return isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer)


def _count_remat_ops(kind, ops, times=1):
    from ..observability.registry import REGISTRY
    counter = REGISTRY.counter(
        "ptpu_remat_ops_total",
        "forward ops of a program that recomputes, by fluid op type, as often "
        "as they run a step: `forward`, and `replayed` a second time in the "
        "backward pass (the body of a loop op that recomputes, a trip, LESS "
        "the ops whose kernel or matmul the loop keeps and does not run "
        "again: ptpu_remat_kept_values_total counts those)")
    for op in ops:
        counter.inc(times, kind=kind, op=op.type)


# Reserved env name carrying the OR of sub-block-confined TensorArray
# overflow flags. Control-flow lowerings thread it through their loop
# carries so flags raised inside nested lax bodies reach the top level.
PROGRAM_ERR = "__tensor_array_overflow__"

# Error-channel keys with this prefix carry float STATISTICS (e.g. the
# sentinel's global grad-norm scalar) instead of boolean assertion
# flags: they fold across steps with max (the K-block's worst value —
# exactly what a spike detector wants), are excluded from the __any__
# reduction, and are peeled off by the executor into `last_stats`
# before error unpacking. The \x00 prefix keeps the namespace disjoint
# from every human-readable assertion message.
GUARD_STAT_PREFIX = "\x00stat\x00"


def is_stat_key(message):
    return message.startswith(GUARD_STAT_PREFIX)


def fold_errors(acc, errors):
    """Accumulate one step's error dict into the running accumulator:
    sticky OR for assertion flags, max for GUARD_STAT_PREFIX stats."""
    return {m: (jnp.maximum(acc[m], errors[m]) if is_stat_key(m)
                else acc[m] | errors[m]) for m in acc}


def accumulate_error(env, flag):
    cur = env.read_opt(PROGRAM_ERR)
    env.write(PROGRAM_ERR, flag if cur is None else cur | flag)


def _annotate_op_error(e, op):
    """Append the failing op's identity and Python creation site
    (Operator.callstack — the reference's op_callstack attr) to a
    lowering-time exception, so errors escaping the trace point at the
    user's layer call instead of framework internals. Mutates the
    exception's message in place (type preserved); nested lower_op
    frames (sub-block bodies) each add one line, capped so a deep op
    stack can't bury the original message."""
    noted = getattr(e, "_op_notes", 0)
    if noted >= 3 or not e.args or not isinstance(e.args[0], str):
        return
    from .utils import format_callstack
    note = "\n  [while lowering op %r (uid %d)" % (op.type, op.uid)
    cs = getattr(op, "callstack", ())
    if cs and noted == 0:
        note += ", created at:\n%s]" % format_callstack(cs, prefix="    ")
    else:
        note += "]"
    e.args = (e.args[0] + note,) + e.args[1:]
    e._op_notes = noted + 1


# --- fluid-op scopes: device time gets the program's own names -------------
# Every op lowers inside one jax.named_scope, "op:<type>/<instance>", which
# reaches the compiled HLO as each instruction's metadata op_name (a fusion
# carries its root instruction's) and from there the profiler's device trace.
# The marker is what no jax primitive or transform can produce: `transpose`,
# `scale` and `sum` are fluid ops AND jax names, and a backward pass wraps
# everything in `transpose(jvp(...))`. A `grad_of` op is named by what it
# differentiates, `<fwd_type>_grad`; the instance is the op's first output
# variable. XLA cuts an op_name at its first "@" (its own `name@function`
# convention), so "x@GRAD" travels as "x~GRAD"; "/", "(" and ")" structure
# the path and turn into "_".
SCOPE_MARK = "op:"
_SCOPE_ESCAPES = str.maketrans({"@": "~", "/": "_", "(": "_", ")": "_"})
_SCOPE_RE = re.compile(r"(?:^|[/(])" + SCOPE_MARK + r"([^/()]+)/([^/()]+)")


# the attribute a model's builder writes on the ops that run a stack of
# layers over the same weights more than once (models/causal_lm.py): the
# passes the op holds, "1-4" on the loop op of four. A grad op has it with
# its forward op's attributes.
PASS_ATTR = "__pass__"
PASS_MARK = "pass:"
# the attribute a model's builder writes on ops whose device time belongs
# under a name of the model's own in the table by op type (the gate
# multiplies of models/causal_lm.py:short_conv): the type `op_scope` names
# the op by. A grad op has it with its forward op's attributes.
SCOPE_ATTR = "__scope__"
# the attribute a model's builder writes on the ops of a module that is not
# part of its stack of layers (models/causal_lm.py's multi-token-prediction
# module, "mtp.0"): `op_scope` puts it before the op's instance,
# "op:<type>/mtp.0.<first output>", so the table by instance tells the
# module's device time from the trunk's. A grad op has it with its forward
# op's attributes.
ROLE_ATTR = "__role__"
_PASS_RE = re.compile(r"(?:^|[/(])" + PASS_MARK + r"(\d+(?:-\d+)?)/")


def scope_type(op):
    """The type `op_scope` names an op by: its own, or the name a model
    gave it under SCOPE_ATTR (the two gate multiplies of a short_conv mixer
    read `short_conv`, not `elementwise_mul`); a `grad_of` op is
    `<that of its forward op>_grad`."""
    if op.type == "grad_of":
        return op.attrs.get("fwd_attrs", {}).get(
            SCOPE_ATTR, op.attrs["fwd_type"]) + "_grad"
    return op.attrs.get(SCOPE_ATTR, op.type)


def op_scope(op):
    """The named scope of one fluid op: "op:<type>/<instance>", under
    "pass:<passes>/" where the op runs passes of a looped stack; the
    instance behind "<role>." where the model gave the op one (ROLE_ATTR)."""
    instance = next((n for names in op.outputs.values() for n in names if n),
                    "-")
    attrs = op.attrs.get("fwd_attrs", ()) if op.type == "grad_of" \
        else op.attrs
    if ROLE_ATTR in attrs:
        instance = "%s.%s" % (attrs[ROLE_ATTR], instance)
    scope = "%s%s/%s" % (SCOPE_MARK, scope_type(op),
                         instance.translate(_SCOPE_ESCAPES))
    if PASS_ATTR in attrs:
        return "%s%s/%s" % (PASS_MARK, attrs[PASS_ATTR], scope)
    return scope


def parse_pass_scope(op_name):
    """The passes of a looped stack an HLO op_name path belongs to, as the
    program wrote them ("1-4": the innermost "pass:<passes>/" of the path),
    or None."""
    found = _PASS_RE.findall(op_name)
    return found[-1] if found else None


def parse_op_scope(op_name):
    """(type, instance) of the fluid op an HLO op_name path such as
    "jit(fn)/transpose(jvp(op:mul/fc_0.tmp_1))/dot_general" belongs to, or
    None where the path carries no fluid scope. That is the innermost scope
    of the path, with one exception: a grad op that calls the linearization
    its forward op kept (_linearizations) transposes equations traced under
    the FORWARD op's scope, "op:layer_norm_grad/x~GRAD/transpose(jvp(op:
    layer_norm/y))/mul", and those are the grad op's work. The inverse of
    `op_scope` for variable names free of "~", "/", "(" and ")"."""
    found = list(_SCOPE_RE.finditer(op_name))
    if not found:
        return None
    op_type, instance = found[-1].groups()
    for m in reversed(found[:-1]):
        if m.group(1) == op_type + "_grad" and "transpose(" in \
                op_name[m.end():found[-1].start() + 1]:
            op_type, instance = m.groups()
            break
    return op_type, instance.replace("~", "@")


# seconds of lower_op calls nested in the one running on this thread (a
# `while` or `conditional` op lowers its sub-block's ops through lower_op)
_nested_lowering = threading.local()


def lower_op(ctx, op, env):
    """Lower one op: the ONE site every rule passes, so also where the
    trace phase is split by fluid op type. Runs at trace time only; an
    op's seconds are its own Python time, less its sub-block's ops. The
    clock sits in this function and in no wrapper around it: jax records
    the Python stack with every equation it traces, so each frame between
    the jitted function and the rules is paid for by every rule (two more
    read 10 % more `jaxpr_trace_s` in the ResNet cell and 30 % more in the
    Qwen3-Next cell, whose kernels trace their bodies; my chip run, PR 35)."""
    t0 = time.perf_counter()
    outer = getattr(_nested_lowering, "seconds", 0.0)
    _nested_lowering.seconds = 0.0
    try:
        with jax.named_scope(op_scope(op)):
            _lower_op_inner(ctx, op, env)
    except EnvReadError as e:
        # str(KeyError) reprs its arg, which would render the multi-line
        # creation-site note as literal \n escapes — re-raise the
        # flagship Env.read failure (and ONLY it; ordinary KeyErrors from
        # rules keep their type) as RuntimeError, chained so the original
        # stays inspectable, and annotate THAT readably
        if not (e.args and isinstance(e.args[0], str)):
            raise
        err = RuntimeError(e.args[0])
        _annotate_op_error(err, op)
        raise err from e
    except Exception as e:
        _annotate_op_error(e, op)
        raise
    finally:
        total = time.perf_counter() - t0
        own = max(0.0, total - _nested_lowering.seconds)
        _nested_lowering.seconds = outer + total
        from ..observability.registry import REGISTRY
        REGISTRY.counter(
            "ptpu_lowering_seconds_total",
            "host seconds in lowering rules under jax's trace, by fluid op "
            "type as op_scope names it (a grad op is <fwd>_grad), less the "
            "ops of an op's sub-block").inc(own, op=scope_type(op))


def _lower_op_inner(ctx, op, env):
    if op.type in _SPECIAL:
        _SPECIAL[op.type](ctx, op, env)
        return
    if op.type == "grad_of":
        _lower_grad_of(ctx, op, env)
        return
    od = registry.get(op.type)
    ins = {slot: [env.read(n) for n in names]
           for slot, names in op.inputs.items()}
    ctx.unread_outputs = _unread_outputs(ctx, od, op.outputs)
    if od.counts is not None:
        od.counts(ctx, op.attrs, ins)
    if op.uid in ctx.linearized:
        # a grad op of this block differentiates this op: run the rule once,
        # under jax.vjp, and keep what the backward needs
        f, primal, out_order = _differentiable(
            ctx, od, op_scope(op), op.type, op.attrs, op.uid, ins,
            _out_order(op.outputs))
        primals, vjp_fn, err = jax.vjp(f, primal, has_aux=True)
        ctx.linearized[op.uid] = (primals, vjp_fn, out_order)
        outs = {slot: [None] * len(names)
                for slot, names in op.outputs.items()}
        for (slot, i, _), p in zip(out_order, primals):
            outs[slot][i] = p
    else:
        if ctx.amp:
            ins = _apply_amp(ctx, op.type, ins, op.attrs)
        ctx.begin_op(op.uid)
        outs = od.lower(ctx, ins, op.attrs)
        err = outs.pop("__errors__", None) if isinstance(outs, dict) else None
    if err is not None:
        accumulate_error(env, err)
    _write_outputs(op, outs, env)


def _write_outputs(op, outs, env):
    acc = op.attrs.get("__accumulate_outputs__", False)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for name, val in zip(names, vals):
            if not name:
                continue
            if acc:
                env.accumulate(name, val)
            else:
                env.write(name, val)


def _is_float(x):
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


def _grad_reorder_by_rank(ctx, op, env):
    """Gradient of reorder_lod_tensor_by_rank: the backward of a row
    permutation is the inverse permutation (reference:
    reorder_lod_tensor_op.cc grad kernel reorders with the inverted rank
    table). Structure-only companions (XLen) carry no grad."""
    fwd_inputs = op.attrs["fwd_inputs"]
    fwd_outputs = op.attrs["fwd_outputs"]
    rt = env.read(fwd_inputs["RankTable"][0])
    og = env.read_opt(fwd_outputs["Out"][0] + GRAD_SUFFIX)
    if og is None:
        return
    xname = fwd_inputs["X"][0]
    if xname in op.attrs.get("no_grad_names", ()):
        return
    inv = jnp.argsort(rt.index)
    env.accumulate(xname + GRAD_SUFFIX, jnp.take(og, inv, axis=0))


# Special (graph-level) forward lowerings that cannot ride the generic
# jax.vjp-of-the-rule path but ARE differentiable: hand-written grad
# emitters keyed by forward op type, plus the input slots that actually
# receive grads (backward.py must not declare @GRAD vars for
# structure-only slots like RankTable — a declared grad marks its
# producer differentiable and would poison the upstream sweep).
SPECIAL_GRADS = {
    "reorder_lod_tensor_by_rank": {"fn": _grad_reorder_by_rank,
                                   "diff_slots": ("X",)},
}


def _out_order(outputs):
    """A forward op's named outputs in a deterministic order: [(slot, index,
    name)]. The differentiated function returns them so (less the slots its
    rule left out: _differentiable), and only the floating-point ones carry
    cotangents."""
    return [(slot, i, n)
            for slot, names in sorted(outputs.items())
            for i, n in enumerate(names) if n]


def _unread_outputs(ctx, od, outputs):
    """The slots among `od.optional_outputs` whose variables nothing can
    see: no op of any block of the program reads one (a grad op names its
    forward op's inputs and its outputs' gradients, not the outputs) or
    names one in a list attribute (a control-flow op's `out_names`,
    `carry_names`: variables of its sub-block), none is fetched and none is
    persistable state. A fact of the Program and of the call's fetch list;
    the rule reads it as ctx.unread_outputs."""
    if not od.optional_outputs:
        return frozenset()
    if ctx._read_names is None:
        # what leaves the step (build_program_fn's leaves_step) and what an
        # op reads
        read = set(ctx.leaves_step)
        for block in ctx.program.blocks:
            for op in block.ops:
                read.update(op.all_input_vars())
                for v in op.attrs.values():
                    if isinstance(v, (list, tuple)):
                        read.update(n for n in v if isinstance(n, str))
        ctx._read_names = read
    return frozenset(
        slot for slot in od.optional_outputs
        if not any(n in ctx._read_names for n in outputs.get(slot, ()) if n))


def _differentiable(ctx, od, scope, op_type, attrs, uid, in_vals, out_order):
    """(f, primal, built): a forward op's rule as a function of its
    floating-point inputs `primal` ({(slot, index): value}), for jax.vjp
    with has_aux. f returns the outputs in `out_order` and, as auxiliary
    data, the rule's `__errors__` flag or None. `built` is filled when f is
    traced: `out_order` less the slots the rule left out (only ones named in
    ctx.unread_outputs: _unread_outputs), which are then no results of the
    differentiated function and get no cotangent. One builder for both
    users: the forward op that keeps its linearization (_lower_op_inner) and
    the grad op that replays the forward (_lower_grad_of), so the two
    differentiate the same function.

    `scope` is the lowering op's own scope, opened once more inside f: jax
    renders a transform around the first scope inside it, and without this
    one that is a Pallas kernel's own name scope, whose HLO instruction
    would be `jvp_ptpu_layer_norm_fwd_` and not `ptpu_layer_norm_fwd`."""
    primal = {(slot, i): v for slot, vals in in_vals.items()
              for i, v in enumerate(vals) if _is_float(v)}
    unread = ctx.unread_outputs     # the caller's, for this op
    built = []

    def f(diff):
        ins = {slot: list(vals) for slot, vals in in_vals.items()}
        for (slot, i), v in diff.items():
            ins[slot][i] = v
        ctx.unread_outputs = unread
        if ctx.amp:
            # the casts live inside the vjp, so bf16 ops get bf16 activation
            # cotangents while f32 master params receive f32 grads (the vjp
            # of the f32->bf16 cast upcasts)
            ins = _apply_amp(ctx, op_type, ins, attrs)
        ctx.begin_op(uid)  # the forward op's exact PRNG stream
        with jax.named_scope(scope):
            outs = od.lower(ctx, ins, attrs)
        built[:] = [o for o in out_order
                    if o[0] in outs or o[0] not in unread]
        return ([outs[slot][i] for slot, i, _ in built],
                outs.get("__errors__"))

    return f, primal, built


def _count_grad_op(path, fwd_type):
    from ..observability.registry import REGISTRY
    REGISTRY.counter(
        "ptpu_lowering_grad_ops_total",
        "grad ops lowered, by forward op type and by whether the op used the "
        "linearization its forward op kept or replayed the forward rule"
    ).inc(path=path, op=fwd_type)


def _lower_grad_of(ctx, op, env):
    """Lower a generic gradient op: the vjp of the forward op's rule.

    The grad op (built by core/backward.py) carries the forward op's type,
    attrs, uid and input/output name maps. Where the forward op kept its
    linearization (ctx.linearized, see _linearizations) the grad op calls that
    vjp_fn; everywhere else it replays the rule under jax.vjp here.
    Cotangents for forward outputs come from env (<out>@GRAD); outputs
    missing a grad var get zeros. Produced input grads are ACCUMULATED into
    <in>@GRAD names, which is correct because backward.py emits grad ops in
    reverse topological order.
    """
    fwd_type = op.attrs["fwd_type"]
    if fwd_type in SPECIAL_GRADS:
        SPECIAL_GRADS[fwd_type]["fn"](ctx, op, env)
        return
    fwd_inputs = op.attrs["fwd_inputs"]    # slot -> [names]
    fwd_outputs = op.attrs["fwd_outputs"]  # slot -> [names]
    # read, not popped: calc_gradient may differentiate one op twice
    kept = ctx.linearized.get(op.attrs.get("fwd_uid"))
    if kept is not None:
        primals, vjp_fn, out_order = kept
    else:
        fwd_in_vals = {slot: [env.read(n) for n in names]
                       for slot, names in fwd_inputs.items()}
        # as its forward op lowered: the slots that one left out are no
        # results of the function differentiated here either
        od = registry.get(fwd_type)
        ctx.unread_outputs = _unread_outputs(ctx, od, fwd_outputs)
        f, primal, out_order = _differentiable(
            ctx, od, op_scope(op), fwd_type,
            op.attrs["fwd_attrs"], op.attrs.get("fwd_uid", 0), fwd_in_vals,
            _out_order(fwd_outputs))
        primals, vjp_fn, _ = jax.vjp(f, primal, has_aux=True)
    _count_grad_op("replayed" if kept is None else "kept", fwd_type)
    if kept is not None and op.attrs["fwd_attrs"].get("recompute"):
        from ..ops.control_ops import count_loop_ops
        count_loop_ops(ctx, "replayed", op.attrs["fwd_attrs"])

    cotangents = []
    for (slot, i, n), p in zip(out_order, primals):
        g = env.read_opt(n + GRAD_SUFFIX)
        if not _is_float(p):
            g = jnp.zeros(p.shape, jax.dtypes.float0)
        elif g is None:
            g = jnp.zeros_like(p)
        else:
            g = jnp.asarray(g, p.dtype)
            if g.shape != p.shape:
                g = jnp.broadcast_to(g, p.shape)
        cotangents.append(g)

    in_grads = vjp_fn(cotangents)[0]

    for (slot, i), g in in_grads.items():
        names = fwd_inputs[slot]
        name = names[i]
        stop = op.attrs.get("no_grad_names", ())
        if name in stop:
            continue
        env.accumulate(name + GRAD_SUFFIX, g)


def build_program_fn(program, feed_names, fetch_names, state_rw, state_ro,
                     state_out, mesh=None, collect_errors=False,
                     shard_constraints=None):
    """Build the pure function for a Program.

    fn(feed_vals, state_rw_vals, state_ro_vals, seed)
        -> (fetch_vals, new_state_vals)            # collect_errors=False
        -> (new_state_vals, fetch_vals, errors)    # collect_errors=True

    collect_errors=True is the form the executors jit (jit_step), and it
    returns the state FIRST: the order of a donating jit's results decides
    which donated buffer each takes (see jit_step).

    state_rw: persistable vars both read and overwritten — safe to donate
    (in-place parameter update on device). state_ro: read-only persistables
    (e.g. the learning-rate var) — must NOT be donated, the Scope keeps them.
    state_out: all persistables written (order of the returned new state;
    analyze_state puts state_rw first — the donation contract, see jit_step).

    errors is a {message: bool_scalar} dict of in-graph assertion flags
    (e.g. TensorArray capacity overflows) the caller must raise on — the
    checkify-style escape hatch for conditions only detectable inside lax
    control flow, where Python can't raise.

    shard_constraints ({var name: NamedSharding}, ParallelExecutor only):
    values written under these names are pinned with
    with_sharding_constraint as they are produced — the ShardingPlan's
    gradient reduce-scatter placement (see Env).
    """
    def fn(feed_vals, state_rw_vals, state_ro_vals, seed):
        base_key = jax.random.fold_in(
            jax.random.key(program.random_seed), seed)
        ctx = LowerCtx(program, base_key=base_key, mesh=mesh)
        # what _unread_outputs must count as read though no op reads it:
        # externally observed values (fetches, persistable state) and
        # everything fed from outside
        ctx.leaves_step = (set(fetch_names) | set(state_out) | set(state_rw)
                           | set(state_ro) | set(feed_names))
        env = Env(constraints=shard_constraints)
        for n, v in zip(feed_names, feed_vals):
            env.write(n, v)
        for n, v in zip(state_rw, state_rw_vals):
            env.write(n, v)
        for n, v in zip(state_ro, state_ro_vals):
            env.write(n, v)
        lower_block(ctx, program.global_block(), env)
        fetches = [env.read(n) for n in fetch_names]
        new_state = [env.read(n) for n in state_out]
        if collect_errors:
            from ..ops.control_ops import TensorArray
            errors = {}
            for name, v in env.values.items():
                if isinstance(v, TensorArray):
                    errors["tensor array %r overflowed its capacity %d "
                           "inside traced control flow; pass a larger "
                           "capacity to create_array()"
                           % (name, v.buffer.shape[0])] = v.overflow
            sub_err = env.read_opt(PROGRAM_ERR)
            if sub_err is not None:
                errors["a tensor array confined to a loop/conditional "
                       "sub-block overflowed its capacity inside traced "
                       "control flow; pass a larger capacity to "
                       "create_array()"] = sub_err
            errors.update(ctx.op_errors)
            if errors:
                # one combined scalar: the caller host-syncs only this in
                # the common (no-error) case, per-message flags only after
                # it trips. A key may carry a VECTOR of flags under a
                # \x00-joined message list (check_finite_guard packs all
                # its per-var flags into one [N] output — N+1 scalar
                # outputs cost real per-dispatch marshalling time);
                # vectors fold in via .any() so __any__ stays scalar.
                # GUARD_STAT_PREFIX entries are float statistics riding
                # the channel, not assertions — they never trip __any__.
                any_flag = jnp.asarray(False)
                for m, f in errors.items():
                    if is_stat_key(m):
                        continue
                    any_flag = any_flag | (
                        f.any() if getattr(f, "ndim", 0) else f)
                errors["__any__"] = any_flag
            return new_state, fetches, errors
        return fetches, new_state

    return fn


# fetch-reduce policies for multi-step execution: how K per-step fetch
# values collapse into the one value the host sees per K-step call
FETCH_REDUCE_POLICIES = ("last", "mean", "stack")


def _mean_acc_dtype(dtype):
    """Accumulation dtype for fetch_reduce='mean': float fetches accumulate
    in (at least) f32 so K bf16 losses don't round to garbage; f64 stays
    f64; bool/int fetches also go through f32 — their mean is a rate."""
    d = jnp.dtype(dtype)
    if jnp.issubdtype(d, jnp.floating):
        return jnp.promote_types(d, jnp.float32)
    return jnp.dtype(jnp.float32)


def multistep_unroll_flag():
    """FLAGS_multistep_unroll: how the K-step loop lowers. Unset/'' = auto
    (unroll on the CPU backend, lax.scan elsewhere): XLA:CPU does not
    intra-op-parallelize ops inside while-loop bodies, so a scanned conv
    step runs single-threaded — measured 9x slower than dispatching the
    steps one by one on ResNet-50 — while TPU loops have no such penalty
    and the scan keeps ONE copy of the step in the module (compile time:
    87s unrolled vs 12s scanned for K=8 ResNet-50 on CPU). '1' forces
    unroll (lets XLA fuse across step boundaries at K-times the compile
    time), '0' forces the scan. Anything else raises LOUDLY (the
    FLAGS_conv_layout rule: a typo must not silently bank numbers under
    the wrong configuration)."""
    import os
    v = os.environ.get("FLAGS_multistep_unroll", "")
    if v == "":
        return None
    if v in ("0", "1"):
        return v == "1"
    raise ValueError(
        "FLAGS_multistep_unroll=%r: expected '' (auto), '0' (lax.scan) "
        "or '1' (full unroll)" % v)


def resolve_multistep_unroll(platform=None):
    """platform: the platform string of the device the program will
    actually DISPATCH to (Executor: place.device().platform;
    ParallelExecutor: the mesh's devices) — not jax.default_backend(),
    which can be 'tpu' while an Executor(CPUPlace()) runs the loop on
    the CPU backend and needs the unrolled lowering."""
    flag = multistep_unroll_flag()
    if flag is not None:
        return flag
    if platform is None:
        platform = jax.default_backend()
    return platform == "cpu"


def lower_multi_step(program, feed_names, fetch_names, state_rw, state_ro,
                     state_out, steps, fetch_reduce="stack",
                     stacked_feed_names=(), mesh=None, unroll=False,
                     shard_constraints=None):
    """K-step device-resident training loop around build_program_fn.

    Returns fn(feed_vals, state_rw_vals, state_ro_vals, seed) with the SAME
    signature and return shape as the single-step collect_errors=True fn —
    (new_state_vals, fetch_vals, errors) — but internally a lax.scan runs
    the step K times with state kept on device: the host syncs once per K
    steps instead of once per step, which is the whole point (TensorFlow's
    in-graph loops made the same move against per-step dispatch).

    Semantics contract (tests/unittests/test_multi_step_executor.py):
      * bit-identical to K sequential single-step calls — step i runs with
        seed+i, exactly the seed sequence Scope.next_seed would have issued,
        so PRNG streams (dropout masks, random inits) line up;
      * feeds in `stacked_feed_names` carry a leading K axis and are sliced
        per step by the scan (the reader pre-staging path); all other feeds
        are closed over and replayed identically every step;
      * in-graph assertion flags are ORed across steps (sticky): a flag
        tripped at step j < K still raises from the K-step call;
      * fetches collapse per `fetch_reduce`: 'last' (step K-1's value),
        'mean' (f32-accumulated mean over K), 'stack' (leading-K stack).

    The scan body traces the program ONCE (one copy of the step in the XLA
    module); loop-carry placeholders for write-only state come from a cheap
    abstract jax.eval_shape of the step, not a second lowering. With
    unroll=True the K steps are emitted as K top-level copies instead of a
    scan — see multistep_unroll_flag for why the CPU backend needs that.
    """
    if steps < 1:
        raise ValueError("steps must be >= 1, got %r" % (steps,))
    if fetch_reduce not in FETCH_REDUCE_POLICIES:
        raise ValueError("fetch_reduce must be one of %r, got %r"
                         % (FETCH_REDUCE_POLICIES, fetch_reduce))
    step_fn = build_program_fn(program, feed_names, fetch_names, state_rw,
                               state_ro, state_out, mesh=mesh,
                               collect_errors=True,
                               shard_constraints=shard_constraints)
    # analyze_state's contract: state_out is state_rw, then write-only names
    n_rw = len(state_rw)
    if list(state_out[:n_rw]) != list(state_rw):
        raise ValueError("state_out must start with state_rw (the order "
                         "lowering.analyze_state returns)")
    stacked = frozenset(stacked_feed_names)

    def fn(feed_vals, state_rw_vals, state_ro_vals, seed):
        def step_feeds(pick):
            return [pick(n, v) for n, v in zip(feed_names, feed_vals)]

        if unroll:
            state = None
            fetch_acc = err_acc = None
            per_step = []
            for i in range(steps):
                cur_feeds = step_feeds(
                    lambda n, v, i=i: v[i] if n in stacked else v)
                rw_vals = state_rw_vals if state is None else state[:n_rw]
                state, fetches, errors = step_fn(
                    cur_feeds, rw_vals, state_ro_vals,
                    jnp.asarray(seed, jnp.uint32) + jnp.uint32(i))
                err_acc = errors if err_acc is None else \
                    fold_errors(err_acc, errors)
                if fetch_reduce == "mean":
                    fetch_acc = (
                        [f.astype(_mean_acc_dtype(f.dtype)) for f in fetches]
                        if fetch_acc is None else
                        [a + f.astype(a.dtype)
                         for a, f in zip(fetch_acc, fetches)])
                elif fetch_reduce == "last":
                    fetch_acc = list(fetches)
                else:
                    per_step.append(fetches)
            if fetch_reduce == "mean":
                fetches = [a / steps for a in fetch_acc]
            elif fetch_reduce == "last":
                fetches = fetch_acc
            else:
                fetches = [jnp.stack([stp[j] for stp in per_step])
                           for j in range(len(fetch_names))]
            return list(state), fetches, err_acc

        # shapes/dtypes of one step's outputs (abstract trace — no XLA)
        state_sh, fetch_sh, err_sh = jax.eval_shape(
            step_fn,
            step_feeds(lambda n, v: jax.ShapeDtypeStruct(
                v.shape[1:] if n in stacked else v.shape, v.dtype)),
            state_rw_vals, state_ro_vals, jnp.uint32(0))
        # loop carry: full state_out row. rw names start from the scope's
        # values; write-only names are overwritten before anyone reads them,
        # so zeros of the right aval satisfy scan's carry typing.
        init_state = list(state_rw_vals) + [
            jnp.zeros(s.shape, s.dtype) for s in state_sh[n_rw:]]
        if fetch_reduce == "mean":
            init_fetch = [jnp.zeros(s.shape, _mean_acc_dtype(s.dtype))
                          for s in fetch_sh]
        elif fetch_reduce == "last":
            init_fetch = [jnp.zeros(s.shape, s.dtype) for s in fetch_sh]
        else:
            init_fetch = []
        init_err = {m: jnp.zeros(s.shape, s.dtype)
                    for m, s in err_sh.items()}
        # step i's seed = seed + i: the exact sequence K sequential run()
        # calls would have drawn from Scope.next_seed (uint32 wrap and all)
        seeds = jnp.asarray(seed, jnp.uint32) + jnp.arange(
            steps, dtype=jnp.uint32)
        xs_feeds = tuple(v for n, v in zip(feed_names, feed_vals)
                         if n in stacked)

        def body(carry, x):
            state_vals, fetch_acc, err_acc = carry
            step_seed, cur_stacked = x
            it = iter(cur_stacked)
            cur_feeds = step_feeds(
                lambda n, v: next(it) if n in stacked else v)
            new_state, fetches, errors = step_fn(
                cur_feeds, state_vals[:n_rw], state_ro_vals, step_seed)
            err_acc = fold_errors(err_acc, errors)
            if fetch_reduce == "mean":
                fetch_acc = [a + f.astype(a.dtype)
                             for a, f in zip(fetch_acc, fetches)]
                ys = ()
            elif fetch_reduce == "last":
                fetch_acc = [jnp.asarray(f, a.dtype)
                             for a, f in zip(fetch_acc, fetches)]
                ys = ()
            else:
                ys = tuple(fetches)
            return (list(new_state), fetch_acc, err_acc), ys

        (final_state, fetch_acc, err_acc), ys = jax.lax.scan(
            body, (init_state, init_fetch, init_err), (seeds, xs_feeds))
        if fetch_reduce == "mean":
            fetches = [a / steps for a in fetch_acc]
        elif fetch_reduce == "last":
            fetches = fetch_acc
        else:
            fetches = list(ys)
        return final_state, fetches, err_acc

    return fn


def analyze_state(program, feed_names, fetch_names=()):
    """Decide which persistable vars are program state (static analysis).

    Returns (state_rw, state_ro, state_out):
      state_rw — read from Scope AND overwritten (donate: in-place update),
                 in order of first write
      state_ro — read from Scope, never written (do not donate)
      state_out — all persistables written (order of returned new state):
                  state_rw IN ITS OWN ORDER, then the write-only names in
                  order of first write

    state_out's order is a contract with jax's donation, not an accident of
    the op walk: jax gives each result, in order, the first free donated
    argument of its shape and dtype, so only results that come in the
    arguments' order get their own variable's buffer (see jit_step). Any
    one order for both lists holds that; the order of first write is the
    one measured fastest of four where the order is all that moves
    (resnet50_train_b256, PERF.md section 6, PR 56: XLA breaks its
    scheduling ties by it).

    `fetch_names` count as reads: fetching a persistable var no op produces
    (the evaluator.eval pattern — an empty program fetching state) reads it
    straight from the Scope."""
    feed = set(feed_names)
    written = set()
    state_in = []
    state_out = []
    seen_out = set()

    def visit_read(name):
        if name in feed or name in written or name in seen_in:
            return
        v = _find_var(program, name)
        if v is not None and v.persistable:
            seen_in.add(name)
            state_in.append(name)

    seen_in = set()
    for op in _all_ops(program):
        for name in op.all_input_vars():
            visit_read(name)
        for name in op.all_output_vars():
            if not name:
                continue
            written.add(name)
            v = _find_var(program, name)
            if v is not None and v.persistable and name not in seen_out:
                seen_out.add(name)
                state_out.append(name)
    # fetches of persistable vars NO op writes read straight from the Scope
    # (evaluator.eval: empty program fetching accumulated state). Processed
    # after the op walk so fetching a var this program produces stays a
    # plain fetch, not a scope read.
    for name in fetch_names:
        visit_read(name)
    state_rw = [n for n in state_out if n in seen_in]
    state_ro = [n for n in state_in if n not in seen_out]
    write_only = [n for n in state_out if n not in seen_in]
    return state_rw, state_ro, state_rw + write_only


def donation_pairing(donated, results):
    """Which result jax gives each donated argument's buffer to, by name.

    donated: [(name, shape, dtype)] of the donated arguments, in argument
    order. results: [(name or None, shape, dtype)] of the flattened results,
    in result order. Returns {donated name: "own" | "other" | "none"}: the
    buffer went to the result of the same name, to some other result, to
    none.

    The rule is jax's (jax/_src/interpreters/mlir.py _set_up_aliases):
    walking the results in order, each takes the first donated argument of
    its (shape, dtype) that no earlier result took. jax knows no names, so
    tests/unittests/test_donation_pairing.py holds this copy of the rule to
    the tf.aliasing_output attributes jax wrote into the lowered module."""
    free = collections.defaultdict(collections.deque)
    for name, shape, dtype in donated:
        free[tuple(shape), jnp.dtype(dtype)].append(name)
    paired = {name: "none" for name, _, _ in donated}
    for name, shape, dtype in results:
        queue = free.get((tuple(shape), jnp.dtype(dtype)))
        if queue:
            taken = queue.popleft()
            paired[taken] = "own" if taken == name else "other"
    return paired


def jit_step(fn, **jit_kwargs):
    """jax.jit of an executor's step (build_program_fn with
    collect_errors=True, or lower_multi_step) with state_rw donated: the
    one place that donates, and the reason the step returns

        (new_state_vals, fetch_vals, errors)

    the state FIRST, in analyze_state's order (state_rw, then the
    write-only names, each in order of first write). jax pairs donated
    buffers with results first come, first served within a (shape, dtype)
    class (donation_pairing), so this order gives every state_rw buffer to
    its own variable's new value and the update runs in place. A fetch or
    an error statistic of a parameter's shape comes after every state result
    and can take only a buffer no variable claimed; so can a write-only
    persistable, which has no buffer of its own. In any other order XLA must
    copy each mispaired new value out of the way of a buffer that is still
    being read: one copy of every parameter and moment a step.

    A variable whose new value has another shape or dtype than its old one
    cannot alias its own buffer; nothing is done about it. Its new value
    takes the buffer of the next variable of the NEW class, which shifts
    that class by one from there on, and
    ptpu_donated_state_buffers_total{paired="other"} shows it.

    `fn` itself is jitted, under no wrapper: a Python frame between the jit
    and the rules costs every traced equation (a wrapper that swapped the
    results read +0.6 to +1.9 s of a first step, PERF.md section 6, PR 56).

    jit_kwargs: in_shardings / out_shardings (ParallelExecutor), in the
    step's argument and result order."""
    return jax.jit(fn, donate_argnums=(1,), **jit_kwargs)


def count_donated_buffers(state_rw, state_rw_vals, state_out, new_state,
                          others):
    """Books ptpu_donated_state_buffers_total for one compiled step: the
    executors call it once a compile (never on the warm path) with the
    values they passed and got. state_rw_vals may be the donated, deleted
    arrays: only their types are read. `others`: the step's results after
    the state (fetches, errors)."""
    from ..observability.registry import REGISTRY
    paired = donation_pairing(
        _named_avals(state_rw, state_rw_vals),
        _named_avals(state_out, new_state)
        + _named_avals(itertools.repeat(None),
                       jax.tree_util.tree_leaves(others)))
    counter = REGISTRY.counter(
        "ptpu_donated_state_buffers_total",
        "state_rw buffers donated to a compiled step, by the result jax "
        "aliased each to: the same variable's new value (own: updated in "
        "place), another result (other: XLA copies), none")
    for how, buffers in collections.Counter(paired.values()).items():
        counter.inc(buffers, paired=how)


def _named_avals(names, vals):
    """[(name, shape, dtype)] of the arrays in `vals`, as jit flattens
    them."""
    return [(n, t.shape, t.dtype) for n, v in zip(names, vals)
            for t in map(jax.typeof, jax.tree_util.tree_leaves(v))]


def build_slot_update_fn():
    """One donated row-writer for decode slot state (serving.DecodeEngine).

    fn(state_vals, slot, row_vals) -> new_state_vals

    state_vals: tuple of [slots, ...] device arrays (the carried decode
    state — KV caches, hidden state, token cursors); slot: scalar row
    index; row_vals: tuple of per-var rows (shape state.shape[1:]).
    Every state array gets ONE row overwritten via
    dynamic_update_index_in_dim with the state buffers DONATED, so an
    admit/reset touches one row in place without copying or host-syncing
    the other slots' live state — the other rows' bits flow through
    untouched, which is exactly the per-slot reset-on-admit obligation
    of the bucket-lattice invariant (ARCHITECTURE §27).

    One jit serves every (engine, admit) at the same avals; pass `slot`
    as a numpy scalar so the index is traced, not baked into the
    executable."""
    def _update(state_vals, slot, row_vals):
        out = []
        for s, r in zip(state_vals, row_vals):
            out.append(jax.lax.dynamic_update_index_in_dim(
                s, jnp.asarray(r, s.dtype), slot, axis=0))
        return tuple(out)
    return jax.jit(_update, donate_argnums=(0,))


def _all_ops(program):
    # grad_of ops list their reads (fwd inputs + out-grads) in op.inputs, so a
    # plain walk sees every data dependency (backward.py guarantees this).
    # Host io ops (readers) are excluded: their reader vars hold host-side
    # ReaderState, never traced arrays, and `read` outputs arrive as feeds.
    from .readers import is_host_io_op
    for block in program.blocks:
        for op in block.ops:
            if not is_host_io_op(op.type):
                yield op


