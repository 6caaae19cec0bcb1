"""Operator registry: op type -> JAX lowering rule (+ optional shape inference).

Parity: the reference's OpInfoMap / OpKernel registration
(paddle/fluid/framework/op_registry.h, op_info.cc). Where the reference
registers separate CPU/CUDA kernels per op and grad-op kernels per grad op,
here each op registers ONE pure-JAX lowering rule; XLA specializes it per
backend, and the backward pass derives gradients from the same rule via
jax.vjp (see core/lowering.py) so no per-op grad kernels exist at all.

Shape inference (the reference's InferShape methods) is generic: run the
lowering rule under jax.eval_shape on ShapeDtypeStructs. A custom `infer`
overrides it in two cases. (1) The output shape can't be derived that way:
data-dependent shapes, sub-block ops. (2) Tracing costs more than the answer
is worth: a rule that holds a Pallas kernel, a loop or more than a few
milliseconds of trace, and whose outputs are shaped like its inputs, writes
the shapes down (`shapes_from`, a table of output slot -> input slot;
`set_like` under it). The trace is the whole rule, kernel body included,
twice where a dim is -1, once an op, and the step traces the same rule
again: fused_attention cost 2.7 s of an 18-layer program's build that way
(PERF.md section 6, PR 53). Such an `infer` must equal what tracing gives
for every program, -1 and the sentinel shapes included
(tests/unittests/test_infer_parity.py holds each to `abstract_eval`); it
imports no kernel module; the static analyzer, which re-derives shapes by
`abstract_eval`, skips an op that has one. "Zero per-op code in the common
case" stands: an elementwise op, a matmul, a reshape trace in a millisecond
or two and have no `infer`.
"""
import time

import numpy as np

from ..observability.registry import REGISTRY

# sentinels substituted for the dynamic batch dim (-1) during abstract shape
# inference. Outputs are inferred under BOTH primes; any output dim that
# DIFFERS between the two runs is batch-derived (even when folded into a
# product by reshape/flatten, e.g. [-1, K] -> [-1*K]) and maps back to -1,
# while dims that agree are genuinely static — so no literal feature size,
# multiple of a sentinel or not, can be miscategorized.
BATCH_SENTINEL = 1021
BATCH_SENTINEL_B = 1031


def int_dtype():
    """int64 when x64 is enabled, else a warning-free int32 (shared by
    lowering rules that declare int64 outputs)."""
    import jax
    import jax.numpy as jnp
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def squeeze_label(label):
    """[B, T, 1] int label tensor -> [B, T] int32 (shared by CRF/CTC ops)."""
    import jax.numpy as jnp
    if label.ndim == 3 and label.shape[-1] == 1:
        label = label.reshape(label.shape[0], label.shape[1])
    return label.astype(jnp.int32)


class OpDef(object):
    def __init__(self, type, lower, infer=None, uses_rng=False,
                 calls_pallas=False, optional_outputs=()):
        self.type = type
        self.lower = lower
        self.infer = infer
        self.uses_rng = uses_rng
        # the rule can reach a Pallas kernel (it imports ops/pallas_kernels).
        # XLA does not merge two runs of one Mosaic custom call as it does
        # two copies of an op it generated itself, so the lowering keeps
        # such an op's linearization for its grad op and does not run the
        # rule again there (core/lowering.py: _linearizations)
        self.calls_pallas = calls_pallas
        # output slots the rule builds only where the program reads them:
        # the lowering names the unread ones in ctx.unread_outputs, the
        # rule may leave those out, and such a slot is then no result of
        # the function its grad op differentiates (core/lowering.py:
        # _unread_outputs)
        self.optional_outputs = tuple(optional_outputs)
        # what the op reports of a forward lowering, `counts(ctx, attrs,
        # ins)`, defined beside the rule and the decider it reads and set by
        # `@counts(type)`; None for an op that reports nothing.
        # core/lowering.py calls it for a forward op alone (not for a grad
        # op's replay, not under shape inference) and names no op
        self.counts = None


_OPS = {}


def register(type, lower=None, infer=None, uses_rng=False,
             calls_pallas=False, optional_outputs=()):
    """Register an op. Usable as decorator: @register('relu')."""
    def deco(fn):
        _OPS[type] = OpDef(type, fn, infer=infer, uses_rng=uses_rng,
                           calls_pallas=calls_pallas,
                           optional_outputs=optional_outputs)
        return fn
    if lower is not None:
        return deco(lower)
    return deco


def counts(type):
    """Decorator: the function that counts a forward lowering of the
    registered op `type` (`OpDef.counts`), in the module of its rule:
    @counts('moe_ffn') above `def _count_moe_layer(ctx, attrs, ins)`."""
    def deco(fn):
        _OPS[type].counts = fn
        return fn
    return deco


def suggest(type, n=3):
    """Registered op names close to `type` (difflib), best match first.
    Shared by `get`'s error message and the analyzer's unregistered-op
    diagnostic so both always agree on the hint."""
    import difflib
    return difflib.get_close_matches(type, sorted(_OPS), n=n, cutoff=0.6)


def get(type):
    od = _OPS.get(type)
    if od is None:
        close = suggest(type)
        raise NotImplementedError(
            "op %r has no registered TPU lowering%s" %
            (type, ("; did you mean %s?" %
                    " / ".join(repr(c) for c in close)) if close else ""))
    return od


def is_registered(type):
    return type in _OPS


def single(ins, slot, default=None):
    """Fetch the single value of an input slot (helper for lowering rules)."""
    vs = ins.get(slot)
    if not vs:
        return default
    return vs[0]


class AbstractCtx(object):
    """LowerCtx stand-in used during eval_shape-based inference."""
    is_startup = False
    is_abstract = True
    mesh = None
    amp = False
    unread_outputs = frozenset()    # shape inference builds every slot

    def rng(self, salt=0, seed=0):
        import jax
        return jax.random.fold_in(jax.random.key(0), salt)

    def begin_op(self, salt):
        pass

    def add_error(self, message, flag):
        pass


def _struct_for(var, idx=0):
    """Abstract struct for inference pass `idx` (0 = BATCH_SENTINEL,
    1 = BATCH_SENTINEL_B). Prefers the var's recorded abstract shapes —
    which preserve folded batch products like B*H*T through reshapes that
    a bare -1 re-substitution would lose — when they are still current
    (i.e. nothing reassigned the public shape since they were recorded)."""
    import jax
    rec = getattr(var, "_abstract_shapes", None)
    if rec is not None and rec[2] == tuple(var.shape or ()):
        return jax.ShapeDtypeStruct(rec[idx], np.dtype(var.dtype))
    if var.shape is None:
        return None
    sentinel = (BATCH_SENTINEL, BATCH_SENTINEL_B)[idx]
    shape = tuple(sentinel if d == -1 else d for d in var.shape)
    return jax.ShapeDtypeStruct(shape, np.dtype(var.dtype))


def set_like(var, src, reshape=None, dtype=None):
    """Declare the output Variable `var` from the input Variable `src`, with
    nothing traced: src's shape (through `reshape`, a function of a shape
    tuple, where given) and src's dtype (or `dtype`). What tracing the rule
    would have recorded, -1 and the sentinel shapes included: `reshape` runs
    on src's two sentinel shapes and a dim that differs between them is -1,
    so a folded batch product (a norm's [-1 * T] statistics) stays one."""
    import jax
    structs = [_struct_for(src, idx) for idx in (0, 1)]
    if structs[0] is None:
        return  # un-inferable input, as abstract_eval leaves it
    shape_a, shape_b = (
        tuple(int(d) for d in (reshape(st.shape) if reshape else st.shape))
        for st in structs)
    var.shape = tuple(-1 if a != b else a for a, b in zip(shape_a, shape_b))
    var._abstract_shapes = (shape_a, shape_b, var.shape)
    var.dtype = np.dtype(jax.dtypes.canonicalize_dtype(
        np.dtype(src.dtype if dtype is None else dtype))).name


def shapes_from(**slots):
    """An `OpDef.infer` written as a table: output slot -> the input slot it
    is shaped and typed like, or (input slot, reshape(shape, attrs)[, dtype])
    where it is not quite (`set_like`). For a rule that holds
    a kernel, a loop or more than a few milliseconds of trace and whose
    outputs' shapes are its inputs': tracing it to learn them costs the
    whole rule, twice where a dim is -1, once an op (module docstring)."""
    def infer(block, op, out_vars):
        for slot, spec in slots.items():
            source, reshape, dtype = (
                ((spec,) if isinstance(spec, str) else spec)
                + (None, None))[:3]
            if not op.inputs.get(source):
                continue
            src = block.var_recursive(op.inputs[source][0])
            for var in out_vars.get(slot, ()):
                set_like(var, src, reshape and (
                    lambda shape: reshape(shape, op.attrs)), dtype)
    return infer


def abstract_eval(block, op):
    """READ-ONLY dual-sentinel abstract evaluation of a registered op.

    Runs the op's lowering rule under jax.eval_shape twice (BATCH_SENTINEL /
    BATCH_SENTINEL_B) and maps sentinel-tracking dims back to -1 — the same
    machinery `infer_and_set_shapes` uses at build time, factored out so the
    static analyzer (paddle_tpu/analysis) can re-derive output shapes/dtypes
    WITHOUT mutating any Variable and compare them against the declared ones.

    Returns {slot: [entry | None]} for the op's declared output slots, each
    entry (public_shape_with_-1, (shape_a, shape_b), dtype_name), or None
    when the op can't be evaluated this way (unregistered, custom `infer`,
    un-inferable input, or the rule raising under eval_shape).
    """
    if not is_registered(op.type):
        return None
    od = get(op.type)
    if od.infer is not None:
        return None  # custom infer mutates vars; not re-runnable read-only
    import jax
    try:
        ins = {}
        ins_b = {}
        has_dynamic = False
        for slot, names in op.inputs.items():
            vars_ = [block.var_recursive(n) for n in names]
            structs = [_struct_for(v) for v in vars_]
            if any(s is None for s in structs):
                return None  # un-inferable input
            has_dynamic = has_dynamic or any(
                -1 in (v.shape or ()) for v in vars_)
            ins[slot] = structs
            ins_b[slot] = [_struct_for(v, 1) for v in vars_]
        ctx = AbstractCtx()
        outs = jax.eval_shape(lambda i: od.lower(ctx, i, op.attrs), ins)
        # second pass under a different sentinel: output dims that move with
        # the sentinel are batch-derived (incl. folded products like
        # [-1, K] -> [-1*K]); dims that agree are genuinely static
        outs_b = jax.eval_shape(lambda i: od.lower(ctx, i, op.attrs),
                                ins_b) if has_dynamic else outs
        result = {}
        for slot, structs in outs.items():
            # slots the rule emits beyond the op's declared outputs
            # (__errors__ flags, optional outs) carry no var to compare
            if slot not in op.outputs or not isinstance(structs,
                                                        (list, tuple)):
                continue
            structs_b = outs_b.get(slot, structs) if has_dynamic else structs
            entries = []
            for st, st_b in zip(structs, structs_b):
                if st is None:
                    entries.append(None)
                    continue
                sa = tuple(int(d) for d in st.shape)
                sb = tuple(int(d) for d in st_b.shape)
                public = tuple(-1 if d != db else d
                               for d, db in zip(sa, sb))
                entries.append((public, (sa, sb), np.dtype(st.dtype).name))
            result[slot] = entries
        return result
    except Exception:
        return None  # inference is best-effort; lowering gives real errors


def infer_and_set_shapes(block, op):
    """Set output Variable shapes/dtypes by abstractly evaluating the lowering.

    Mirrors OpDesc::InferShape/InferVarType in the reference, but with zero
    per-op code in the common case.
    """
    if not is_registered(op.type):
        return  # ops lowered specially (grad_of, control-flow) set shapes themselves
    od = get(op.type)
    # the build phase's twin of lower_op's clock: every
    # append_op(infer_shape=True) and prepend_op passes here and nowhere
    # else. The clock sits in this function and in no wrapper around it:
    # jax records the Python stack with every equation eval_shape traces
    t0 = time.perf_counter()
    try:
        out_vars = {slot: [block.var_recursive(n) for n in names]
                    for slot, names in op.outputs.items()}
        if od.infer is not None:
            od.infer(block, op, out_vars)
            return
        res = abstract_eval(block, op)
        if res is None:
            return
        for slot, entries in res.items():
            for var, entry in zip(out_vars[slot], entries):
                if entry is None:
                    continue
                public, (shape_a, shape_b), dtype = entry
                var.shape = public
                # keep the exact sentinel shapes for downstream inference
                # (a -1 re-substitution would lose folded batch products);
                # the public snapshot invalidates the record if anything
                # reassigns shape
                var._abstract_shapes = (shape_a, shape_b, var.shape)
                var.dtype = dtype
    finally:
        seconds = time.perf_counter() - t0
        how = "custom" if od.infer is not None else "eval_shape"
        REGISTRY.counter(
            "ptpu_infer_shape_seconds_total",
            "host seconds of shape inference while a Program is built, by "
            "op type and by how: eval_shape (the lowering rule traced "
            "abstractly, twice where a dim is -1; what jax reports of "
            "that trace lies inside these seconds) or custom (the op's "
            "own OpDef.infer)").inc(seconds, op=op.type, how=how)
        REGISTRY.counter(
            "ptpu_infer_shape_calls_total",
            "ops counted into ptpu_infer_shape_seconds_total").inc(
                op=op.type, how=how)
