"""Graph IR: Program / Block / Operator / Variable / Parameter.

Parity: python/paddle/fluid/framework.py and paddle/fluid/framework/{program_desc,
block_desc,op_desc,var_desc}.{cc,h} in the reference. Same define-then-run model:
layer functions append Operators to the current Block of the default Program; an
Executor later runs the Program. TPU-native difference: the Program is lowered
whole into a single XLA computation (see core/lowering.py) instead of being
interpreted op-by-op, so the IR here is pure Python (no protobuf round-trip on
the hot path); `Program.to_string` provides the debug/serialization surface.
"""
import contextlib
import copy
import itertools
import os
import re
import sys
import threading
import time

import numpy as np

from . import unique_name
from ..observability import trace as _trace
from ..observability.registry import REGISTRY

GRAD_SUFFIX = "@GRAD"

# the paddle_tpu package directory: frames inside it are framework
# internals, filtered out of recorded op creation stacks
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep


def _op_callstack(limit=4):
    """Python creation site of an Operator: up to `limit` frames of the
    USER code that (transitively) appended the op, innermost first —
    frames inside the paddle_tpu package are skipped so diagnostics point
    at the layer CALL, not framework internals (parity: the reference's
    op_callstack attr, framework.py Operator.__init__). Raw
    (filename, lineno, function) triples — no source lines are read here,
    keeping op creation cheap; core.utils.format_callstack renders them
    lazily. FLAGS_op_callstack=0 disables recording entirely; any other
    integer value is a frame-depth override (FLAGS_op_callstack=8 walks
    8 user frames — deep wrapper stacks around the layers API need more
    than the default 4 for the diagnostic to reach the caller)."""
    flag = os.environ.get("FLAGS_op_callstack", "1")
    if flag in ("0", "false", "False"):
        return ()
    try:
        if int(flag) > 1:
            limit = int(flag)
    except ValueError:
        pass  # FLAGS_op_callstack=true/... : default depth
    try:
        f = sys._getframe(1)
    except ValueError:  # pragma: no cover - no caller frame
        return ()
    frames = []
    while f is not None and len(frames) < limit:
        code = f.f_code
        filename = code.co_filename
        if not filename.startswith(_PKG_DIR) and \
                "importlib" not in filename:
            frames.append((filename, f.f_lineno, code.co_name))
        f = f.f_back
    return tuple(frames)

_dtype_aliases = {
    "float32": "float32",
    "float64": "float64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "int8": "int8",
    "int16": "int16",
    "int32": "int32",
    "int64": "int64",
    "uint8": "uint8",
    "bool": "bool",
}


def convert_dtype(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        key = dtype.lower()
    else:
        key = np.dtype(dtype).name
    if key not in _dtype_aliases:
        raise ValueError("unsupported dtype: %s" % dtype)
    return _dtype_aliases[key]


def grad_var_name(name):
    return name + GRAD_SUFFIX


class Variable(object):
    """A named tensor in a Block.

    Parity: fluid.framework.Variable. Carries static shape (-1 = dynamic batch
    dim), dtype string, lod_level (number of variable-length sequence levels;
    see core/lod.py), persistable (lives in the Scope across runs) and
    stop_gradient flags.
    """

    def __init__(self, block, name=None, shape=None, dtype="float32",
                 lod_level=0, persistable=False, stop_gradient=False,
                 is_data=False, initializer=None, type=None, capacity=None):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_dtype(dtype) if dtype is not None else None
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.initializer = initializer
        self.error_clip = None  # BaseErrorClipAttr; applied by append_backward
        # name of the int32 [num_seqs] companion tensor holding true sequence
        # lengths; set for lod_level>0 vars (SURVEY.md §6.3: LoD → dense
        # padded + lengths-as-device-tensor)
        self.seq_len_var = None
        # type: None (dense tensor) | 'tensor_array' | 'rank_table'
        self.type = type
        self.capacity = capacity
        self.op = None  # producer op, set by append_op

    # ---- convenience -------------------------------------------------
    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def astype(self, dtype):
        from ..layers import tensor as _tensor
        return _tensor.cast(self, dtype)

    def set_error_clip(self, error_clip):
        """Era setter form (reference framework.py Variable
        .set_error_clip); same field append_backward consults."""
        self.error_clip = error_clip

    def to_string(self, throw_on_error=False, with_details=False):
        return repr(self)

    def __repr__(self):
        return "Variable(%s, shape=%s, dtype=%s, lod=%d%s)" % (
            self.name, self.shape, self.dtype, self.lod_level,
            ", persistable" if self.persistable else "")

    __str__ = __repr__


class Parameter(Variable):
    """Trainable persistable Variable.

    Parity: fluid.framework.Parameter — carries optimize/regularizer/clip attrs.
    """

    def __init__(self, block, shape, dtype, **kwargs):
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        kwargs.setdefault("persistable", True)
        super(Parameter, self).__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.stop_gradient = False


class Operator(object):
    """A node in the op graph.

    Parity: fluid.framework.Operator / op_desc.cc. inputs/outputs map slot
    names to lists of Variable *names* (string refs into the Block), matching
    the reference's OpDesc. attrs are plain Python values; sub-blocks (While,
    conditional_block) are referenced by block index in attrs['sub_block'].
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # Stable op identity: salts the per-op PRNG stream so that re-lowering
        # the op inside jax.vjp (backward) reproduces identical randomness.
        # PROGRAM-local (not process-global): a given program builds the same
        # uids no matter what other programs were created before it, so
        # random inits are reproducible across processes and test orderings.
        self.uid = block.program._next_op_uid()
        # user-code frames that created this op (the reference's
        # op_callstack): analyzer diagnostics and lowering-time errors
        # point here instead of at framework internals
        self.callstack = _op_callstack()
        self.inputs = {}   # slot -> [var name]
        self.outputs = {}  # slot -> [var name]
        self.attrs = dict(attrs) if attrs else {}
        if inputs:
            for slot, vs in inputs.items():
                self.inputs[slot] = [v.name if isinstance(v, Variable) else v
                                     for v in _as_list(vs)]
        if outputs:
            for slot, vs in outputs.items():
                self.outputs[slot] = [v.name if isinstance(v, Variable) else v
                                      for v in _as_list(vs)]

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_names(self):
        return list(self.inputs)

    @property
    def output_names(self):
        return list(self.outputs)

    def all_input_vars(self):
        return [n for vs in self.inputs.values() for n in vs]

    def all_output_vars(self):
        return [n for vs in self.outputs.values() for n in vs]

    def has_attr(self, name):
        return name in self.attrs

    def attr(self, name):
        return self.attrs[name]

    # ---- era surface (reference framework.py Operator) ---------------
    @property
    def attr_names(self):
        return list(self.attrs)

    def attr_type(self, name):
        """Python type of the attr (the era returned the proto AttrType
        enum; callers branch on kind, which the type answers)."""
        return type(self.attrs[name])

    @property
    def input_arg_names(self):
        return self.all_input_vars()

    @property
    def output_arg_names(self):
        return self.all_output_vars()

    def rename_input(self, old_name, new_name):
        """Era contract (op_desc.cc RenameInput): raises when old_name
        is not referenced — a silent no-op would surface later as a
        confusing missing-var error at execution."""
        if not any(old_name in names for names in self.inputs.values()):
            raise ValueError(
                "rename_input: op %r has no input named %r"
                % (self.type, old_name))
        for slot, names in self.inputs.items():
            self.inputs[slot] = [new_name if n == old_name else n
                                 for n in names]

    def rename_output(self, old_name, new_name):
        if not any(old_name in names for names in self.outputs.values()):
            raise ValueError(
                "rename_output: op %r has no output named %r"
                % (self.type, old_name))
        for slot, names in self.outputs.items():
            self.outputs[slot] = [new_name if n == old_name else n
                                  for n in names]

    def to_string(self, throw_on_error=False):
        return repr(self)

    def __repr__(self):
        ins = ", ".join("%s=%s" % (k, v) for k, v in self.inputs.items())
        outs = ", ".join("%s=%s" % (k, v) for k, v in self.outputs.items())
        return "{%s} = %s(%s) attrs=%s" % (outs, self.type, ins, self.attrs)


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class Block(object):
    """A sequence of Operators plus a symbol table of Variables.

    Parity: fluid.framework.Block / block_desc.cc, including parent-block
    variable lookup for sub-blocks of control-flow ops.
    """

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def create_var(self, **kwargs):
        v = Variable(self, **kwargs)
        self.vars[v.name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, shape, dtype, name=None, **kwargs):
        if name is None:
            name = unique_name.generate("_param")
        p = Parameter(self, shape=shape, dtype=dtype, name=name, **kwargs)
        self.vars[name] = p
        self.program._bump_version()
        return p

    def has_var(self, name):
        return name in self.vars

    def has_var_recursive(self, name):
        b = self
        while b is not None:
            if name in b.vars:
                return True
            b = b.parent_block
        return False

    def var(self, name):
        v = self.vars.get(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def var_recursive(self, name):
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        raise ValueError("Variable %r not found (searched up from block %d)"
                         % (name, self.idx))

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # ---- era surface (reference framework.py Block) -------------------
    def iter_parameters(self):
        return iter(self.all_parameters())

    def clone_variable(self, var):
        """Clone a variable (from any block) into this block as a
        persistable var — the era transpiler idiom for materializing a
        remote var locally (reference framework.py:921)."""
        return self.create_var(
            name=var.name, shape=var.shape, dtype=var.dtype,
            lod_level=var.lod_level, persistable=True, type=var.type)

    def copy_param_info_from(self, other):
        """Copy Parameter metadata (trainable/optimize/regularizer/
        gradient clip/ERROR clip — everything backward.py consults)
        from same-named parameters of another block. A source param
        missing here raises (era contract: copy_param_info_from
        enforced the match rather than silently skipping)."""
        for p in other.all_parameters():
            mine = self.vars.get(p.name)
            if mine is None:
                raise ValueError(
                    "copy_param_info_from: no var named %r in this "
                    "block" % p.name)
            if isinstance(mine, Parameter):
                mine.trainable = p.trainable
                mine.optimize_attr = dict(p.optimize_attr)
                mine.regularizer = p.regularizer
                mine.gradient_clip_attr = p.gradient_clip_attr
                mine.do_model_average = p.do_model_average
                mine.stop_gradient = p.stop_gradient
            mine.error_clip = p.error_clip

    def delete_ops(self, ops):
        """Remove the given ops from this block (era transpilers slice
        optimize ops out before shipping a sub-program)."""
        doomed = set(id(op) for op in ops)
        self.ops = [op for op in self.ops if id(op) not in doomed]
        self.program._bump_version()

    def slice_ops(self, start, end):
        return self.ops[start:end]

    def rename_var(self, name, new_name):
        """Rename a var and every reference to it in this block's ops
        (the era pserver-transpiler primitive). Sequence-length
        companions riding on the var are renamed with it."""
        if name not in self.vars:
            raise ValueError("rename_var: no var named %r here" % name)
        if new_name in self.vars:
            raise ValueError("rename_var: %r already exists" % new_name)
        v = self.vars.pop(name)
        v.name = new_name
        self.vars[new_name] = v
        # a var and its @GRAD companion rename together: grad ops write
        # <name>@GRAD derived from the forward name, and error-clip ops
        # reference the grad name directly
        renames = {name: new_name,
                   grad_var_name(name): grad_var_name(new_name)}

        def _sub(n):
            return renames.get(n, n)

        def _rewrite_attrs(attrs):
            # names also live in ATTRS: grad_of snapshots the forward
            # op's input/output maps, and control-flow lowerings bind
            # sub-block placeholders via *_name/_names attrs — a rename
            # that missed them would fail at lowering with a
            # read-before-write on the stale name
            for k, v in list(attrs.items()):
                if k in ("fwd_inputs", "fwd_outputs"):
                    attrs[k] = {s: [_sub(n) for n in ns]
                                for s, ns in v.items()}
                elif k.endswith("_name") and v in renames:
                    attrs[k] = renames[v]
                elif k.endswith("_names") and isinstance(v, (list, tuple)):
                    attrs[k] = type(v)(_sub(n) for n in v)

        for op in self.ops:
            # op-level rename raises on absent names (era contract);
            # this block-wide sweep rewrites only where referenced
            for old in renames:
                if old in op.all_input_vars():
                    op.rename_input(old, renames[old])
                if old in op.all_output_vars():
                    op.rename_output(old, renames[old])
            _rewrite_attrs(op.attrs)
        gname = grad_var_name(name)
        if gname in self.vars:
            gv = self.vars.pop(gname)
            gv.name = grad_var_name(new_name)
            self.vars[gv.name] = gv
        for other in self.vars.values():
            if getattr(other, "seq_len_var", None) == name:
                other.seq_len_var = new_name
        self.program._bump_version()
        return v

    def to_string(self, throw_on_error=False, with_details=False):
        lines = ["block_%d {" % self.idx]
        for vname in sorted(self.vars):
            lines.append("  var " + repr(self.vars[vname]))
        for op in self.ops:
            lines.append("  op " + repr(op))
        lines.append("}")
        return "\n".join(lines)

    # ops whose outputs are per-sequence (not per-timestep): do not inherit lod
    _LOD_CLEARING_OPS = frozenset([
        "sequence_pool", "sequence_last_step", "sequence_first_step",
        "reduce_sum", "reduce_mean", "mean", "cross_entropy", "topk",
        "accuracy", "lod_tensor_to_array",
    ])

    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        out_vars = []
        for vs in (outputs or {}).values():
            for v in _as_list(vs):
                if isinstance(v, Variable):
                    v.op = op
                    out_vars.append(v)
        # propagate sequence structure: timestep-preserving ops hand their
        # first sequence-input's lod/lengths to outputs (reference: runtime
        # LoD copy in op kernels; here it's static graph metadata)
        if type not in Block._LOD_CLEARING_OPS:
            for vs in (inputs or {}).values():
                src = next((v for v in _as_list(vs) if isinstance(v, Variable)
                            and v.lod_level > 0), None)
                if src is not None:
                    for ov in out_vars:
                        if ov.lod_level == 0:
                            ov.lod_level = src.lod_level
                            ov.seq_len_var = src.seq_len_var
                    break
        self.program._bump_version()
        if infer_shape:
            from . import registry
            registry.infer_and_set_shapes(self, op)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None,
                   infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump_version()
        if infer_shape:
            from . import registry
            registry.infer_and_set_shapes(self, op)
        return op

    def __repr__(self):
        lines = ["block %d (parent %d):" % (self.idx, self.parent_idx)]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)


class Program(object):
    """A list of Blocks; block 0 is the global block.

    Parity: fluid.framework.Program / program_desc.cc. `_version` is bumped on
    every mutation and keys the Executor's compile cache (the reference
    re-interprets every run; we re-jit only when the graph actually changed).
    """

    _uid_counter = itertools.count(1)

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._version = 0
        self._seed = None  # program-level rng seed override
        self.random_seed = 0
        self._op_uid_counter = 0
        self._amp = False  # bf16 mixed precision (enable_mixed_precision)
        # exact accumulator-var -> param-name map recorded by
        # Optimizer._add_accumulator; consumed by ParallelExecutor's
        # sharded_weight_update so accumulator layouts never have to be
        # guessed from name substrings
        self._accumulator_owner = {}
        # process-unique identity for the Executor's compile cache: id() of
        # a GC'd program can be recycled by a new one, silently serving a
        # stale jitted fn; this never recycles
        self._uid = next(Program._uid_counter)

    def _next_op_uid(self):
        self._op_uid_counter += 1
        return self._op_uid_counter

    def _bump_version(self):
        self._version += 1

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.blocks.append(Block(self, new_idx, parent))
        self.current_block_idx = new_idx
        self._bump_version()
        return self.current_block()

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx
        self._bump_version()

    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    # ---- era surface (reference framework.py Program) ------------------
    def block(self, index):
        return self.blocks[index]

    def copy_param_info_from(self, other):
        self.global_block().copy_param_info_from(other.global_block())

    def inference_optimize(self):
        """Era standalone form of clone(for_test=True): a copy with
        is_test flipped everywhere (reference prune.cc:187 — it never
        pruned ops, only flipped the attr)."""
        return self.clone(for_test=True)

    @staticmethod
    def parse_from_string(binary_str):
        """Deserialize a program serialized by this build
        (program_to_bytes); the era parsed its protobuf here — for
        REFERENCE-era protobuf descs use
        reference_format.parse_program_desc / io.load_reference_model."""
        from .program_desc import program_from_bytes
        return program_from_bytes(binary_str)

    def enable_mixed_precision(self, enable=True):
        """TPU bf16 training path (SURVEY §7 M5; no 2018-fluid counterpart).

        When on, the lowering pass runs the MXU contractions (conv2d, mul,
        matmul) in bfloat16 (f32 accumulation where the backend provides it:
        explicit for mul/matmul, the MXU's internal accumulate for conv),
        keeps normalization statistics and losses in float32, and leaves
        every parameter in the Scope as a float32 master copy — so
        optimizers, checkpoints and the user API are unchanged. Purely a
        compile-time switch: no graph rewrite, no extra state."""
        self._amp = bool(enable)
        self._bump_version()

    # ---- clone / prune (parity: Program.clone, Program.prune) --------
    def clone(self, for_test=False):
        p = copy.deepcopy(self)
        p._uid = next(Program._uid_counter)  # a clone is a distinct program
        if for_test:
            p._set_test_mode()
        return p

    def append_backward(self, target, no_grad_set=None):
        """Era method form (reference framework.py:1058 — test_layers.py
        calls program.append_backward(avg_cost)); delegates to the
        module-level backward builder. Returns [(Parameter, grad
        Variable)] like fluid.append_backward."""
        from .backward import append_backward as _ab
        if not isinstance(target, Variable):
            raise TypeError("append_backward target must be a Variable, "
                            "got %r" % type(target).__name__)
        if target.block.program is not self:
            raise ValueError(
                "append_backward target %r belongs to a different "
                "Program" % target.name)
        return _ab(target, no_grad_set=no_grad_set)

    def _set_test_mode(self):
        for blk in self.blocks:
            for op in blk.ops:
                if "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                    op.attrs["is_test"] = True

    def prune(self, targets, for_test=False):
        """Return a copy containing only the ops/vars the targets depend on
        (parity: fluid.framework.Program.prune, framework.py:1002).

        Backward slice from the target variables: optimizer/backward ops,
        metrics branches and anything else not on a target's path are
        dropped — the inference-serving subgraph. Sub-blocks of kept
        control-flow ops survive intact; orphaned sub-blocks are emptied
        (block indices stay stable for attrs['sub_block'] refs). for_test
        additionally flips is_test attrs, sparing a second deepcopy vs
        prune().clone(for_test=True)."""
        p = self.clone(for_test=for_test)
        if not isinstance(targets, (list, tuple)):
            targets = [targets]
        needed = set()
        for t in targets:
            name = t.name if isinstance(t, Variable) else t
            needed.add(name)
            v = p.global_block().vars.get(name)
            if v is not None and getattr(v, "seq_len_var", None):
                needed.add(v.seq_len_var)

        def op_reads(op):
            names = [n for ns in op.inputs.values() for n in ns if n]
            for idx in _sub_block_indices(op):
                for sop in p.blocks[idx].ops:
                    names.extend(op_reads(sop))
            return names

        kept = []
        for op in reversed(p.global_block().ops):
            if any(n in needed
                   for ns in op.outputs.values() for n in ns if n):
                kept.append(op)
                needed.update(op_reads(op))
        kept.reverse()
        p.global_block().ops = kept

        # empty unreachable sub-blocks (their ops would otherwise leak into
        # state analysis via _all_ops)
        reachable = {0}
        frontier = list(kept)
        while frontier:
            op = frontier.pop()
            for idx in _sub_block_indices(op):
                if idx not in reachable:
                    reachable.add(idx)
                    frontier.extend(p.blocks[idx].ops)
        for blk in p.blocks:
            if blk.idx not in reachable:
                blk.ops = []
                blk.vars = {}

        # drop global vars nothing kept references
        used = set(needed)
        for op in kept:
            for ns in op.outputs.values():
                used.update(n for n in ns if n)
        blk = p.global_block()
        blk.vars = {k: v for k, v in blk.vars.items() if k in used}
        p._bump_version()
        return p

    def to_string(self, throw_on_error=False, with_details=False):
        return "\n".join(repr(b) for b in self.blocks)

    __repr__ = to_string
    __str__ = to_string


def _sub_block_indices(op):
    """Block indices an op's attrs reference (sub_block is the convention;
    grad_of ops may carry fwd attrs with one too)."""
    out = []
    for key, val in op.attrs.items():
        if key.endswith("sub_block") and isinstance(val, int):
            out.append(val)
        elif key == "fwd_attrs" and isinstance(val, dict) \
                and isinstance(val.get("sub_block"), int):
            out.append(val["sub_block"])
    return out


# ops that behave differently at inference time
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
    "nce": ("is_test",),
}

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program():
    return _main_program_


def default_startup_program():
    return _startup_program_


def switch_main_program(program):
    global _main_program_
    old = _main_program_
    _main_program_ = program
    return old


def switch_startup_program(program):
    global _startup_program_
    old = _startup_program_
    _startup_program_ = program
    return old


_build_open = threading.local()     # .phases: the build_phases open on
# the calling thread, outermost first


def _op_count(programs):
    return sum(len(b.ops) for p in programs for b in p.blocks)


class build_phase(object):
    """`with build_phase("append_backward", program):` is one phase of
    building a Program, seen from inside: a span `build/<phase>` of
    cat="build" whose parent is the build phase already open on the
    thread (the outermost one mints the trace id they all share), and,
    from the span's own two clock readings,
    `ptpu_build_seconds_total{phase}` with `ptpu_build_ops_total{phase}`
    beside it: the ops `programs` (the main and the startup program)
    gained while it was open. No span an op (a benchmark cell appends
    400 to 1,400): shape inference, the one costly thing an append_op
    does, is booked by op type in registry.infer_and_set_shapes."""

    __slots__ = ("phase", "programs", "span", "t0", "ops0")

    def __init__(self, phase, *programs):
        self.phase = phase
        self.programs = tuple({id(p): p for p in programs}.values())

    def __enter__(self):
        phases = getattr(_build_open, "phases", None)
        if phases is None:
            phases = _build_open.phases = []
        outer = phases[-1].span if phases else None
        self.ops0 = _op_count(self.programs)
        self.t0 = time.perf_counter()
        self.span = _trace.span(
            "build/" + self.phase, cat="build", _t0=self.t0,
            trace=_trace.new_trace() if outer is None else outer.trace,
            parent=None if outer is None else outer.sid)
        phases.append(self)
        return self

    def __exit__(self, etype, exc, tb):
        t1 = time.perf_counter()
        _build_open.phases.pop()
        ops = max(0, _op_count(self.programs) - self.ops0)
        self.span.end(_t1=t1, ops=ops,
                      **({"error": etype.__name__} if etype else {}))
        REGISTRY.counter(
            "ptpu_build_seconds_total",
            "host seconds building Programs, by phase: program (the "
            "outermost program_guard on a thread, what a benchmark times "
            "from outside as build_s), minimize (Optimizer.minimize) and "
            "its parts append_backward, clip, regularize, optimize_pass; "
            "a phase's seconds hold its children's").inc(
                t1 - self.t0, phase=self.phase)
        REGISTRY.counter(
            "ptpu_build_ops_total",
            "ops the main and the startup program gained while a build "
            "phase of ptpu_build_seconds_total was open").inc(
                ops, phase=self.phase)
        return False


def in_build_phase():
    """Whether a build_phase is open on the calling thread."""
    return bool(getattr(_build_open, "phases", None))


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    # the OUTERMOST guard on a thread is build/program; one opened under
    # a build phase (minimize's, a control-flow layer's, an LR schedule's)
    # is part of that phase: no span, and no second count
    with contextlib.nullcontext() if in_build_phase() else build_phase(
            "program", main_program,
            startup_program or default_startup_program()):
        old_main = switch_main_program(main_program)
        old_startup = None
        if startup_program is not None:
            old_startup = switch_startup_program(startup_program)
        try:
            yield
        finally:
            switch_main_program(old_main)
            if old_startup is not None:
                switch_startup_program(old_startup)


def get_var(name, program=None):
    """Get a variable by name from a program's global block
    (parity: fluid.framework.get_var)."""
    if program is None:
        program = default_main_program()
    if not isinstance(program, Program):
        raise TypeError("get_var expects a Program, got %r" % (program,))
    return program.global_block().var(name)
