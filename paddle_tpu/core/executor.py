"""Executor + Scope.

Parity: python/paddle/fluid/executor.py and paddle/fluid/framework/
{executor.cc,scope.cc}. API-identical `Executor(place).run(program, feed,
fetch_list)`; internally each distinct (program version, feed signature,
fetch list) is lowered ONCE to a jitted XLA computation and cached —
subsequent runs are a single device dispatch, vs. the reference's per-op
kernel launches every run.
"""
import collections
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_cache
from . import lowering
from . import readers
from .framework import default_main_program, convert_dtype
from .lod import LoDTensor
from .utils import find_var as _find_feed_var
from ..observability import trace as _trace


class Scope(object):
    """Name -> host/device array store (parity: framework::Scope, incl. the
    kid-scope tree: new_scope()/parent lookup/drop_kids used by
    default_scope_funcs and the reference's local-scope executor runs)."""

    def __init__(self, parent=None):
        self._vars = {}
        self._lods = {}
        self._rng_counter = 0
        self._parent = parent
        self._kids = []

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def parent(self):
        return self._parent

    def drop_kids(self):
        self._kids = []

    def set(self, name, value, lod=None):
        self._vars[name] = value
        if lod is not None:
            self._lods[name] = lod

    def get(self, name):
        if name in self._vars:
            return self._vars[name]
        if self._parent is not None:
            return self._parent.get(name)
        return None

    def has(self, name):
        return name in self._vars or (
            self._parent is not None and self._parent.has(name))

    def find_var(self, name):
        """Search this scope then ancestors (parity: Scope::FindVar)."""
        if name in self._vars:
            return _ScopeVar(self, name)
        if self._parent is not None:
            return self._parent.find_var(name)
        return None

    def var(self, name):
        if name not in self._vars:
            self._vars[name] = None
        return _ScopeVar(self, name)

    def names(self):
        return list(self._vars)

    def drop(self, name):
        self._vars.pop(name, None)
        self._lods.pop(name, None)

    def next_seed(self):
        self._rng_counter += 1
        return self._rng_counter

    def next_seed_block(self, k):
        """Reserve k consecutive seeds, returning the first. A K-step
        device-resident run consumes seed..seed+K-1 inside the loop; the
        counter must advance past all of them so a later run never replays
        a seed a loop step already used."""
        first = self._rng_counter + 1
        self._rng_counter += k
        return first

    def seed_state(self):
        """The rng cursor as checkpoint payload: with it restored
        (set_seed_state), the runs after a resume draw exactly the seeds
        the straight-through run would have — per-step dropout masks and
        every other in-graph rng replay bit-for-bit. Exported by
        checkpoint.CheckpointManager at each snapshot."""
        return int(self._rng_counter)

    def set_seed_state(self, counter):
        self._rng_counter = int(counter)


class _ScopeVar(object):
    def __init__(self, scope, name):
        self.scope = scope
        self.name = name

    def get_tensor(self):
        return self.scope.get(self.name)

    def set(self, value, place=None):
        self.scope.set(self.name, value)


_global_scope = Scope()


def global_scope():
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope):
    old = switch_scope(scope)
    try:
        yield
    finally:
        switch_scope(old)


def _feed_signature(feed):
    sig = []
    for name in sorted(feed):
        a = feed[name]
        sig.append((name, tuple(np.shape(a)), str(np.asarray(a).dtype)
                    if not hasattr(a, "dtype") else str(a.dtype)))
    return tuple(sig)


def as_numpy(tensor):
    return np.asarray(tensor)


class FetchHandle(object):
    """Lazy fetch result (`return_numpy=False`): wraps the device-resident
    jax.Array so the caller decides when (if ever) to pay the device->host
    sync. `np.asarray(handle)` / `.numpy()` materialize; `.array` hands out
    the raw jax.Array (usable in jnp expressions via __jax_array__, still
    async); `.block()` waits without copying. The dispatch that produced it
    has already been enqueued — a timing loop should end with
    core.utils.device_fetch_barrier, which unwraps handles."""

    __slots__ = ("_arr",)

    def __init__(self, arr):
        self._arr = arr

    @property
    def array(self):
        return self._arr

    @property
    def shape(self):
        return self._arr.shape

    @property
    def dtype(self):
        return self._arr.dtype

    def numpy(self):
        from .. import profiler as _prof
        _prof.note_sync("fetch/materialize")
        return np.asarray(self._arr)

    def block(self):
        from .. import profiler as _prof
        _prof.note_sync("fetch/block")
        jax.block_until_ready(self._arr)
        return self

    def __array__(self, dtype=None, copy=None):
        from .. import profiler as _prof
        _prof.note_sync("fetch/materialize")
        a = np.asarray(self._arr)
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._arr

    def __repr__(self):
        return "FetchHandle(shape=%r, dtype=%s)" % (
            tuple(self._arr.shape), self._arr.dtype)


def convert_feeds(program, feed, host=False):
    """Feed dict -> arrays for the jitted program. LoDTensor feeds expand
    to padded dense + the @SEQLEN lengths companion; plain arrays coerce
    to the feed var's dtype. Shared by Executor and ParallelExecutor (the
    reference's feed path lived once in executor.cc for both); host=True
    keeps host values as numpy for a caller that places them itself."""
    feed_arrays = {}
    for name, value in feed.items():
        var = _find_feed_var(program, name)
        if isinstance(value, LoDTensor):
            # sequence feed: expand to padded dense + lengths companion
            padded, lengths = value.to_padded()
            if var is not None and var.dtype is not None:
                padded = padded.astype(convert_dtype(var.dtype),
                                       copy=False)
            feed_arrays[name] = padded if host else jnp.asarray(padded)
            feed_arrays[name + "@SEQLEN"] = \
                lengths if host else jnp.asarray(lengths)
            continue
        if var is not None and var.lod_level > 0:
            try:  # ragged python lists make np.ndim itself raise
                ndim = np.ndim(value)
            except ValueError:
                ndim = -1
            if ndim != len(var.shape or ()) or \
                    name + "@SEQLEN" not in feed:
                raise TypeError(
                    "variable %r is a sequence (lod_level=%d): feed a "
                    "LoDTensor (fluid.create_lod_tensor / "
                    "LoDTensor.from_sequences), or a padded [num_seqs, "
                    "max_len, ...] array plus %r lengths" %
                    (name, var.lod_level, name + "@SEQLEN"))
        feed_arrays[name] = _to_array(value, var, host=host)
    return feed_arrays


class _DispatchCancelled(Exception):
    """Internal: a watchdog-abandoned worker reached a cancellation
    checkpoint; the dispatch unwinds without touching more state."""


def run_host_io_prepass(program, scope, feed_arrays, host=False,
                        validate=None, steps=1, stacked_out=None,
                        cancelled=None, place=None, popped_out=None):
    """io pre-pass: reader ops execute host-side (core/readers.py).
    create_* ops build ReaderState objects in the scope; each `read` op
    pops the next record and injects it as a feed of the jitted program
    (EOFException propagates to the caller — check reader.eof() first).
    Global block only: file IO inside traced control flow has no TPU
    lowering. Shared by Executor and ParallelExecutor. host=True keeps
    numpy records on the host for the caller's own sharded device_put;
    records a DoubleBufferReader already staged stay device-resident
    (device-to-device resharding beats forcing them back through the
    host). `validate(record, out_vars)` runs before the record is accepted
    (out_vars are the declared read_file output Variables, for shape-aware
    checks); on failure the record is pushed back so the error doesn't
    consume it.

    place: the dispatch place. A reader that stages asynchronously
    (DoubleBufferReader) gets it pinned (`pin_place`) so its staging
    thread device_puts to the DEVICE THE DISPATCH RUNS ON — without the
    pin the worker stages to the process default device and a
    non-default place re-pays the transfer on the main thread (an
    explicit double_buffer(place=...) always wins).

    popped_out: refund ledger for the pipelined-dispatch prefetcher
    (core/dispatch.py) — every (reader_state, records) block that REMAINS
    consumed when this call returns is appended, in pop order, so a
    staged-but-never-dispatched prepass can push everything back exactly.
    Blocks an internal failure already rolled back are not listed.

    steps=K (multi-step execution): each `read` op pops K records
    ATOMICALLY (ReaderBase.next_many pushes all K back on a mid-block EOF
    or validation failure) and stacks each field with a leading K axis —
    the device loop slices step t's feed out of the stack, and a
    DoubleBufferReader keeps pre-staging records (lod padding +
    device_put on its worker thread) for the NEXT K-block while the
    current one computes. Atomicity spans ALL read ops of the program: a
    failure at the second reader (EOF, validation, unstackable shapes)
    pushes the first reader's already-popped block back too, so a failed
    K-step run consumes nothing anywhere and paired streams (e.g. image
    + label readers) can never skew. The stacked feed names are added to
    `stacked_out` so the executor can key/slice them."""
    multi_blocks = []     # [(state, records)] popped so far this call
    multi_stacks = {}     # name -> stacked [K, ...] array, committed last

    def _rollback():
        if cancelled is not None and cancelled.is_set():
            # watchdog-abandoned worker: the caller's recovery restores
            # the readers' positions itself — a late refund here would
            # prepend stale records into the freshly restored stream
            return
        for st, recs in reversed(multi_blocks):
            for rec in reversed(recs):
                st.push_back(rec)

    for op in program.global_block().ops:
        if cancelled is not None and cancelled.is_set():
            # watchdog-abandoned worker: stop consuming reader records
            # NOW — the caller's recovery (rollback) is about to rewind
            # the very readers this loop would keep advancing (no
            # refund either: see _rollback)
            raise _DispatchCancelled()
        if op.type == "read":
            state = scope.get(op.inputs["Reader"][0])
            if state is None:
                raise RuntimeError(
                    "reader %r has no state; run the startup program "
                    "first" % op.inputs["Reader"][0])
            if place is not None and hasattr(state, "pin_place"):
                # async-staging readers stage straight to the dispatch
                # device (H2D on the staging thread, not re-paid here)
                state.pin_place(place)
            out_names = op.outputs["Out"]
            out_vars = [_find_feed_var(program, n) for n in out_names]

            def _check(record):
                if len(record) != len(out_names):
                    raise ValueError(
                        "reader yielded %d fields but read_file declared "
                        "%d" % (len(record), len(out_names)))
                if validate is not None:
                    validate(record, out_vars)

            if steps == 1:
                record = state.next()
                try:
                    _check(record)
                except Exception:
                    state.push_back(record)
                    raise
                for out_name, val, var in zip(out_names, record, out_vars):
                    feed_arrays[out_name] = _to_array(val, var, host=host)
                if popped_out is not None:
                    popped_out.append((state, [record]))
            else:
                if hasattr(state, "ensure_staging_depth"):
                    # a double buffer must be able to pre-stage the NEXT
                    # K-block while this one computes
                    state.ensure_staging_depth(steps)
                try:
                    # next_many pushes ITS block back itself on failure;
                    # _rollback returns every EARLIER reader's block
                    records = state.next_many(steps, validate=_check)
                except Exception:
                    _rollback()
                    raise
                multi_blocks.append((state, records))
                # convert+stack BEFORE committing to feed_arrays: records
                # whose field shapes differ can't stack, and that failure
                # must also consume nothing (anywhere)
                try:
                    for i, (out_name, var) in enumerate(zip(out_names,
                                                            out_vars)):
                        fields = [_to_array(rec[i], var, host=host)
                                  for rec in records]
                        multi_stacks[out_name] = (
                            np.stack(fields) if host else jnp.stack(fields))
                except Exception:
                    _rollback()
                    raise
        elif readers.is_host_io_op(op.type):
            if steps > 1:
                # an earlier read op may already have popped its K-block;
                # this refusal must consume nothing anywhere, like every
                # other multi-step failure
                _rollback()
                raise RuntimeError(
                    "program contains host io op %r in its main block: "
                    "with steps=%d it would run once per CALL, not once "
                    "per step like %d sequential runs would. Keep reader "
                    "creation in the startup program (the standard "
                    "split), or run this program with steps=1."
                    % (op.type, steps, steps))
            readers.run_host_io_op(op, scope)
    # all readers delivered their K-block: commit the stacks together
    if multi_stacks:
        feed_arrays.update(multi_stacks)
        if stacked_out is not None:
            stacked_out.update(multi_stacks)
    if popped_out is not None:
        popped_out.extend(multi_blocks)


def _array_safety_enabled():
    """In-graph TensorArray overflow checking (default ON). The check costs
    one scalar device->host sync per run for programs that contain tensor
    arrays (zero for programs that don't) — a latency-critical decode loop
    that provably sizes its arrays can set FLAGS_tensor_array_safety=0 to
    keep fully-async dispatch."""
    import os
    return os.environ.get("FLAGS_tensor_array_safety", "1") not in (
        "0", "false", "False")


# message prefix check_finite_guard (ops/guard_ops.py) stamps on its
# sticky assertion flags; _raise_program_errors keys the typed raise on it
GUARD_MSG_PREFIX = "numerical guard:"


class NumericalGuardError(RuntimeError):
    """A device-side numerical guard (resilience.install_numeric_guards)
    tripped: non-finite loss/grad/param detected in-graph. The gated
    state updates of the offending step were skipped on device, so the
    scope still holds the last-good values — a supervisor can skip the
    batch, retry, or roll back without fearing poisoned params."""


class DispatchTimeoutError(RuntimeError):
    """Executor.run(timeout=)/ParallelExecutor.run(timeout=) watchdog: a
    dispatch (io pre-pass + device computation) exceeded its deadline.
    `cache_key` carries the compile-cache key of the wedged program when
    it got far enough to compute one. After this raise the abandoned
    worker stops at its next cancellation checkpoint (before each read
    op of the io pre-pass, before dispatch, and before the scope
    write-back — which in watchdog mode runs only AFTER the device
    sync, so a wedged execution can never park unresolved arrays in the
    scope). The checkpoints are check-then-act: a worker that passed
    one microseconds before the deadline may still complete that one
    action, and donated buffers may already be consumed — device state
    is indeterminate, so recover by rollback/abort, not by trusting the
    scope (resilience.Supervisor encodes exactly that)."""

    def __init__(self, message, cache_key=None):
        super(DispatchTimeoutError, self).__init__(message)
        self.cache_key = cache_key


# the watchdog plumbing lives ONCE in the shared dispatch core
# (core/dispatch.py); re-exported here because DispatchTimeoutError and
# every historical import site (resilience/watchdog.py, tests) live on
# this module's surface
from .dispatch import (dispatch_with_deadline,  # noqa: E402,F401
                       run_with_deadline)


# Fault-injection hook (resilience/faults.py): None in production. When a
# FaultPlan is armed it points at the plan's executor hook, which may
# raise an injected dispatch error or sleep (slow-step) at the chosen
# step indices — the single seam every recovery path is proved through.
_fault_hook = None

# Step-barrier hook (resilience/cluster.py): None outside elastic runs.
# An elastic worker installs one that raises ClusterFenced when the
# cluster plan has moved past the generation this process is training
# under. It fires at the very top of every dispatch — BEFORE the fault
# hook, the io pre-pass and the seed draw — so a fenced attempt consumes
# nothing (no reader records, no rng) and the step replays bit-exactly
# once the cohort reconfigures, even when the fence lands mid-train()
# inside a loop the worker does not control.
_barrier_hook = None


def _raise_program_errors(errors, include_non_guard=True):
    """Raise on tripped in-graph assertion flags (one host sync of the
    combined '__any__' scalar in the common clean case). ALL tripped
    flags are reported, not just the first: a K-step run can trip several
    independent assertions and fixing them one raise at a time wastes a
    full compile+run each round. Messages that name a variable sort
    before the generic sub-block one so the most actionable line leads.

    Guard flags (GUARD_MSG_PREFIX) raise the typed NumericalGuardError so
    a supervisor can classify the fault without string matching; with
    include_non_guard=False (FLAGS_tensor_array_safety=0 but guards
    installed) only guard messages are considered. A \\x00-joined key
    carries a VECTOR of flags (check_finite_guard packs its per-var
    checks into one output); it is unpacked here, one sync, after
    __any__ tripped. GUARD_STAT_PREFIX keys are float statistics, not
    assertions — normally peeled off by pop_guard_stats before this
    runs, but skipped here too so a caller that didn't peel stays
    correct."""
    from .lowering import is_stat_key
    if not errors or not bool(errors.get("__any__", False)):
        return
    tripped = []
    for msg, flag in errors.items():
        if msg == "__any__" or is_stat_key(msg):
            continue
        if "\x00" in msg:
            vals = np.asarray(flag)
            tripped.extend(m for m, f in zip(msg.split("\x00"), vals)
                           if bool(f))
        elif bool(flag):
            tripped.append(msg)
    if not include_non_guard:
        tripped = [m for m in tripped if m.startswith(GUARD_MSG_PREFIX)]
    if not tripped:
        return
    named = [m for m in tripped if m.startswith("tensor array '")]
    generic = [m for m in tripped if not m.startswith("tensor array '")]
    ordered = named + generic
    cls = (NumericalGuardError
           if any(m.startswith(GUARD_MSG_PREFIX) for m in ordered)
           else RuntimeError)
    if len(ordered) == 1:
        raise cls(ordered[0])
    raise cls(
        "%d in-graph assertions tripped in this run:\n- %s"
        % (len(ordered), "\n- ".join(ordered)))


def pop_guard_stats(errors):
    """Peel GUARD_STAT_PREFIX float statistics out of a dispatch's error
    dict (in place), returning {short_name: device_value}. Called right
    after the jitted call, BEFORE any error sync — the values stay
    device-resident (no host sync here); the sentinel materializes them
    lazily after the executor's existing __any__ sync, so the grad-norm
    watch adds zero host round-trips to the dispatch path."""
    if not errors:
        return {}
    from .lowering import GUARD_STAT_PREFIX, is_stat_key
    stats = {}
    for msg in [m for m in errors if is_stat_key(m)]:
        stats[msg[len(GUARD_STAT_PREFIX):]] = errors.pop(msg)
    return stats


def _validate_program_flag():
    """FLAGS_validate_program: strict mode — every program is statically
    verified (paddle_tpu/analysis) before its first lowering; analyzer
    ERRORS raise ProgramVerificationError instead of surfacing later as
    opaque trace/XLA failures. Same resolution style as
    FLAGS_check_nan_inf; Executor.run(validate=...) overrides per call."""
    return os.environ.get("FLAGS_validate_program", "") not in (
        "", "0", "false", "False")


def maybe_validate_program(program, feed_arrays, fetch_names, steps,
                           cache, validate=None, deploy=None):
    """Shared strict-mode gate for Executor.run and ParallelExecutor.run:
    resolve the validate setting (explicit arg wins over the env flag),
    run the static analyzer once per (program version, feed/fetch
    signature, multi-step, deployment) — `cache` is the caller's set —
    and raise ProgramVerificationError on findings. Must run BEFORE the
    io pre-pass: a raise here consumes no reader records. `deploy` (a
    DeploymentContext) arms the deployment tier on top of the base
    pipeline — ParallelExecutor passes its armed ShardingPlan through
    here, so plan/program drift fails at the run() boundary."""
    if not (_validate_program_flag() if validate is None
            else bool(validate)):
        return
    vkey = (program._uid, program._version, tuple(sorted(feed_arrays)),
            tuple(fetch_names), steps > 1,
            deploy.cache_key() if deploy is not None else None)
    if vkey in cache:
        return
    from ..analysis import validate_or_raise
    validate_or_raise(program, feed_names=list(feed_arrays),
                      fetch_names=fetch_names, steps=steps, deploy=deploy)
    cache.add(vkey)


def _nan_inf_enabled(flag):
    """Resolve a check_nan_inf setting: explicit flag wins, else the
    FLAGS_check_nan_inf env var (parity: the reference's gflag of the same
    name guarding TensorContainsNAN/Inf sweeps, operator.cc)."""
    if flag is not None:
        return bool(flag)
    import os
    return os.environ.get("FLAGS_check_nan_inf", "") not in ("", "0",
                                                             "false", "False")


def check_finite(named_arrays, context=""):
    """Raise naming the first variable containing NaN/Inf.

    Parity: paddle/fluid/framework/tensor_util.cc:163 TensorContainsNAN /
    TensorContainsInf + the executor's FLAGS_check_nan_inf sweep. TPU-native
    form: one `jnp.isfinite(...).all()` reduction per floating array (device
    side), host-synced only in debug mode where this runs.
    """
    for name, v in named_arrays:
        if v is None:
            continue
        dt = getattr(v, "dtype", None)
        if dt is None or not jnp.issubdtype(jnp.asarray(v).dtype,
                                            jnp.floating):
            continue
        if not bool(jnp.isfinite(v).all()):
            a = np.asarray(v, dtype=np.float32)
            kind = "NaN" if np.isnan(a).any() else "Inf"
            raise RuntimeError(
                "Operator output variable %r contains %s%s (first bad of "
                "%d elements; enable smaller LR / grad clipping, or inspect "
                "with fluid.debuger)" %
                (name, kind, " after %s" % context if context else "",
                 a.size))


def _jit_cache_capacity():
    """Max live compiled programs per executor (LRU beyond this). Bucketed
    padding keeps the shape-signature space small in normal training, but
    unbounded feed-shape variety must not accumulate XLA executables
    forever. PADDLE_TPU_JIT_CACHE_SIZE overrides (0 = unbounded)."""
    try:
        return int(os.environ.get("PADDLE_TPU_JIT_CACHE_SIZE", "64"))
    except ValueError:
        return 64


def _cache_put_lru(cache, key, entry, capacity):
    """Insert into an OrderedDict LRU, evicting least-recently-used."""
    cache[key] = entry
    cache.move_to_end(key)
    if capacity > 0:
        while len(cache) > capacity:
            cache.popitem(last=False)


class Executor(object):
    def __init__(self, place=None, check_nan_inf=None):
        from ..places import CPUPlace
        self.place = place if place is not None else CPUPlace()
        self._cache = collections.OrderedDict()
        self._check_nan_inf = _nan_inf_enabled(check_nan_inf)
        self._array_safety = _array_safety_enabled()
        self._validated = set()  # (uid, version, feeds, fetches, multi)
        self._prefetcher = None  # core/dispatch.HostIoPrefetcher, armed
        # lazily by the first run(prefetch=True) on a reader-fed program
        self._has_read = {}  # (uid, version) -> program has `read` ops
        self.last_stats = {}  # guard stat channel (grad_norm, ...):
        # device-resident values peeled off the newest dispatch's error
        # dict — the sentinel's zero-extra-sync tap

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, steps=1,
            fetch_reduce="stack", validate=None, timeout=None,
            prefetch=False):
        """Run `program` once — or, with steps=K > 1, K times inside ONE
        device-resident lax.scan dispatch: params/optimizer state stay
        donated on device across the K steps and the host syncs once per
        call instead of once per step. Explicit `feed` entries are replayed
        identically every step; in-graph reader (`read` op) feeds are
        popped K records at a time and sliced per step inside the loop.
        `fetch_reduce` picks what the K per-step fetch values collapse to:
        'stack' (default, leading-K axis), 'last', or 'mean'.

        return_numpy=False returns FetchHandle objects (device-resident,
        non-blocking): materialize with np.asarray(h) / h.numpy() when the
        value is actually needed.

        validate=True runs the static analyzer (paddle_tpu/analysis) over
        the program BEFORE lowering — use-before-def, shape/dtype
        consistency, unregistered ops, reader placement — and raises
        ProgramVerificationError on findings, pointing at the layer call
        that built the bad op. Default None defers to the
        FLAGS_validate_program env flag; validation is cached per
        (program version, feed/fetch signature) so steady-state runs pay
        nothing.

        timeout=SECONDS arms the hang watchdog (None = off, the default,
        zero overhead): the whole dispatch — io pre-pass, compile if any,
        device execution, fetch readiness — runs on a monitored worker
        thread, and a dispatch that exceeds the deadline raises
        DispatchTimeoutError carrying the compile-cache key. Watchdog
        mode syncs each call (the deadline needs a completion signal), so
        it trades PR-1's async dispatch pipelining for bounded latency —
        that is the watchdog's documented cost. After a timeout the
        abandoned worker never writes the scope, but donated buffers may
        already be consumed: recover by checkpoint rollback or abort.

        prefetch=True pipelines the host-io prepass (ARCHITECTURE.md
        §22): after each dispatch of a reader-fed program, a background
        stage pops the NEXT step's records (or the next K-block), pads
        and device_puts them while the current step executes on device;
        the next run() consumes the staged feeds instead of paying the
        prepass on the dispatch path. A fence, fault, checkpoint
        capture, or any signature change rolls the staged pops back
        exactly (push_back refunds the stream position), so retry
        bit-exactness and fence-consumes-nothing hold unchanged. With a
        prefetcher armed, poll end-of-data via the EOFException (it
        surfaces here with stream position intact), not reader.eof()."""
        if timeout is None:
            return self._run_impl(program, feed, fetch_list, scope,
                                  return_numpy, use_program_cache, steps,
                                  fetch_reduce, validate,
                                  prefetch=prefetch)
        return dispatch_with_deadline(
            lambda cancelled, info: self._run_impl(
                program, feed, fetch_list, scope, return_numpy,
                use_program_cache, steps, fetch_reduce, validate,
                cancelled=cancelled, info=info, sync=True,
                prefetch=prefetch),
            timeout, "Executor.run dispatch")

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, steps, fetch_reduce, validate,
                  cancelled=None, info=None, sync=False, prefetch=False):
        # one trace per training step (ARCHITECTURE.md §24), via the
        # executors' ONE shared wrapper (core/dispatch.run_step_traced):
        # the root span lives on THIS thread — in watchdog mode that is
        # the monitored worker, so a wedged dispatch leaves its step
        # trace (and whichever child span it is stuck inside) OPEN for
        # the diagnostic bundle's recorder dump to capture.
        from .dispatch import run_step_traced
        return run_step_traced(
            "exe", cancelled,
            lambda phases: self._run_traced(
                program, feed, fetch_list, scope, return_numpy,
                use_program_cache, steps, fetch_reduce, validate,
                cancelled, info, sync, prefetch, phases))

    def _run_traced(self, program, feed, fetch_list, scope, return_numpy,
                    use_program_cache, steps, fetch_reduce, validate,
                    cancelled, info, sync, prefetch, phases):
        # `phases` (core/dispatch.StepPhases) opened exec/prepare; the
        # shared helpers and the enter() calls below move the step
        # through the rest of its children
        if program is None:
            program = default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()
        steps = int(steps)
        if steps < 1:
            raise ValueError("steps must be >= 1, got %r" % (steps,))
        phases.step.set(program=str(program._uid),
                        version=int(program._version), steps=steps)
        if fetch_reduce not in lowering.FETCH_REDUCE_POLICIES:
            raise ValueError("fetch_reduce must be one of %r, got %r"
                             % (lowering.FETCH_REDUCE_POLICIES, fetch_reduce))

        fetch_names = [f if isinstance(f, str) else f.name for f in fetch_list]
        feed_arrays = convert_feeds(program, feed)

        maybe_validate_program(program, feed_arrays, fetch_names, steps,
                               self._validated, validate=validate)

        if info is not None:
            # preliminary watchdog identity: a dispatch that wedges in
            # the io pre-pass (or an injected pre-pass fault) still gets
            # a cache key on its DispatchTimeoutError; refined below
            # once the stacked-feed set is known
            info["cache_key"] = (program._uid, program._version,
                                 _feed_signature(feed_arrays),
                                 tuple(fetch_names))

        # pre-dispatch hooks + host-io consume: the shared dispatch-guard
        # seam (core/dispatch.py) — the cluster fence and fault-injection
        # hooks fire BEFORE the io pre-pass and seed draw (a fenced or
        # faulted attempt consumes nothing), with any staged prefetch
        # block refunded on a hook raise
        from . import dispatch as _dispatch
        pf = self._prefetcher
        _dispatch.run_dispatch_hooks(program, steps, feed_arrays,
                                     prefetcher=pf, cancelled=cancelled)
        stacked_names = set()
        staged = _dispatch.consume_host_io(
            self, program, scope, steps, False, cancelled, feed_arrays,
            stacked_names, phases, place=self.place)
        if staged is _dispatch.CANCELLED:
            return None  # deadline raised on the caller's thread
        if cancelled is not None and cancelled.is_set():
            return None

        feed_names = sorted(feed_arrays)
        # program._uid is mandatory (as in ParallelExecutor): id() of a GC'd
        # program can be recycled and silently serve a stale jitted fn.
        # trace_env_key() carries every trace-time env flag (conv layout,
        # flash dispatch, kernel selection) — flipping one between runs must
        # re-trace, not silently serve the other configuration's fn.
        # (steps, fetch_reduce, stacked feed set) shape the traced loop the
        # same way: a K=8 'mean' fn must never serve a K=4 'stack' call.
        from .lowering import trace_env_key
        unroll = lowering.resolve_multistep_unroll(
            self.place.device().platform) if steps > 1 else False
        multi_sig = (steps, fetch_reduce if steps > 1 else None, unroll,
                     tuple(sorted(stacked_names)))
        key = (program._uid, program._version,
               _feed_signature(feed_arrays), tuple(fetch_names),
               trace_env_key(), multi_sig)
        if info is not None:
            info["cache_key"] = key

        def read_state(names):
            vals = []
            for n in names:
                v = scope.get(n)
                if v is None:
                    raise RuntimeError(
                        "persistable variable %r is not initialized in the "
                        "scope; run the startup program first" % n)
                vals.append(v)
            return vals

        compiled = False
        aot_hit = False
        aot_saved = 0.0
        aot_compile_s = 0.0  # eager lower+compile time paid THIS call
        aot_entry = None  # (dir, key_hash) when this call loaded from disk
        entry = self._cache.get(key) if use_program_cache else None
        if entry is not None:
            self._cache.move_to_end(key)  # LRU touch
        else:
            state_rw, state_ro, state_out = lowering.analyze_state(
                program, feed_names, fetch_names)
            # persistent AOT artifact cache (core/compile_cache.py): on
            # an in-process miss, a warm disk entry replaces the whole
            # trace+lower+compile with one deserialize — the restart /
            # serving-warmup cold-start killer. Off (akey=None) unless
            # FLAGS_aot_cache_dir / enable_aot_cache enabled it.
            # use_program_cache=False opts out of caching wholesale:
            # consulting the disk cache there would re-deserialize (and
            # count a hit + 'time saved') on EVERY call of the loop.
            aot_dir = (compile_cache.active_aot_cache_dir()
                       if use_program_cache else None)
            akey = None
            if aot_dir is not None:
                akey = compile_cache.aot_entry_key(
                    program, _feed_signature(feed_arrays),
                    tuple(fetch_names), trace_env_key(), multi_sig,
                    self.place.device())
            executable = None
            if akey is not None:
                loaded = compile_cache.aot_load(
                    aot_dir, akey[0], akey[1], [self.place.device()])
                if loaded is not None:
                    executable, aot_saved = loaded
                    aot_hit = True
                    aot_entry = (aot_dir, akey[0])
            if executable is None:
                compiled = True
                if steps > 1:
                    fn = lowering.lower_multi_step(
                        program, feed_names, fetch_names, state_rw,
                        state_ro, state_out, steps,
                        fetch_reduce=fetch_reduce,
                        stacked_feed_names=stacked_names, unroll=unroll)
                else:
                    fn = lowering.build_program_fn(
                        program, feed_names, fetch_names, state_rw,
                        state_ro, state_out, collect_errors=True)
                if akey is not None:
                    # eager AOT: lower+compile NOW (against the real
                    # argument avals — .lower only traces, it consumes
                    # nothing) so the executable can be serialized; the
                    # cold process keeps THIS executable too — one
                    # compile, not two. A failed store still leaves a
                    # usable executable.
                    try:
                        t0c = time.perf_counter()
                        with jax.default_device(self.place.device()):
                            comp = lowering.jit_step(fn).lower(
                                [feed_arrays[n] for n in feed_names],
                                read_state(state_rw),
                                read_state(state_ro),
                                np.uint32(0)).compile()
                        aot_compile_s = time.perf_counter() - t0c
                        compile_cache.aot_store(
                            aot_dir, akey[0], akey[1], comp, aot_compile_s)
                        executable = comp
                    except Exception:  # noqa: BLE001 — best-effort
                        # cache; the jitted fn path raises real trace
                        # errors with their op annotations at dispatch
                        pass
                if executable is None:
                    executable = lowering.jit_step(fn)
            entry = (executable, state_rw, state_ro, state_out)
            if use_program_cache:
                _cache_put_lru(self._cache, key, entry,
                               _jit_cache_capacity())
        jitted, state_rw, state_ro, state_out = entry

        seed = np.uint32(scope.next_seed() if steps == 1
                         else scope.next_seed_block(steps))
        from .. import profiler as _prof
        profiling = _prof.is_active()
        # device-enqueue span: async dispatch, so the duration is the
        # host-side enqueue (+ trace/compile when compiling) — a hang
        # inside leaves it OPEN, which is exactly what the bundle's
        # recorder dump needs to show
        dsp = phases.enter("exec/dispatch")
        t0 = time.perf_counter() if profiling else 0.0

        def _call(fn_obj):
            dev = self.place.device()
            with jax.default_device(dev):
                feeds = [feed_arrays[n] for n in feed_names]
                rw, ro = read_state(state_rw), read_state(state_ro)
                if any(getattr(f, "committed", False) for f in feeds):
                    # committed feeds (a reader staging to the place, a
                    # caller's device_put) commit this call's outputs.
                    # Commit the state too, or the next step — whose
                    # state IS those outputs — lowers under another
                    # argument signature and XLA compiles the whole
                    # program a second time.
                    rw = _commit(rw, dev)
                    placed = _commit(ro, dev)
                    for n, old, new in zip(state_ro, ro, placed):
                        if new is not old:
                            scope.set(n, new)  # never donated: keep it
                    ro = placed
                with phases:    # exec/jit_call
                    return fn_obj(feeds, rw, ro, seed)

        def _find_aot_entry():
            aot_dir = compile_cache.active_aot_cache_dir()
            if not aot_dir:
                return None
            akey = compile_cache.aot_entry_key(
                program, _feed_signature(feed_arrays),
                tuple(fetch_names), trace_env_key(), multi_sig,
                self.place.device())
            return (aot_dir, akey[0])

        def _rebuild():
            # fresh (retracing, donating) jit — see call_with_aval_fallback
            if steps > 1:
                fn = lowering.lower_multi_step(
                    program, feed_names, fetch_names, state_rw, state_ro,
                    state_out, steps, fetch_reduce=fetch_reduce,
                    stacked_feed_names=stacked_names, unroll=unroll)
            else:
                fn = lowering.build_program_fn(
                    program, feed_names, fetch_names, state_rw, state_ro,
                    state_out, collect_errors=True)
            fresh = lowering.jit_step(fn)
            if use_program_cache:
                _cache_put_lru(self._cache, key,
                               (fresh, state_rw, state_ro, state_out),
                               _jit_cache_capacity())
            return fresh

        # lowering.jit_step's order: the state first, for the donation
        (new_state, fetches, errors), fell_back = \
            _dispatch.call_with_aval_fallback(
                _call, jitted, aot_entry, _find_aot_entry, _rebuild)
        if fell_back:
            compiled, aot_hit, aot_saved, aot_entry = \
                True, False, 0.0, None
        if compiled:
            # once a compile: which result jax gave each donated buffer to
            # (the scope still holds the donated arrays; types are enough)
            lowering.count_donated_buffers(
                state_rw, [scope.get(n) for n in state_rw], state_out,
                new_state, (fetches, errors))
            # and what lets the profiler ask jax for this executable again
            # without a compile (profiler.step_op_names), beside the entry
            _note_compiled_step(self, key, feed_arrays, feed_names, scope,
                                seed)
        # sentinel stat tap: peel float statistics (grad norm) off the
        # error dict before any error sync; values stay device-resident
        self.last_stats = pop_guard_stats(errors)
        dsp.set(compiled=compiled, aot_hit=aot_hit)
        if cancelled is not None and cancelled.is_set():
            # the caller already raised DispatchTimeoutError and may be
            # mid-rollback: a late scope write here would race the
            # restore and resurrect stale state
            return None
        if sync:
            # watchdog mode: the deadline needs a completion signal, so
            # the worker waits for the device BEFORE the scope write-back
            # — an execution-phase hang must leave the scope without the
            # unresolved async arrays (np.asarray on one would block the
            # diagnostic-bundle capture and any inspection forever; the
            # old donated-and-deleted buffers raise instead, which
            # write_bundle records per-var as state_unavailable)
            _prof.note_sync("executor/watchdog_sync")
            phases.enter("exec/watchdog_sync")
            jax.block_until_ready((fetches, new_state))
            if cancelled is not None and cancelled.is_set():
                return None
        phases.enter("exec/writeback")
        # write state back BEFORE anything that can raise (including the
        # profiler's block_until_ready): state_rw inputs were donated to the
        # jit, so on an exception path the scope must already hold the
        # (valid) output buffers or it is left pointing at deleted arrays
        # and the caller can't even checkpoint/inspect.
        for n, v in zip(state_out, new_state):
            scope.set(n, v)
        # pipelined dispatch: kick the NEXT step's host-io prepass NOW —
        # the staging thread pops/pads/device_puts while this step's
        # device work (and any sync below: guard flags, profiling,
        # return_numpy D2H) proceeds. Kicked only for reader-fed
        # programs; a cancelled (watchdog-abandoned) worker never kicks.
        if prefetch:
            pf = _dispatch.kick_next_prepass(
                self, program, scope, steps, False, cancelled, "exe",
                place=self.place)
        def _sync_extra():
            if not profiling:
                return
            tag = "program_%s(v%d)%s fetch=%s" % (
                getattr(program, "_uid", "?"), program._version,
                " x%d" % steps if steps > 1 else "",
                ",".join(fetch_names) or "-")
            _dispatch.profile_dispatch(
                tag, "executor/profiling", t0,
                (fetches, new_state), compiled, aot_hit, aot_saved,
                aot_compile_s)

        # guard-flag raise + FLAGS_check_nan_inf sweep + refund-on-raise:
        # the shared post-dispatch choreography (core/dispatch.py)
        _dispatch.run_post_dispatch_checks(
            errors, fetches, fetch_names, new_state, state_out,
            self._array_safety, self._check_nan_inf, "Executor.run",
            prefetcher=pf, cancelled=cancelled, sync_fn=_sync_extra)
        if return_numpy:
            _prof.note_sync("executor/return_numpy")
            phases.enter("exec/d2h")
            return [np.asarray(f) for f in fetches]
        return [FetchHandle(f) for f in fetches]




def _note_compiled_step(exe, key, feed_arrays, feed_names, scope, seed):
    """Once a compile: describe to the profiler the arguments `_call` just
    gave the step `exe` holds under `key` (nothing where it holds none:
    use_program_cache=False). The scope still holds the donated arrays,
    and types are enough; `_call` committed the state where a feed was."""
    held = exe._cache.get(key)
    if held is None:
        return
    from .. import profiler as _prof
    _, state_rw, state_ro, _ = held
    dev = exe.place.device()
    feeds = [feed_arrays[n] for n in feed_names]
    placed = jax.sharding.SingleDeviceSharding(dev) if any(
        getattr(f, "committed", False) for f in feeds) else None

    def seen(vals, otherwise=None):     # a committed array stays put
        return [v.sharding if getattr(v, "committed", False) else otherwise
                for v in vals]
    rw = [scope.get(n) for n in state_rw]
    ro = [scope.get(n) for n in state_ro]
    _prof.note_step(held[0], "exe", (feeds, rw, ro, seed),
                    (seen(feeds), seen(rw, placed), seen(ro, placed)),
                    device=dev)


def _commit(vals, device):
    """`vals` committed to `device` (no copy for an array already
    there)."""
    return [v if getattr(v, "committed", False)
            else jax.device_put(v, device) for v in vals]


def _to_array(value, var=None, host=False):
    """host=True keeps numpy values on the host (the ParallelExecutor path:
    its single sharded device_put must be the only transfer — staging via
    the default device first would double the volume and concentrate the
    full batch on device 0)."""
    if isinstance(value, jax.Array):
        # already device-resident: never round-trip via host, but still
        # honor the declared dtype (device-side cast is a cheap XLA op)
        if var is not None and var.dtype is not None:
            want = convert_dtype(var.dtype)
            if str(value.dtype) != want:
                value = value.astype(want)
        return value
    arr = np.asarray(value)
    if var is not None and var.dtype is not None:
        arr = arr.astype(convert_dtype(var.dtype), copy=False)
    return arr if host else jnp.asarray(arr)


def switch_scope(scope):
    """Swap the process-global scope, returning the previous one
    (parity: fluid.executor.switch_scope; scope_guard builds on it there)."""
    global _global_scope
    old = _global_scope
    _global_scope = scope
    return old


def fetch_var(name, scope=None, return_numpy=True):
    """Fetch a variable's value from `scope` (default: the global scope).
    Parity: fluid.executor.fetch_var."""
    if scope is None:
        scope = _global_scope
    v = scope.find_var(name)
    if v is None:
        raise RuntimeError(
            "cannot find variable %r in the scope; only persistable vars "
            "survive Executor.run (set persistable=True or fetch it in "
            "fetch_list)" % name)
    val = v.get_tensor()
    return np.asarray(val) if return_numpy else val
