"""The shared dispatch core: overlap, guard, watchdog and fault-tap
plumbing both runtimes front.

Both hot loops — the serving batcher and the training executors — used to
leave the device idle behind host work: the batcher's one worker formed,
padded, dispatched and scattered strictly in sequence, and Executor.run
performed the whole host-io prepass (reader pops, padding, H2D) serially
before every dispatch. This module is the one seam both runtimes front
instead of triplicating the overlap machinery (the first slice of the
ROADMAP item-5 shared runtime core) — and, since the fleet PR, also the
ONE home of the per-dispatch guard/watchdog/fault-tap choreography that
used to live three times (Executor, ParallelExecutor, serving/engine):
`run_dispatch_hooks`, `consume_host_io`, `run_post_dispatch_checks`,
`call_with_aval_fallback`, `run_with_deadline`/`dispatch_with_deadline`,
`run_compile_probe` and `ReplicaTap` (see the "dispatch-guard seam"
section below):

  * `InflightWindow` — bounds how many dispatches may be outstanding on
    the device at once (the serving batcher's continuous-batching window).
    Dispatches already return pre-D2H FetchHandles, so "outstanding" is
    tracked by a dedicated completion thread that blocks on the OLDEST
    dispatch's handles — the only place a host sync happens, and it is
    off the dispatch path by construction. The completion thread also
    measures device idle gaps (time between one dispatch's completion
    and the next dispatch's enqueue) for the profiler's utilization
    columns.

  * `HostIoPrefetcher` — runs the NEXT step's host-io prepass (reader
    pops, lod padding, stacking, H2D placement) on a background thread
    while the current step executes on device. The staged block is
    consumed by the next matching `run()` call; anything else — a fence,
    an injected fault, a checkpoint capture, a different program/steps
    signature — rolls the staged reader pops back exactly
    (`ReaderBase.push_back` refunds `_consumed`), so every replay
    invariant the serial prepass proved (retry bit-exactness,
    fence-consumes-nothing, checkpoint reader positions) survives the
    overlap. See ARCHITECTURE.md §22 for the invariant proofs.

Checkpoint composition: `rollback_all_staged(scope)` is the quiesce hook
`checkpoint.CheckpointManager` calls before capturing or restoring reader
positions — a staged-but-untrained block must never be recorded as
consumed.
"""
import queue
import threading
import time
import weakref

from ..observability import registry as _obsreg
from ..observability import trace as _trace

__all__ = ["InflightWindow", "HostIoPrefetcher", "rollback_all_staged",
           "CANCELLED"]


# sentinel: take() observed the caller's watchdog cancellation while
# waiting for the staging thread — the run unwinds without a refund (the
# caller's recovery restores reader positions itself, exactly like the
# serial prepass's cancelled-rollback contract)
CANCELLED = object()

_CLOSE = object()


class InflightWindow(object):
    """Bounded window of dispatched-but-not-device-complete batches.

    The dispatch worker `acquire()`s a slot before enqueueing a batch and
    hands the resulting (lazy, pre-D2H) fetch handles to `track()`; a
    dedicated completion thread blocks on each tracked dispatch's handles
    in FIFO order and releases the slot when the device finishes. With
    depth >= 2 the device always has the next batch queued behind the
    running one while the host pads the one after — continuous batching.

    Device-idle accounting: completion of dispatch i at t_ready and
    enqueue of dispatch i+1 at t_enq > t_ready means the device sat idle
    for (t_enq - t_ready); the completion thread sums these gaps per
    window and reports them through `profiler.record_idle` under the
    window's tag (the host-observable lower bound on device idleness —
    a dispatch enqueued before the previous completed counts zero)."""

    def __init__(self, depth, tag=None):
        if depth < 1:
            raise ValueError("InflightWindow depth must be >= 1, got %r"
                             % (depth,))
        self.depth = int(depth)
        self.tag = tag
        self._sem = threading.Semaphore(self.depth)
        self._q = queue.Queue()
        self._lock = threading.Lock()
        self._last_ready = None   # monotonic completion of previous batch
        self._idle_s = 0.0
        self._gaps = 0
        self._completed = 0
        self._iterations = 0  # decode iterations (note_iteration)
        self._thread = threading.Thread(
            target=self._completion_loop, daemon=True,
            name="ptpu-window-%s" % (tag or "anon"))
        self._thread.start()
        # observability: depth/completed/idle surface on /metrics for
        # this window's lifetime (weakref — closed windows drop off)
        _obsreg.note_window(self)

    # ------------------------------------------------------------ slots --
    def acquire(self, timeout=None):
        """Take one in-flight slot (blocks while `depth` dispatches are
        outstanding). Returns False on timeout."""
        return self._sem.acquire(timeout=timeout) if timeout is not None \
            else self._sem.acquire()

    def release(self):
        """Give a slot back WITHOUT tracking (the dispatch failed before
        any device work was enqueued)."""
        self._sem.release()

    def track(self, handles, enqueued_at=None, on_complete=None):
        """Register an enqueued dispatch's fetch handles; the completion
        thread releases the slot (and accounts the idle gap) once the
        device finishes them. `handles` may be empty (a dispatch that
        produced no device work releases immediately). `on_complete`
        (kwargs-only; called with error=<exception class name> when the
        device-side wait raised) runs on the completion thread right
        after the device finishes — the trace layer rides it to close
        the batch's window-occupancy span at the real completion
        instant, carrying the device failure if there was one."""
        self._q.put((tuple(handles or ()),
                     time.monotonic() if enqueued_at is None
                     else enqueued_at, on_complete))

    # ------------------------------------------------------- completion --
    def _completion_loop(self):
        import jax
        from .. import profiler as _prof
        while True:
            item = self._q.get()
            if item is _CLOSE:
                return
            handles, enq_t, on_complete = item
            arrays = [getattr(h, "array", h) for h in handles]
            err = None
            try:
                if arrays:
                    # the window's ONE host sync — on the completion
                    # thread, never the dispatch path
                    _prof.note_sync("window/completion")
                    jax.block_until_ready(arrays)
            except Exception as e:  # noqa: BLE001 — a failed batch
                # already failed its futures; the slot must come back
                # regardless, but the EXECUTION span must not render as
                # a clean completion in the postmortem timeline
                err = type(e).__name__
            if on_complete is not None:
                try:
                    on_complete(**({"error": err} if err else {}))
                except Exception:  # noqa: BLE001 — an observer must
                    pass           # never wedge slot recycling
            ready = time.monotonic()
            with self._lock:
                if self._last_ready is not None and enq_t > self._last_ready:
                    gap = enq_t - self._last_ready
                    self._idle_s += gap
                    self._gaps += 1
                    if self.tag and _prof.is_active():
                        _prof.record_idle(self.tag, gap)
                self._last_ready = ready
                self._completed += 1
            self._sem.release()

    def note_iteration(self):
        """Count one decode iteration against this window.  A decode
        step-loop (serving.DecodeBatcher) runs MANY jitted steps per
        tracked dispatch slot; the per-step count is the unit the
        bucket-lattice invariant is proved at under slot reuse (every
        iteration re-establishes 'row result depends only on that row at
        this fixed shape'), so it surfaces in stats()/metrics distinctly
        from `completed` (tracked dispatches)."""
        with self._lock:
            self._iterations += 1

    def stats(self):
        with self._lock:
            return {"idle_s": self._idle_s, "gaps": self._gaps,
                    "completed": self._completed,
                    "iterations": self._iterations}

    def close(self, timeout=None):
        self._q.put(_CLOSE)
        self._thread.join(timeout)


# ---------------------------------------------------------------------------
# Host-io prefetch
# ---------------------------------------------------------------------------

_live_prefetchers = weakref.WeakSet()


class _StagedBlock(object):
    """One prefetched prepass result, parked until the next dispatch.

    Identity (program/scope/steps/host) decides whether the next run may
    consume it; `popped` is the exact refund ledger — (reader_state,
    records) in pop order, so `refund()` restores every stream position
    bit-exactly (push_back reversed, like the prepass's own rollback)."""

    __slots__ = ("program", "scope", "steps", "host", "arrays", "stacked",
                 "popped", "error", "dropped")

    def __init__(self, program, scope, steps, host):
        self.program = program
        self.scope = scope
        self.steps = steps
        self.host = host
        self.arrays = {}
        self.stacked = set()
        self.popped = []     # [(reader_state, [record, ...])]
        self.error = None
        self.dropped = False  # cancelled: recovery owns the positions

    def matches(self, program, scope, steps, host):
        return (self.program is program and self.scope is scope
                and self.steps == steps and self.host == host)

    def refund(self):
        if self.dropped:
            return
        for state, records in reversed(self.popped):
            for rec in reversed(records):
                state.push_back(rec)
        self.popped = []


class _OrEvent(object):
    """is_set() over two events: the run-local watchdog cancellation and
    the prefetcher's own abandon flag — run_host_io_prepass's
    cancellation checkpoints honor either."""

    __slots__ = ("_a", "_b")

    def __init__(self, a, b):
        self._a, self._b = a, b

    def is_set(self):
        return (self._a is not None and self._a.is_set()) or \
            self._b.is_set()


class HostIoPrefetcher(object):
    """Background host-io prepass: pops, pads and places step N+1's
    reader records while step N executes on device.

    Protocol (one owner executor, calls from its dispatch thread):
      * `kick(...)` at the end of a successful dispatch starts the
        background prepass for the next step.
      * `take(program, scope, steps, host)` at the top of the next
        dispatch (AFTER the barrier/fault hooks — a hook that raises
        must find the staged pops refundable) waits for the staging
        thread and returns the staged block when the identity matches;
        a mismatch refunds the staged pops and returns None (the caller
        runs the prepass inline); a staged prepass ERROR re-raises here,
        on the consuming thread, with nothing consumed (the staging
        thread refunded before parking the error). Returns the CANCELLED
        sentinel when the caller's watchdog fired mid-wait.
      * `rollback()` refunds whatever is staged (fence/fault/checkpoint
        paths).

    The staging thread is the ONLY consumer of the readers between kick
    and take, so `ReaderBase` needs no new locking; `reader.eof()` polls
    from other threads race the staging pop and are unsupported while a
    prefetcher is armed — end epochs on the EOFException instead (it
    surfaces at take(), stream position intact).

    Cost model: one fresh daemon thread per kick (~50-100us create) —
    deliberate, because a staged block's lifetime must end crisply at
    take/rollback and take()'s join wakes the moment the thread exits.
    Against the millisecond-class steps where prefetch pays at all
    (K-blocks amortize it further) the churn is noise; a step fast
    enough to feel it gains nothing from prefetch in the first place —
    leave it off there."""

    def __init__(self, name="prefetch"):
        self.name = name
        self._lock = threading.Lock()
        self._thread = None
        self._inflight = None        # _StagedBlock the thread is filling
        self._staged = None          # _StagedBlock once the thread ran
        self._abandon = threading.Event()
        _live_prefetchers.add(self)

    # ----------------------------------------------------------- status --
    def has_work(self):
        """A staging thread is running or a block is parked."""
        with self._lock:
            return self._thread is not None or self._staged is not None

    # ------------------------------------------------------------- kick --
    def kick(self, program, scope, steps, host, place=None, validate=None,
             stage_fn=None, cancelled=None):
        """Start the background prepass for the next step. `place` pins
        the staging device for the Executor path (jnp placement on the
        staging thread targets the dispatch device, not the thread's
        default); `stage_fn(arrays, stacked)` lets the ParallelExecutor
        do its own sharded device_put per feed on the staging thread;
        `validate` is the per-record check (PE divisibility), forwarded
        to the prepass."""
        from .executor import run_host_io_prepass
        if self.has_work():
            # defensive: the owner always take()s/rolls back before
            # kicking again; a stale block must not leak records
            self.rollback()
        with self._lock:
            self._abandon.clear()
            block = _StagedBlock(program, scope, steps, host)
            cancel = _OrEvent(cancelled, self._abandon)

            def work():
                # the overlap itself, made visible: this span runs on
                # the staging thread concurrently with the consuming
                # step's exec/dispatch span — the timeline SHOWS the
                # host-io prepass hidden behind device execution
                ssp = _trace.span("exec/prefetch_stage", cat="train",
                                  prefetcher=self.name, steps=steps)
                try:
                    ctx = None
                    if place is not None:
                        import jax
                        ctx = jax.default_device(place.device())
                        ctx.__enter__()
                    try:
                        run_host_io_prepass(
                            program, scope, block.arrays, host=host,
                            validate=validate, steps=steps,
                            stacked_out=block.stacked,
                            cancelled=cancel, place=place,
                            popped_out=block.popped)
                        if stage_fn is not None:
                            stage_fn(block.arrays, block.stacked)
                    finally:
                        if ctx is not None:
                            ctx.__exit__(None, None, None)
                except BaseException as e:  # noqa: BLE001 — parked for
                    # the consuming thread. Refund anything this block
                    # committed before failing (steps>1 prepass rolls
                    # back internally and commits nothing on failure;
                    # steps=1 commits pop-by-pop, and an error block is
                    # discarded whole — its earlier pops must go back so
                    # the error consumes NOTHING, which is what the
                    # fence/retry invariants need)
                    block.refund()
                    block.error = e
                ssp.end(**({"error": type(block.error).__name__}
                           if block.error is not None else {}))
                with self._lock:
                    self._staged = block
                    self._inflight = None
                    self._thread = None

            t = threading.Thread(target=work, daemon=True,
                                 name="ptpu-prefetch-%s" % self.name)
            self._thread = t
            self._inflight = block
            t.start()

    # ------------------------------------------------------------- take --
    def take(self, program, scope, steps, host, cancelled=None):
        """Claim the staged block for this dispatch (see class doc).
        Identity is checked BEFORE a parked staging error: an error
        staged for a DIFFERENT signature (e.g. EOF from a steps=8 kick
        when only 5 records remained, followed by a steps=1 tail pass
        or an eval program through the same executor) consumed nothing
        — the staging thread refunded before parking it — so this
        mismatched dispatch must fall back to its own inline prepass,
        not fail on a stranger's error. The error re-raises only when
        the MATCHING dispatch arrives, exactly where the serial prepass
        would have raised it."""
        block = self._wait(cancelled)
        if block is CANCELLED:
            return CANCELLED
        if block is None:
            return None
        if not block.matches(program, scope, steps, host):
            if block.error is None:
                block.refund()
            return None
        if block.error is not None:
            raise block.error
        return block

    def rollback(self, cancelled=None):
        """Refund the staged pops (fence / fault / checkpoint quiesce).
        With `cancelled` set the block is dropped WITHOUT refund — the
        caller's recovery restores reader positions itself, and a late
        refund would prepend stale records into the restored stream."""
        block = self._wait(cancelled)
        if block is CANCELLED or block is None:
            return
        block.refund()

    def _wait(self, cancelled=None):
        """Join the staging thread and detach the staged block. On
        watchdog cancellation mid-wait: abandon the staging thread (it
        stops at its next prepass checkpoint without refunding) and mark
        the block it is filling as dropped — whoever detaches it later
        discards it without refund, because the caller's recovery owns
        the reader positions from here."""
        while True:
            with self._lock:
                t = self._thread
                if t is None:
                    block, self._staged = self._staged, None
                    if block is not None and block.dropped:
                        block = None  # parked by an abandoned staging run
                    return block
            if cancelled is not None and cancelled.is_set():
                self._abandon.set()
                with self._lock:
                    if self._staged is not None:
                        self._staged.dropped = True
                        self._staged = None
                    if self._inflight is not None:
                        self._inflight.dropped = True
                return CANCELLED
            t.join(timeout=0.05)

    def close(self):
        """Refund anything staged and forget the prefetcher (executor
        teardown / tests)."""
        self.rollback()
        _live_prefetchers.discard(self)


def has_read_ops(program, cache):
    """Does `program` pop reader records in its main block? Cached per
    (uid, version) in the caller's dict — consulted per dispatch, walked
    once per program."""
    key = (program._uid, program._version)
    if key not in cache:
        cache[key] = any(op.type == "read"
                         for op in program.global_block().ops)
    return cache[key]


def kick_next_prepass(executor, program, scope, steps, host, cancelled,
                      name, **kick_kw):
    """The executors' shared kick choreography (ONE copy for
    Executor._run_impl and ParallelExecutor._run_impl): lazily arm the
    executor's prefetcher and kick the next step's prepass — a no-op
    for readerless programs (nothing to stage) and for a cancelled
    (watchdog-abandoned) worker (its recovery owns the readers).
    Returns the (possibly just-created) prefetcher. `kick_kw` carries
    the per-executor staging strategy: Executor pins `place=`;
    ParallelExecutor passes `validate=`/`stage_fn=` for its sharded
    device_put."""
    if cancelled is not None and cancelled.is_set():
        return executor._prefetcher
    if not has_read_ops(program, executor._has_read):
        return executor._prefetcher
    pf = executor._prefetcher
    if pf is None:
        pf = executor._prefetcher = HostIoPrefetcher(name=name)
    pf.kick(program, scope, steps, host, cancelled=cancelled, **kick_kw)
    return pf


class StepPhases(object):
    """One exec/step and the children that tile it, ONE copy for both
    executors. A call passes through them in this order, each opened at
    the instant the one before ends (`Span.then`), so that their sum is
    the step:

      exec/prepare        entry to the host-io consume: feed conversion,
                          validation, the barrier and fault hooks
      exec/host_io        the staged block's claim, else the inline
                          prepass (`consume_host_io`)
      exec/lookup         cache key and in-process cache; on a miss
                          analyze_state, build_program_fn /
                          lower_multi_step, the AOT cache's load or its
                          eager compile
      exec/dispatch       state read, feed placement and, inside it,
        exec/jit_call     the call of the jitted function alone
                          (`with phases:`)
      exec/watchdog_sync  watchdog mode only
      exec/writeback      scope write-back, prefetch kick, post-dispatch
                          checks, fetch handles
      exec/d2h            return_numpy only

    `compile_events` collects what jax.monitoring reported while the
    step was open on this thread (core/compile_cache.py books it when
    the step ends); `innermost()` is the span such an event belongs
    under."""

    __slots__ = ("step", "cur", "inner", "compile_events")

    def __init__(self, step):
        self.step = step
        self.cur = step.child("exec/prepare")
        self.inner = None
        self.compile_events = []

    def enter(self, name):
        self.cur = self.cur.then(name)
        return self.cur

    def __enter__(self):
        """`with phases:` around the call of the jitted function alone is
        exec/jit_call. A `with` and no method that makes the call: a first
        call traces under it, and jax records the Python stack with every
        equation, so a frame here would be paid for by every rule."""
        self.inner = self.cur.child("exec/jit_call")

    def __exit__(self, *exc):
        self.inner.end()
        self.inner = None
        return False

    def innermost(self):
        return self.inner if self.inner is not None else self.cur


_open_steps = threading.local()


def open_step():
    """The StepPhases of the exec/step open on the calling thread, or
    None outside an executor's run."""
    return getattr(_open_steps, "phases", None)


def run_step_traced(label, cancelled, body_fn, **span_args):
    """The executors' shared step-trace wrapper (ONE copy for
    Executor._run_impl and ParallelExecutor._run_impl — its error
    semantics changed three times during review hardening, exactly the
    drift hand-mirrored copies invite): mint one trace per step —
    inheriting the thread's ambient trace when a layer above (the
    serving batcher's per-batch scope_trace) already owns one, so a
    serving dispatch's exec/step span correlates with its batch — call
    `body_fn(phases)` with the step's StepPhases, and close the trace
    honestly: a raise ends every
    open span of the trace with the error name; a watchdog-cancelled
    body that unwedged after the caller's DispatchTimeoutError must not
    render as a clean step. Runs on the dispatching thread (the
    monitored worker in watchdog mode), so a wedge leaves the step's
    spans OPEN for the diagnostic bundle."""
    from . import compile_cache
    compile_cache.watch_compile_phases()
    tr = _trace.ambient()
    tspan = _trace.span("exec/step", cat="train",
                        trace=tr if tr is not None else _trace.new_trace(),
                        executor=label, **span_args)
    phases = StepPhases(tspan)
    outer, _open_steps.phases = open_step(), phases
    try:
        out = body_fn(phases)
    except BaseException as e:
        err = type(e).__name__
        _trace.end_open(tspan.trace, error=err)
        tspan.end(error=err)
        raise
    finally:
        _open_steps.phases = outer
        if phases.compile_events:
            compile_cache.book_compile_phases(phases.compile_events)
    if cancelled is not None and cancelled.is_set():
        _trace.end_open(tspan.trace, error="DispatchCancelled")
        tspan.end(error="DispatchCancelled")
        return out
    phases.cur.end()
    tspan.end()
    return out


# ---------------------------------------------------------------------------
# The dispatch-guard seam: ONE copy of the per-dispatch plumbing that
# `Executor._run_traced`, `ParallelExecutor._run_traced` and the serving
# engine used to carry separately (guards, watchdog, fault taps, cache
# fallback). The hook VARIABLES (`core.executor._fault_hook` /
# `_barrier_hook`) stay where resilience/faults.py and
# resilience/cluster.py install them; the choreography around them lives
# here, once.
# ---------------------------------------------------------------------------


def run_dispatch_hooks(program, steps, feed_arrays, prefetcher=None,
                       cancelled=None):
    """The pre-dispatch hook choreography: the cluster step barrier
    first (a fenced cohort stops before anything is consumed), then the
    fault-injection seam (an injected dispatch failure or slow step
    consumes no reader records and no rng — a retried step replays
    bit-exactly). Either hook raising refunds anything a prefetcher
    staged, so fence-consumes-nothing covers the staged block too."""
    from . import executor as _exe
    try:
        if _exe._barrier_hook is not None:
            _exe._barrier_hook("dispatch", program=program, steps=steps)
        if _exe._fault_hook is not None:
            _exe._fault_hook("dispatch", program=program, steps=steps,
                             feed_arrays=feed_arrays)
    except BaseException:
        if prefetcher is not None:
            prefetcher.rollback(cancelled=cancelled)
        raise


def consume_host_io(executor, program, scope, steps, host, cancelled,
                    feed_arrays, stacked_names, phases, **inline_kw):
    """The host-io consume choreography, shared by both executors: claim
    the prefetcher's staged block when its identity matches (refunding a
    mismatched one BEFORE the inline prepass pops the stream, or the
    staged records would replay out of order), else run the inline
    prepass; the exec/host_io span closes honestly on every path, and
    exec/lookup opens where it ends.
    Returns the staged block, None (inline prepass ran), or the
    CANCELLED sentinel (the caller's watchdog fired — unwind without
    touching more state). `inline_kw` carries the per-executor prepass
    strategy (Executor pins place=; ParallelExecutor passes
    validate=)."""
    from .executor import run_host_io_prepass, _DispatchCancelled
    pf = executor._prefetcher
    staged = None
    iosp = phases.enter("exec/host_io")
    try:
        if pf is not None and pf.has_work():
            # consult the prefetcher even on a prefetch=False call: a
            # staged block for a different signature must be refunded
            # before the inline prepass pops the stream
            staged = pf.take(program, scope, steps, host,
                             cancelled=cancelled)
            if staged is CANCELLED:
                iosp.end(error="DispatchCancelled")
                return CANCELLED
        if staged is not None:
            feed_arrays.update(staged.arrays)
            stacked_names.update(staged.stacked)
        else:
            try:
                run_host_io_prepass(program, scope, feed_arrays,
                                    host=host, steps=steps,
                                    stacked_out=stacked_names,
                                    cancelled=cancelled, **inline_kw)
            except _DispatchCancelled:
                iosp.end(error="DispatchCancelled")
                return CANCELLED
    except BaseException as e:  # EOF / reader faults: close the span,
        iosp.end(error=type(e).__name__)  # the fault rides up
        raise
    iosp.set(staged=staged is not None)
    phases.enter("exec/lookup")
    return staged


def run_post_dispatch_checks(errors, fetches, fetch_names, new_state,
                             state_out, array_safety, check_nan_inf,
                             context, prefetcher=None, cancelled=None,
                             sync_fn=None):
    """The post-dispatch guard choreography: the in-graph assertion-flag
    raise (guard flags raise even with FLAGS_tensor_array_safety=0 — a
    program that INSTALLED guards opted into the one-fetch sync) and the
    optional FLAGS_check_nan_inf sweep. Any raise — including from
    `sync_fn`, the executor-specific profiling / CPU-collective sync
    that precedes the checks — refunds the prefetcher's just-kicked next
    block first, so the stream position is exactly what the failed step
    left (its own records consumed, nothing more)."""
    from .executor import (GUARD_MSG_PREFIX, _raise_program_errors,
                           check_finite)
    try:
        if sync_fn is not None:
            sync_fn()
        has_guards = bool(errors) and any(
            m.startswith(GUARD_MSG_PREFIX) for m in errors)
        if array_safety or has_guards:
            _raise_program_errors(errors, include_non_guard=array_safety)
        if check_nan_inf:
            check_finite(list(zip(fetch_names, fetches)) +
                         list(zip(state_out, new_state)), context=context)
    except BaseException:
        if prefetcher is not None:
            prefetcher.rollback(cancelled=cancelled)
        raise


def call_with_aval_fallback(call, jitted, aot_entry, find_aot_entry,
                            rebuild):
    """The fixed-aval Compiled call-time fallback, one copy for both
    executors: a plain jit retraces by itself (a TypeError/ValueError
    there is real), but a `jax.stages.Compiled` — AOT-loaded from disk,
    or an in-process eager-AOT entry whose state avals drifted under an
    unchanged key — rejects the live argument avals (TypeError) or their
    device placement (ValueError: a deserialized artifact is bound to
    the concrete devices it was compiled for). Aval/placement checking
    precedes execution, so nothing was donated or consumed: discard the
    disk entry and call `rebuild()`'s fresh (retracing, donating) jit —
    the cache's only failure mode. Returns (result, fell_back)."""
    import jax as _jax
    try:
        return call(jitted), False
    except (TypeError, ValueError):
        if aot_entry is None and not isinstance(jitted,
                                                _jax.stages.Compiled):
            raise
        if aot_entry is None:
            aot_entry = find_aot_entry()
        if aot_entry is not None:
            from . import compile_cache
            compile_cache.discard_bad_entry(
                *aot_entry, reason="argument avals rejected at call time")
        return call(rebuild()), True


def profile_dispatch(tag, sync_tag, t0, arrays, compiled, aot_hit,
                     aot_saved, aot_compile_s):
    """Profiling-mode dispatch accounting (one copy): sync, then per-tag
    host seconds (a compiled call's seconds include its eager-AOT compile —
    it ran before t0, so add it back or Compile(s) reports a 30s compile
    as free). Device idle is not estimated here: a host clock behind a
    sync cannot see it; the device trace does (profiler.device_op_table,
    the benchmark's device_idle_share)."""
    import jax as _jax
    from .. import profiler as _prof
    _prof.note_sync(sync_tag)
    _jax.block_until_ready(arrays)
    _prof.record_run(tag, time.perf_counter() - t0
                     + (aot_compile_s if compiled else 0.0),
                     compiled=compiled, aot_hit=aot_hit, saved_s=aot_saved)


def run_with_deadline(fn, timeout, what="dispatch"):
    """Run fn(cancelled_event) on a watchdog-monitored worker thread and
    join with `timeout` seconds. On expiry the worker is abandoned (its
    cancelled event set, so it won't touch the scope when it eventually
    unblocks) and DispatchTimeoutError raises on the caller's thread.
    The jax context that matters (default_device) is thread-local, so fn
    must establish it itself."""
    from .executor import DispatchTimeoutError
    box = {}
    cancelled = threading.Event()

    def work():
        try:
            box["value"] = fn(cancelled)
        except BaseException as e:  # noqa: BLE001 — re-raised on caller
            box["error"] = e

    t = threading.Thread(target=work, daemon=True, name="ptpu-watchdog")
    t.start()
    t.join(timeout)
    if t.is_alive():
        cancelled.set()
        raise DispatchTimeoutError(
            "%s did not complete within %.3fs (hang watchdog)"
            % (what, timeout))
    if "error" in box:
        raise box["error"]
    return box.get("value")


def dispatch_with_deadline(run_impl, timeout, what):
    """The executors' shared watchdog wrapper: run
    `run_impl(cancelled, info)` under `run_with_deadline` and attach the
    compile-cache key the impl recorded in `info` to a timeout raise —
    ONE copy of the protocol for Executor.run and
    ParallelExecutor.run."""
    from .executor import DispatchTimeoutError
    info = {}
    try:
        return run_with_deadline(
            lambda cancelled: run_impl(cancelled, info), timeout,
            what=what)
    except DispatchTimeoutError as e:
        e.cache_key = info.get("cache_key")
        raise


def run_compile_probe(cache, run_fn):
    """Did `run_fn()` insert a new compiled entry into `cache`? Compares
    the key SET, not its length — at LRU capacity an insert+evict keeps
    the length constant. The serving engine's compile detection (warmup
    accounting, the steady-state-never-compiles gate), one copy for its
    Executor and ParallelExecutor paths. Returns (result, compiled)."""
    before = set(cache)
    out = run_fn()
    return out, any(k not in before for k in cache)


class TapCounter(object):
    """A replica's monotone dispatch counter — the key serving faults
    fire on. Owned by the pool's replica slot (NOT the tap) so the count
    survives engine swaps: `reload()` attaches a fresh ReplicaTap per
    engine generation, and a fault plan keyed on dispatch N must see one
    consistent per-replica sequence across generations."""

    __slots__ = ("_lock", "n")

    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def take(self):
        with self._lock:
            n, self.n = self.n, self.n + 1
            return n


class ReplicaTap(object):
    """The serving-side fault-injection tap — the serving runtime's
    frontend of the same fault registry the executor hooks above serve
    (resilience/faults.py). The ReplicaPool attaches one per replica
    engine (and one to a canary engine, replica_id="canary"); the engine
    fires it at the top of every batch dispatch, BEFORE padding, so a
    raise fails only that group and the batcher's isolation turns it
    into per-request exceptions the pool can fail over.

    The tap captures the engine it is ATTACHED to, never resolving the
    replica's engine pointer at dispatch time: during a swap the
    outgoing engine's drain still dispatches, and a replica_poison
    landing there must poison the engine being drained — not NaN the
    freshly promoted replacement's weights through a stale tap."""

    __slots__ = ("replica_id", "engine", "counter")

    def __init__(self, replica_id, engine, counter=None):
        self.replica_id = replica_id
        self.engine = engine
        self.counter = counter if counter is not None else TapCounter()

    def __call__(self):
        count = self.counter.take()
        from ..resilience import faults as _faults
        plan = _faults.active_plan()
        if plan is not None:
            plan.serving_fault(self.replica_id, count, engine=self.engine)


def rollback_all_staged(scope=None):
    """Quiesce hook: refund every live prefetcher's staged pops (all
    prefetchers, or only those staging for `scope`). Checkpoint save
    calls this before reading reader positions — a staged block's
    records have not trained, so recording them as consumed would skip
    them on resume; restore calls it before replaying positions so a
    stale staged block can't refund into the freshly reset stream
    afterwards. Runs on the trainer thread between dispatches, where no
    take() is concurrently in flight."""
    for pf in list(_live_prefetchers):
        if not pf.has_work():
            continue
        if scope is not None:
            block = pf._staged if pf._staged is not None else pf._inflight
            if block is not None and block.scope is not scope:
                continue
        pf.rollback()
