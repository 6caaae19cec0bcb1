"""CheckpointManager: fault-tolerant asynchronous checkpointing with
bit-exact resume.

What a snapshot captures (all of it at ONE step boundary, so the saved
state is exactly "the moment after step N"):

  * every persistable scope value — params, optimizer accumulators,
    beta-pow counters, the @LR_DECAY_COUNTER@ — tagged in the manifest
    with its owner param when it is an optimizer accumulator
  * every in-graph reader's position (`ReaderBase.state_dict`), including
    a DoubleBufferReader's staging depth
  * the Scope seed cursor (`Scope.seed_state`), so per-step dropout/rng
    after resume replays the straight-through run bit-for-bit
  * the training program itself (core/program_desc bytes) + its version

Async protocol: `save(step)` captures state synchronously — reader
positions and the seed cursor are cheap host dicts; device values are
captured as fresh device-side copies (`jnp.copy`, an async dispatch), so
the next training step's donated in-place update can't mutate or delete
what the snapshot references — then hands the job to a single background
writer thread that materializes, hashes and atomically publishes the
snapshot (snapshot.py) while training continues. A bounded in-flight
budget (`max_in_flight`) makes `save` block when the writer falls behind,
so back-to-back saves can't pile up unboundedly in memory.

`restore` walks back to the newest snapshot whose hash tree verifies
(corruption/torn saves are skipped, never half-loaded) and puts
everything back: values, reader positions, seed cursor.

Reshard-on-restore (the elasticity refactor): a snapshot records the
DEVICE LAYOUT it was captured under — the cohort shape
(parallel.DeviceLayout) in snapshot.json and, per value, the mesh
PartitionSpec the live array was sharded with. Arrays are always
PERSISTED as full global host arrays (the background writer's np.asarray
is the re-GATHER across the source mesh), so `restore(layout=...)` can
re-SPLIT them onto any target mesh: each value is device_put with its
recorded spec adapted to the target (axes the new mesh lacks are
dropped; a dim the new axis size no longer divides falls back to
replicated). A snapshot written under N devices therefore restores
under M<N, M>N or M=N — and at M=N the values are bit-identical to a
plain `restore()`, only placement differs. This is what lets the
cluster Supervisor roll a shrunken/grown cohort back onto a new mesh
shape (resilience/cluster.py).
"""
import os
import threading
import time

import numpy as np

from . import snapshot as _snap
from ..observability import registry as _obsreg
from ..observability import trace as _otrace
from .retention import RetentionPolicy, apply_retention

__all__ = ["CheckpointManager", "SaveHandle"]


# ------------------------------------------------------------ sharding --
def _spec_to_json(spec):
    """PartitionSpec -> JSON list (str | [str, ...] | None per dim).
    ONE implementation, in parallel/plan.py (the plan serializes specs
    into cache keys with the same encoding restore reads back — two
    copies drifting would silently split placement from keying);
    imported lazily to keep checkpoint import-light."""
    from ..parallel.plan import _spec_to_json as impl
    return impl(spec)


def _adapt_spec(spec_json, mesh, shape):
    """A recorded per-var spec, adapted to the TARGET mesh: mesh axes
    the target doesn't have are dropped, and a dim whose new combined
    axis size no longer divides it falls back to replicated on that dim
    (correctness first — an uneven split would corrupt the value)."""
    from jax.sharding import PartitionSpec as P
    if not spec_json:
        return P()
    out = []
    for i, ent in enumerate(spec_json[:len(shape)]):
        axes = (list(ent) if isinstance(ent, (list, tuple))
                else ([] if ent is None else [ent]))
        kept = [a for a in axes if a in mesh.shape]
        if kept:
            factor = 1
            for a in kept:
                factor *= int(mesh.shape[a])
            if factor <= 0 or int(shape[i]) % factor != 0:
                kept = []
        out.append(tuple(kept) if len(kept) > 1
                   else (kept[0] if kept else None))
    return P(*out)


def _resolve_layout_mesh(layout):
    """restore(layout=...) accepts a parallel.DeviceLayout, a live jax
    Mesh, a parallel.ShardingPlan (its mesh is the target; its specs
    become authoritative placement, see restore), or a bare device
    count (int) — normalize to (mesh, plan-or-None)."""
    import jax
    from jax.sharding import Mesh
    if isinstance(layout, Mesh):
        return layout, None
    if hasattr(layout, "sharding_for") and hasattr(layout, "mesh"):
        return layout.mesh, layout  # a ShardingPlan (duck-typed)
    if isinstance(layout, int):
        from ..parallel.distributed import DeviceLayout
        layout = DeviceLayout(local_device_count=layout)
    if hasattr(layout, "local_mesh"):
        return layout.local_mesh(), None
    raise TypeError(
        "restore(layout=...) wants a parallel.DeviceLayout, a jax Mesh, "
        "a parallel.ShardingPlan or a device count, got %r" % (layout,))


def _capture_value(val):
    """Snapshot one scope value so later training steps can't touch it.
    jax.Arrays get a device-side copy: the copy is a NEW buffer, so the
    next Executor.run donating the original (in-place param update) can
    neither mutate nor delete what we hold; the dispatch is async, so
    capture doesn't stall training on a device sync. FetchHandles (PR-1
    return_numpy=False) unwrap to their device array first. Host numpy
    values are copied host-side."""
    import jax
    import jax.numpy as jnp
    from ..core.executor import FetchHandle
    if isinstance(val, FetchHandle):
        val = val.array
    if isinstance(val, jax.Array):
        return jnp.copy(val)
    return np.array(val, copy=True)


def _live_sharding_spec(val):
    """The JSON'd PartitionSpec of a NamedSharding'd device value, or
    None for replicated/host values (nothing worth recording: restore
    treats an absent spec as replicated)."""
    import jax
    from jax.sharding import NamedSharding
    if not isinstance(val, jax.Array):
        return None
    sh = getattr(val, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = _spec_to_json(sh.spec)
    return spec if any(p is not None for p in spec) else None


def skip_reader_records(scope, reader_names, skip):
    """Advance live reader streams past `skip` records each (or
    per-reader counts when `skip` is a {name: count} dict) by pulling
    and DISCARDING records — the data-routing half of
    rollback_skip_data. A discarded record that raises while being read
    still counts (skipping a poisoned record is the point); EOF
    propagates. Returns the total number of records discarded."""
    from ..core.readers import EOFException
    per = skip if isinstance(skip, dict) else None
    total = 0
    for rname in reader_names:
        live = scope.get(rname)
        if live is None or not hasattr(live, "next"):
            continue
        want = int(per.get(rname, 0)) if per is not None else int(skip)
        for _ in range(max(0, want)):
            try:
                live.next()
            except EOFException:
                raise
            except Exception:
                pass
            total += 1
    return total


class SaveHandle(object):
    """One in-flight (or finished) save. `result()` blocks until the
    snapshot is published and returns its directory; a failed save
    re-raises its error here (and from CheckpointManager.wait)."""

    def __init__(self, step):
        self.step = int(step)
        self._done = threading.Event()
        self._path = None
        self._exc = None
        self._observed = False  # error already delivered via result()
        self.write_seconds = None  # background write+fsync+hash duration

    def done(self):
        return self._done.is_set()

    def exception(self):
        return self._exc

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("checkpoint save for step %d still in "
                               "flight after %ss" % (self.step, timeout))
        if self._exc is not None:
            self._observed = True
            raise self._exc
        return self._path

    def _finish(self, path=None, exc=None):
        self._path = path
        self._exc = exc
        self._done.set()

    def __repr__(self):
        state = ("failed" if self._exc is not None else
                 "done" if self._done.is_set() else "in-flight")
        return "SaveHandle(step=%d, %s)" % (self.step, state)


class _SaveJob(object):
    __slots__ = ("step", "values", "meta", "program_bytes", "validate",
                 "handle")

    def __init__(self, step, values, meta, program_bytes, validate,
                 handle):
        self.step = step
        self.values = values
        self.meta = meta
        self.program_bytes = program_bytes
        self.validate = validate
        self.handle = handle


class CheckpointManager(object):
    def __init__(self, checkpoint_dir, max_to_keep=None,
                 keep_every_n_steps=None, async_save=True,
                 max_in_flight=2, validate=None):
        """max_to_keep=None keeps every snapshot (the legacy
        io.save_checkpoint behavior the shim preserves); set it to bound
        disk. validate=None defers to FLAGS_validate_program (the PR-2
        strict-mode flag): when armed, the program recorded in each
        snapshot is statically verified at save time — a checkpoint that
        cannot be re-lowered is a failed save, not a surprise at resume."""
        self.checkpoint_dir = str(checkpoint_dir)
        self.policy = RetentionPolicy(max_to_keep=max_to_keep,
                                      keep_every_n_steps=keep_every_n_steps)
        self.async_save = bool(async_save)
        self._inflight = threading.Semaphore(max(1, int(max_in_flight)))
        self._validate = validate
        self._lock = threading.Lock()
        self._pending = []           # SaveHandles not yet collected
        self._queue = None
        self._thread = None
        self._closed = False
        _live_managers.add(self)

    # --------------------------------------------------------- capture --
    def _resolve_validate(self):
        if self._validate is not None:
            return bool(self._validate)
        from ..core.executor import _validate_program_flag
        return _validate_program_flag()

    def save(self, step, program=None, scope=None, wait=False, extra=None,
             layout=None):
        """Snapshot full training state after step `step`. Returns a
        SaveHandle; with async_save the write happens on the background
        thread and this call only pays capture (device-side copies +
        host dicts) — unless `max_in_flight` older saves are still
        writing, in which case it blocks until one drains.

        `layout` (a parallel.DeviceLayout) records the cohort shape the
        snapshot was captured under; defaults to the process's active
        layout (parallel.active_layout()) when one is set. Per-value
        mesh shardings are recorded from the live arrays either way, so
        restore(layout=...) can reshard onto a different mesh."""
        if self._closed:
            raise RuntimeError("CheckpointManager is closed")
        # capture span (ARCHITECTURE.md §24): the synchronous cost the
        # training loop pays — device-side copies + host dicts; the
        # background write has its own span on the writer thread
        csp = _otrace.span("checkpoint/capture", cat="checkpoint",
                           step=int(step))
        try:
            job = self._capture_job(step, program, scope, extra, layout)
        except BaseException as e:
            # a failed capture (uninitialized persistable, a donated-
            # and-deleted buffer) must not strand the span open — a
            # phantom "open checkpoint/capture" in later bundles would
            # point the postmortem at a save that died long ago
            csp.end(error=type(e).__name__)
            raise
        csp.end(values=len(job.values),
                sync=bool(wait or not self.async_save))
        if wait or not self.async_save:
            # inline write: raises on failure (the sync contract)
            self._run_job(job, reraise=True)
            return job.handle
        with self._lock:
            # prune finished handles (a day-long run must not accumulate
            # one per save) and surface the first background failure HERE,
            # loudly — a trainer that ignores its SaveHandles must not run
            # for days believing checkpoints exist while every write fails
            failed = [h for h in self._pending
                      if h.done() and h.exception() is not None
                      and not h._observed]
            self._pending = [h for h in self._pending if not h.done()]
            if not failed:
                self._pending.append(job.handle)
        if failed:
            # this save is NOT enqueued: checkpointing is broken and the
            # caller must know before trusting another interval to it
            raise failed[0].exception()
        self._inflight.acquire()  # bounded budget: backpressure here
        self._ensure_thread()
        self._queue.put(job)
        return job.handle

    def _capture_job(self, step, program, scope, extra, layout):
        """The synchronous capture half of save(): quiesce staged
        prefetches, snapshot every persistable + reader position + the
        seed cursor, and return the _SaveJob the writer publishes."""
        from ..core.framework import Parameter, default_main_program
        from ..core.executor import global_scope
        from ..core.readers import ReaderBase
        from ..core import program_desc as _pd
        from ..io import _is_reader_var, _reader_var_names
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()

        # pipelined-dispatch quiesce: a prefetcher may hold a staged
        # K-block it popped for the NEXT step — those records have not
        # trained, so they must be refunded before reader positions are
        # read, or the snapshot would record them as consumed and resume
        # would skip them (core/dispatch.py, ARCHITECTURE.md §22)
        from ..core.dispatch import rollback_all_staged
        rollback_all_staged(scope)

        reader_names = _reader_var_names(program)
        acc_owner = getattr(program, "_accumulator_owner", {})
        # only OUTERMOST readers are recorded: an inner reader (one some
        # decorator wraps as its `_under`) is replayed THROUGH the
        # decorator's load_state_dict — recording it too would replay the
        # chain twice, race the decorator's worker thread against the
        # main-thread replay, and make restore order-dependent. Inner-ness
        # is decided by live-object identity (the creation ops live in the
        # STARTUP program, which save never sees).
        inner_reader_ids = set()
        for v in program.list_vars():
            if not v.persistable:
                continue
            under = getattr(scope.get(v.name), "_under", None)
            while under is not None:
                inner_reader_ids.add(id(under))
                under = getattr(under, "_under", None)
        values, reader_states = [], {}
        for v in program.list_vars():
            if not v.persistable:
                continue
            val = scope.get(v.name)
            # same classification io.save_vars applies: live readers are
            # runtime plumbing, not tensor payload
            if isinstance(val, ReaderBase) or _is_reader_var(
                    v, reader_names):
                if hasattr(val, "state_dict") \
                        and id(val) not in inner_reader_ids:
                    reader_states[v.name] = val.state_dict()
                continue
            if val is None:
                raise RuntimeError(
                    "checkpoint save: persistable variable %r has no "
                    "value in the scope — the snapshot would silently "
                    "omit it and resume would leave it at init. Run the "
                    "startup program first." % v.name)
            entry = {"is_param": isinstance(v, Parameter)}
            if v.name in acc_owner:
                # optimizer accumulator: tie it to its owner param in the
                # manifest ("" = optimizer-global state like beta pows)
                entry["owner"] = acc_owner[v.name]
            captured = _capture_value(val)
            spec = _live_sharding_spec(captured)
            if spec:
                # the spec this value was sharded with on its SOURCE
                # mesh — what restore(layout=) adapts to the target
                entry["sharding"] = spec
            values.append((v.name, entry, captured))

        meta = {"seed_cursor": int(scope.seed_state()),
                "reader_states": reader_states,
                "program_version": int(getattr(program, "_version", 0)),
                "wall_time": time.time()}
        if layout is None:
            from ..parallel.distributed import active_layout
            layout = active_layout()
        if layout is not None:
            meta["device_layout"] = layout.to_json()
        if extra:
            meta["extra"] = dict(extra)
        return _SaveJob(int(step), values, meta,
                        _pd.program_to_bytes(program),
                        self._resolve_validate(), SaveHandle(step))

    # ----------------------------------------------------------- write --
    def _run_job(self, job, reraise=False):
        wsp = _otrace.span("checkpoint/write", cat="checkpoint",
                           step=job.step)
        reg = _obsreg.REGISTRY
        try:
            if job.validate:
                # verify the program the snapshot RECORDS (parsed back
                # from its own bytes, so what is checked is what a resume
                # will actually load)
                from ..core import program_desc as _pd
                from ..analysis import DeploymentContext, validate_or_raise
                # generic deployment tier rides along: a snapshot with a
                # torn int8 rewrite (@QVAL without scales) or donation-
                # unsafe state ordering is the artifact a RESUME or a
                # from_checkpoint engine will load — cheaper to refuse
                # the write than to debug the load
                validate_or_raise(_pd.program_from_bytes(job.program_bytes),
                                  deploy=DeploymentContext.generic())
            t0 = time.perf_counter()
            path = _snap.write_snapshot(
                self.checkpoint_dir, job.step, job.values, job.meta,
                program_bytes=job.program_bytes)
            apply_retention(self.checkpoint_dir, self.policy,
                            protect=(job.step,))
            job.handle.write_seconds = time.perf_counter() - t0
            job.handle._finish(path=path)
            wsp.end()
            # save-latency surface (ARCHITECTURE.md §24): the registry's
            # histogram is what /metrics reads — one observation per
            # published snapshot
            reg.histogram(
                "ptpu_checkpoint_save_seconds",
                "background snapshot write+hash+fsync latency"
            ).observe(job.handle.write_seconds)
            reg.counter("ptpu_checkpoint_saves_total",
                        "snapshot saves by outcome").inc(status="ok")
        except BaseException as e:  # surfaced via handle / wait()
            wsp.end(error=type(e).__name__)
            reg.counter("ptpu_checkpoint_saves_total",
                        "snapshot saves by outcome").inc(status="error")
            job.handle._finish(exc=e)
            if reraise:
                raise
        finally:
            job.values = None  # release captured device copies promptly

    def _writer_loop(self):
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                self._run_job(job)
            finally:
                self._inflight.release()

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            import queue as _q
            self._queue = _q.Queue()
            self._thread = threading.Thread(target=self._writer_loop,
                                            daemon=True,
                                            name="ckpt-writer")
            self._thread.start()

    def wait(self, timeout=None):
        """Drain every in-flight save; re-raises the first failure. A
        handle that is still in flight when `timeout` expires goes BACK
        on the pending list — its eventual failure must surface at the
        next save()/wait()/close(), not vanish with the timeout."""
        with self._lock:
            handles, self._pending = self._pending, []
        first_exc = None
        unfinished = []
        for h in handles:
            try:
                h.result(timeout)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_exc is None:
                    first_exc = e
                if not h.done():
                    unfinished.append(h)
        if unfinished:
            with self._lock:
                self._pending = unfinished + self._pending
        if first_exc is not None:
            raise first_exc
        return handles

    def close(self, timeout=30.0):
        """Drain pending saves and stop the writer thread."""
        if self._closed:
            return
        self._closed = True
        try:
            self.wait(timeout)
        finally:
            if self._thread is not None and self._thread.is_alive():
                self._queue.put(None)
                self._thread.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --------------------------------------------------------- restore --
    def latest_step(self, deep=True):
        found = _snap.find_valid_snapshot(self.checkpoint_dir, deep=deep)
        return None if found is None else found[0]

    def steps(self):
        """All published steps, oldest first (validity not checked)."""
        return [s for s, _ in _snap.list_steps(self.checkpoint_dir)]

    def restore(self, program=None, scope=None, executor=None, step=None,
                allow_missing=False, before=None, layout=None,
                skip_records=None):
        """Load the newest VALID snapshot (or `step`) into `scope`:
        persistable values, reader positions, seed cursor. Returns the
        restored step, or None when no snapshot exists at all. A snapshot
        whose hash tree fails verification is skipped and the next-newest
        one is used — a torn or bit-flipped save can cost at most one
        checkpoint interval, never a wrong resume. A PINNED `step` that
        is missing or corrupt raises instead: the caller asked for
        exactly that state, and a silent fresh start would overwrite
        good checkpoints via retention.

        `before=N` restricts to snapshots strictly older than step N —
        the resilience supervisor's rollback entry point: a second
        rollback that made no progress past its last restore walks back
        one snapshot further instead of reloading the same (possibly
        poisoned-at-capture) state forever.

        With `program`, the restore is strict the way load_vars is: every
        persistable the program declares (reader plumbing aside) must be
        in the manifest, and live reader states recorded in the snapshot
        must exist in the scope (run the startup program first).

        `layout` (a parallel.DeviceLayout, a jax Mesh, a
        parallel.ShardingPlan, or a device count) RESHARDS the restore
        onto that target: every loaded value is device_put with its
        recorded source PartitionSpec adapted to the target mesh
        (absent axes dropped — the update-state shard axis included —
        non-dividing dims replicated; values recorded without a spec
        replicate). A ShardingPlan target goes further: for every var
        the plan covers, the PLAN's spec is authoritative (still
        divisibility-guarded), so the restored state lands exactly in
        the layout the new cohort's ParallelExecutor will run it under
        — no second device_put on the first step.
        The snapshot may have been written under a different device
        count — persisted arrays are global, so shrink (M<N), grow
        (M>N) and same-shape (M=N) all load the same bytes; at M=N the
        values are bit-identical to a plain restore. A layout the live
        process cannot satisfy (fewer devices than it names) raises
        before anything lands in the scope.

        `skip_records` (int, or {reader_name: int}) advances each
        restored reader stream PAST that many records after its position
        is replayed — the data half of the sentinel's
        rollback_skip_data action (ARCHITECTURE.md §29): restore the
        newest snapshot, then route every stream around the offending
        batch window, so the resumed run is bit-exact vs a from-scratch
        run that never saw those records. EOF while skipping propagates
        (the window ran off the end of the epoch); a record that raises
        while being discarded is still counted as skipped — discarding
        a poisoned record is the point."""
        del executor  # parity with io signatures; scope is the store
        from ..core.executor import global_scope
        scope = scope if scope is not None else global_scope()
        # pipelined-dispatch quiesce BEFORE reader replay: a staged
        # prefetch block refunded AFTER load_state_dict's reset+replay
        # would prepend stale records into the freshly restored stream
        from ..core.dispatch import rollback_all_staged
        rollback_all_staged(scope)
        # resolve the target mesh FIRST: an unsatisfiable layout must
        # raise before any snapshot bytes (or scope writes) are touched
        target_mesh, target_plan = (None, None) if layout is None \
            else _resolve_layout_mesh(layout)
        # resume entry point: sweep dead writers' droppings first — this
        # also RECOVERS a step dir a killed same-step re-save left parked
        # as step_<N>.old.<pid> (see snapshot.clean_stale_tmp)
        _snap.clean_stale_tmp(self.checkpoint_dir)
        for found_step, path in self._candidates(step):
            if before is not None and found_step >= before:
                continue
            # cheap structural probe (snapshot.json, manifest hash,
            # files exist, program hash); array payloads are verified
            # below AS they are read — one pass over the bytes, not a
            # hash pass plus a load pass
            if _snap.verify_snapshot_light(path):
                continue
            manifest = _snap.load_manifest(path)
            meta = _snap.read_snapshot_meta(path)

            if program is not None and not allow_missing:
                from ..io import _is_reader_var, _reader_var_names
                reader_names = _reader_var_names(program)
                want = set(v.name for v in program.list_vars()
                           if v.persistable
                           and not _is_reader_var(v, reader_names))
                absent = sorted(want - set(manifest))
                if absent:
                    raise RuntimeError(
                        "checkpoint restore: snapshot step_%d at %r does "
                        "not carry %d persistable variable(s) the program "
                        "needs: %s (allow_missing=True for a deliberate "
                        "partial restore)" % (found_step,
                                              self.checkpoint_dir,
                                              len(absent), absent))
            reader_states = ({} if meta.get("legacy")
                             else meta.get("reader_states") or {})
            if program is not None:
                # liveness BEFORE the first scope.set: raising after
                # params landed would leave a half-restored scope
                for rname in reader_states:
                    if not hasattr(scope.get(rname), "load_state_dict"):
                        raise RuntimeError(
                            "checkpoint restore: snapshot records reader "
                            "state for %r but the scope has no live "
                            "reader there — run the startup program "
                            "first, then restore" % rname)
            try:
                loaded = _snap.load_verified_arrays(path, manifest)
            except (OSError, ValueError):
                continue  # torn or bit-flipped arrays: walk back
            if target_mesh is not None:
                # reshard: re-split every global array onto the target
                # mesh per its adapted source spec. device_put the whole
                # set BEFORE the first scope.set — a placement failure
                # (bad spec, device loss) must not leave the scope
                # half-restored.
                import jax
                from jax.sharding import NamedSharding
                placed = {}
                for name, arr in loaded.items():
                    spec_json = manifest.get(name, {}).get("sharding")
                    if target_plan is not None:
                        plan_spec = target_plan.spec_for(name)
                        if plan_spec is not None:
                            # the new world's plan wins over the
                            # recorded source spec — but through the
                            # same divisibility guard, so a plan built
                            # for a different program shape can't split
                            # a value unevenly
                            spec_json = _spec_to_json(plan_spec)
                    spec = _adapt_spec(spec_json, target_mesh,
                                       np.shape(arr))
                    placed[name] = jax.device_put(
                        arr, NamedSharding(target_mesh, spec))
                loaded = placed
            # all-or-nothing from here: every value is in memory and
            # verified, so nothing below can leave scope half-updated
            for name, arr in loaded.items():
                scope.set(name, arr)

            if not meta.get("legacy") and "seed_cursor" in meta:
                scope.set_seed_state(meta["seed_cursor"])
            for rname, rstate in reader_states.items():
                live = scope.get(rname)
                if hasattr(live, "load_state_dict"):
                    live.load_state_dict(rstate)
            if skip_records:
                skip_reader_records(scope, reader_states, skip_records)
            return found_step
        if step is not None:
            raise ValueError(
                "checkpoint restore: pinned step_%d under %r is missing "
                "or fails verification — refusing to silently start "
                "fresh (omit `step` to fall back to the newest valid "
                "snapshot)" % (int(step), self.checkpoint_dir))
        return None

    def _candidates(self, step=None):
        """Snapshot dirs to try, newest first (or the one pinned step)."""
        if step is not None:
            path = os.path.join(self.checkpoint_dir,
                                _snap.step_dir_name(step))
            return [(int(step), path)] if os.path.isdir(path) else []
        return list(reversed(_snap.list_steps(self.checkpoint_dir)))

    def load_program(self, step=None, before=None):
        """The training program recorded in the newest valid snapshot (or
        `step`), parsed — the servable-model hook serving/engine.py rides.
        Returns (program, step, snapshot_path). `before` restricts to
        steps strictly older — a caller that found the returned
        snapshot's ARRAYS corrupt walks back by retrying with
        before=<that step>."""
        from ..core import program_desc as _pd
        _snap.clean_stale_tmp(self.checkpoint_dir)
        for found_step, path in self._candidates(step):
            if before is not None and found_step >= before:
                continue
            # light verify covers everything this path reads (the
            # program's own hash included); callers loading arrays from
            # the returned path verify them as they read
            # (snapshot.load_verified_arrays)
            if _snap.verify_snapshot_light(path):
                continue
            meta = _snap.read_snapshot_meta(path)
            prog = meta.get("program")
            if not prog:
                raise ValueError(
                    "snapshot step_%d carries no recorded program "
                    "(legacy io.save_checkpoint layout?)" % found_step)
            with open(os.path.join(path, prog["file"]), "rb") as f:
                program = _pd.program_from_bytes(f.read())
            return program, found_step, path
        raise FileNotFoundError(
            "no valid snapshot under %r" % self.checkpoint_dir)


# Interpreter-exit safety: drain live managers so an in-flight async save
# finishes (or is abandoned at a kill point the atomic protocol already
# tolerates) instead of dying as a half-written tmp dir on clean exits.
import atexit
import weakref

_live_managers = weakref.WeakSet()


@atexit.register
def _drain_managers():
    for m in list(_live_managers):
        try:
            m.close(timeout=30.0)
        except Exception:
            pass
