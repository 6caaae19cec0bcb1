"""ParallelExecutor: data-parallel training over a device mesh.

Parity: python/paddle/fluid/parallel_executor.py + paddle/fluid/framework/
parallel_executor.cc + details/ (SSA graph, NCCL allreduce op handles,
num_threads / allow_op_delay scheduling knobs).

TPU-native design: NO replicated programs, NO explicit allreduce. The same
whole-program XLA function the single-chip Executor builds is jitted
(pjit) under an explicit ShardingPlan (parallel/plan.py): feeds sharded
on the batch dim over the 'dp' mesh axis, params/optimizer state placed
per the plan — replicated in the reference-parity default, split 1/N
over the shard axis with `sharded_weight_update=True` (ZeRO-style,
arXiv:2004.13336: grads reduce-scatter onto the owning shard, the update
runs on the shard, params all-gather on use). XLA partitions the
computation and inserts the collectives over ICI automatically,
overlapping them with the backward pass (what the reference's
allow_op_delay tried to approximate by hand). The scheduling knobs are
accepted and ignored — XLA owns the schedule.
"""
import collections
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..core import lowering
from ..core.framework import default_main_program
from ..core.executor import (global_scope, _feed_signature,
                             _nan_inf_enabled, _array_safety_enabled,
                             convert_feeds, _cache_put_lru,
                             _jit_cache_capacity)
from ..core.utils import find_var as _find_var
from ..observability import trace as _otrace
from .mesh import data_parallel_mesh, replicated, batch_sharded, NamedSharding, P
from .plan import ShardingPlan, _match_accumulator_param  # noqa: F401
# (_match_accumulator_param re-exported: the fallback attribution moved
# into plan.py with the rest of the partitioner)


def _var_batch_leading(v):
    """True iff a feed var shards over the batch axis: its declared shape
    has a -1 (dynamic batch) leading dim. Fixed-leading-dim vars (record
    metadata, lookup tables) replicate instead. Single source of truth for
    both record validation and feed sharding."""
    shape = tuple(getattr(v, "shape", None) or ()) if v is not None else ()
    return not shape or shape[0] in (-1, None)


class ParallelExecutor(object):
    def __init__(self, use_cuda=None, loss_name=None, main_program=None,
                 num_threads=None, allow_op_delay=False, share_vars_from=None,
                 use_tpu=None, devices=None, mesh=None, param_shardings=None,
                 batch_axis=None, check_nan_inf=None,
                 sharded_weight_update=False, plan=None, shard_axis=None,
                 tp_axis=None):
        self._program = main_program if main_program is not None \
            else default_main_program()
        self._validated = set()  # strict-mode analysis cache (see run)
        if plan is not None:
            # the plan IS the distribution config: silently ignoring a
            # conflicting mesh/partitioner kwarg would split placement
            # across two meshes (state per plan.mesh, feeds per the
            # other) or drop overrides the caller thinks are in force
            if mesh is not None and mesh != plan.mesh:
                raise ValueError(
                    "plan= was built over mesh %r but mesh= is %r — "
                    "pass one or the other"
                    % (dict(plan.mesh.shape), dict(mesh.shape)))
            if param_shardings or sharded_weight_update \
                    or shard_axis is not None or tp_axis is not None:
                raise ValueError(
                    "plan= already decides param_shardings / "
                    "sharded_weight_update / shard_axis / tp_axis; "
                    "build the plan with those (ShardingPlan.build) "
                    "instead of passing both")
            if batch_axis is not None and batch_axis != plan.batch_axis:
                raise ValueError(
                    "plan= was built with batch_axis=%r but "
                    "batch_axis=%r was passed — the plan decides"
                    % (plan.batch_axis, batch_axis))
            mesh = plan.mesh
        self.mesh = mesh if mesh is not None else data_parallel_mesh(
            devices=devices)
        self._batch_axis = plan.batch_axis if plan is not None \
            else (batch_axis if batch_axis is not None else "dp")
        # The distribution plan (parallel/plan.py, ARCHITECTURE.md §21):
        # every param, gradient and optimizer accumulator gets a
        # PartitionSpec over the mesh. sharded_weight_update=True arms
        # the ZeRO-style assignment (Xu et al. 2020, arXiv:2004.13336):
        # params + accumulators split dim 0 over the shard axis, so GSPMD
        # turns the gradient all-reduce into reduce-scatter, each replica
        # updates only its 1/N shard, and the new weights all-gather on
        # use — optimizer-state memory drops ~N-fold. tp_axis="tp" arms
        # the intra-layer tensor-parallel per-family rule over that
        # mesh axis (ARCHITECTURE.md §23). Precedence inside the
        # partitioner: explicit param_shardings > ParamAttr mesh_axes
        # annotations (accumulators follow) > auto TP > auto ZeRO.
        # shard_axis defaults to the batch axis, or to the active
        # DeviceLayout's recorded shard axis when one is set (the
        # elastic-training handoff: a resharded cohort keeps the
        # snapshot's update-sharding axis).
        if plan is None:
            if shard_axis is None:
                from .distributed import active_layout
                lay = active_layout()
                shard_axis = getattr(lay, "shard_axis", None) \
                    if lay is not None else None
                if shard_axis is not None \
                        and shard_axis not in self.mesh.axis_names:
                    # INHERITED from the active DeviceLayout, not
                    # user-typed: an eval/aux executor over a plain dp
                    # mesh in an elastic process whose cohort shards
                    # over 'zero' must fall back leniently (like the
                    # batch-axis default), not trip the typo guard
                    shard_axis = None
            plan = ShardingPlan.build(
                self._program, self.mesh, batch_axis=self._batch_axis,
                shard_axis=shard_axis, shard_update=sharded_weight_update,
                overrides=param_shardings, tp_axis=tp_axis)
        self.plan = plan
        # legacy view: param name -> PartitionSpec for every var the plan
        # shards (or the caller pinned); anything absent is replicated
        self._param_shardings = plan.spec_map()
        self._cache = collections.OrderedDict()
        self.last_stats = {}  # guard stat channel (see Executor)
        # XLA:CPU collectives deadlock when several executions are in
        # flight at once (each rendezvous needs one thread per virtual
        # device; concurrent programs starve the pool and abort). Real TPU
        # collectives don't have this failure mode — only serialize
        # dispatch on the CPU (test/virtual-mesh) backend.
        self._device0 = self.mesh.devices.flat[0]
        self._sync_dispatch = self._device0.platform == "cpu"
        self._check_nan_inf = _nan_inf_enabled(check_nan_inf)
        self._array_safety = _array_safety_enabled()
        self._scope = global_scope()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope
        self._prefetcher = None  # core/dispatch.HostIoPrefetcher, armed
        # lazily by the first run(prefetch=True) on a reader-fed program
        self._has_read = {}  # (uid, version) -> program has `read` ops

    def _state_sharding(self, name):
        return self.plan.sharding_for(name)

    @property
    def device_count(self):
        return self.mesh.devices.size

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True,
            steps=1, fetch_reduce="stack", timeout=None, prefetch=False):
        """Sharded run; steps=K runs the K-step device-resident loop (see
        Executor.run): the scan composes with the GSPMD shardings — feeds
        stay batch-sharded per step, params keep their replicated / ZeRO
        (sharded_weight_update) / tensor-parallel layouts across the loop
        carry, and XLA still inserts the gradient collectives inside the
        loop body. One host sync per K steps per call.

        timeout=SECONDS arms the same hang watchdog Executor.run(timeout=)
        has: the dispatch runs on a monitored worker thread and raises
        DispatchTimeoutError past the deadline (device state then
        indeterminate — recover by rollback/abort, see
        paddle_tpu.resilience).

        prefetch=True pipelines the host-io prepass exactly like
        Executor.run(prefetch=True) — the next step's reader records
        pop, pad AND device_put (with their batch shardings) on a
        background stage while the current step executes; staged pops
        roll back exactly on fence/fault/checkpoint (ARCHITECTURE.md
        §22)."""
        if timeout is None:
            return self._run_impl(fetch_list, feed, feed_dict, return_numpy,
                                  steps, fetch_reduce, prefetch=prefetch)
        from ..core.dispatch import dispatch_with_deadline
        return dispatch_with_deadline(
            lambda cancelled, info: self._run_impl(
                fetch_list, feed, feed_dict, return_numpy, steps,
                fetch_reduce, cancelled=cancelled, info=info, sync=True,
                prefetch=prefetch),
            timeout, "ParallelExecutor.run dispatch")

    def _run_impl(self, fetch_list, feed=None, feed_dict=None,
                  return_numpy=True, steps=1, fetch_reduce="stack",
                  cancelled=None, info=None, sync=False, prefetch=False):
        # one trace per training step via the executors' ONE shared
        # wrapper (core/dispatch.run_step_traced), on the dispatching
        # thread (the watchdog worker in timeout mode — a wedge leaves
        # the step's spans open for the bundle). See Executor._run_impl.
        from ..core.dispatch import run_step_traced
        return run_step_traced(
            "pexe", cancelled,
            lambda phases: self._run_traced(
                fetch_list, feed, feed_dict, return_numpy, steps,
                fetch_reduce, cancelled, info, sync, prefetch, phases),
            devices=int(self.mesh.devices.size))

    def _run_traced(self, fetch_list, feed, feed_dict, return_numpy,
                    steps, fetch_reduce, cancelled, info, sync, prefetch,
                    phases):
        feed = feed if feed is not None else (feed_dict or {})
        program = self._program
        scope = self._scope
        fetch_names = [f if isinstance(f, str) else f.name for f in fetch_list]
        steps = int(steps)
        if steps < 1:
            raise ValueError("steps must be >= 1, got %r" % (steps,))
        phases.step.set(program=str(program._uid),
                        version=int(program._version), steps=steps)
        if fetch_reduce not in lowering.FETCH_REDUCE_POLICIES:
            raise ValueError("fetch_reduce must be one of %r, got %r"
                             % (lowering.FETCH_REDUCE_POLICIES, fetch_reduce))

        feed_arrays = convert_feeds(program, feed, host=True)

        # strict mode (FLAGS_validate_program): same pre-lowering static
        # verification Executor.run performs, plus the deployment tier
        # against the ARMED plan — a stale/mismatched ShardingPlan fails
        # here with a named entry instead of as a device_put shape error
        # per var mid-dispatch
        from ..core.executor import maybe_validate_program
        from ..analysis import DeploymentContext
        maybe_validate_program(
            program, feed_arrays, fetch_names, steps, self._validated,
            deploy=DeploymentContext.for_training(plan=self.plan,
                                                  steps=steps))

        if info is not None:
            # preliminary watchdog identity (refined after the prepass)
            info["cache_key"] = (program._uid, program._version,
                                 _feed_signature(feed_arrays),
                                 tuple(fetch_names))

        # pre-dispatch hooks (cluster fence + fault seam) via the shared
        # dispatch-guard choreography — before the io pre-pass and seed
        # draw, staged prefetch refunded on a hook raise (ONE copy with
        # Executor: core/dispatch.run_dispatch_hooks)
        from ..core import dispatch as _dispatch
        pf = self._prefetcher
        _dispatch.run_dispatch_hooks(program, steps, feed_arrays,
                                     prefetcher=pf, cancelled=cancelled)

        def _batch_leading(name):
            return _var_batch_leading(_find_var(program, name))

        # the batch dim shards over the batch axis only — a dp×sp/pp/ep
        # mesh must not demand divisibility by the full device count
        dp = self.mesh.shape.get(self._batch_axis, 1)

        def _check_divisible(arr, what):
            if np.shape(arr) and np.shape(arr)[0] % dp != 0:
                raise ValueError(
                    "batch size %d of %s must divide evenly across the "
                    "%d-way %r axis" % (np.shape(arr)[0], what, dp,
                                        self._batch_axis))

        for name, arr in feed_arrays.items():
            if _batch_leading(name):
                _check_divisible(arr, "feed %r" % name)
        # in-graph reader programs work data-parallel too: records pop
        # host-side and shard over the mesh like any feed (validated before
        # the record is consumed). Only batch-leading fields must divide
        # across devices; fixed-leading-dim fields replicate below.
        def _validate_record(rec, out_vars):
            for f, v in zip(rec, out_vars):
                if _var_batch_leading(v):
                    _check_divisible(
                        f, "reader record field %r" % getattr(v, "name", "?"))

        # host-io consume via the shared choreography (ONE copy with
        # Executor: staged-block identity check, mismatch refund, inline
        # prepass fallback, honest span closure)
        stacked_names = set()
        staged = _dispatch.consume_host_io(
            self, program, scope, steps, True, cancelled, feed_arrays,
            stacked_names, phases, validate=_validate_record)
        if staged is _dispatch.CANCELLED:
            return None  # watchdog deadline raised on the caller
        feed_names = sorted(feed_arrays)

        def _sharding_for(name, ndim, stacked):
            if _batch_leading(name):
                # stacked reader feeds carry a leading K (time) axis; their
                # batch dim moved to position 1 — the scan slices K off and
                # each step sees the usual batch-dim-0 sharding
                return batch_sharded(self.mesh, ndim,
                                     axis_name=self._batch_axis,
                                     batch_dim=1 if name in stacked
                                     else 0)
            return replicated(self.mesh)

        def _feed_sharding(name, ndim):
            return _sharding_for(name, ndim, stacked_names)

        # every trace-time env flag (conv layout, flash dispatch, kernel
        # selection) is traced into the fn — key on them so an env-var flip
        # re-traces instead of serving the other configuration. (steps,
        # fetch_reduce, stacked feeds) shape the traced loop the same way.
        from ..core import compile_cache
        from ..core.lowering import trace_env_key
        unroll = lowering.resolve_multistep_unroll(
            self._device0.platform) if steps > 1 else False
        multi_sig = (steps, fetch_reduce if steps > 1 else None, unroll,
                     tuple(sorted(stacked_names)))
        key = (program._uid, program._version,
               _feed_signature(feed_arrays), tuple(fetch_names),
               trace_env_key(), multi_sig)
        if info is not None:
            info["cache_key"] = key
        def build_jitted(state_rw, state_ro, state_out):
            rep = replicated(self.mesh)
            in_shardings = (
                [_feed_sharding(n, feed_arrays[n].ndim)
                 for n in feed_names],
                [self._state_sharding(n) for n in state_rw],
                [self._state_sharding(n) for n in state_ro],
                rep,
            )
            # lowering.jit_step's order: the state first, for the donation
            out_shardings = ([self._state_sharding(n) for n in state_out],
                             rep, rep)
            # the plan's gradient constraints pin each sharded param's
            # grad to the owner's shard layout inside the traced step, so
            # GSPMD lowers the cross-replica gradient sum as
            # reduce-scatter straight onto the updating shard; the
            # tensor-parallel gather constraints pin each TP param's
            # traced value replicated at the step's entry (weights
            # sharded at rest, all-gathered on use — bit-exact compute,
            # ARCHITECTURE.md §23). Param names and grad names never
            # collide (GRAD_SUFFIX), so one dict carries both.
            constraints = dict(self.plan.grad_constraints())
            constraints.update(self.plan.param_gather_constraints())
            constraints = constraints or None
            if steps > 1:
                fn = lowering.lower_multi_step(
                    program, feed_names, fetch_names, state_rw,
                    state_ro, state_out, steps,
                    fetch_reduce=fetch_reduce,
                    stacked_feed_names=stacked_names, mesh=self.mesh,
                    unroll=unroll, shard_constraints=constraints)
            else:
                fn = lowering.build_program_fn(
                    program, feed_names, fetch_names, state_rw,
                    state_ro, state_out, mesh=self.mesh,
                    collect_errors=True, shard_constraints=constraints)
            return lowering.jit_step(fn, in_shardings=in_shardings,
                                     out_shardings=out_shardings)

        def aot_key():
            # the sharded executable is keyed on everything that shapes
            # it beyond the Executor signature — mesh topology, axis
            # names, and the FULL ShardingPlan in canonical JSON
            # (serialized executables bake the partitioning in; any plan
            # change — a different shard axis, one var's override — is a
            # different executable and must be a different key)
            aot_dir = compile_cache.active_aot_cache_dir()
            if aot_dir is None:
                return None, None
            return aot_dir, compile_cache.aot_entry_key(
                program, _feed_signature(feed_arrays),
                tuple(fetch_names), trace_env_key(), multi_sig,
                self._device0,
                extra={
                    "executor": "parallel",
                    "num_devices": int(self.mesh.devices.size),
                    "mesh_axes": {a: int(s) for a, s in
                                  self.mesh.shape.items()},
                    # the concrete span, in mesh order: two replicas of
                    # one model over DIFFERENT device spans must store
                    # separate artifacts (see aot_entry_key device_id)
                    "mesh_device_ids": [int(getattr(d, "id", -1))
                                        for d in self.mesh.devices.flat],
                    "batch_axis": self._batch_axis,
                    "plan": self.plan.to_json(),
                })

        compiled = False
        aot_hit = False
        aot_saved = 0.0
        aot_compile_s = 0.0  # eager lower+compile time paid THIS call
        aot_entry = None  # (dir, key_hash) when loaded from disk
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.move_to_end(key)  # LRU touch
        else:
            state_rw, state_ro, state_out = lowering.analyze_state(
                program, feed_names, fetch_names)
            aot_dir, akey = aot_key()
            executable = None
            if akey is not None:
                loaded = compile_cache.aot_load(
                    aot_dir, akey[0], akey[1],
                    list(self.mesh.devices.flat))
                if loaded is not None:
                    executable, aot_saved = loaded
                    aot_hit = True
                    aot_entry = (aot_dir, akey[0])
            if executable is None:
                compiled = True
                if akey is not None:
                    try:
                        t0c = _time.perf_counter()

                        # Lower from AVALS, not live values: scope
                        # arrays may still be committed to a DIFFERENT
                        # plan's layout (fresh executor over a scope
                        # another plan trained — the elastic-reshard
                        # handoff), and lowering committed arrays
                        # against conflicting explicit in_shardings
                        # raises, silently forfeiting the artifact; the
                        # in_shardings alone decide placement.
                        def _aval(v):
                            return jax.ShapeDtypeStruct(
                                np.shape(v),
                                getattr(v, "dtype", None)
                                or np.asarray(v).dtype)

                        with jax.default_device(self._device0):
                            comp = build_jitted(
                                state_rw, state_ro, state_out).lower(
                                [_aval(feed_arrays[n])
                                 for n in feed_names],
                                [_aval(scope.get(n)) for n in state_rw],
                                [_aval(scope.get(n)) for n in state_ro],
                                jax.ShapeDtypeStruct(
                                    (), np.uint32)).compile()
                        aot_compile_s = _time.perf_counter() - t0c
                        compile_cache.aot_store(
                            aot_dir, akey[0], akey[1], comp,
                            aot_compile_s)
                        executable = comp
                    except Exception:  # noqa: BLE001 — cache is
                        pass           # best-effort; jit path raises
                if executable is None:
                    executable = build_jitted(state_rw, state_ro,
                                              state_out)
            entry = (executable, state_rw, state_ro, state_out)
            _cache_put_lru(self._cache, key, entry, _jit_cache_capacity())
        jitted, state_rw, state_ro, state_out = entry

        def read_state(names, commit=False):
            vals = []
            for n in names:
                v = scope.get(n)
                if v is None:
                    raise RuntimeError(
                        "persistable var %r not initialized; run the startup "
                        "program with Executor first" % n)
                want = self._state_sharding(n)
                if not (isinstance(v, jax.Array) and v.sharding == want):
                    v = jax.device_put(v, want)
                    if commit:
                        # commit the re-placed value to the scope so the
                        # at-rest layout IS the plan's: read-only state
                        # (inference params on a TP serving mesh, the LR
                        # var) would otherwise keep its full host/loader
                        # copy forever and re-pay the transfer+reshard
                        # every dispatch — for a sharded-at-rest plan
                        # the scope copy is THE 1/N residency claim.
                        # Never for rw state: those buffers are donated,
                        # and a committed-then-donated array would leave
                        # the scope holding a deleted buffer if the
                        # dispatch raises before the post-step
                        # write-back (the original host copy survives
                        # that today).
                        scope.set(n, v)
                vals.append(v)
            return vals

        # device-enqueue span (async; see Executor) — open = wedged
        # here. The feeds' sharded placement is part of it.
        dsp = phases.enter("exec/dispatch")
        feed_vals = [jax.device_put(
            feed_arrays[n], _feed_sharding(n, feed_arrays[n].ndim))
            for n in feed_names]

        seed = jnp.asarray(np.uint32(
            scope.next_seed() if steps == 1
            else scope.next_seed_block(steps)))
        from .. import profiler as _prof
        profiling = _prof.is_active()

        t0 = _time.perf_counter() if profiling else 0.0

        def _call(fn_obj):
            # the pin names the mesh's platform to trace-time dispatch
            # decisions (kernel_config.dispatch_platform); placement is
            # the in_shardings' alone
            with jax.default_device(self._device0):
                rw = read_state(state_rw)
                ro = read_state(state_ro, commit=True)
                with phases:    # exec/jit_call
                    return fn_obj(feed_vals, rw, ro, seed)

        def _find_aot_entry():
            aot_dir_, akey_ = aot_key()
            return (aot_dir_, akey_[0]) if akey_ is not None else None

        def _rebuild():
            # fresh donating jit — see call_with_aval_fallback
            fresh = build_jitted(state_rw, state_ro, state_out)
            _cache_put_lru(self._cache, key,
                           (fresh, state_rw, state_ro, state_out),
                           _jit_cache_capacity())
            return fresh

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
            # without a compile (profiler.step_op_names): the call's
            # arguments lay as the plan says (read_state saw to it)
            _prof.note_step(
                self._cache[key][0], "pexe", (
                    feed_vals, [scope.get(n) for n in state_rw],
                    [scope.get(n) for n in state_ro], seed), (
                    [v.sharding for v in feed_vals],
                    [self._state_sharding(n) for n in state_rw],
                    [self._state_sharding(n) for n in state_ro]),
                device=self._device0)
        # sentinel stat tap: peel float statistics (grad norm) off the
        # error dict before any error sync (see Executor._run_impl)
        from ..core.executor import pop_guard_stats
        self.last_stats = pop_guard_stats(errors)
        dsp.set(compiled=compiled, aot_hit=aot_hit)
        if cancelled is not None and cancelled.is_set():
            # caller already raised DispatchTimeoutError; a late scope
            # write would race its rollback (see Executor._run_impl)
            return None
        if sync:
            # watchdog mode: device-sync BEFORE the scope write-back so
            # an execution-phase hang can't park unresolved arrays in
            # the scope (see Executor._run_impl)
            phases.enter("exec/watchdog_sync")
            jax.block_until_ready((fetches, new_state))
            if cancelled is not None and cancelled.is_set():
                return None
        phases.enter("exec/writeback")
        # state write-back precedes any raise point (incl. the sync below):
        # rw inputs were donated (see Executor.run)
        for n, v in zip(state_out, new_state):
            scope.set(n, v)
        # pipelined dispatch: stage the NEXT step's reader block (pop,
        # pad, sharded device_put) while this step's device work — and
        # the CPU-backend collective sync below — proceeds
        if prefetch:
            def _stage(arrays, stacked):
                # the prefetched feeds' H2D happens HERE, on the
                # staging thread, already in their batch shardings —
                # the dispatch thread's device_put then sees an
                # identically-sharded array (no transfer)
                for n, a in list(arrays.items()):
                    arrays[n] = jax.device_put(
                        a, _sharding_for(n, np.ndim(a), stacked))

            pf = _dispatch.kick_next_prepass(
                self, program, scope, steps, True, cancelled, "pexe",
                validate=_validate_record, stage_fn=_stage)
        def _sync_extra():
            if self._sync_dispatch and not sync:
                _prof.note_sync("pexe/cpu_collective_serialize")
                jax.block_until_ready((fetches, new_state))
            if profiling:
                tag = "pexe_program_%s(v%d)x%d fetch=%s" % (
                    program._uid, program._version, self.device_count,
                    ",".join(fetch_names) or "-")
                _dispatch.profile_dispatch(
                    tag, "pexe/profiling", t0,
                    (fetches, new_state), compiled, aot_hit, aot_saved,
                    aot_compile_s)

        # guard-flag raise + FLAGS_check_nan_inf sweep + refund-on-raise
        # via the shared post-dispatch choreography (ONE copy with
        # Executor: core/dispatch.run_post_dispatch_checks)
        _dispatch.run_post_dispatch_checks(
            errors, fetches, fetch_names, new_state, state_out,
            self._array_safety, self._check_nan_inf,
            "ParallelExecutor.run", prefetcher=pf, cancelled=cancelled,
            sync_fn=_sync_extra)
        if return_numpy:
            _prof.note_sync("pexe/return_numpy")
            phases.enter("exec/d2h")
            return [np.asarray(f) for f in fetches]
        from ..core.executor import FetchHandle
        return [FetchHandle(f) for f in fetches]
