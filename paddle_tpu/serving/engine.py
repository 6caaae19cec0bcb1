"""InferenceEngine: a loaded model + private Scope + bucketed dispatch.

Load path: a `save_inference_model` directory (native versioned JSON
desc) or a reference-era `save_inference_model` directory (era-wire
ProgramDesc protobuf, via `io.load_reference_model`) — auto-detected.
The program goes through the full `paddle_tpu/analysis` pass pipeline AT
LOAD: a malformed model is rejected with structured `Diagnostic`s before
it can take traffic, instead of surfacing as an opaque trace/XLA error
inside some unlucky request's batch.

Shape discipline (the TVM fixed-shape-artifact idea applied to serving):
every dispatch — coalesced batch or single request — runs at a shape from
a small configured lattice of (batch bucket, seq bucket) pairs, pre-traced
at startup (`warmup()`) so steady state never compiles. Bucketing is also
what makes the correctness invariant testable: at a FIXED compiled shape,
XLA row results depend only on that row's values, so a request's rows are
bit-identical whether it was dispatched alone (`run_direct` at the same
bucket) or coalesced with strangers. Across DIFFERENT shapes XLA may
vectorize reductions differently — which is exactly why the engine never
dispatches at ad-hoc shapes.

Sequence feeds ride the `core/lod.py` machinery: each request's LoDTensor
pads to the batch's seq bucket (`to_padded(max_len=seq_bucket)`) and the
`@SEQLEN` companion carries true lengths; pad rows get length 1 over zero
data so length-normalizing ops can't manufacture NaN/Inf in rows nobody
reads.
"""
import os
import threading
import time

import numpy as np

from ..core.executor import Executor, Scope, scope_guard
from ..core.framework import convert_dtype
from ..core.lod import LoDTensor
from ..core.utils import find_var
from ..observability import trace as _trace
from .batcher import Batcher, DecodeBatcher, ServingError
from .metrics import ServingMetrics

__all__ = ["InferenceEngine", "ResultSlice", "InvalidRequestError",
           "DecodeEngine"]

SEQLEN_SUFFIX = "@SEQLEN"


class InvalidRequestError(ServingError):
    """The request's feeds don't match the model contract (missing feed,
    wrong feature dims, sequence longer than the largest bucket, ...)."""


def _default_batch_buckets(max_batch_size):
    buckets, b = [], 1
    while b < max_batch_size:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch_size)
    return buckets


def _covering_bucket(buckets, n, what):
    for b in buckets:
        if b >= n:
            return b
    raise InvalidRequestError(
        "%s %d exceeds the largest configured bucket %d"
        % (what, n, buckets[-1]))


class ResultSlice(object):
    """One request's share of a dispatched batch: lazy FetchHandles plus
    this request's row range. The dispatch has been enqueued on device;
    `numpy()` pays the device->host copy for THESE rows only (the row
    slice happens device-side before the transfer on a real
    accelerator; on the CPU backend np.asarray is already a zero-copy
    view, so slicing host-side skips a ~200us XLA slice dispatch per
    request). Per-fetch row policy comes from the engine's static
    classification: "rows" (declared leading dim -1: always slice),
    "whole" (parameters/persistables/scalars: never per-row), "dynamic"
    (concrete non-param leading dim: slice whenever the runtime leading
    dim equals the bucket — when ambiguous, slicing is the safe default,
    since returning the full batch would hand one client co-batched
    strangers' rows)."""

    __slots__ = ("_fetch_names", "_handles", "_row_policy",
                 "_device_slice", "_lo", "_hi", "_bucket_rows", "bucket",
                 "_trace")

    def __init__(self, fetch_names, handles, row_policy, lo, hi,
                 bucket_rows, bucket, device_slice=True, trace=None):
        self._fetch_names = fetch_names
        self._handles = handles
        self._row_policy = row_policy  # name -> rows|whole|dynamic
        self._device_slice = device_slice
        self._lo = lo
        self._hi = hi
        self._bucket_rows = bucket_rows
        self.bucket = bucket  # (batch_bucket, seq_bucket | None)
        self._trace = trace   # the request's trace id: the materialize
        # span records under it, completing the per-request timeline

    def numpy(self):
        from .. import profiler as _prof
        _prof.note_sync("serving/materialize")
        with _trace.span("serving/materialize", cat="serving",
                         trace=self._trace):
            out = {}
            for name, h in zip(self._fetch_names, self._handles):
                policy = self._row_policy[name]
                slice_rows = policy == "rows" or (
                    policy == "dynamic" and h.shape
                    and h.shape[0] == self._bucket_rows)
                if not slice_rows:
                    out[name] = np.asarray(h.array)
                elif self._device_slice:
                    out[name] = np.asarray(h.array[self._lo:self._hi])
                else:
                    out[name] = np.asarray(h.array)[self._lo:self._hi]
            return out

    def __repr__(self):
        return "ResultSlice(rows=[%d:%d), bucket=%r)" % (
            self._lo, self._hi, self.bucket)


class _NormalizedRequest(object):
    """A request's feeds, validated and split by kind: dense arrays
    (dtype-cast, [rows, *feat]) and sequence LoDTensors (+max length).
    `shape_sig` captures every CONCRETE feature shape: requests only
    coalesce within a signature, so a model with free (-1) feature dims
    can serve mixed widths without one width poisoning the other's
    batch (they can't share one padded array)."""

    __slots__ = ("rows", "dense", "seqs", "max_seq_len", "shape_sig")

    def __init__(self, rows, dense, seqs, max_seq_len):
        self.rows = rows
        self.dense = dense          # name -> np.ndarray [rows, *feat]
        self.seqs = seqs            # name -> LoDTensor with `rows` seqs
        self.max_seq_len = max_seq_len
        self.shape_sig = tuple(sorted(
            [(n, a.shape[1:]) for n, a in dense.items()] +
            [(n, lt.data.shape[1:]) for n, lt in seqs.items()]))


class InferenceEngine(object):
    def __init__(self, model_dir=None, model_format="auto",
                 model_filename=None, params_filename=None, place=None,
                 name=None, program=None, feed_names=None, fetch_vars=None,
                 batch_buckets=None, seq_buckets=None, max_batch_size=None,
                 max_queue_delay_ms=None, queue_capacity=256,
                 default_deadline_ms=None, validate=True, warmup=True,
                 latency_window=2048, pipeline_depth=None, tp=None,
                 mesh_devices=None, weights_dtype=None):
        from ..places import CPUPlace
        self.name = name or (os.path.basename(os.path.normpath(model_dir))
                             if model_dir else "model")
        self._scope = Scope()
        self._exe = Executor(place if place is not None else CPUPlace())
        self._run_lock = threading.Lock()   # Executor cache isn't
        self.default_deadline_ms = default_deadline_ms  # thread-safe
        self.closed = False
        # tensor-parallel engine (ARCHITECTURE.md §23): tp=M spans this
        # replica over M devices — one mesh {'dp': 1, 'tp': M}, params
        # sharded 1/M per chip at rest by the ShardingPlan's auto
        # row/col rule (gather placement: bit-identical results to a
        # mesh-1 engine on the same weights), dispatch through a
        # ParallelExecutor bound to this engine's program + Scope. The
        # loader Executor above stays: model files load host-side; the
        # first TP dispatch device_puts the scope per the plan.
        # mesh_devices pins the exact device span (the ReplicaPool's
        # per-replica slicing); default = the first M visible devices.
        if tp is not None and int(tp) < 1:
            # validate BEFORE the falsy-None mapping: tp=0 (a
            # miscomputed ndev//replicas) silently serving single-device
            # replicas would be the worst kind of "sharded" deployment
            raise ValueError("tp must be >= 1, got %r" % (tp,))
        self.tp = int(tp) if tp is not None else None
        self._mesh_devices = list(mesh_devices) if mesh_devices else None
        if self._mesh_devices is not None and self.tp is None:
            self.tp = len(self._mesh_devices)
        self.mesh = None
        self.plan = None
        self._pexe = None
        # device-side row slicing only pays for itself when there is a
        # transfer to shrink; on the CPU backend it's a pure ~200us
        # dispatch tax per request (np.asarray is zero-copy there)
        self._device_slice = \
            self._exe.place.device().platform != "cpu"

        validated_at_load = False
        if program is None:
            if model_dir is None:
                raise ValueError("need model_dir or an in-memory program")
            program, feed_names, fetch_vars = self._load(
                model_dir, model_format, model_filename, params_filename)
            # under FLAGS_validate_program=1 the native loader already
            # ran the full pipeline (io.load_inference_model) — don't
            # walk the program a second time at startup
            from ..core.executor import _validate_program_flag
            validated_at_load = (self._loaded_format == "native"
                                 and _validate_program_flag())
        elif feed_names is None or fetch_vars is None:
            raise ValueError("in-memory program needs feed_names and "
                             "fetch_vars")
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [v if isinstance(v, str) else v.name
                            for v in fetch_vars]

        if validate and not validated_at_load:
            from .. import analysis
            analysis.validate_or_raise(self.program,
                                       feed_names=self.feed_names,
                                       fetch_names=self.fetch_names)

        # weight-dtype reduction (ARCHITECTURE.md §25 / serving/
        # quantize.py): bf16 halves weight HBM + runs the MXU ops bf16;
        # int8 stores matmul/conv weights quantized per channel behind
        # an in-graph dequantize. Applied to the loaded scope before
        # the first trace; fp32 master checkpoints/exports untouched.
        self.quantize_report = None
        self._set_weights_dtype(weights_dtype)
        if model_dir is not None:
            # params are in the scope already (loaded above)
            self._apply_weights_dtype()
        elif self.weights_dtype != "fp32":
            # an in-memory program has no loaded weights to quantize;
            # silently serving fp32 under an int8 label would pass every
            # divergence gate trivially. from_checkpoint owns the one
            # deferred path (it applies after its verified arrays land).
            raise ValueError(
                "weights_dtype=%r needs a model_dir load or "
                "InferenceEngine.from_checkpoint; an in-memory program= "
                "engine has no loaded weights to quantize"
                % (self.weights_dtype,))

        if max_queue_delay_ms is None:
            max_queue_delay_ms = 5.0

        # feed contract: per-feed declared feature dims + sequence-ness
        self._feed_vars = {}
        self._seq_feeds = set()
        for n in self.feed_names:
            var = find_var(self.program, n)
            if var is None:
                # a broken ARTIFACT (deploy fault), not a bad request —
                # InvalidRequestError here would file it as a client 400
                raise ValueError(
                    "model metadata names feed %r but the program has no "
                    "such variable" % n)
            self._feed_vars[n] = var
            if var.lod_level > 1:
                raise ValueError(
                    "feed %r has lod_level=%d: the serving batcher "
                    "coalesces single-level sequences only (the era "
                    "served nested-LoD decodes from host loops, not "
                    "saved graphs)" % (n, var.lod_level))
            if var.lod_level > 0 or find_var(
                    self.program, n + SEQLEN_SUFFIX) is not None:
                self._seq_feeds.add(n)

        # per-fetch row policy, decided ONCE: leading dim -1 = "rows"
        # (what layers.data/infer-shape propagate for batch outputs);
        # parameters/persistables/scalars = "whole" (never per-row);
        # a concrete non-param leading dim = "dynamic" — sliced when it
        # matches the dispatched bucket, because returning it whole
        # would leak co-batched strangers' rows to every client.
        from ..core.framework import Parameter
        self._fetch_row_policy = {}
        for n in self.fetch_names:
            var = find_var(self.program, n)
            shape = list(var.shape or []) if var is not None else []
            if var is not None and (isinstance(var, Parameter)
                                    or var.persistable or not shape):
                self._fetch_row_policy[n] = "whole"
            elif shape and shape[0] == -1:
                self._fetch_row_policy[n] = "rows"
            else:
                self._fetch_row_policy[n] = "dynamic"

        if self.tp is not None:
            import jax
            from ..parallel.mesh import make_mesh
            from ..parallel.parallel_executor import ParallelExecutor
            from ..parallel.plan import ShardingPlan
            devices = self._mesh_devices
            if devices is None:
                avail = jax.devices()
                if len(avail) < self.tp:
                    raise ValueError(
                        "tp=%d needs %d devices but only %d are visible"
                        % (self.tp, self.tp, len(avail)))
                devices = avail[:self.tp]
            elif len(devices) != self.tp:
                raise ValueError(
                    "tp=%d but mesh_devices has %d devices"
                    % (self.tp, len(devices)))
            # dp stays in the mesh at size 1 so the ParallelExecutor's
            # feed sharding path is untouched: request batches replicate
            # over the tp axis (no divisibility constraint on buckets)
            self.mesh = make_mesh({"dp": 1, "tp": self.tp}, devices)
            self.plan = ShardingPlan.build(self.program, self.mesh,
                                           tp_axis="tp")
            self._pexe = ParallelExecutor(main_program=self.program,
                                          plan=self.plan)
            self._pexe._scope = self._scope
            self._device_slice = devices[0].platform != "cpu"

        # deployment tier (analysis/deployment.py): prove the serving
        # contracts on the REWRITTEN program — row-independence of every
        # sliced fetch (the Batcher's coalescing contract), quant-pair
        # well-formedness after _apply_weights_dtype, plan coherence for
        # tp engines — then let warmup's empirical probes confirm what
        # was already proven. The per-fetch certificates are recorded
        # and CONSUMED below: a sliced fetch the analysis could not
        # certify row-independent (a warning-severity mix on a
        # "dynamic"/"whole" fetch — error-severity mixes on "rows"
        # fetches raise here) disables cross-request coalescing, so
        # correctness degrades to per-request batches instead of letting
        # strangers' rows bleed into each other. validate=False skips
        # the tier entirely and keeps full coalescing — the caller owns
        # the contract, exactly as before this tier existed.
        self.deployment_report = None
        self.row_certificates = {}
        self._row_safe = True
        if validate:
            from .. import analysis
            sliced = [n for n in self.fetch_names
                      if self._fetch_row_policy[n] != "whole"]
            deploy = analysis.DeploymentContext.for_serving(
                row_fetches=[n for n in self.fetch_names
                             if self._fetch_row_policy[n] == "rows"],
                whole_fetches=[n for n in self.fetch_names
                               if self._fetch_row_policy[n] != "rows"],
                weights_dtype=("bf16" if self.weights_dtype == "bf16"
                               else "int8" if self.weights_dtype == "int8"
                               else None),
                plan=self.plan)
            self.deployment_report = analysis.analyze_deployment(
                self.program, deploy, feed_names=self.feed_names,
                fetch_names=self.fetch_names)
            self.deployment_report.raise_if_errors()
            self.row_certificates = dict(
                self.deployment_report.certificates)
            self._row_safe = all(
                self.row_certificates.get(n, {}).get("status") != "mixed"
                for n in sliced)

        if batch_buckets:
            self.batch_buckets = sorted(set(int(b) for b in batch_buckets))
            self.max_batch_size = (int(max_batch_size) if max_batch_size
                                   else self.batch_buckets[-1])
        else:
            self.max_batch_size = int(max_batch_size or 32)
            self.batch_buckets = _default_batch_buckets(self.max_batch_size)
        if self.max_batch_size > self.batch_buckets[-1]:
            raise ValueError(
                "max_batch_size %d exceeds the largest batch bucket %d"
                % (self.max_batch_size, self.batch_buckets[-1]))
        self.seq_buckets = (sorted(set(int(s) for s in seq_buckets))
                            if seq_buckets else
                            ([16, 32, 64, 128, 256] if self._seq_feeds
                             else []))

        # continuous batching (ARCHITECTURE.md §22): how many dispatches
        # may be outstanding on the device while the next batch forms.
        # Default 2 — the device executes one batch while the next is
        # already enqueued behind it. 0 = the serial PR-3 loop (bench
        # baseline). FLAGS_serving_pipeline_depth overrides the default;
        # an explicit constructor argument wins.
        if pipeline_depth is None:
            try:
                pipeline_depth = int(os.environ.get(
                    "FLAGS_serving_pipeline_depth", "2"))
            except ValueError:
                pipeline_depth = 2
        self.pipeline_depth = int(pipeline_depth)

        self.metrics = ServingMetrics(latency_window=latency_window)
        self._batcher = Batcher(
            self._dispatch, max_batch_size=self.max_batch_size,
            max_queue_delay_ms=max_queue_delay_ms,
            queue_capacity=queue_capacity, metrics=self.metrics,
            name=self.name, pipeline_depth=self.pipeline_depth,
            coalesce=self._row_safe)
        if warmup:
            try:
                self.warmup()
            except Exception:
                # the batcher worker is already running: a constructor
                # that raises must not leak a live thread per retry
                self.close(drain=False)
                raise

    # ------------------------------------------------------------ load --
    @classmethod
    def from_checkpoint(cls, checkpoint_dir, fetch_list, feed_names=None,
                        step=None, warmup=True, **engine_kw):
        """Serve the newest VALID training checkpoint directly — no
        export step between "training saved a snapshot" and "it takes
        traffic". The snapshot's recorded program is pruned to the fetch
        subgraph (backward/optimizer ops dropped, exactly like
        save_inference_model), its hash-verified param values load into
        the engine's private Scope, and the engine warms up its bucket
        lattice as usual. A torn or bit-flipped newest snapshot is
        skipped for the newest one that verifies, so a crashed trainer
        can never push garbage weights into serving.

        fetch_list: fetch var names in the training program.
        feed_names: defaults to the pruned program's data vars (the
        layers.data inputs feeding the fetch subgraph).
        step pins an exact snapshot; default newest valid.
        """
        from ..checkpoint import CheckpointManager, load_verified_arrays
        target_names = [v if isinstance(v, str) else v.name
                        for v in fetch_list]
        mgr = CheckpointManager(checkpoint_dir, async_save=False)
        try:
            before = None
            while True:
                program, found_step, snap_path = mgr.load_program(
                    step=step, before=before)
                inference = program.prune(target_names, for_test=True)
                wanted = set(v.name for v in inference.list_vars()
                             if v.persistable)
                try:
                    # single pass: each param file is read once, hashed
                    # against the manifest, and decoded from those bytes
                    arrays = load_verified_arrays(snap_path, names=wanted)
                    break
                except (OSError, ValueError):
                    if step is not None:
                        raise  # the user pinned THIS snapshot
                    before = found_step  # corrupt arrays: walk back
        finally:
            mgr.close()
        if feed_names is None:
            feed_names = [v.name for v in inference.list_vars()
                          if getattr(v, "is_data", False)
                          and not v.persistable]
        fetch_vars = [inference.global_block().var(n)
                      for n in target_names]
        # weights_dtype is handled HERE, not by the program= constructor
        # (which rejects it: an in-memory program has no weights yet)
        weights_dtype = engine_kw.pop("weights_dtype", None)
        engine = cls(program=inference, feed_names=feed_names,
                     fetch_vars=fetch_vars,
                     name=engine_kw.pop("name", None)
                     or "ckpt-step-%d" % found_step,
                     warmup=False, **engine_kw)
        try:
            # params BEFORE warmup: the first traced bucket already needs
            # initialized persistables
            for name, arr in arrays.items():
                engine._scope.set(name, arr)
            # weights_dtype applies HERE, after the verified fp32 arrays
            # land and before any trace — the checkpoint on disk stays
            # the fp32 master copy
            engine._set_weights_dtype(weights_dtype)
            engine._apply_weights_dtype()
            if warmup:
                engine.warmup()
        except Exception:
            engine.close(drain=False)  # no thread leak per failed load
            raise
        engine.checkpoint_step = found_step
        return engine

    def _set_weights_dtype(self, weights_dtype):
        """Validate + record the weight-dtype contract (shared by the
        constructor and from_checkpoint's deferred path)."""
        from .quantize import WEIGHTS_DTYPES
        self.weights_dtype = (weights_dtype or "fp32").lower()
        if self.weights_dtype not in WEIGHTS_DTYPES:
            raise ValueError("weights_dtype must be one of %s, got %r"
                             % (WEIGHTS_DTYPES, weights_dtype))
        if self.weights_dtype == "int8" and self.tp is not None:
            raise ValueError(
                "weights_dtype='int8' does not compose with "
                "tensor-parallel engines yet (the sharding plan "
                "partitions the fp32 param names, not the @QVAL "
                "rewrite); use weights_dtype='bf16' for TP replicas")

    def _apply_weights_dtype(self):
        """Apply weights_dtype to the loaded (program, scope) pair —
        once, before the first trace. __init__ calls it for model_dir
        loads; from_checkpoint calls it after the verified arrays land
        in the scope (the constructor defers — the values aren't there
        yet). No-op for fp32 or when already applied."""
        if self.weights_dtype == "fp32" or self.quantize_report is not None:
            return
        from .quantize import apply_weights_dtype
        self.quantize_report = apply_weights_dtype(
            self.program, self._scope, self.weights_dtype)

    def _load(self, model_dir, model_format, model_filename,
              params_filename):
        from .. import io as _io
        if model_format == "auto":
            native_meta = os.path.join(model_dir, "__model_meta__.json")
            model_format = ("native" if os.path.exists(native_meta)
                            else "reference")
        self._loaded_format = model_format
        with scope_guard(self._scope):
            if model_format == "native":
                return _io.load_inference_model(
                    model_dir, self._exe, model_filename=model_filename,
                    params_filename=params_filename)
            if model_format == "reference":
                return _io.load_reference_model(
                    model_dir, self._exe, model_filename=model_filename,
                    params_filename=params_filename)
        raise ValueError("model_format must be auto|native|reference, "
                         "got %r" % model_format)

    # ------------------------------------------------------- normalize --
    def normalize_feed(self, feed):
        """Validate one request's feed dict against the model contract.
        Dense feeds: array-likes [rows, *feat] (feature dims checked
        against declared dims where those are concrete). Sequence feeds:
        a LoDTensor or a list of per-sequence arrays."""
        missing = [n for n in self.feed_names if n not in feed]
        if missing:
            raise InvalidRequestError("request is missing feeds %r (model "
                                      "expects %r)" % (missing,
                                                       self.feed_names))
        extra = [n for n in feed if n not in self.feed_names]
        if extra:
            raise InvalidRequestError("request has unknown feeds %r (model "
                                      "expects %r)" % (extra,
                                                       self.feed_names))
        rows = None
        dense, seqs, max_seq_len = {}, {}, 0
        for n in self.feed_names:
            var, value = self._feed_vars[n], feed[n]
            if n in self._seq_feeds:
                if isinstance(value, LoDTensor):
                    if value.lod_level() > 1:
                        raise InvalidRequestError(
                            "feed %r: nested (multi-level) LoD is not "
                            "servable; send single-level sequences" % n)
                    lt = value
                elif isinstance(value, (list, tuple)):
                    lt = LoDTensor.from_sequences(
                        [np.asarray(s) for s in value])
                else:
                    raise InvalidRequestError(
                        "feed %r is a sequence input: send a LoDTensor or "
                        "a list of per-sequence arrays" % n)
                lengths = lt.seq_lengths() if lt.lod else \
                    np.asarray([len(lt.data)], dtype=np.int32)
                n_seqs = len(lengths)
                if n_seqs == 0:
                    raise InvalidRequestError(
                        "feed %r carries zero sequences" % n)
                if len(lengths) and int(lengths.min()) < 1:
                    # a real row with @SEQLEN=0 divides-by-zero in
                    # length-normalizing ops — the client's fault, so a
                    # typed 400 here, not a NaN-shaped 500 later
                    raise InvalidRequestError(
                        "feed %r contains an empty sequence; every "
                        "sequence needs at least one step" % n)
                # per-token feature dims must match the declaration HERE:
                # a bad shape discovered inside the batcher's concat
                # would fail every innocent co-batched request
                want = list(var.shape or [])[2:]
                got = list(lt.data.shape)[1:]
                if len(got) != len(want) or any(
                        w >= 0 and w != g for w, g in zip(want, got)):
                    raise InvalidRequestError(
                        "feed %r has per-token shape %r but the model "
                        "declares %r" % (n, got, want))
                max_seq_len = max(max_seq_len,
                                  int(lengths.max()) if n_seqs else 0)
                seqs[n] = lt
                r = n_seqs
            else:
                arr = np.asarray(value)
                if var.dtype is not None:
                    arr = arr.astype(convert_dtype(var.dtype), copy=False)
                if arr.ndim < 1:
                    raise InvalidRequestError(
                        "feed %r must carry a leading batch-rows dim, "
                        "got a scalar" % n)
                want = list(var.shape or [])[1:]
                got = list(arr.shape)[1:]
                if len(got) != len(want) or any(
                        w >= 0 and w != g for w, g in zip(want, got)):
                    raise InvalidRequestError(
                        "feed %r has per-row shape %r but the model "
                        "declares %r" % (n, got, want))
                dense[n] = arr
                r = arr.shape[0]
            if rows is None:
                rows = r
            elif r != rows:
                raise InvalidRequestError(
                    "feeds disagree on batch rows: %r carries %d, earlier "
                    "feeds carry %d" % (n, r, rows))
        if rows < 1:
            raise InvalidRequestError("request carries zero rows")
        return _NormalizedRequest(rows, dense, seqs, max_seq_len)

    # --------------------------------------------------------- padding --
    def _pad_batch(self, normalized, batch_bucket, seq_bucket):
        """Coalesce normalized requests into one bucket-shaped feed dict.
        Shared by the batcher dispatch AND `run_direct`, so the reference
        path pads byte-identically to the serving path."""
        feed = {}
        for n in self.feed_names:
            var = self._feed_vars[n]
            if n in self._seq_feeds:
                data_parts, len_parts = [], []
                for req in normalized:
                    padded, lengths = req.seqs[n].to_padded(
                        max_len=seq_bucket)
                    if var.dtype is not None:
                        padded = padded.astype(convert_dtype(var.dtype),
                                               copy=False)
                    data_parts.append(padded)
                    len_parts.append(lengths)
                data = np.concatenate(data_parts, axis=0)
                lengths = np.concatenate(len_parts, axis=0)
                pad_rows = batch_bucket - data.shape[0]
                if pad_rows:
                    data = np.concatenate(
                        [data, np.zeros((pad_rows,) + data.shape[1:],
                                        dtype=data.dtype)], axis=0)
                    lengths = np.concatenate(
                        [lengths, np.ones(pad_rows, dtype=lengths.dtype)])
                feed[n] = data
                feed[n + SEQLEN_SUFFIX] = lengths
            else:
                arr = np.concatenate([req.dense[n] for req in normalized],
                                     axis=0)
                pad_rows = batch_bucket - arr.shape[0]
                if pad_rows:
                    arr = np.concatenate(
                        [arr, np.zeros((pad_rows,) + arr.shape[1:],
                                       dtype=arr.dtype)], axis=0)
                feed[n] = arr
        return feed

    def _pick_buckets(self, rows, max_seq_len):
        batch_bucket = _covering_bucket(self.batch_buckets, rows,
                                        "batch rows")
        seq_bucket = None
        if self._seq_feeds:
            seq_bucket = _covering_bucket(self.seq_buckets,
                                          max(max_seq_len, 1),
                                          "sequence length")
        return batch_bucket, seq_bucket

    # -------------------------------------------------------- dispatch --
    def _run(self, feed):
        """One executor dispatch under the run lock; returns lazy
        FetchHandles and whether this call compiled a new bucket.
        Compile detection compares the cache KEY SET, not its length —
        at LRU capacity an insert+evict keeps the length constant.
        A tensor-parallel engine dispatches through its mesh-bound
        ParallelExecutor instead (same Scope, same bucket lattice,
        same FetchHandle surface — the batcher can't tell)."""
        from ..core.dispatch import run_compile_probe
        with self._run_lock:
            if self._pexe is not None:
                return run_compile_probe(
                    self._pexe._cache,
                    lambda: self._pexe.run(self.fetch_names, feed=feed,
                                           return_numpy=False))
            # validate=False: the engine already verified the program at
            # load; re-validating per (bucket) feed signature would walk
            # the whole program once more per warmup shape under
            # FLAGS_validate_program=1
            return run_compile_probe(
                self._exe._cache,
                lambda: self._exe.run(self.program, feed=feed,
                                      fetch_list=self.fetch_names,
                                      scope=self._scope,
                                      return_numpy=False,
                                      validate=False))

    def _dispatch(self, requests):
        """Batcher callback. Requests are grouped by concrete-shape
        signature (one group, in the common all-dims-declared case) and
        each group pads into one bucket dispatch; a group that fails
        fails only ITS requests, never a co-batched group's. Returns the
        batch's lazy fetch handles so the batcher's in-flight window can
        observe device completion (off this thread)."""
        groups = {}
        for req in requests:
            groups.setdefault(req.feed.shape_sig, []).append(req)
        all_handles = []
        for reqs in groups.values():
            try:
                all_handles.extend(self._dispatch_group(reqs) or ())
            except Exception as e:  # noqa: BLE001 — isolate the group
                for r in reqs:
                    if not r.future.done():
                        r.future.set_exception(e)
                self.metrics.on_error(len(reqs))
        return all_handles

    # pre-dispatch tap: the ReplicaPool points this at its per-replica
    # fault/bookkeeping hook (dispatch counting, injected replica faults).
    # Raising here fails only this group — the batcher's group isolation
    # turns it into per-request exceptions the pool can fail over.
    _replica_tap = None

    def _dispatch_group(self, requests):
        """Pad one shape-compatible group -> one run -> scatter."""
        tap = self._replica_tap
        if tap is not None:
            tap()
        t0 = time.monotonic()
        normalized = [req.feed for req in requests]  # pre-normalized
        traces = [getattr(req, "trace", None) for req in requests]
        rows = sum(r.rows for r in normalized)
        batch_bucket, seq_bucket = self._pick_buckets(
            rows, max(r.max_seq_len for r in normalized))
        # with-blocks, not manual end(): a raise here is the routine
        # fail-this-group-not-the-worker path (the _dispatch wrapper
        # catches it) and must not strand the spans open
        with _trace.span("serving/pad_h2d", cat="serving",
                         traces=traces, rows=rows) as psp:
            feed = self._pad_batch(normalized, batch_bucket, seq_bucket)
            psp.set(bucket=batch_bucket)
        with _trace.span("serving/enqueue", cat="serving",
                         traces=traces, bucket=batch_bucket) as esp:
            handles, compiled = self._run(feed)
            esp.set(compiled=compiled)
        now = time.monotonic()
        offset, latencies = 0, []
        for req, norm, rtrace in zip(requests, normalized, traces):
            req.future.bucket = (batch_bucket, seq_bucket)
            req.future.latency_s = now - req.enqueued_at
            latencies.append(req.future.latency_s)
            req.future.set_result(ResultSlice(
                self.fetch_names, handles, self._fetch_row_policy,
                offset, offset + norm.rows, batch_bucket,
                (batch_bucket, seq_bucket),
                device_slice=self._device_slice, trace=rtrace))
            offset += norm.rows
        self.metrics.on_batch(len(requests), rows, batch_bucket, latencies)
        from .. import profiler as _prof
        if _prof.is_active():
            tag = "serving/%s b%d%s" % (
                self.name, batch_bucket,
                "s%d" % seq_bucket if seq_bucket else "")
            _prof.record_run(tag, now - t0, compiled=compiled)
        return handles

    # ---------------------------------------------------------- public --
    def submit(self, feed, deadline_ms=None):
        """Enqueue one request for coalesced dispatch; returns a
        RequestFuture whose result is a ResultSlice. Normalization happens
        HERE, on the caller's thread — a malformed request fails fast and
        never costs the batcher loop anything. Oversized requests are the
        batcher's check (RequestTooLargeError at its submit)."""
        return self.submit_normalized(self.normalize_feed(feed),
                                      deadline_ms=deadline_ms)

    def submit_normalized(self, norm, deadline_ms=None):
        """Enqueue an already-normalized request (a `normalize_feed`
        result). The ReplicaPool normalizes once on the caller's thread
        and resubmits the SAME normalized request to a different replica
        on failover — every engine of a pool serves one program, so the
        contract check never needs repeating."""
        if self._seq_feeds:     # reject unservable lengths before queueing
            _covering_bucket(self.seq_buckets, max(norm.max_seq_len, 1),
                             "sequence length")
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        return self._batcher.submit(norm, norm.rows,
                                    deadline_ms=deadline_ms)

    def infer(self, feed, deadline_ms=None, timeout=30.0):
        """Synchronous convenience: submit + wait + materialize this
        request's rows. Returns {fetch_name: np.ndarray}."""
        return self.submit(feed, deadline_ms=deadline_ms) \
            .result(timeout).numpy()

    def run_direct(self, feed, batch_bucket=None, seq_bucket=None):
        """The reference path every test leans on: ONE request, padded by
        the same `_pad_batch` helper, run directly through Executor.run —
        no queue, no coalescing. At a given bucket shape this is
        bit-identical to the rows the same request gets back from a
        coalesced batch, because both run the same compiled executable at
        the same shape. Returns ({fetch_name: np.ndarray}, bucket)."""
        norm = self.normalize_feed(feed)
        auto_b, auto_s = self._pick_buckets(norm.rows, norm.max_seq_len)
        batch_bucket = batch_bucket or auto_b
        seq_bucket = seq_bucket or auto_s
        if batch_bucket < norm.rows:
            raise InvalidRequestError(
                "batch_bucket=%d cannot hold the request's %d rows"
                % (batch_bucket, norm.rows))
        if seq_bucket is not None and seq_bucket < norm.max_seq_len:
            raise InvalidRequestError(
                "seq_bucket=%d cannot hold the request's longest "
                "sequence (%d steps)" % (seq_bucket, norm.max_seq_len))
        padded = self._pad_batch([norm], batch_bucket, seq_bucket)
        handles, _ = self._run(padded)
        res = ResultSlice(self.fetch_names, handles,
                          self._fetch_row_policy, 0, norm.rows,
                          batch_bucket, (batch_bucket, seq_bucket),
                          device_slice=self._device_slice)
        return res.numpy(), (batch_bucket, seq_bucket)

    def warmup(self, buckets=None):
        """Pre-trace the bucket lattice so steady state never compiles.
        `buckets`: explicit [(batch, seq|None), ...] (default: the full
        configured lattice). Feature dims that the model declares as -1
        warm up at 1 — real traffic at other dims compiles on first hit."""
        if buckets is None:
            if self._seq_feeds:
                buckets = [(b, s) for b in self.batch_buckets
                           for s in self.seq_buckets]
            else:
                buckets = [(b, None) for b in self.batch_buckets]
        from ..core.executor import _jit_cache_capacity
        capacity = _jit_cache_capacity()
        if 0 < capacity < len(buckets):
            raise ValueError(
                "bucket lattice has %d shapes but the executor keeps at "
                "most %d compiled programs (LRU): warmup would evict its "
                "own buckets and steady state would recompile. Shrink "
                "the lattice or raise PADDLE_TPU_JIT_CACHE_SIZE."
                % (len(buckets), capacity))
        compiled = 0
        for batch_bucket, seq_bucket in buckets:
            feed = {}
            for n in self.feed_names:
                var = self._feed_vars[n]
                dtype = convert_dtype(var.dtype) if var.dtype else "float32"
                if n in self._seq_feeds:
                    feat = [d if d >= 0 else 1
                            for d in list(var.shape or [])[2:]]
                    feed[n] = np.zeros([batch_bucket, seq_bucket or 1]
                                       + feat, dtype=dtype)
                    feed[n + SEQLEN_SUFFIX] = np.ones(batch_bucket,
                                                      dtype=np.int32)
                else:
                    feat = [d if d >= 0 else 1
                            for d in list(var.shape or [])[1:]]
                    feed[n] = np.zeros([batch_bucket] + feat, dtype=dtype)
            _, did_compile = self._run(feed)
            compiled += bool(did_compile)
        self.metrics.on_warmup_compile(compiled)
        return compiled

    def queue_depth(self):
        return self._batcher.queue_depth()

    def device_span(self):
        """The devices this engine's dispatches own: the mesh's devices
        for a tensor-parallel engine (M entries), else the single place
        device — what the pool's `pool_state()` and `/metrics` expose so
        an operator can see which chips a replica holds."""
        if self.mesh is not None:
            return [str(d) for d in self.mesh.devices.flat]
        return [str(self._exe.place.device())]

    def describe(self):
        """The /v1/models entry for this engine."""
        return {
            "name": self.name,
            "tp": self.tp,
            "weights_dtype": self.weights_dtype,
            "devices": self.device_span(),
            "feeds": [
                {"name": n,
                 "shape": list(self._feed_vars[n].shape or []),
                 "dtype": convert_dtype(self._feed_vars[n].dtype)
                 if self._feed_vars[n].dtype else None,
                 "sequence": n in self._seq_feeds}
                for n in self.feed_names],
            "fetches": self.fetch_names,
            "batch_buckets": self.batch_buckets,
            "seq_buckets": self.seq_buckets,
            "max_batch_size": self.max_batch_size,
            "pipeline_depth": self.pipeline_depth,
            "status": "closed" if self.closed else "serving",
            "metrics": self.metrics.snapshot(),
        }

    def drain(self, timeout=None):
        """Complete everything queued/mid-dispatch WITHOUT closing — the
        batcher's shared drain implementation, the same one
        close(drain=True) runs. The pool's zero-downtime engine swap
        rides it (via close) on the outgoing engine after the atomic
        pointer flip: requests accepted before the flip finish against
        the weights they were accepted under, with nothing dropped."""
        return self._batcher.drain(timeout)

    def close(self, drain=True, timeout=None):
        """Graceful shutdown: stop intake, drain queued requests (every
        in-flight batch completes and scatters), join the worker."""
        self.closed = True
        self._batcher.close(drain=drain, timeout=timeout)


# ---------------------------------------------------------------------------
# DecodeEngine: slot-resident generative serving (ARCHITECTURE.md §27)
# ---------------------------------------------------------------------------

class DecodeEngine(object):
    """A decode-step program + private Scope + iteration-level batcher.

    The served artifact is ONE step of an autoregressive loop, authored
    (or exported) at a fixed [max_slots, ...] batch shape with its
    carried state — KV caches, hidden state, token cursors — held in
    persistable "slot vars" (one slot per batch row). Every iteration is
    one `Executor.run` of that step at the ONE compiled shape: the
    executor's state machinery keeps the slot state device-resident and
    DONATES the read-and-written arrays (the KV cache never round-trips
    the host), the AOT compile cache and the trace-env key compose
    unchanged because a step IS an ordinary run, and the DecodeBatcher
    admits/retires streams between iterations (Orca-style continuous
    batching — see serving/batcher.DecodeBatcher).

    Bit-exactness contract: the program must be deterministic (greedy
    decode — no dropout/sampling ops), and then a stream's token
    sequence is bit-identical to a solo decode of that stream on a
    fresh engine, whatever shared the batch or previously used its
    slot: at the fixed shape a row's outputs and next state depend only
    on that row, and admit rewrites EVERY slot var's row (init rows
    provided by the stream, zeros otherwise), so no previous resident
    can leak through carried state.

    Export caveat: `save_inference_model` prunes to the fetch subgraph —
    a decode step must be saved with its state-writing outputs among the
    fetch targets (token and finished vars first; the engine takes
    fetch[0]/fetch[1] as token/finished by default) or the state
    `assign`s would be silently pruned."""

    def __init__(self, model_dir=None, model_format="auto",
                 model_filename=None, params_filename=None, place=None,
                 name=None, program=None, startup_program=None,
                 token_var=None,
                 finished_var=None, slot_vars=None, max_slots=8,
                 queue_capacity=256, default_max_new_tokens=128,
                 default_deadline_ms=None, validate=True, warmup=True,
                 latency_window=4096):
        from ..places import CPUPlace
        from .metrics import DecodeMetrics
        self.name = name or (os.path.basename(os.path.normpath(model_dir))
                             if model_dir else "decode")
        self._scope = Scope()
        self._exe = Executor(place if place is not None else CPUPlace())
        self._run_lock = threading.Lock()
        self.closed = False
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1, got %r"
                             % (max_slots,))
        self.default_deadline_ms = default_deadline_ms

        if program is None:
            if model_dir is None:
                raise ValueError("need model_dir or an in-memory program")
            program, _feeds, fetch_vars = InferenceEngine._load(
                self, model_dir, model_format, model_filename,
                params_filename)
            fetch_names = [v if isinstance(v, str) else v.name
                           for v in fetch_vars]
            if token_var is None or finished_var is None:
                if len(fetch_names) < 2:
                    raise ValueError(
                        "a decode model dir must be saved with at least "
                        "[token, finished] fetch targets (got %r); or "
                        "pass token_var/finished_var explicitly"
                        % (fetch_names,))
                token_var = token_var or fetch_names[0]
                finished_var = finished_var or fetch_names[1]
        elif token_var is None or finished_var is None:
            raise ValueError("an in-memory decode program needs "
                             "token_var and finished_var")
        self.program = program
        self.token_name = token_var if isinstance(token_var, str) \
            else token_var.name
        self.finished_name = finished_var if isinstance(finished_var, str) \
            else finished_var.name
        self.fetch_names = [self.token_name, self.finished_name]
        for n in self.fetch_names:
            if find_var(self.program, n) is None:
                raise ValueError("decode program has no variable %r" % n)
        if validate:
            from .. import analysis
            analysis.validate_or_raise(self.program, feed_names=[],
                                       fetch_names=self.fetch_names)
        if startup_program is not None:
            # in-memory authoring path: initialize weights into the
            # private scope (deterministic given the program seeds, so
            # two engines over the same pair decode identically). Slot
            # vars re-zero below regardless — slot state always starts
            # from the same zeros a fresh solo engine starts from.
            self._exe.run(startup_program, scope=self._scope)

        # state classification: the step feeds on NOTHING (everything
        # it consumes is carried persistable state), so analyze_state
        # sees every scope read/write
        from ..core.lowering import analyze_state, build_slot_update_fn
        self._state_rw, self._state_ro, self._state_out = analyze_state(
            self.program, feed_names=[], fetch_names=self.fetch_names)
        state_read = list(self._state_rw) + list(self._state_ro)

        # slot vars: explicit list wins; else every WRITTEN persistable
        # (inference programs never write weights, so written state is
        # carried decode state) plus read-only state whose leading dim
        # is exactly max_slots (per-slot context set at admit). The
        # leading-dim heuristic can mistake a [max_slots, d] weight for
        # slot state — pass slot_vars explicitly in that case.
        if slot_vars is None:
            slot_vars = list(self._state_out)
            for n in self._state_ro:
                var = find_var(self.program, n)
                shape = list(var.shape or []) if var is not None else []
                if shape and shape[0] in (-1, self.max_slots):
                    slot_vars.append(n)
        self.slot_vars = [v if isinstance(v, str) else v.name
                          for v in slot_vars]
        if not self.slot_vars:
            raise ValueError(
                "decode program carries no slot state (no persistable "
                "var is written and none matches max_slots=%d); a decode "
                "step must carry its loop state in persistables"
                % self.max_slots)
        self._slot_var_meta = {}   # name -> (row_shape, dtype)
        for n in self.slot_vars:
            var = find_var(self.program, n)
            if var is None or not var.persistable:
                raise ValueError(
                    "slot var %r is not a persistable variable of the "
                    "decode program" % n)
            shape = list(var.shape or [])
            if not shape or shape[0] not in (-1, self.max_slots):
                raise ValueError(
                    "slot var %r has shape %r; its leading dim must be "
                    "the slot count (max_slots=%d, or -1)"
                    % (n, shape, self.max_slots))
            feat = shape[1:]
            if any(d < 0 for d in feat):
                raise ValueError(
                    "slot var %r has free feature dims %r; decode slot "
                    "state needs concrete per-slot shapes" % (n, feat))
            dtype = convert_dtype(var.dtype) if var.dtype else "float32"
            self._slot_var_meta[n] = (tuple(feat), dtype)

        # deployment tier with the DECODE context: slot vars are the row
        # sources (row i of every fetch may depend only on slot i's own
        # state — the DecodeBatcher's isolation contract), slot state
        # must be written exactly once per step with static shapes, and
        # no fetch may alias a donated slot update. Runs after slot
        # inference so the context describes what the engine will
        # actually carry; errors here name the offending op instead of
        # surfacing as a wrong token three streams later.
        self.deployment_report = None
        self.row_certificates = {}
        if validate:
            from .. import analysis
            deploy = analysis.DeploymentContext.for_decode(
                slot_vars=self.slot_vars, max_slots=self.max_slots,
                row_fetches=self.fetch_names)
            self.deployment_report = analysis.analyze_deployment(
                self.program, deploy, feed_names=[],
                fetch_names=self.fetch_names)
            self.deployment_report.raise_if_errors()
            self.row_certificates = dict(
                self.deployment_report.certificates)

        # non-slot state the step reads must exist in the scope too
        # (zero-init whatever the model load didn't provide)
        self._reset_slot_state()
        for n in state_read:
            if n not in self._slot_var_meta \
                    and self._scope.get(n) is None:
                var = find_var(self.program, n)
                shape = [d if d >= 0 else 1 for d in (var.shape or [1])]
                dtype = convert_dtype(var.dtype) if var.dtype \
                    else "float32"
                self._scope.set(n, np.zeros(shape, dtype=dtype))

        self._update_rows = build_slot_update_fn()
        self.metrics = DecodeMetrics(latency_window=latency_window)
        self._batcher = DecodeBatcher(
            self._step, self._admit, self.max_slots,
            queue_capacity=queue_capacity,
            default_max_new_tokens=default_max_new_tokens,
            metrics=self.metrics, name=self.name)
        if warmup:
            try:
                self.warmup()
            except Exception:
                self.close(drain=False)   # no thread leak per failed
                raise                     # constructor

    # ----------------------------------------------------- slot state --
    def _zero_row(self, name):
        feat, dtype = self._slot_var_meta[name]
        return np.zeros(feat, dtype=dtype)

    def _reset_slot_state(self):
        """All slots to zeros — startup and post-warmup (a warmup step
        mutates carried state; serving must start from the same zeros a
        fresh solo engine starts from)."""
        for n, (feat, dtype) in self._slot_var_meta.items():
            self._scope.set(n, np.zeros((self.max_slots,) + feat,
                                        dtype=dtype))

    def _admit(self, slot, feeds):
        """DecodeBatcher admit callback: overwrite row `slot` of EVERY
        slot var — the stream's init rows where provided, zeros
        otherwise. One donated jitted row-write per admit; rows of other
        slots flow through bit-untouched (the slot-reuse half of the
        invariant)."""
        feeds = feeds or {}
        names = list(self.slot_vars)
        with self._run_lock:
            vals = tuple(self._scope.get(n) for n in names)
            rows = tuple(feeds[n] if n in feeds else self._zero_row(n)
                         for n in names)
            new_vals = self._update_rows(vals, np.int32(slot), rows)
            for n, v in zip(names, new_vals):
                self._scope.set(n, v)

    def _step(self):
        """DecodeBatcher step callback: ONE fixed-shape decode
        iteration through the ordinary Executor path (donated rw state,
        AOT cache, dispatch guards all compose). Returns host copies of
        the token/finished fetches — the per-iteration host sync is
        inherent to decode scheduling (the loop must see `finished` to
        admit/retire) — plus the lazy handles for window tracking."""
        with self._run_lock:
            handles = self._exe.run(self.program, feed={},
                                    fetch_list=self.fetch_names,
                                    scope=self._scope,
                                    return_numpy=False, validate=False)
        tokens = np.asarray(handles[0].array)
        finished = np.asarray(handles[1].array).reshape(-1).astype(bool)
        return tokens, finished, handles

    def warmup(self):
        """Compile the step (one run) and reset slot state to zeros, so
        the first admitted stream never pays the trace/compile."""
        from ..core.dispatch import run_compile_probe
        with self._run_lock:
            _, compiled = run_compile_probe(
                self._exe._cache,
                lambda: self._exe.run(self.program, feed={},
                                      fetch_list=self.fetch_names,
                                      scope=self._scope,
                                      return_numpy=False,
                                      validate=False))
        self._reset_slot_state()
        return int(bool(compiled))

    # ---------------------------------------------------------- public --
    def normalize_stream_feed(self, feeds):
        """Validate one stream's init rows: {slot var: row} with row
        shape == the var's per-slot shape (dtype cast here). Unknown
        names and shape mismatches are client faults (400s)."""
        feeds = dict(feeds or {})
        out = {}
        for n, value in feeds.items():
            if n not in self._slot_var_meta:
                raise InvalidRequestError(
                    "unknown slot var %r (decode slot state: %r)"
                    % (n, self.slot_vars))
            feat, dtype = self._slot_var_meta[n]
            row = np.asarray(value).astype(dtype, copy=False)
            if tuple(row.shape) != feat:
                raise InvalidRequestError(
                    "init row for %r has shape %r but the slot carries "
                    "%r per stream" % (n, tuple(row.shape), feat))
            out[n] = row
        return out

    def submit(self, feeds=None, max_new_tokens=None, deadline_ms=None):
        """Admit one sequence for continuous-batched decode; returns its
        DecodeStream (tokens arrive incrementally). `feeds` are per-slot
        init rows for a subset of `slot_vars` (e.g. the start token and
        an encoder context vector); everything else resets to zeros."""
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        return self._batcher.submit(self.normalize_stream_feed(feeds),
                                    max_new_tokens=max_new_tokens,
                                    deadline_ms=deadline_ms)

    def decode(self, feeds=None, max_new_tokens=None, deadline_ms=None,
               timeout=120.0):
        """Synchronous convenience: submit + wait; returns the stacked
        token array."""
        return self.submit(feeds, max_new_tokens=max_new_tokens,
                           deadline_ms=deadline_ms).result(timeout)

    def solo_clone(self, name=None, warmup=True):
        """A fresh engine over the SAME program and weights — the
        bit-exactness reference: decode one stream at a time on the
        clone and compare against the continuously-batched original.
        Read-only persistables (the weights — never donated) are shared
        by reference; writable non-slot state is copied (two engines
        must not donate one buffer); slot state starts from zeros, as
        always."""
        clone = DecodeEngine(
            program=self.program, token_var=self.token_name,
            finished_var=self.finished_name,
            slot_vars=list(self.slot_vars), max_slots=self.max_slots,
            place=self._exe.place, name=name or (self.name + "-solo"),
            validate=False, warmup=False,
            default_max_new_tokens=self._batcher.default_max_new_tokens)
        for n in self._state_ro:
            if n not in self._slot_var_meta:
                v = self._scope.get(n)
                if v is not None:
                    clone._scope.set(n, v)
        for n in set(self._state_rw) | set(self._state_out):
            if n not in self._slot_var_meta:
                v = self._scope.get(n)
                if v is not None:
                    clone._scope.set(n, np.array(np.asarray(v)))
        if warmup:
            try:
                clone.warmup()
            except Exception:
                clone.close(drain=False)
                raise
        return clone

    def decode_stats(self):
        return self._batcher.decode_stats()

    def queue_depth(self):
        return self._batcher.queue_depth()

    def device_span(self):
        return [str(self._exe.place.device())]

    def describe(self):
        """The /v1/models entry for this engine."""
        return {
            "name": self.name,
            "mode": "decode",
            "devices": self.device_span(),
            "slot_vars": [
                {"name": n, "row_shape": list(feat), "dtype": dtype}
                for n, (feat, dtype) in sorted(
                    self._slot_var_meta.items())],
            "token_var": self.token_name,
            "finished_var": self.finished_name,
            "max_slots": self.max_slots,
            "default_max_new_tokens":
                self._batcher.default_max_new_tokens,
            "status": "closed" if self.closed else "serving",
            "metrics": self.decode_stats(),
        }

    def drain(self, timeout=None):
        return self._batcher.drain(timeout)

    def close(self, drain=True, timeout=None):
        """Stop intake; drain=True retires every pending and resident
        stream first, drain=False fails them typed (no hang)."""
        self.closed = True
        self._batcher.close(drain=drain, timeout=timeout)
