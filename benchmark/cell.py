"""Runs one cell: set-up (build, startup program, batch, reference check,
first step), then the measured window, then the metrics.

What a configuration's own code file gives (benchmark/configs/<name>.py, or
the file its .json names as `module`), all of them functions of the .json's
sizes `cfg` and the traffic mix `traffic`:

  build(fluid, cfg, traffic)    builds the program in the current guard and
                                returns {name: variable}, what every step
                                fetches, the step's scalar (a loss) first
  make_batch(cfg, traffic, key) the feed of one step, traced in one jit
  host_batches(cfg, traffic, rng, n)   numpy batches, for `feed: host_u8`
  reference(cfg, traffic, params, batch)   the same names from a plain
                                float32 forward on the program's weights
  check(cfg, first, want, scalars)   ({verdict: bool}, what it found): the
                                first step against the reference, and the
                                scalars of all steps (benchmark/checks.py)
  samples_per_step, ops_per_sample, SAMPLE   what a step trains

The system under test is reached only through what a user calls:
`fluid.Program`, the model builders, `Executor.run` / `ParallelExecutor.run`,
`DoubleBufferReader` and `enable_persistent_cache`. No FLAGS_*, PADDLE_TPU_*
or BENCH_* variable is set and no tile or crossover is passed: a cell runs
the program's defaults.
"""
import contextlib
import itertools
import json
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from . import manifest

TRACE_SECONDS = 4.0     # how much of the window a --trace 1 run records
# a fetch of at most this many elements a step (a count an expert, never a
# crop of the logits) is kept for every step of the window: what the run
# itself says each step did, for a reader that counts work from it
KEEP_ELEMENTS = 1 << 12
COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# the program draws the seed of every run from the scope's counter, a value
# and not a constant of the compiled program: --seed moves the counter, so
# every seed finds the same executables in the cache
PROGRAM_SEED = 1
SEED_STRIDE = 1 << 12


class NoChip(Exception):
    """The machine does not hold what the cell asks for."""


def say(msg):
    print("bench: " + msg, flush=True)


class Spans(object):
    """Host spans of the benchmark's own calls into the program: name ->
    list of (start, end) on time.perf_counter."""

    def __init__(self):
        self.by_name = {}

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.by_name.setdefault(name, []).append(
                (t0, time.perf_counter()))

    def seconds(self, name):
        return [b - a for a, b in self.by_name.get(name, [])]


def quartiles(xs):
    if len(xs) < 2:
        return (xs[0],) * 3
    return tuple(statistics.quantiles(xs, n=4, method="inclusive"))


def _devices_for(cell, jax, rehearse):
    devices = jax.devices()
    dev = devices[0]
    say("platform: %s device_kind: %r devices: %d jax: %s"
        % (dev.platform, dev.device_kind, len(devices), jax.__version__))
    if dev.platform != "tpu" and not rehearse:
        raise NoChip("jax found platform %r and no TPU: nothing was measured "
                     "(--rehearse walks the path on the CPU and reports no "
                     "device metric)" % dev.platform)
    if len(devices) < cell.chips:
        raise NoChip("cell %r asks for %d chip(s), this machine has %d"
                     % (cell.name, cell.chips, len(devices)))
    return devices[:cell.chips]


class Loop(object):
    """The user's own loop for one traffic mix. `step()` is one `run` call
    (of `steps_per_call` steps) and returns what the configuration's `build`
    asked to fetch, {name: device array}, not waited for. The first fetch is
    the step's scalar (a training cell's loss)."""

    def __init__(self, fluid, cell, devices, main, fetches):
        traffic = cell.traffic
        self.fluid, self.main = fluid, main
        self.names = list(fetches)
        self.fetch_list = [fetches[n] for n in self.names]
        self.devices = devices
        self.steps_per_call = int(traffic.get("steps_per_call", 1))
        self.kw = {"steps": self.steps_per_call} \
            if self.steps_per_call > 1 else {}
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.pexe = self.mesh = self.next_feed = None
        if traffic["executor"] == "ParallelExecutor":
            from paddle_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(traffic["mesh"], devices)
        elif traffic["executor"] != "Executor":
            raise ValueError("traffic executor %r: expected Executor or "
                             "ParallelExecutor" % traffic["executor"])
        self.sharded_update = bool(traffic.get("sharded_weight_update",
                                               False))

    def run_startup(self, startup):
        """The startup program on one device, as a user runs it; then the
        ParallelExecutor over the mesh, which takes the state from there."""
        self.exe.run(startup)
        if self.mesh is not None:
            self.pexe = self.fluid.ParallelExecutor(
                main_program=self.main, loss_name=self.fetch_list[0].name,
                mesh=self.mesh, sharded_weight_update=self.sharded_update)

    def batch_sharding(self, jax, ndim):
        if self.mesh is None:
            return jax.sharding.SingleDeviceSharding(self.devices[0])
        from paddle_tpu.parallel.mesh import batch_sharded
        return batch_sharded(self.mesh, ndim, next(iter(self.mesh.shape)))

    def step(self):
        feed = self.next_feed()
        if self.pexe is not None:
            out = self.pexe.run([v.name for v in self.fetch_list], feed=feed,
                                return_numpy=False, **self.kw)
        else:
            out = self.exe.run(self.main, feed=feed,
                               fetch_list=self.fetch_list,
                               return_numpy=False, **self.kw)
        return {n: getattr(o, "array", o) for n, o in zip(self.names, out)}

    def first_step(self):
        """One step's fetches on the host; of a call of several steps, the
        first step's."""
        out = {n: np.asarray(o) for n, o in self.step().items()}
        if self.steps_per_call > 1:
            out = {n: o[0] for n, o in out.items()}
        return out


def _device_batch(jax, cell, loop, seed):
    """The step's feed, made on the device(s) in one jitted call from the
    seed, already laid out as the traffic says."""
    cfg, traffic, mod = cell.config, cell.traffic, cell.config_module
    shapes = jax.eval_shape(lambda k: mod.make_batch(cfg, traffic, k),
                            jax.random.key(0))
    shardings = {n: loop.batch_sharding(jax, s.ndim)
                 for n, s in shapes.items()}
    make = jax.jit(lambda k: mod.make_batch(cfg, traffic, k),
                   out_shardings=shardings)
    batch = make(jax.random.key(seed))
    jax.block_until_ready(batch)
    return batch


def _host_reader(cell, loop, seed):
    """uint8 batches staged host -> device by the program's own
    DoubleBufferReader, capacity 2 (a traffic file's `feed: host_u8`:
    three host batches in rotation, so the copy is real and the generator
    costs nothing)."""
    from paddle_tpu.core.readers import DoubleBufferReader, IteratorReader
    if loop.pexe is not None or loop.steps_per_call > 1:
        raise ValueError(
            "feed host_u8 runs under Executor, one step a call: "
            "run(steps=K) replays one staged batch K times and would credit "
            "K steps to one copy")
    batches = cell.config_module.host_batches(
        cell.config, cell.traffic, np.random.RandomState(seed), 3)
    names = sorted(batches[0])
    reader = DoubleBufferReader(IteratorReader(
        lambda: (tuple(b[n] for n in names)
                 for b in itertools.cycle(batches))),
        capacity=2, place=loop.fluid.TPUPlace())
    return batches[0], lambda: dict(zip(names, reader.next()))


def _reference(jax, cell, loop, params, batch):
    """What the program fetches, from the plain float32 forward of the
    configuration's own file on the program's initial weights and the same
    batch: {name: numpy array}."""
    cfg, traffic, mod = cell.config, cell.traffic, cell.config_module
    if loop.mesh is not None:
        from paddle_tpu.parallel.mesh import replicated
        params = jax.device_put(params, replicated(loop.mesh))
    fn = jax.jit(lambda p, b: mod.reference(cfg, traffic, p, b))
    return {n: np.asarray(o) for n, o in fn(params, batch).items()}


def _check_state_placement(jax, loop, scope, batch):
    """(ok, what was found): the state lives on the cell's devices, split
    as the traffic says."""
    want = set(loop.devices)
    n = 0
    for name in scope.names():
        v = scope.get(name)
        if not isinstance(v, jax.Array):
            continue
        n += 1
        got = set(v.devices())
        if v.ndim and got != want or not got <= want:
            return False, "%r lives on %s, not on %s" % (
                name, sorted(map(str, got)), sorted(map(str, want)))
    if n == 0:
        return False, "no device-resident state in the scope"
    for name, v in batch.items():
        if not isinstance(v, jax.Array):
            continue            # host-fed: the reader stages it
        shards = v.addressable_shards
        if {s.device for s in shards} != want or any(
                s.data.shape[0] * len(want) != v.shape[0] for s in shards):
            return False, "feed %r is not split over %d device(s): %s" % (
                name, len(want), v.sharding)
    return True, "%d state arrays and %d feeds on %d distinct device(s)" % (
        n, len(batch), len(want))


def _setup(jax, fluid, cell, args, devices, spans):
    cfg, traffic, mod = cell.config, cell.traffic, cell.config_module
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = PROGRAM_SEED
    with spans.span("build"):
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            fetches = mod.build(fluid, cfg, traffic)
    scope = fluid.Scope()
    scope.set_seed_state((args.seed % (1 << 19)) * SEED_STRIDE)
    loop = Loop(fluid, cell, devices, main, fetches)
    check = {}
    with fluid.scope_guard(scope):
        with spans.span("startup"):
            loop.run_startup(startup)
        with spans.span("batch"):
            if traffic["feed"] == "device":
                batch = _device_batch(jax, cell, loop, args.seed)
                loop.next_feed = lambda: batch
            elif traffic["feed"] == "host_u8":
                batch, loop.next_feed = _host_reader(cell, loop, args.seed)
            else:
                raise ValueError("traffic feed %r: expected device or "
                                 "host_u8" % traffic["feed"])
        with spans.span("reference"):
            params = [scope.get(p.name)
                      for p in main.global_block().all_parameters()]
            check["reference"] = _reference(jax, cell, loop, params, batch)
            del params
        with spans.span("first_step"):
            check["first"] = loop.first_step()
        check["placed"], check["placement"] = _check_state_placement(
            jax, loop, scope, batch)
    return loop, scope, check


def _window(jax, fluid, loop, scope, cell, args, spans, counters):
    """Blocks of whole steps until `--seconds` have passed; every block ends
    in block_until_ready and lasts from the end of the block before it. With
    --trace 1 the profiler records the first TRACE_SECONDS and the run ends
    there."""
    annotate = jax.profiler.TraceAnnotation
    calls_per_block = max(1, int(cell.traffic["steps_per_block"])
                          // loop.steps_per_call)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    blocks, scalars, kept, attempted, failed = [], [], {}, 0, 0
    trace_dir = None
    requests_before = counters["compile_requests"]
    with fluid.scope_guard(scope):
        if args.trace:
            trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench_tr")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # the host's TraceMe spans do
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_open = t_prev = time.perf_counter()
        try:
            while True:
                out = None
                for _ in range(calls_per_block):
                    attempted += loop.steps_per_call
                    try:
                        with annotate("bench/run_call"), \
                                spans.span("run_call"):
                            out = loop.step()
                    except Exception as e:  # noqa: BLE001 — counted; ends
                        failed += loop.steps_per_call
                        say("step %d raised %s: %s"
                            % (attempted, type(e).__name__, e))
                        break
                    scalars.append(out[loop.names[0]])
                    for name in loop.names[1:]:
                        if out[name].size <= KEEP_ELEMENTS \
                                * loop.steps_per_call:
                            kept.setdefault(name, []).append(out[name])
                if failed:
                    break
                with annotate("bench/block_sync"):
                    jax.block_until_ready(out)
                now = time.perf_counter()
                with annotate("bench/between_blocks"):
                    blocks.append((t_prev, now,
                                   calls_per_block * loop.steps_per_call))
                    t_prev = now
                if now - t_open >= seconds:
                    break
        finally:
            if args.trace:
                jax.profiler.stop_trace()
    compiles = counters["compile_requests"] - requests_before
    scalars = [float(v) for x in scalars for v in np.ravel(np.asarray(x))]
    fetches = {name: np.concatenate([
        np.asarray(x).reshape(loop.steps_per_call, -1) for x in calls])
        for name, calls in kept.items()}         # [steps, elements] each
    return dict(blocks=blocks, scalars=scalars, fetches=fetches,
                attempted=attempted,
                failed=failed, compiles_in_window=compiles,
                trace_dir=trace_dir, t_open=t_open)


def _memory_peak(devices):
    """Peak bytes on the fullest device. The TPU runtime counts the arrays
    that are alive under `peak_bytes_in_use` and the scratch space it
    reserves for a loaded program's temporaries under `peak_bytes_reserved`
    (for a training step most of the memory: on the v5e the two add up to
    the total of the compiled step's memory_analysis()). The two peaks need
    not fall together, so the sum is an upper bound, and the honest one: a
    batch that makes it pass the device's limit does not fit."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            return None
        peaks.append(stats["peak_bytes_in_use"]
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def run(cell, args, t_process):
    """The whole run of one cell; returns the result object of the last
    line. Raises NoChip where nothing may be measured."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.compile_cache import enable_persistent_cache

    spans = Spans()
    spans.by_name["imports"] = [(t_process, time.perf_counter())]
    cache_dir = enable_persistent_cache()
    # the benchmark's own jits (the batch, the reference) and the program's
    # small ones compile in under jax's threshold of 1 s and would never be
    # kept where JAX_COMPILATION_CACHE_DIR places the cache: a second run
    # has to find every program there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counters = {"compile_requests": 0, "cache_hits": 0}

    def on_event(event, **_):
        if event == COMPILE_REQUEST:
            counters["compile_requests"] += 1
        elif event == CACHE_HIT:
            counters["cache_hits"] += 1
    jax.monitoring.register_event_listener(on_event)

    with spans.span("backend"):     # the accelerator runtime's own start
        devices = _devices_for(cell, jax, args.rehearse)
    on_chip = devices[0].platform == "tpu"
    peak = manifest.peak_for(devices[0].device_kind) if on_chip else None
    say("cell: %s config: %s traffic: %s seed: %d seconds: %g trace: %d "
        "cache_dir: %s" % (cell.name, cell.config["name"],
                           cell.traffic["name"], args.seed, args.seconds,
                           args.trace, cache_dir))

    loop, scope, check = _setup(jax, fluid, cell, args, devices, spans)
    setup_counters = dict(counters)
    # the accelerator runtime's own start is not the program's, the
    # benchmark's or a PR's, and on the v5e it is the unsteady part (6 to
    # 13 s, drifting by seconds on one machine): reported, not counted
    setup_s = time.perf_counter() - t_process - sum(spans.seconds("backend"))
    say("set-up %.2fs without the backend's start: imports %.2f backend %.2f "
        "build %.2f startup %.2f batch %.2f reference %.2f first_step %.2f; "
        "compile requests %d, persistent-cache hits %d"
        % ((setup_s,) + tuple(sum(spans.seconds(n)) for n in (
            "imports", "backend", "build", "startup", "batch", "reference",
            "first_step"))
           + (counters["compile_requests"], counters["cache_hits"])))

    win = _window(jax, fluid, loop, scope, cell, args, spans, counters)

    say("memory of device 0 after the window: %s"
        % json.dumps(devices[0].memory_stats()))
    mod = cell.config_module
    samples = mod.samples_per_step(cell.config, cell.traffic)
    rates = [n * samples / (b - a) for a, b, n in win["blocks"]]
    record = dict(
        cell=cell, chips=cell.chips, on_chip=on_chip, peak=peak,
        setup_s=setup_s, spans=spans, counters=setup_counters,
        samples_per_step=samples,
        ops_per_sample=mod.ops_per_sample(cell.config, cell.traffic),
        block_rates=rates, window=win,
        memory_peak_bytes=_memory_peak(devices), trace=None)
    if rates:
        q1, q2, q3 = quartiles(rates)
        say("window: %d blocks of %d step(s), %d steps in %.2fs; %ss/s over "
            "all chips: median %.2f quartiles %.2f .. %.2f"
            % (len(rates), win["blocks"][0][2], win["attempted"],
               win["blocks"][-1][1] - win["t_open"], mod.SAMPLE, q2, q1, q3))

    # ---- correct ---------------------------------------------------------
    # the configuration's own file says what a right answer is (its first
    # step against its reference, its scalars over the window); what holds
    # for every cell is checked here
    scalars = [float(np.ravel(check["first"][loop.names[0]])[0])] \
        + win["scalars"]
    failed = win["failed"] + sum(not np.isfinite(x) for x in win["scalars"])
    verdicts, found = mod.check(cell.config, check["first"],
                                check["reference"], scalars)
    verdicts = dict(
        verdicts, finite=bool(np.isfinite(scalars).all()) and failed == 0,
        no_compile_in_window=win["compiles_in_window"] == 0,
        placement=check["placed"])
    say("correct: %s; compile requests in the window %d; %s; verdicts %s"
        % (found, win["compiles_in_window"], check["placement"],
           json.dumps(verdicts)))

    # ---- trace -----------------------------------------------------------
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": all(verdicts.values()),
              "attempted": win["attempted"], "failed": int(failed)}
    if args.trace:
        from . import trace_reduce
        summary = trace_reduce.reduce_dir(win["trace_dir"])
        if args.keep_trace is None:
            shutil.rmtree(win["trace_dir"], ignore_errors=True)
        record["trace"] = summary
        if on_chip:
            if not summary["busy_s"] > 0:
                raise RuntimeError("the trace holds no device operation")
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {
                "device_ops": summary["top_ops"][:10],
                "idle_gaps": summary["idle_gaps"][:10]}
        say("trace: %s" % json.dumps(
            {k: summary[k] for k in ("planes", "window_s", "busy_s",
                                     "category_s")}))

    # ---- metrics ---------------------------------------------------------
    metrics = {}
    for entry, reader in cell.metrics["per_layer" if args.trace
                                      else "end_to_end"]:
        if not on_chip and entry["source"] != "program_counter":
            continue        # a CPU run has counts and no device number
        value = reader.read(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    # what `correct` compared, each number beside its limit as the
    # configuration's check words it: the line's last key, and the last
    # lines of standard error, where a run that is not correct is read
    result["compared"] = {"verdicts": verdicts, "found": found}
    print("bench: compared: %s\nbench: verdicts: %s"
          % (found, json.dumps(verdicts)), file=sys.stderr, flush=True)
    return result
