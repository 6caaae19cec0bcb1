#!/usr/bin/env python3
"""ptpu_serve — serve a saved model over HTTP with dynamic micro-batching.

    tools/ptpu_serve.py <model-dir> [--port 8080] [--host 127.0.0.1]
        [--format auto|native|reference] [--params-filename NAME]
        [--name NAME] [--place cpu|tpu] [--replicas N] [--tp M]
        [--warmup-buckets 1,4,8x32,8x64] [--max-batch 32]
        [--max-delay-ms 5] [--deadline-ms N] [--queue-capacity 256]

`--replicas N` serves N engine replicas behind one endpoint (a
`serving.ReplicaPool`): least-loaded routing, per-replica health-gated
circuit breakers, failover with bounded retry, adaptive admission, and
zero-downtime weight reload. /metrics labels every serving family
{model, replica}; /healthz carries the pool state.

`--warmup-buckets` configures the (batch, seq) lattice: bare integers are
batch buckets, `BxS` pairs add S to the seq-bucket set (sequence models
warm the full batch-buckets x seq-buckets product). Endpoints:
/v1/models, /v1/models/<name>:predict, /healthz, /metrics.

Deploy smoke gate:

    tools/ptpu_serve.py <model-dir> --selfcheck 32

loads the model, fires N random requests through the REAL batcher from
concurrent threads, compares every response bit-for-bit against a direct
single-request Executor.run at the same bucket, prints a verdict, and
exits nonzero on any mismatch — wire it before flipping traffic. With
`--replicas N --kill-replica IDX` the gate hard-kills replica IDX while
the first wave of requests is in flight and submits a second wave after:
any client-visible error fails the deploy — the failover invariant
(traffic redistributes with zero dropped requests) as a gate.

Generative decode deploys (`--decode`): serve a state-carrying decode-
step export through iteration-level continuous batching
(serving.DecodeEngine, ARCHITECTURE.md §27) — `--max-slots` concurrent
streams per replica, `--max-new-tokens` default token budget,
`--stream-deadline-ms` per-stream deadline; POST :decode streams NDJSON.
`--decode --selfcheck N` fires N concurrent streams with mixed token
budgets through the REAL continuous batcher and compares every stream
token-for-token against a solo decode of the same feed (a clone sharing
the weights) — bit-exactness under slot reuse as the deploy gate.
"""
import argparse
import json
import os
import signal
import sys
import threading

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def parse_buckets(spec):
    """'1,4,8x32,8x64' -> (batch_buckets=[1,4,8], seq_buckets=[32,64])."""
    if not spec:
        return None, None
    batch, seq = set(), set()
    for part in spec.split(","):
        part = part.strip().lower()
        if not part:
            continue
        if "x" in part:
            b, s = part.split("x", 1)
            batch.add(int(b))
            seq.add(int(s))
        else:
            batch.add(int(part))
    return sorted(batch) or None, sorted(seq) or None


def selfcheck(engine, n_requests, rows_max=4, seed=0, kill_replica=None,
              reference=None, divergence_bound=0.0, stats=None):
    """Fire n random requests through the batcher concurrently; verify
    each against run_direct at the bucket the batch actually used.
    Returns the number of mismatches (submit failures count).

    kill_replica (pools only): hard-kill that replica index MID-GATE —
    the first half of the requests is in flight when the replica dies,
    the second half is submitted after. Any client-visible error or bit
    mismatch fails the gate: this is the failover invariant (traffic
    redistributes with zero dropped requests) as a deploy check.

    reference (quantized deploys): an fp32 engine over the SAME model —
    each response is additionally compared against the fp32 run_direct
    at the same bucket, and max |q - f| / (max|f| + 1e-6) over
    `divergence_bound` counts as a mismatch (the bounded-divergence
    gate of weights_dtype serving). stats, when passed, gets
    {"max_divergence": float} filled in."""
    import time

    import numpy as np
    rng = np.random.RandomState(seed)
    rows_max = max(1, min(rows_max, engine.max_batch_size))
    feed_specs = engine.describe()["feeds"]
    requests = []
    for _ in range(n_requests):
        rows = int(rng.randint(1, rows_max + 1))
        feed = {}
        for spec in feed_specs:
            name, dtype = spec["name"], spec["dtype"] or "float32"
            if spec["sequence"]:
                feat = [d if d >= 0 else 1 for d in spec["shape"][2:]]
                max_s = engine.seq_buckets[-1] if engine.seq_buckets else 8
                lens = rng.randint(1, max(2, max_s // 2), size=rows)
                if "int" in dtype:
                    feed[name] = [rng.randint(0, 4, [int(l)] + feat)
                                  .astype(dtype) for l in lens]
                else:
                    feed[name] = [rng.randn(*([int(l)] + feat))
                                  .astype(dtype) for l in lens]
            else:
                feat = [d if d >= 0 else 1 for d in spec["shape"][1:]]
                if "int" in dtype:
                    feed[name] = rng.randint(0, 4, [rows] + feat) \
                        .astype(dtype)
                else:
                    feed[name] = rng.randn(*([rows] + feat)).astype(dtype)
        requests.append(feed)

    from paddle_tpu.serving import QueueFullError
    futures = [None] * n_requests

    # the gate tests BIT-EXACTNESS, not deadline shedding: a server-level
    # --deadline-ms default would false-fail the whole check the moment
    # the first uncached bucket compiles (hundreds of ms); disable it for
    # the selfcheck traffic and restore after
    saved_deadline = engine.default_deadline_ms
    engine.default_deadline_ms = None

    def fire(i):
        deadline = time.monotonic() + 30
        while True:
            try:
                futures[i] = engine.submit(requests[i])
                return
            except QueueFullError:       # smoke gate: back off, retry
                if time.monotonic() > deadline:
                    futures[i] = QueueFullError("retries exhausted")
                    return
                time.sleep(0.005)
            except Exception as e:  # noqa: BLE001 — a gate must report,
                futures[i] = e      # not die with a thread traceback
                return

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(n_requests)]
    if kill_replica is None:
        for t in threads:
            t.start()
    else:
        # two waves around the kill: wave 1 is in flight (some of it
        # queued ON the victim) when the replica dies, wave 2 arrives
        # after — both must come back complete and bit-exact
        half = max(1, n_requests // 2)
        for t in threads[:half]:
            t.start()
        time.sleep(0.05)          # let wave 1 spread across the queues
        engine.kill_replica(kill_replica)
        for t in threads[half:]:
            t.start()
    for t in threads:
        t.join()
    engine.default_deadline_ms = saved_deadline

    mismatches = 0
    max_div = 0.0
    for i, fut in enumerate(futures):
        if not hasattr(fut, "result"):   # submit failed: counts as fail
            mismatches += 1
            print("selfcheck FAILED SUBMIT: request %d: %r" % (i, fut),
                  file=sys.stderr)
            continue
        try:
            got = fut.result(120).numpy()
        except Exception as e:  # noqa: BLE001
            mismatches += 1
            print("selfcheck FAILED REQUEST: %d: %r" % (i, e),
                  file=sys.stderr)
            continue
        want, _ = engine.run_direct(requests[i],
                                    batch_bucket=fut.bucket[0],
                                    seq_bucket=fut.bucket[1])
        for name in engine.fetch_names:
            if not np.array_equal(got[name], want[name]):
                mismatches += 1
                print("selfcheck MISMATCH: request %d fetch %r "
                      "(bucket %r)" % (i, name, fut.bucket),
                      file=sys.stderr)
                break
        if reference is not None:
            ref, _ = reference.run_direct(requests[i],
                                          batch_bucket=fut.bucket[0],
                                          seq_bucket=fut.bucket[1])
            for name in engine.fetch_names:
                f = np.asarray(ref[name], dtype=np.float64)
                q = np.asarray(got[name], dtype=np.float64)
                div = float(np.abs(q - f).max()
                            / (np.abs(f).max() + 1e-6)) if f.size else 0.0
                max_div = max(max_div, div)
                if div > divergence_bound:
                    mismatches += 1
                    print("selfcheck DIVERGENCE: request %d fetch %r: "
                          "%.3e > bound %.3e" % (i, name, div,
                                                 divergence_bound),
                          file=sys.stderr)
                    break
    if stats is not None:
        stats["max_divergence"] = max_div
    return mismatches


def decode_selfcheck(engine, n_streams, seed=0, max_new_tokens=16,
                     rows_from=None):
    """The --decode deploy gate: N concurrent streams with mixed token
    budgets through the real continuous batcher (admits/retires under
    slot reuse), each compared token-for-token against a solo decode of
    the same feed through a clone sharing the weights. Returns the
    number of mismatched streams (submit/stream failures count)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    solo_src = rows_from or (engine.replicas[0]
                             if hasattr(engine, "replicas") else engine)
    specs = solo_src.describe()["slot_vars"]
    feeds, budgets = [], []
    for i in range(n_streams):
        f = {}
        for spec in specs:
            shape, dtype = spec["row_shape"], spec["dtype"] or "float32"
            if "bool" in dtype:
                f[spec["name"]] = rng.randint(0, 2, shape).astype(dtype)
            elif "int" in dtype:
                f[spec["name"]] = rng.randint(0, 4, shape).astype(dtype)
            else:
                f[spec["name"]] = rng.randn(*shape).astype(dtype)
        feeds.append(f)
        budgets.append(int(rng.randint(max(2, max_new_tokens // 2),
                                       max_new_tokens + 1)))

    streams = [None] * n_streams

    def fire(i):
        try:
            streams[i] = engine.submit(feeds[i],
                                       max_new_tokens=budgets[i])
        except Exception as e:  # noqa: BLE001 — a gate must report,
            streams[i] = e      # not die with a thread traceback

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    mismatches, got = 0, {}
    for i, s in enumerate(streams):
        if not hasattr(s, "result"):
            mismatches += 1
            print("decode selfcheck FAILED SUBMIT: stream %d: %r"
                  % (i, s), file=sys.stderr)
            continue
        try:
            got[i] = np.asarray(s.result(300)).reshape(-1)
        except Exception as e:  # noqa: BLE001
            mismatches += 1
            print("decode selfcheck FAILED STREAM: %d: %r" % (i, e),
                  file=sys.stderr)

    solo = solo_src.solo_clone(name="selfcheck-solo")
    try:
        for i, toks in sorted(got.items()):
            want = np.asarray(solo.decode(
                feeds[i], max_new_tokens=budgets[i])).reshape(-1)
            if toks.shape != want.shape or not np.array_equal(toks, want):
                mismatches += 1
                print("decode selfcheck MISMATCH: stream %d: batched %s "
                      "vs solo %s" % (i, toks.tolist(), want.tolist()),
                      file=sys.stderr)
    finally:
        solo.close()
    return mismatches


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ptpu_serve",
        description="batched online inference server for saved models")
    ap.add_argument("model_dir")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--format", default="auto",
                    choices=["auto", "native", "reference"])
    ap.add_argument("--model-filename", default=None)
    ap.add_argument("--params-filename", default=None)
    ap.add_argument("--name", default=None,
                    help="model name in URLs (default: dir basename)")
    ap.add_argument("--place", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--warmup-buckets", default=None,
                    help="e.g. 1,4,8x32,8x64 (BxS adds a seq bucket)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip startup tracing (first requests compile)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="max coalesced rows per dispatch (default: the "
                         "largest batch bucket, or 32 with no explicit "
                         "buckets)")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline (requests may "
                         "override per call)")
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    metavar="D",
                    help="continuous-batching in-flight window: up to D "
                    "dispatches outstanding per engine while the next "
                    "batch forms (default 2; 0 = the serial batcher)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve N engine replicas behind one endpoint "
                         "(least-loaded routing, health-gated circuit "
                         "breakers, failover, zero-downtime reload) — "
                         "round-robin over the visible devices")
    ap.add_argument("--autoscale", default=None, metavar="MIN,MAX",
                    help="self-scaling pool: grow/shrink replicas "
                         "between MIN and MAX off the admission/queue/"
                         "idle signals (serving.PoolAutoscaler); "
                         "--replicas is the starting size (default "
                         "MIN). Scale-up rides the AOT warm start; "
                         "scale-down drains, never drops")
    ap.add_argument("--extra-model", action="append", default=[],
                    metavar="NAME=DIR[@PRIORITY]",
                    help="serve additional models from one process (a "
                         "serving.ModelFleet): repeatable; each extra "
                         "model gets its own replica pool with the "
                         "same engine config. Priorities drive fleet "
                         "brownout — the LOWEST priority tier sheds "
                         "first under overload (default 0)")
    ap.add_argument("--priority", type=int, default=0,
                    help="the main model's fleet priority (only "
                         "meaningful with --extra-model)")
    ap.add_argument("--tp", type=int, default=None, metavar="M",
                    help="tensor parallelism: each replica spans M "
                         "devices (weights sharded 1/M per chip by the "
                         "ShardingPlan's row/col rule — serve models "
                         "bigger than one chip); replica i owns the "
                         "contiguous device span [i*M, (i+1)*M). "
                         "/metrics + /healthz expose each replica's "
                         "span")
    ap.add_argument("--attempt-timeout-s", type=float, default=30.0,
                    help="pool failover: per-replica attempt timeout "
                         "(how long a wedged replica can hold a request "
                         "before it retries elsewhere)")
    ap.add_argument("--hedge-delay-ms", type=float, default=None,
                    help="pool tail hedging: duplicate a quiet request "
                         "onto a second replica after this delay")
    ap.add_argument("--selfcheck", type=int, default=0, metavar="N",
                    help="fire N local requests through the batcher, "
                         "verify bit-exactness vs direct runs, exit "
                         "(nonzero on any mismatch) — deploy smoke gate")
    ap.add_argument("--kill-replica", type=int, default=None,
                    metavar="IDX",
                    help="with --selfcheck on a --replicas pool: hard-"
                         "kill replica IDX mid-gate; ANY client-visible "
                         "error fails the gate (the failover invariant "
                         "as a deploy check)")
    ap.add_argument("--weights-dtype", default=None,
                    choices=["fp32", "bf16", "int8"],
                    help="weight precision at load: bf16 halves weight "
                         "HBM + runs MXU ops bf16; int8 stores matmul/"
                         "conv weights per-channel quantized behind an "
                         "in-graph dequantize (fp32 master files "
                         "untouched). --selfcheck additionally gates "
                         "max divergence vs a local fp32 engine")
    ap.add_argument("--decode", action="store_true",
                    help="serve a state-carrying decode-step export with "
                         "iteration-level continuous batching (one batch "
                         "row slot per stream, admits/retires between "
                         "decode iterations; POST :decode streams "
                         "NDJSON). --replicas N builds a DecodePool")
    ap.add_argument("--max-slots", type=int, default=8, metavar="S",
                    help="--decode: concurrent streams per replica (the "
                         "fixed compiled batch dimension)")
    ap.add_argument("--max-new-tokens", type=int, default=128,
                    metavar="T",
                    help="--decode: default per-stream token budget "
                         "(requests may override per call)")
    ap.add_argument("--stream-deadline-ms", type=float, default=None,
                    help="--decode: default per-stream deadline, "
                         "admission to last token")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.decode and (args.autoscale or args.extra_model
                        or args.weights_dtype or args.tp
                        or args.kill_replica is not None):
        ap.error("--decode does not compose with --autoscale/"
                 "--extra-model/--weights-dtype/--tp/--kill-replica")
    if args.kill_replica is not None and not args.selfcheck:
        ap.error("--kill-replica requires --selfcheck")
    if args.kill_replica is not None and args.replicas < 2:
        ap.error("--kill-replica needs --replicas >= 2 (killing the only "
                 "replica cannot redistribute anything)")
    autoscale = None
    if args.autoscale:
        try:
            lo_s, hi_s = args.autoscale.split(",", 1)
            autoscale = (int(lo_s), int(hi_s))
        except ValueError:
            ap.error("--autoscale wants MIN,MAX (e.g. 1,4)")
        if autoscale[0] < 1 or autoscale[1] < autoscale[0]:
            ap.error("--autoscale wants 1 <= MIN <= MAX")
        if args.replicas > autoscale[1]:
            ap.error("--replicas %d starts above --autoscale MAX %d; "
                     "the controller could never shrink past its own "
                     "ceiling" % (args.replicas, autoscale[1]))
    extra_models = []
    for spec in args.extra_model:
        if "=" not in spec:
            ap.error("--extra-model wants NAME=DIR[@PRIORITY], got %r"
                     % spec)
        mname, _, rest = spec.partition("=")
        mdir, _, prio = rest.partition("@")
        extra_models.append((mname.strip(), mdir.strip(),
                             int(prio) if prio else 0))
    if extra_models and args.selfcheck:
        ap.error("--selfcheck gates one model; run it per model dir")

    if args.place == "cpu":
        # only pin the platform for an explicitly-CPU server, and only
        # BEFORE jax initializes; --place tpu leaves the env alone and
        # TPUPlace raises if there is no chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu as fluid
    from paddle_tpu import serving

    # serving warmup is the cold start that hurts most: pre-tracing the
    # whole bucket lattice recompiles every shape on every restart.
    # Default BOTH compile caches on (placed by the one rule in
    # core/compile_cache.py) so a restarted server loads its lattice
    # from disk; FLAGS_aot_cache_dir='' stays the AOT off switch.
    from paddle_tpu.core.compile_cache import enable_aot_cache
    enable_aot_cache()

    batch_buckets, seq_buckets = parse_buckets(args.warmup_buckets)
    engine_kw = dict(
        model_format=args.format, model_filename=args.model_filename,
        params_filename=args.params_filename, name=args.name,
        batch_buckets=batch_buckets, seq_buckets=seq_buckets,
        max_batch_size=args.max_batch,
        max_queue_delay_ms=args.max_delay_ms,
        queue_capacity=args.queue_capacity, warmup=not args.no_warmup,
        pipeline_depth=args.pipeline_depth,
        weights_dtype=args.weights_dtype)
    fleet = None
    try:
        if args.decode:
            place = (fluid.TPUPlace() if args.place == "tpu"
                     else fluid.CPUPlace())
            base = args.name or os.path.basename(
                os.path.normpath(args.model_dir))
            dec_kw = dict(
                model_format=args.format,
                model_filename=args.model_filename,
                params_filename=args.params_filename, place=place,
                max_slots=args.max_slots,
                queue_capacity=args.queue_capacity,
                default_max_new_tokens=args.max_new_tokens,
                default_deadline_ms=args.stream_deadline_ms,
                warmup=not args.no_warmup)
            if args.replicas > 1:
                engine = serving.DecodePool(
                    [serving.DecodeEngine(args.model_dir,
                                          name="%s-%d" % (base, i),
                                          **dec_kw)
                     for i in range(args.replicas)], name=base)
            else:
                engine = serving.DecodeEngine(args.model_dir, name=base,
                                              **dec_kw)
        elif args.replicas > 1 or autoscale or extra_models:
            # pool placement: None = TPUPlace(i) round-robin over the
            # visible accelerators; an explicit --place cpu pins all
            # replicas to the host backend
            engine_kw.pop("name")
            pool_kw = dict(
                replicas=args.replicas, tp=args.tp,
                place=fluid.CPUPlace() if args.place == "cpu" else None,
                default_deadline_ms=args.deadline_ms,
                attempt_timeout_s=args.attempt_timeout_s,
                hedge_delay_ms=args.hedge_delay_ms, **engine_kw)
            if autoscale:
                pool_kw.update(autoscale=True,
                               min_replicas=autoscale[0],
                               max_replicas=autoscale[1],
                               replicas=max(args.replicas, autoscale[0]))
            engine = serving.ReplicaPool(args.model_dir, name=args.name,
                                         **pool_kw)
            if extra_models:
                fleet = serving.ModelFleet()
                fleet.add_model(engine.name, pool=engine,
                                priority=args.priority)
                for mname, mdir, prio in extra_models:
                    fleet.add_model(mname, priority=prio,
                                    model_dir=mdir, **pool_kw)
        else:
            place = (fluid.TPUPlace() if args.place == "tpu"
                     else fluid.CPUPlace())
            engine = serving.InferenceEngine(
                args.model_dir, place=place, tp=args.tp,
                default_deadline_ms=args.deadline_ms, **engine_kw)
    except fluid.ProgramVerificationError as e:
        print("ptpu_serve: model REJECTED by the static verifier:\n%s"
              % e, file=sys.stderr)
        return 2

    if args.selfcheck and args.decode:
        bad = decode_selfcheck(engine, args.selfcheck,
                               max_new_tokens=min(args.max_new_tokens,
                                                  16))
        reps = (engine.replicas if hasattr(engine, "replicas")
                else [engine])
        snaps = [r.decode_stats() for r in reps]
        iters = sum(s["iterations"] for s in snaps)
        record = {
            "selfcheck": "pass" if bad == 0 else "fail",
            "mode": "decode", "streams": args.selfcheck,
            "mismatches": bad,
            "max_slots": args.max_slots,
            "iterations": iters,
            "tokens_total": sum(s["tokens_total"] for s in snaps),
            # >1 proves streams actually SHARED iterations (continuous
            # batching engaged), not that they queued up serially
            "mean_slot_occupancy": round(
                sum(s["iterations"] * s["mean_slot_occupancy"]
                    for s in snaps) / max(iters, 1), 3)}
        if hasattr(engine, "pool_state"):
            record["replicas"] = args.replicas
            record["pool"] = engine.pool_state()
        print(json.dumps(record))
        engine.close()
        return 1 if bad else 0

    if args.selfcheck:
        reference, bound = None, 0.0
        if args.weights_dtype in ("bf16", "int8"):
            # the bounded-divergence gate: a local fp32 twin of the
            # model (no batcher needed — selfcheck drives run_direct)
            from paddle_tpu.serving.quantize import divergence_bound
            ref_kw = dict(engine_kw, weights_dtype=None, warmup=False,
                          name="fp32-reference")
            reference = serving.InferenceEngine(
                args.model_dir,
                place=(fluid.TPUPlace() if args.place == "tpu"
                       else fluid.CPUPlace()), **ref_kw)
            bound = divergence_bound(args.weights_dtype)
        qstats = {}
        bad = selfcheck(engine, args.selfcheck,
                        kill_replica=args.kill_replica,
                        reference=reference, divergence_bound=bound,
                        stats=qstats)
        if reference is not None:
            reference.close()
        if hasattr(engine, "replica_metrics"):   # pool: aggregate
            snaps = [m.snapshot()
                     for m in engine.replica_metrics().values()]
            batches = sum(s["batches_total"] for s in snaps)
            occupancy = round(
                sum(s["batches_total"] * s["mean_batch_occupancy"]
                    for s in snaps) / max(batches, 1), 3)
        else:
            snap = engine.metrics.snapshot()
            batches = snap["batches_total"]
            occupancy = snap["mean_batch_occupancy"]
        record = {
            "selfcheck": "pass" if bad == 0 else "fail",
            "requests": args.selfcheck, "mismatches": bad,
            "mean_batch_occupancy": occupancy, "batches": batches}
        if args.weights_dtype:
            record["weights_dtype"] = args.weights_dtype
        if reference is not None:
            record["max_divergence"] = round(
                qstats.get("max_divergence", 0.0), 6)
            record["divergence_bound"] = bound
        if hasattr(engine, "pool_state"):
            record["replicas"] = args.replicas
            # pool_state carries per-replica engine config
            # (weights_dtype, pipeline_depth, tp, devices): a deploy
            # that accidentally mixed configs is VISIBLE in the gate
            # output, not silent
            record["pool"] = engine.pool_state()
            if args.kill_replica is not None:
                record["killed_replica"] = args.kill_replica
        else:
            record["engine"] = {
                "weights_dtype": engine.weights_dtype,
                "pipeline_depth": engine.pipeline_depth,
                "tp": engine.tp}
        print(json.dumps(record))
        engine.close()
        return 1 if bad else 0

    server = serving.ModelServer(fleet if fleet is not None else engine,
                                 host=args.host, port=args.port,
                                 verbose=args.verbose)
    if args.decode:
        print("ptpu_serve: %r (decode, %d slots x %d replicas) on "
              "http://%s — POST /v1/models/%s:decode streams NDJSON"
              % (engine.name, args.max_slots, args.replicas,
                 server.address, engine.name))
    else:
        print("ptpu_serve: %r (%s) on http://%s — buckets batch=%s "
              "seq=%s%s"
              % (engine.name, args.format, server.address,
                 engine.batch_buckets, engine.seq_buckets or "-",
                 " + %d extra models" % len(extra_models)
                 if extra_models else ""))

    def handle_sig(signum, frame):
        # only unblock serve_forever from a side thread here (calling the
        # blocking httpd.shutdown() on the main thread would deadlock);
        # the DRAIN runs synchronously on the main thread below, so the
        # process cannot exit before in-flight batches complete
        threading.Thread(target=server.httpd.shutdown,
                         daemon=True).start()

    signal.signal(signal.SIGTERM, handle_sig)
    signal.signal(signal.SIGINT, handle_sig)  # Ctrl-C takes the same
    server.serve_forever()                    # drain path as SIGTERM
    server.shutdown()   # idempotent: stop loop, drain engines, join
    return 0


if __name__ == "__main__":
    sys.exit(main())
