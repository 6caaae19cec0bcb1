"""Headline benchmark: ResNet-50 ImageNet training throughput on TPU.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": "images/sec/chip",
"vs_baseline": N}. Baseline = 300 images/sec/chip (Paddle Fluid on V100,
fp32, the era's published ResNet-50 number — BASELINE.json north star says
"≥ Paddle's own V100 images/sec/chip").

Runs on the TPU jax exposes, in one process that owns the chip, and fails
rather than falling back when there is none (JAX_PLATFORMS=cpu is the one
explicit CPU mode, for smoke runs); synthetic data, full training step
(fwd + bwd + momentum update).
"""
import json
import os
import sys
import time

import numpy as np


def _emit(rec):
    """Every record line — success AND error placeholder — goes out
    through here: schema-checked against paddle_tpu.benchd.schema (the
    store/gate contract, ARCHITECTURE.md §28) so a malformed leg is a
    loud tier-1 failure, not a silently unreadable store entry."""
    from paddle_tpu.benchd.schema import check_record
    print(json.dumps(check_record(rec)))


def _error_line(msg):
    """The one-JSON-line error payload, with the SAME metric/unit mapping
    as the success paths so downstream aggregators keyed on metric names
    bucket error lines correctly."""
    if os.environ.get("BENCH_SERVING") == "1":
        return {"metric": "serving_throughput", "value": 0.0,
                "unit": "requests/sec/chip", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_POOL") == "1":
        return {"metric": "serving_pool_throughput", "value": 0.0,
                "unit": "requests/sec/chip", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_FLEET") == "1":
        return {"metric": "serving_fleet_autoscale_qps", "value": 0.0,
                "unit": "requests/sec/chip", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_CKPT") == "1":
        return {"metric": "ckpt_async_steps_per_sec", "value": 0.0,
                "unit": "steps/sec", "vs_baseline": None, "error": msg}
    if os.environ.get("BENCH_RESIL") == "1":
        return {"metric": "resil_guarded_steps_per_sec", "value": 0.0,
                "unit": "steps/sec", "vs_baseline": None, "error": msg}
    if os.environ.get("BENCH_SENTINEL") == "1":
        return {"metric": "sentinel_steps_per_sec", "value": 0.0,
                "unit": "steps/sec", "vs_baseline": None, "error": msg}
    if os.environ.get("BENCH_COMPILE_CACHE") == "1":
        return {"metric": "compile_cache_serving_warmup", "value": 0.0,
                "unit": "x cold/warm warmup_s", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_SHARDED") == "1":
        return {"metric": "sharded_update_steps_per_sec", "value": 0.0,
                "unit": "steps/sec", "vs_baseline": None, "error": msg}
    if os.environ.get("BENCH_TP") == "1":
        return {"metric": "tp_train_steps_per_sec", "value": 0.0,
                "unit": "steps/sec", "vs_baseline": None, "error": msg}
    if os.environ.get("BENCH_PIPELINE") == "1":
        return {"metric": "pipeline_dispatch_open_qps", "value": 0.0,
                "unit": "requests/sec/chip", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_OBS") == "1":
        return {"metric": "observability_overhead", "value": 0.0,
                "unit": "steps/sec/chip", "vs_baseline": None,
                "error": msg}
    if os.environ.get("BENCH_DECODE") == "1" \
            and os.environ.get("BENCH_MODEL", "") != "transformer":
        # the standalone continuous-batching leg (BENCH_MODEL=transformer
        # BENCH_DECODE=1 is the older KV-cache beam-decode leg below)
        return {"metric": "decode_continuous_tokens_per_sec", "value": 0.0,
                "unit": "tokens/sec/chip", "vs_baseline": None,
                "error": msg}
    model = os.environ.get("BENCH_MODEL", "resnet50")
    decode = os.environ.get("BENCH_DECODE") == "1"
    token_metric = {"transformer": "transformer_cached_decode_throughput"
                    if decode else "transformer_train_throughput",
                    "stacked_lstm": "stacked_lstm_train_throughput"}
    tok = model in token_metric
    if model == "transformer" and decode:
        unit = "emitted tokens/sec/chip"   # matches the success path
    elif tok:
        unit = "tokens/sec/chip"
    else:
        unit = "images/sec/chip"
    return {"metric": token_metric.get(
                model, "%s_imagenet_train_throughput" % model),
            "value": 0.0,
            "unit": unit,
            "vs_baseline": 0.0 if model == "resnet50" else None,
            "error": msg}


def _multistep():
    """BENCH_MULTISTEP=K: run the timed loop through the executors'
    device-resident K-step mode (run(steps=K)) — one host dispatch/sync
    per K training steps instead of per step. K=1 (default) is the plain
    single-step path, byte-identical to the pre-multistep bench."""
    return max(1, int(os.environ.get("BENCH_MULTISTEP", "1")))


def _step_plan(steps, multistep):
    """(outer_calls, total_steps): BENCH_STEPS counts TRAINING steps in
    both modes, rounded up to a whole number of K-step blocks so a
    K-misaligned BENCH_STEPS can't silently measure fewer steps."""
    if multistep == 1:
        return steps, steps
    outer = max(1, -(-steps // multistep))
    return outer, outer * multistep


def _run_kw(multistep):
    """Extra Executor.run kwargs for the timed loop. fetch_reduce='last'
    mirrors what the single-step loop keeps (only the final out survives
    the loop variable), so the loss sanity check sees the same value."""
    return {"steps": multistep, "fetch_reduce": "last"} \
        if multistep > 1 else {}


# bf16 peak TFLOPs per chip by device_kind substring (docs values); the
# device-blind 197 default misreported MFU on anything that isn't a v5e
_PEAK_TFLOPS_BY_KIND = [
    ("v6e", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]


def _peak_tflops():
    """The MFU denominator: BENCH_PEAK_TFLOPS when set (explicit pin
    wins), else keyed on the actual device_kind so each chip reports
    honest MFU. An accelerator that is not in the table is an error,
    not a default; on the CPU (main() admits it only under
    JAX_PLATFORMS=cpu) there is no peak and MFU is not measured: None."""
    env = os.environ.get("BENCH_PEAK_TFLOPS", "")
    if env:
        return float(env)
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    for sub, peak in _PEAK_TFLOPS_BY_KIND:
        if sub in dev.device_kind.lower():
            return peak
    raise ValueError(
        "no peak-TFLOPs entry for device_kind %r; add it to "
        "_PEAK_TFLOPS_BY_KIND or pin BENCH_PEAK_TFLOPS" % dev.device_kind)


def _mfu(flops_per_sec):
    """Model FLOPs utilization against the chip's peak (_peak_tflops:
    keyed on device_kind, BENCH_PEAK_TFLOPS overrides), so every bench
    line self-describes how far it sits from the >=25% north star
    (SURVEY.md section 5). None where there is no peak (CPU)."""
    peak = _peak_tflops()
    if peak is None:
        return None
    return round(flops_per_sec / (peak * 1e12), 4)


def bench_transformer():
    """Transformer training throughput through the pallas flash-attention
    path (BENCH_MODEL=transformer). Base-ish config (d_model 512, 8 heads,
    6 layers, seq 256); prints one JSON tokens/sec line (no reference-era
    baseline exists for this metric -> vs_baseline null)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.models import transformer

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10")))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    seq = int(os.environ.get("BENCH_SEQ", "256"))
    n_layer = int(os.environ.get("BENCH_LAYERS", "6"))
    d_model = int(os.environ.get("BENCH_DMODEL", "512"))
    n_head = int(os.environ.get("BENCH_HEADS", "8"))
    vocab = int(os.environ.get("BENCH_VOCAB", "30000"))
    fused = os.environ.get("BENCH_FUSED_ATTN", "1") == "1"
    fused_qkv = os.environ.get("BENCH_FUSED_QKV", "0") == "1"
    dtype = os.environ.get("BENCH_DTYPE", "bf16")

    main_prog, startup = fluid.Program(), fluid.Program()
    if dtype == "bf16":
        main_prog.enable_mixed_precision()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        sum_cost, avg_cost, _ = transformer.build_train(
            src_vocab_size=vocab, trg_vocab_size=vocab, max_length=seq,
            n_layer=n_layer, n_head=n_head, d_key=d_model // n_head,
            d_value=d_model // n_head, d_model=d_model,
            d_inner_hid=d_model * 4, label_smooth_eps=0.1,
            use_fused_attention=fused, use_qkv_fusion=fused_qkv)

    rng = np.random.RandomState(0)
    srcs = [rng.randint(3, vocab, seq).tolist() for _ in range(batch)]
    feed = transformer.prepare_batch(srcs, srcs, seq, n_head, fused=fused)
    feed = {k: jnp.asarray(v) for k, v in feed.items()}
    jax.block_until_ready(list(feed.values()))

    multistep = _multistep()
    outer, total_steps = _step_plan(steps, multistep)
    run_kw = _run_kw(multistep)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(warmup):
            exe.run(main_prog, feed=feed, fetch_list=[avg_cost], **run_kw)
        t0 = time.perf_counter()
        for _ in range(outer):
            out = exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                          return_numpy=False, **run_kw)
        device_fetch_barrier(out)
        dt = time.perf_counter() - t0
        loss = np.asarray(out[0])
        assert np.isfinite(loss).all(), "non-finite loss"

    tps = batch * seq * total_steps / dt
    # training FLOPs/token ~ 6 * params (72*L*d^2 with d_inner=4d) plus
    # the attention matmuls (~12*L*seq*d fwd+bwd) plus the vocab
    # projection (6*d*V — at base config it rivals the whole body:
    # 92M vs 113M FLOPs/token; omitting it undercounted MFU pre-round-4)
    flops_per_token = 72.0 * n_layer * d_model ** 2 \
        + 12.0 * n_layer * seq * d_model \
        + 6.0 * d_model * vocab
    _emit({
        "metric": "transformer_train_throughput",
        "value": round(tps, 1), "unit": "tokens/sec/chip",
        "vs_baseline": None, "batch": batch, "seq": seq,
        "multistep": multistep,
        "layers": n_layer, "d_model": d_model, "dtype": dtype,
        "fused_attention": fused, "fused_qkv": fused_qkv,
        "device": str(jax.devices()[0]),
        "mfu": _mfu(tps * flops_per_token),
        "peak_tflops": _peak_tflops(),
        "loss": float(loss.reshape(-1)[0])})


def bench_transformer_decode():
    """KV-cache incremental beam decode throughput (BENCH_MODEL=transformer
    BENCH_DECODE=1): tokens generated per second through
    build_cached_decode's while_loop (caches as carries, O(T) decoder
    work). The reference era re-ran the decoder on the growing prefix per
    step; this metric is the TPU-native serving headline."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.models import transformer

    batch = int(os.environ.get("BENCH_BATCH", "16"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "5")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    n_layer = int(os.environ.get("BENCH_LAYERS", "6"))
    d_model = int(os.environ.get("BENCH_DMODEL", "512"))
    n_head = int(os.environ.get("BENCH_HEADS", "8"))
    vocab = int(os.environ.get("BENCH_VOCAB", "30000"))
    beam = int(os.environ.get("BENCH_BEAM", "4"))

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(prog, startup):
        ids, scores = transformer.build_cached_decode(
            src_vocab_size=vocab, trg_vocab_size=vocab, max_length=seq,
            n_layer=n_layer, n_head=n_head, d_key=d_model // n_head,
            d_value=d_model // n_head, d_model=d_model,
            d_inner_hid=d_model * 4, beam_size=beam)

    rng = np.random.RandomState(0)
    srcs = [rng.randint(3, vocab, seq - 2).tolist() for _ in range(batch)]
    feed = transformer.prepare_cached_decode_batch(srcs, seq, n_head, beam)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(warmup):
            exe.run(prog, feed=feed, fetch_list=[ids])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = exe.run(prog, feed=feed, fetch_list=[ids],
                          return_numpy=False)
        device_fetch_barrier(out)
        dt = time.perf_counter() - t0

    # Throughput of EMITTED tokens (the returned hypotheses): each run
    # decodes seq-1 positions per batch element. The decoder also scores
    # beam-1 discarded hypotheses per step — that work is real but its
    # tokens are not output, so counting them would inflate tokens/sec
    # (ADVICE r4 #4); beam is in the JSON for FLOP reconstruction.
    tps = batch * (seq - 1) * steps / dt
    _emit({
        "metric": "transformer_cached_decode_throughput",
        "value": round(tps, 1), "unit": "emitted tokens/sec/chip",
        "vs_baseline": None, "batch": batch, "beam": beam, "seq": seq,
        "layers": n_layer, "d_model": d_model,
        "device": str(jax.devices()[0])})


def bench_stacked_lstm():
    """Stacked dynamic-LSTM sentiment training (the reference benchmark
    suite's stacked_dynamic_lstm.py workload): embedding -> 3x (fc+lstm)
    -> pools -> fc. One JSON tokens/sec line."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.lod import LoDTensor
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.models.understand_sentiment import stacked_lstm_net

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10")))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    seq = int(os.environ.get("BENCH_SEQ", "64"))
    vocab = int(os.environ.get("BENCH_VOCAB", "10000"))
    hid = int(os.environ.get("BENCH_HIDDEN", "512"))
    stacked = int(os.environ.get("BENCH_LAYERS", "3"))
    dtype = os.environ.get("BENCH_DTYPE", "bf16")

    main_prog, startup = fluid.Program(), fluid.Program()
    if dtype == "bf16":
        main_prog.enable_mixed_precision()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        data = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                 lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        pred = stacked_lstm_net(
            data, dict_dim=vocab, class_dim=2, emb_dim=hid, hid_dim=hid,
            stacked_num=stacked)
        cost = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.Adam(learning_rate=0.002).minimize(cost)

    rng = np.random.RandomState(0)
    seqs = [rng.randint(1, vocab, (seq, 1)).astype("int64")
            for _ in range(batch)]
    feed = {"words": LoDTensor.from_sequences(seqs),
            "label": rng.randint(0, 2, (batch, 1)).astype("int64")}

    multistep = _multistep()
    outer, total_steps = _step_plan(steps, multistep)
    run_kw = _run_kw(multistep)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(warmup):
            exe.run(main_prog, feed=feed, fetch_list=[cost], **run_kw)
        t0 = time.perf_counter()
        for _ in range(outer):
            out = exe.run(main_prog, feed=feed, fetch_list=[cost],
                          return_numpy=False, **run_kw)
        device_fetch_barrier(out)
        dt = time.perf_counter() - t0
        loss = np.asarray(out[0])
        assert np.isfinite(loss).all(), "non-finite loss"

    tps = batch * seq * total_steps / dt
    # fluid packing: dynamic_lstm(size=hid) has hidden width h = hid/4.
    # fwd FLOPs/token: layer 1 fc [emb=4h -> 4h] + recurrent [h, 4h]
    # = 2*4h*(4h+h) = 40h^2; layers >=2 take concat [4h+h -> 4h] + rec
    # = 48h^2. train ~ 3x fwd. (The first cut of this formula assumed
    # hidden == hid and overcounted MFU ~6x.)
    h = hid // 4
    flops_per_token = 3 * (40.0 * h * h + (stacked - 1) * 48.0 * h * h)
    _emit({
        "metric": "stacked_lstm_train_throughput",
        "value": round(tps, 1), "unit": "tokens/sec/chip",
        "vs_baseline": None, "batch": batch, "seq": seq,
        "multistep": multistep,
        "hidden": hid, "stacked": stacked, "dtype": dtype,
        "device": str(jax.devices()[0]),
        "mfu": _mfu(tps * flops_per_token),
        "peak_tflops": _peak_tflops(),
        "loss": float(loss.reshape(-1)[0])})


def _lat_ms(latencies, q):
    """Nearest-rank percentile of a latency list, in ms (the SAME
    percentile the serving /metrics endpoint reports — one definition)."""
    from paddle_tpu.serving.metrics import _percentile
    return round(_percentile(sorted(latencies), q) * 1e3, 3)


def bench_serving():
    """BENCH_SERVING=1: the online-inference leg (paddle_tpu/serving).
    Saves a small MLP via save_inference_model, loads it into an
    InferenceEngine (bucket warmup included), then measures

      * serial baseline — the same requests one at a time, batch=1,
        direct Executor.run (what serving WITHOUT a batcher would do),
      * closed loop — BENCH_SERVING_CLIENTS threads, each firing its next
        request when the previous completes,
      * open loop — a FIXED arrival schedule computed up front (i/rate
        offsets; no wall-clock dependence in what gets dispatched), rate
        BENCH_SERVING_ARRIVAL_QPS (default 2x the serial baseline).

    One JSON line: requests/sec (closed loop) as the headline value plus
    open-loop qps, the serial baseline, latency percentiles and mean
    batch occupancy. The coalescing win is value/serial_qps."""
    import threading

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import serving

    # clients >= max_batch by default so the closed loop can FILL a batch
    # (a full batch dispatches immediately; a partial one waits out
    # max_delay — with fewer clients than batch rows every cycle pays the
    # full coalescing delay and throughput can't beat serial)
    n_requests = int(os.environ.get("BENCH_SERVING_REQUESTS", "256"))
    n_clients = int(os.environ.get("BENCH_SERVING_CLIENTS", "16"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "8"))
    max_delay = float(os.environ.get("BENCH_SERVING_MAX_DELAY_MS", "5"))
    feat = int(os.environ.get("BENCH_SERVING_FEATURES", "64"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "256"))
    # depth sets the DISPATCH cost (kernels per jitted call) — the fixed
    # per-call overhead batching amortizes; per-row compute stays small.
    # A 2-layer toy on CPU is so dispatch-light that python queueing
    # overhead rivals it and the coalescing win drowns in host noise.
    n_layers = int(os.environ.get("BENCH_SERVING_LAYERS", "4"))
    n_serial = min(n_requests, int(os.environ.get("BENCH_SERVING_SERIAL",
                                                  "64")))

    import tempfile
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        h = x
        for _ in range(n_layers):
            h = fluid.layers.fc(input=h, size=hidden, act="relu")
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace())
    model_dir = tempfile.mkdtemp(prefix="ptpu_bench_serving_")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_prog)

    engine = serving.InferenceEngine(
        model_dir, place=fluid.TPUPlace(), name="bench",
        max_batch_size=max_batch, max_queue_delay_ms=max_delay,
        queue_capacity=max(1024, n_requests))
    import shutil
    shutil.rmtree(model_dir, ignore_errors=True)  # loaded; don't leak
    # a model dir per bench/CI run into the temp dir
    rng = np.random.RandomState(0)
    inputs = [rng.rand(1, feat).astype("float32")
              for _ in range(n_requests)]

    # Loud-honesty rule (same as every other BENCH leg): a request only
    # counts when its result has MATERIALIZED on the host — .numpy() per
    # request, the slice a real client reads. Counting at scatter time
    # would credit enqueue rate (JAX async dispatch) against a serial
    # baseline that pays full execution + D2H, and the coalescing "win"
    # could never lose.

    # serial batch=1 baseline: direct Executor.run per request, no queue
    t0 = time.perf_counter()
    for i in range(n_serial):
        engine.run_direct({"x": inputs[i]}, batch_bucket=1)
    serial_qps = n_serial / (time.perf_counter() - t0)

    # closed loop; latency = client-observed submit -> materialized.
    # A client thread dying silently would SHORTEN the wall clock while
    # the request count stays nominal — inflating the headline — so any
    # client failure fails the whole leg through the _error_line path.
    closed_lat, client_errors, lat_lock = [], [], threading.Lock()
    per_client = n_requests // n_clients

    def client(cid):
        lats = []
        try:
            for i in range(per_client):
                t = time.perf_counter()
                fut = engine.submit({"x": inputs[cid * per_client + i]})
                fut.result(120).numpy()
                lats.append(time.perf_counter() - t)
        except Exception as e:  # noqa: BLE001 - reported as leg failure
            with lat_lock:
                client_errors.append("client %d: %r" % (cid, e))
        with lat_lock:
            closed_lat.extend(lats)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    closed_dt = time.perf_counter() - t0
    if client_errors:
        engine.close(drain=False)
        _emit(_error_line(
            "serving closed loop: %d client(s) failed: %s"
            % (len(client_errors), "; ".join(client_errors[:3]))))
        sys.stdout.flush()
        os._exit(2)
    closed_qps = (per_client * n_clients) / closed_dt

    # open loop: fixed schedule, rate defaults to 2x the serial baseline
    rate = float(os.environ.get("BENCH_SERVING_ARRIVAL_QPS", "0")) \
        or 2.0 * serial_qps
    schedule = [i / rate for i in range(n_requests)]
    futures, submit_at, open_lat = [], [], []
    t0 = time.perf_counter()
    try:  # same one-JSON-line contract as the closed loop on failure
        for i, offset in enumerate(schedule):
            delay = t0 + offset - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            submit_at.append(time.perf_counter())
            futures.append(engine.submit({"x": inputs[i]}))
        for f, ts in zip(futures, submit_at):
            f.result(120).numpy()
            open_lat.append(time.perf_counter() - ts)
    except Exception as e:  # noqa: BLE001 - reported as leg failure
        engine.close(drain=False)
        _emit(_error_line(
            "serving open loop failed after %d/%d results: %r"
            % (len(open_lat), n_requests, e)))
        sys.stdout.flush()
        os._exit(2)
    open_dt = time.perf_counter() - t0
    open_qps = n_requests / open_dt

    snap = engine.metrics.snapshot()
    engine.close()
    _emit({
        "metric": "serving_throughput",
        "value": round(closed_qps, 1),
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "serial_qps": round(serial_qps, 1),
        "open_qps": round(open_qps, 1),
        "open_arrival_qps": round(rate, 1),
        "clients": n_clients, "requests": n_requests,
        "max_batch": max_batch, "max_delay_ms": max_delay,
        "layers": n_layers, "hidden": hidden,
        "mean_batch_occupancy": snap["mean_batch_occupancy"],
        "row_utilization": snap["row_utilization"],
        "closed_p50_ms": _lat_ms(closed_lat, 0.50),
        "closed_p95_ms": _lat_ms(closed_lat, 0.95),
        "closed_p99_ms": _lat_ms(closed_lat, 0.99),
        "open_p50_ms": _lat_ms(open_lat, 0.50),
        "open_p95_ms": _lat_ms(open_lat, 0.95),
        "open_p99_ms": _lat_ms(open_lat, 0.99),
        "device": str(jax.devices()[0])})


def bench_decode():
    """BENCH_DECODE=1 (BENCH_MODEL unset): the iteration-level
    continuous-batching decode leg (ARCHITECTURE.md §27). Builds a
    state-carrying decode-step program (greedy argmax feedback through an
    MLP over carried hidden + context rows — the control shape of a
    seq2seq decoder without the transformer bulk), serves it through a
    DecodeEngine, and measures

      * serial baseline — the SAME streams one at a time through a
        solo_clone sharing the engine's weights (decode serving without
        continuous batching). Doubles as the bit-exactness reference.
      * open loop — a FIXED arrival schedule computed up front (i/rate
        offsets), rate BENCH_DECODE_ARRIVAL_QPS streams/sec (default 2x
        the serial baseline), streams admitted into free slots and
        retired at iteration boundaries mid-flight. Mixed per-stream
        token budgets force admits/retires while other streams decode.

    One JSON line: continuous tokens/sec as the headline value plus the
    serial baseline, inter-token p50/p99, mean slot occupancy and
    divergence_vs_solo — HARD-gated: any stream whose token sequence
    differs from its solo decode fails the leg (exit 2). Tokens count
    only when materialized on the host (each iteration host-syncs the
    token row — that sync IS the decode scheduling loop)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import serving

    slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
    n_streams = int(os.environ.get("BENCH_DECODE_STREAMS", "48"))
    base_tokens = int(os.environ.get("BENCH_DECODE_TOKENS", "24"))
    hidden = int(os.environ.get("BENCH_DECODE_HIDDEN", "256"))
    vocab = int(os.environ.get("BENCH_DECODE_VOCAB", "4096"))
    n_layers = int(os.environ.get("BENCH_DECODE_LAYERS", "4"))

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        tok = fluid.layers.create_global_var([slots, 1], 0, "int64",
                                             persistable=True, name="tok")
        h = fluid.layers.create_global_var([slots, hidden], 0.0, "float32",
                                           persistable=True, name="h")
        ctx = fluid.layers.create_global_var([slots, hidden], 0.0,
                                             "float32", persistable=True,
                                             name="ctx")
        z = fluid.layers.concat(
            [fluid.layers.cast(tok, "float32"), h, ctx], axis=1)
        for _ in range(n_layers):
            z = fluid.layers.fc(input=z, size=hidden, act="tanh")
        logits = fluid.layers.fc(input=z, size=vocab)
        nxt = fluid.layers.reshape(
            fluid.layers.argmax(logits, axis=1), shape=[slots, 1])
        fin = fluid.layers.equal(
            nxt, fluid.layers.fill_constant([slots, 1], "int64", 0))
        fluid.layers.assign(nxt, output=tok)
        fluid.layers.assign(z, output=h)

    # mixed budgets: retires happen while other streams keep decoding, so
    # the open loop provably admits INTO a half-full running batch
    budgets = [max(4, base_tokens // 2 + (i * 7) % base_tokens)
               for i in range(n_streams)]
    rng = np.random.RandomState(0)
    feeds = [{"tok": np.array([i % (vocab - 1) + 1], dtype="int64"),
              "ctx": rng.randn(hidden).astype("float32")}
             for i in range(n_streams)]

    engine = serving.DecodeEngine(
        program=main_prog, startup_program=startup,
        token_var=nxt, finished_var=fin, max_slots=slots,
        name="bench-decode", queue_capacity=max(1024, n_streams),
        default_max_new_tokens=base_tokens)

    # serial baseline + bit-exactness reference: one stream at a time
    # through a clone sharing this engine's weights
    solo = engine.solo_clone(name="bench-decode-solo")
    serial_out = []
    t0 = time.perf_counter()
    try:
        for f, budget in zip(feeds, budgets):
            serial_out.append(np.asarray(
                solo.decode(f, max_new_tokens=budget)).reshape(-1))
    except Exception as e:  # noqa: BLE001 - reported as leg failure
        _emit(_error_line(
            "decode serial baseline failed after %d/%d streams: %r"
            % (len(serial_out), n_streams, e)))
        sys.stdout.flush()
        os._exit(2)
    serial_dt = time.perf_counter() - t0
    solo.close()
    serial_tokens = int(sum(len(s) for s in serial_out))
    serial_tps = serial_tokens / serial_dt

    # open loop: fixed schedule, rate defaults to 2x the serial
    # stream-completion rate — pressure enough that slots stay multiply
    # occupied without the pending queue growing unboundedly
    rate = float(os.environ.get("BENCH_DECODE_ARRIVAL_QPS", "0")) \
        or 2.0 * (n_streams / serial_dt)
    schedule = [i / rate for i in range(n_streams)]
    streams, cont_out = [], []
    t0 = time.perf_counter()
    try:
        for i, offset in enumerate(schedule):
            delay = t0 + offset - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            streams.append(engine.submit(feeds[i],
                                         max_new_tokens=budgets[i]))
        for s in streams:
            cont_out.append(np.asarray(s.result(300)).reshape(-1))
    except Exception as e:  # noqa: BLE001 - reported as leg failure
        engine.close(drain=False)
        _emit(_error_line(
            "decode open loop failed after %d/%d streams: %r"
            % (len(cont_out), n_streams, e)))
        sys.stdout.flush()
        os._exit(2)
    cont_dt = time.perf_counter() - t0
    cont_tokens = int(sum(len(s) for s in cont_out))
    stats = engine.decode_stats()
    engine.close()

    mismatched = [i for i, (a, b) in enumerate(zip(cont_out, serial_out))
                  if a.shape != b.shape or not np.array_equal(a, b)]
    divergence = len(mismatched) / float(n_streams)
    if mismatched:  # the per-stream bit-exactness contract is the POINT
        _emit(_error_line(
            "continuous decode diverged from solo on %d/%d streams "
            "(first: stream %d)" % (len(mismatched), n_streams,
                                    mismatched[0])))
        sys.stdout.flush()
        os._exit(2)

    _emit({
        "metric": "decode_continuous_tokens_per_sec",
        "value": round(cont_tokens / cont_dt, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "serial_tokens_per_s": round(serial_tps, 1),
        "speedup_vs_serial": round((cont_tokens / cont_dt) / serial_tps, 2),
        "divergence_vs_solo": divergence,
        "streams": n_streams, "slots": slots,
        "tokens": cont_tokens,
        "open_arrival_streams_per_s": round(rate, 2),
        "mean_slot_occupancy": stats["mean_slot_occupancy"],
        "inter_token_p50_ms": stats["inter_token_p50_ms"],
        "inter_token_p99_ms": stats["inter_token_p99_ms"],
        "iterations": stats["iterations"],
        "layers": n_layers, "hidden": hidden, "vocab": vocab,
        "device": str(jax.devices()[0])})


def bench_pipeline():
    """BENCH_PIPELINE=1: pipelined dispatch vs the serial paths, both
    runtimes (ARCHITECTURE.md §22).

    Serving: the deep-and-narrow MLP served twice through the SAME
    fixed open-loop arrival schedule — once with the serial PR-3
    batcher (pipeline_depth=0), once with continuous batching
    (pipeline_depth=BENCH_PIPELINE_DEPTH, default 2). Headline: open-
    loop qps + p50/p99 at fixed load; per leg, ~16 COALESCED results
    (through the real submit path) are compared against run_direct at
    each request's recorded bucket — that max divergence gates
    bit-equality at 0.0.

    Training: a host-io-bound trainer (wide reader records, narrow
    model — the prepass' pop+pad+H2D rivals the device step) run to EOF
    twice from IDENTICAL init: serial prepass vs prefetch=True.
    Headline: steps/s both legs; final params gate bit-equality.
    Epoch 1 warms the compile caches untimed; epoch 2 is measured.

    Knobs: BENCH_PIPELINE_DEPTH, BENCH_PIPELINE_ARRIVAL_QPS (default
    1.2x the measured serial batch=1 capacity — between the two legs'
    sustainable rates on overlapping hardware), BENCH_PIPELINE_REQUESTS,
    BENCH_SERVING_MAX_BATCH/FEATURES/HIDDEN/LAYERS (serving model),
    BENCH_PIPELINE_RECORDS/BATCH/FEAT/HIDDEN/TLAYERS/K (trainer).
    Loud-honesty rules as everywhere: requests/steps count only when
    materialized; any client error fails the leg."""
    import shutil
    import tempfile
    import threading

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.core.readers import EOFException, ReaderBase

    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "2"))
    n_requests = int(os.environ.get("BENCH_PIPELINE_REQUESTS", "192"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "8"))
    max_delay = float(os.environ.get("BENCH_SERVING_MAX_DELAY_MS", "5"))
    feat = int(os.environ.get("BENCH_SERVING_FEATURES", "64"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "256"))
    n_layers = int(os.environ.get("BENCH_SERVING_LAYERS", "4"))

    # --- the serving model (same deep-and-narrow family as
    # bench_serving: dispatch-bound, so per-batch host work is the cost
    # the pipeline hides) -------------------------------------------------
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        h = x
        for _ in range(n_layers):
            h = fluid.layers.fc(input=h, size=hidden, act="relu")
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace())
    model_dir = tempfile.mkdtemp(prefix="ptpu_bench_pipeline_")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_prog)

    rng = np.random.RandomState(0)
    inputs = [rng.rand(1, feat).astype("float32")
              for _ in range(n_requests)]

    def serve_leg(pipeline_depth, rate):
        """One open-loop pass over the fixed schedule; returns
        (qps, lat list, max divergence of COALESCED results vs
        run_direct at each sampled request's recorded bucket — the gate
        must go through the batcher's submit path, not compare two
        run_direct calls that bypass the machinery under test). Any
        client error fails the whole bench with one JSON error line."""
        engine = serving.InferenceEngine(
            model_dir, place=fluid.TPUPlace(), name="pipe%d" %
            pipeline_depth, max_batch_size=max_batch,
            max_queue_delay_ms=max_delay,
            queue_capacity=max(1024, n_requests),
            pipeline_depth=pipeline_depth)
        try:
            schedule = [i / rate for i in range(n_requests)]
            futures, submit_at, lats = [], [], []
            sampled = {}  # req idx -> (outputs, bucket) off the batcher
            sample_every = max(1, n_requests // 16)
            t0 = time.perf_counter()
            for i, offset in enumerate(schedule):
                delay = t0 + offset - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                submit_at.append(time.perf_counter())
                futures.append(engine.submit({"x": inputs[i]}))
            for i, (f, ts) in enumerate(zip(futures, submit_at)):
                out = f.result(120).numpy()   # materialized = counted
                lats.append(time.perf_counter() - ts)
                if i % sample_every == 0:
                    sampled[i] = (out, f.bucket)
            dt = time.perf_counter() - t0
            div = 0.0
            for i, (out, bucket) in sampled.items():
                ref, _ = engine.run_direct({"x": inputs[i]},
                                           batch_bucket=bucket[0],
                                           seq_bucket=bucket[1])
                for k in ref:
                    div = max(div, float(np.max(np.abs(
                        np.asarray(out[k], dtype="f8")
                        - np.asarray(ref[k], dtype="f8")))))
            return n_requests / dt, lats, div
        finally:
            engine.close()

    try:
        # serial engine measures the baseline rate first (one calibration
        # pass at an arbitrary high rate would skew the comparison, so:
        # a short closed burst through run_direct decides the load). The
        # timer starts AFTER construction + warmup + a couple of primed
        # calls — on real hardware the lattice compile costs seconds
        # while the calibration calls cost milliseconds, and folding it
        # in would underestimate serial capacity by orders of magnitude
        # (the derived load point would then stress neither leg).
        cal_n = min(48, n_requests)
        cal_engine = serving.InferenceEngine(
            model_dir, place=fluid.TPUPlace(), name="cal",
            max_batch_size=max_batch, pipeline_depth=0)
        for i in range(2):
            cal_engine.run_direct({"x": inputs[i]}, batch_bucket=1)
        t0 = time.perf_counter()
        for i in range(cal_n):
            cal_engine.run_direct({"x": inputs[i]}, batch_bucket=1)
        serial_qps = cal_n / (time.perf_counter() - t0)
        cal_engine.close()
        # default load point: 1.2x the serial batch=1 capacity — above
        # what the serial batcher sustains without queue growth, inside
        # what the pipelined batcher absorbs (on hardware where host and
        # device actually overlap), so the p50/p99 gap IS the win. On a
        # single shared core both legs saturate identically — CPU
        # numbers here gate correctness, not speed.
        rate = float(os.environ.get("BENCH_PIPELINE_ARRIVAL_QPS", "0")) \
            or 1.2 * serial_qps
        ser_qps, ser_lat, ser_div = serve_leg(0, rate)
        pipe_qps, pipe_lat, pipe_div = serve_leg(depth, rate)
        serving_div = max(ser_div, pipe_div)
    except Exception as e:  # noqa: BLE001 — one JSON error line
        shutil.rmtree(model_dir, ignore_errors=True)
        _emit(_error_line("serving leg failed: %r" % (e,)))
        sys.stdout.flush()
        os._exit(2)
    shutil.rmtree(model_dir, ignore_errors=True)

    # --- the trainer: host-io-bound (records are WIDE, the model is
    # narrow — pop+pad+H2D per step rivals the device step, which is
    # exactly the work prefetch moves off the dispatch path) -------------
    t_records = int(os.environ.get("BENCH_PIPELINE_RECORDS", "48"))
    t_batch = int(os.environ.get("BENCH_PIPELINE_BATCH", "32"))
    t_feat = int(os.environ.get("BENCH_PIPELINE_FEAT", "2048"))
    t_hidden = int(os.environ.get("BENCH_PIPELINE_HIDDEN", "64"))
    t_layers = int(os.environ.get("BENCH_PIPELINE_TLAYERS", "2"))
    t_k = int(os.environ.get("BENCH_PIPELINE_K", "1"))

    rng = np.random.RandomState(1)
    t_data = [(rng.rand(t_batch, t_feat).astype("float32"),
               rng.rand(t_batch, 1).astype("float32"))
              for _ in range(t_records)]

    def t_reader():
        for rec in t_data:
            yield rec

    tdir = tempfile.mkdtemp(prefix="ptpu_bench_pipeline_t_")
    rio = os.path.join(tdir, "train.recordio")
    fluid.recordio_writer.convert_reader_to_recordio_file(rio, t_reader)

    def build_trainer():
        main, st = fluid.Program(), fluid.Program()
        main.random_seed = 11
        st.random_seed = 11
        with fluid.unique_name.guard(), fluid.program_guard(main, st):
            r = fluid.layers.open_recordio_file(
                rio, shapes=[[-1, t_feat], [-1, 1]],
                dtypes=["float32", "float32"], lod_levels=[0, 0])
            xin, yin = fluid.layers.read_file(r)
            hh = xin
            for _ in range(t_layers):
                hh = fluid.layers.fc(input=hh, size=t_hidden, act="relu")
            pp = fluid.layers.fc(input=hh, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pp, label=yin))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return main, st, loss

    def reset_readers(scope):
        outermost = {id(scope.get(n)) for n in scope.names()
                     if isinstance(scope.get(n), ReaderBase)}
        for n in scope.names():
            v = scope.get(n)
            under = getattr(v, "_under", None)
            while under is not None:
                outermost.discard(id(under))
                under = getattr(under, "_under", None)
        for n in scope.names():
            v = scope.get(n)
            if isinstance(v, ReaderBase) and id(v) in outermost:
                v.reset()

    def train_leg(prefetch):
        main, st, loss = build_trainer()
        texe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            texe.run(st)
            # identical init across legs: same seeds, same program build
            def epoch(timed):
                n = 0
                last = None
                t0 = time.perf_counter()
                while True:
                    try:
                        last = texe.run(main, fetch_list=[loss],
                                        steps=t_k, prefetch=prefetch,
                                        return_numpy=False)[0]
                    except EOFException:
                        break
                    n += t_k
                # loud honesty: the epoch ends only when the final
                # fetch (and with it the queued device work) is real
                if last is not None:
                    jax.block_until_ready(last.array)
                return n, time.perf_counter() - t0
            epoch(timed=False)          # warm: compiles + caches
            reset_readers(scope)
            n_steps, dt = epoch(timed=True)
            params = {n: np.asarray(scope.get(n))
                      for n in scope.names()
                      if hasattr(scope.get(n), "dtype")}
        return n_steps / dt, n_steps, params

    try:
        ser_sps, n_steps, ser_params = train_leg(False)
        pre_sps, n_steps2, pre_params = train_leg(True)
        assert n_steps == n_steps2, "legs trained different step counts"
        train_div = max(
            float(np.max(np.abs(ser_params[k].astype("f8")
                                - pre_params[k].astype("f8"))))
            for k in ser_params)
    except Exception as e:  # noqa: BLE001 — one JSON error line
        shutil.rmtree(tdir, ignore_errors=True)
        _emit(_error_line("training leg failed: %r" % (e,)))
        sys.stdout.flush()
        os._exit(2)
    shutil.rmtree(tdir, ignore_errors=True)

    _emit({
        "metric": "pipeline_dispatch_open_qps",
        "value": round(pipe_qps, 1),
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "pipeline_depth": depth,
        "open_arrival_qps": round(rate, 1),
        "requests": n_requests,
        "serial_open_qps": round(ser_qps, 1),
        "serial_p50_ms": _lat_ms(ser_lat, 0.50),
        "serial_p99_ms": _lat_ms(ser_lat, 0.99),
        "pipelined_p50_ms": _lat_ms(pipe_lat, 0.50),
        "pipelined_p99_ms": _lat_ms(pipe_lat, 0.99),
        "serving_divergence": serving_div,
        "train_steps": n_steps,
        "train_k": t_k,
        "train_record_bytes": int(t_batch * (t_feat + 1) * 4),
        "train_serial_steps_s": round(ser_sps, 2),
        "train_prefetch_steps_s": round(pre_sps, 2),
        "train_speedup": round(pre_sps / ser_sps, 3),
        "train_divergence": train_div,
        "device": str(jax.devices()[0])})


def bench_obs():
    """BENCH_OBS=1: the tracing-overhead gate (ARCHITECTURE.md §24).

    The flight recorder is ALWAYS ON in production, so its cost must be
    provably negligible on both hot loops. Two legs, recorder on vs
    off (trace.set_enabled — the only supported use of the switch):

      * training — a dispatch-bound feed-fed MLP (small device step, so
        the per-step span cost is maximally visible); steps/s per leg.
      * serving — the deep-and-narrow MLP through the depth-2 pipelined
        batcher; closed-loop burst from BENCH_OBS_CLIENTS threads; p99
        per leg.

    Contention discipline (the bench_resil lesson): legs run in
    INTERLEAVED rounds and each leg keeps its BEST round (max steps/s,
    min p99) — a noisy-neighbour stall hits one round, the best drops
    it. One JSON line with both overheads, the span count the on-legs
    recorded (proof the recorder was live), and the profiler snapshot's
    on-dispatch-path sync count (must stay 0 with tracing on — spans
    are host timestamps, never device syncs). Knobs:
    BENCH_OBS_ROUNDS/STEPS/REQUESTS/CLIENTS,
    BENCH_SERVING_MAX_BATCH/FEATURES/HIDDEN/LAYERS."""
    import shutil
    import tempfile
    import threading

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import profiler, serving
    from paddle_tpu.observability import trace

    rounds = int(os.environ.get("BENCH_OBS_ROUNDS", "5"))
    n_steps = int(os.environ.get("BENCH_OBS_STEPS", "60"))
    n_requests = int(os.environ.get("BENCH_OBS_REQUESTS", "64"))
    # fewer clients than max_batch ON PURPOSE: batches never fill, so
    # every request pays the deterministic coalescing window — p99 is
    # then a realistic, stable several-ms number and the on/off delta
    # measures the spans, not scheduler jitter on a microsecond tail
    n_clients = int(os.environ.get("BENCH_OBS_CLIENTS", "4"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "8"))
    feat = int(os.environ.get("BENCH_SERVING_FEATURES", "64"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "128"))
    n_layers = int(os.environ.get("BENCH_SERVING_LAYERS", "4"))

    profiler.reset_profiler()
    trace.configure(capacity=8192)

    # --- training: the bench_resil-scale deep-narrow smoke MLP — a
    # realistic millisecond-class step (per-step span cost is ~13us of
    # host work; gating it against a degenerate micro-step would
    # measure the ratio of two numbers nothing real ever exhibits) ----
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 7
    startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for _ in range(4):
            h = fluid.layers.fc(input=h, size=128, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    xb = rng.rand(256, 64).astype("float32")
    feed = {"x": xb, "y": xb[:, :1].copy()}

    def train_round():
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
        jax.block_until_ready(out[0].array)  # honest: work is real
        return n_steps / (time.perf_counter() - t0)

    spans_recorded = 0
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            train_round()  # warm: compile outside the measurement
            train_sps = {True: 0.0, False: 0.0}
            for _ in range(rounds):
                for enabled in (True, False):
                    trace.set_enabled(enabled)
                    sps = train_round()
                    train_sps[enabled] = max(train_sps[enabled], sps)
            trace.set_enabled(True)
            spans_recorded = len(trace.dump()["events"])
    except Exception as e:  # noqa: BLE001 — one JSON error line
        trace.set_enabled(True)
        _emit(_error_line("training leg failed: %r" % (e,)))
        sys.stdout.flush()
        os._exit(2)

    # --- serving: pipelined batcher, closed-loop burst -----------------
    sm, sst = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(sm, sst):
        sx = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        sh = sx
        for _ in range(n_layers):
            sh = fluid.layers.fc(input=sh, size=hidden, act="relu")
        spred = fluid.layers.fc(input=sh, size=10, act="softmax")
    model_dir = tempfile.mkdtemp(prefix="ptpu_bench_obs_")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(sst)
        fluid.io.save_inference_model(model_dir, ["x"], [spred], exe, sm)
    rng = np.random.RandomState(1)
    inputs = [rng.rand(1, feat).astype("float32")
              for _ in range(n_requests)]

    def serve_round(engine):
        lats = [None] * n_requests
        errors = []
        idx_lock = threading.Lock()
        cursor = {"i": 0}

        def client():
            while True:
                with idx_lock:
                    i = cursor["i"]
                    if i >= n_requests:
                        return
                    cursor["i"] = i + 1
                t0 = time.perf_counter()
                try:
                    engine.submit({"x": inputs[i]}).result(120).numpy()
                except Exception as e:  # noqa: BLE001 — loud below
                    errors.append(e)
                    return
                lats[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return _lat_ms(sorted(lats), 0.99)

    try:
        engine = serving.InferenceEngine(
            model_dir, place=fluid.TPUPlace(), name="obs",
            max_batch_size=max_batch, max_queue_delay_ms=5,
            queue_capacity=max(1024, n_requests), pipeline_depth=2)
        try:
            serve_round(engine)  # warm
            p99 = {True: float("inf"), False: float("inf")}
            for _ in range(rounds):
                for enabled in (True, False):
                    trace.set_enabled(enabled)
                    p99[enabled] = min(p99[enabled],
                                       serve_round(engine))
            trace.set_enabled(True)
        finally:
            engine.close()
    except Exception as e:  # noqa: BLE001 — one JSON error line
        trace.set_enabled(True)
        shutil.rmtree(model_dir, ignore_errors=True)
        _emit(_error_line("serving leg failed: %r" % (e,)))
        sys.stdout.flush()
        os._exit(2)
    shutil.rmtree(model_dir, ignore_errors=True)

    snap = profiler.snapshot()  # the machine-readable satellite surface
    train_overhead = (train_sps[False] - train_sps[True]) \
        / max(train_sps[False], 1e-9)
    serving_overhead = (p99[True] - p99[False]) / max(p99[False], 1e-9)
    _emit({
        "metric": "observability_overhead",
        "value": round(train_sps[True], 2),
        "unit": "steps/sec/chip",
        "vs_baseline": None,
        "rounds": rounds,
        "train_steps_per_round": n_steps,
        "train_sps_on": round(train_sps[True], 2),
        "train_sps_off": round(train_sps[False], 2),
        "train_overhead": round(train_overhead, 4),
        "serving_requests": n_requests,
        "serving_p99_on_ms": round(p99[True], 3),
        "serving_p99_off_ms": round(p99[False], 3),
        "serving_overhead": round(serving_overhead, 4),
        "spans_recorded": spans_recorded,
        "sync_on_dispatch": snap["sync_stats"]["on_dispatch_path"],
        "device": str(jax.devices()[0])})


def bench_pool():
    """BENCH_POOL=1: the serving-HA leg (serving/pool.ReplicaPool).
    Saves the deep-and-narrow serving MLP once, then for each replica
    count in BENCH_POOL_REPLICAS (default "1,2,4") drives the SAME
    open-loop arrival schedule through a pool and injects the two
    events the subsystem exists to survive:

      * mid-run replica kill (at 1/3 of the schedule, pools with >1
        replica): a hard `kill_replica` while requests are queued on
        the victim — traffic must redistribute with zero client-visible
        errors,
      * mid-run weight reload (at 2/3): `pool.reload(model_dir)` swaps
        a freshly warmed engine into every replica under load — zero
        dropped requests.

    One JSON line: per-leg qps, p50/p99 client latency, error counts
    (the acceptance number is 0), retries/timeouts, and whether the
    kill/reload fired. Latency = submit -> materialized on the client
    thread (failovers included), the same loud-honesty rule as
    bench_serving. Knobs: BENCH_POOL_REQUESTS, BENCH_POOL_REPLICAS,
    BENCH_POOL_ARRIVAL_QPS (default 1.5x the measured serial qps),
    BENCH_POOL_MAX_BATCH, BENCH_SERVING_LAYERS/HIDDEN/FEATURES."""
    import shutil
    import tempfile
    import threading

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import serving

    n_requests = int(os.environ.get("BENCH_POOL_REQUESTS", "240"))
    replica_counts = [int(r) for r in os.environ.get(
        "BENCH_POOL_REPLICAS", "1,2,4").split(",") if r.strip()]
    max_batch = int(os.environ.get("BENCH_POOL_MAX_BATCH", "8"))
    max_delay = float(os.environ.get("BENCH_POOL_MAX_DELAY_MS", "5"))
    feat = int(os.environ.get("BENCH_SERVING_FEATURES", "64"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "64"))
    n_layers = int(os.environ.get("BENCH_SERVING_LAYERS", "10"))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        h = x
        for _ in range(n_layers):
            h = fluid.layers.fc(input=h, size=hidden, act="relu")
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace())
    model_dir = tempfile.mkdtemp(prefix="ptpu_bench_pool_")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_prog)

    rng = np.random.RandomState(0)
    inputs = [rng.rand(1, feat).astype("float32")
              for _ in range(n_requests)]

    # serial baseline (sets the open-loop arrival rate)
    probe = serving.InferenceEngine(model_dir, place=fluid.TPUPlace(),
                                    name="pool-probe",
                                    max_batch_size=max_batch,
                                    max_queue_delay_ms=max_delay)
    t0 = time.perf_counter()
    n_serial = min(48, n_requests)
    for i in range(n_serial):
        probe.run_direct({"x": inputs[i]}, batch_bucket=1)
    serial_qps = n_serial / (time.perf_counter() - t0)
    probe.close()
    rate = float(os.environ.get("BENCH_POOL_ARRIVAL_QPS", "0")) \
        or 1.5 * serial_qps

    legs = {}
    for n_rep in replica_counts:
        pool = serving.ReplicaPool(
            model_dir, replicas=n_rep, name="bench-pool",
            max_batch_size=max_batch, max_queue_delay_ms=max_delay,
            queue_capacity=max(1024, n_requests),
            attempt_timeout_s=30.0, retries=3)
        kill_at = n_requests // 3 if n_rep > 1 else None
        reload_at = (2 * n_requests) // 3
        events, futures, submit_at = [], [], []
        errors, latencies, lat_lock = [], [], threading.Lock()

        def finish(i, fut, ts):
            try:
                fut.result(120).numpy()
                with lat_lock:
                    latencies.append(time.perf_counter() - ts)
            except Exception as e:  # noqa: BLE001 — the error COUNT is
                with lat_lock:      # the leg's acceptance number
                    errors.append("req %d: %r" % (i, e))

        waiters = []
        t0 = time.perf_counter()
        for i in range(n_requests):
            delay = t0 + i / rate - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            if kill_at is not None and i == kill_at:
                pool.kill_replica(n_rep - 1)
                events.append("kill@%d" % i)
            if i == reload_at:
                # reload the SAME weights, CONCURRENTLY with the arrival
                # stream: the event under test is the swap-under-load,
                # and bit-identical weights keep every response
                # comparable. The thread is joined before the leg ends
                # so its completion is part of the measured wall.
                reload_t = threading.Thread(
                    target=pool.reload, kwargs={"model_dir": model_dir})
                reload_t.start()
                waiters.append(reload_t)
                events.append("reload@%d" % i)
            ts = time.perf_counter()
            try:
                fut = pool.submit({"x": inputs[i]})
            except Exception as e:  # noqa: BLE001
                with lat_lock:
                    errors.append("submit %d: %r" % (i, e))
                continue
            w = threading.Thread(target=finish, args=(i, fut, ts))
            w.start()
            waiters.append(w)
        for w in waiters:
            w.join()
        wall = time.perf_counter() - t0
        snap = pool.metrics.snapshot()
        pool.close()
        legs[str(n_rep)] = {
            "qps": round(len(latencies) / wall, 1),
            "p50_ms": _lat_ms(latencies, 0.50),
            "p99_ms": _lat_ms(latencies, 0.99),
            "errors": len(errors),
            "error_samples": errors[:3],
            "completed": len(latencies),
            "retries": snap["retries_total"],
            "attempt_timeouts": snap["attempt_timeouts_total"],
            "events": events,
        }

    shutil.rmtree(model_dir, ignore_errors=True)
    headline = legs[str(replica_counts[-1])]
    _emit({
        "metric": "serving_pool_throughput",
        "value": headline["qps"],
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "serial_qps": round(serial_qps, 1),
        "arrival_qps": round(rate, 1),
        "requests": n_requests, "max_batch": max_batch,
        "layers": n_layers, "hidden": hidden,
        "legs": legs,
        "total_errors": sum(l["errors"] for l in legs.values()),
        "device": str(jax.devices()[0])})


def bench_fleet():
    """BENCH_FLEET=1: the self-scaling fleet leg (serving/autoscaler).
    One load step, two pools, same closed-loop client schedule:

      * FIXED leg — 1 replica, small queue, autoscale OFF: the load
        step sheds sustained 429s for its whole duration (the
        reference-era fixed-size deployment failure mode).
      * AUTOSCALED leg — the same pool with autoscale [1,
        BENCH_FLEET_MAX_REPLICAS]: the controller grows the pool off
        the shed/queue signals (scale-up latency = engine build +
        warmup, an AOT-cache disk load when the cache is armed) until
        the shedding stops; after the load the pool drains back to 1.

    One JSON line: per-leg qps, total and TAIL-third 429 rates (the
    acceptance number: fixed stays shedding, autoscaled returns to
    ~0), scale-up count + latency, final replica count, client errors
    (must be 0). Clients retry 429s after the server's Retry-After
    hint, so completed counts are comparable across legs. On the
    1-core CPU container extra replicas add queue+admission capacity,
    not compute — qps parity is expected there and the 429-rate drop
    is the measured claim; on TPU the replicas land on distinct chips
    and qps scales too. Knobs: BENCH_FLEET_CLIENTS,
    BENCH_FLEET_SECONDS, BENCH_FLEET_MAX_REPLICAS,
    BENCH_FLEET_QUEUE_CAP, BENCH_SERVING_LAYERS/HIDDEN/FEATURES."""
    import shutil
    import tempfile
    import threading

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import serving

    n_clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "12"))
    seconds = float(os.environ.get("BENCH_FLEET_SECONDS", "3"))
    max_replicas = int(os.environ.get("BENCH_FLEET_MAX_REPLICAS", "3"))
    queue_cap = int(os.environ.get("BENCH_FLEET_QUEUE_CAP", "8"))
    max_batch = int(os.environ.get("BENCH_POOL_MAX_BATCH", "8"))
    feat = int(os.environ.get("BENCH_SERVING_FEATURES", "64"))
    hidden = int(os.environ.get("BENCH_SERVING_HIDDEN", "64"))
    n_layers = int(os.environ.get("BENCH_SERVING_LAYERS", "10"))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        h = x
        for _ in range(n_layers):
            h = fluid.layers.fc(input=h, size=hidden, act="relu")
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace())
    model_dir = tempfile.mkdtemp(prefix="ptpu_bench_fleet_")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_prog)
    rng = np.random.RandomState(0)
    inputs = [rng.rand(1, feat).astype("float32") for _ in range(64)]

    def drive(pool):
        """Closed-loop clients for `seconds`; 429s retried after the
        pool's own Retry-After hint. Returns wall, completions,
        reject timestamps, client errors."""
        t0 = time.perf_counter()
        done, rejects, errors = [], [], []
        lock = threading.Lock()

        def client(ci):
            k = 0
            while time.perf_counter() - t0 < seconds:
                try:
                    pool.submit({"x": inputs[(ci * 7 + k) % 64]}) \
                        .result(60).numpy()
                    with lock:
                        done.append(time.perf_counter() - t0)
                except serving.QueueFullError as e:
                    with lock:
                        rejects.append(time.perf_counter() - t0)
                    time.sleep(min(e.retry_after_s or 0.003, 0.05))
                except Exception as e:  # noqa: BLE001 — the acceptance
                    with lock:          # count is 0
                        errors.append(repr(e))
                k += 1

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, done, rejects, errors

    def leg_record(wall, done, rejects, errors):
        tail_t = 2.0 * seconds / 3.0
        tail_done = sum(1 for t in done if t >= tail_t)
        tail_rej = sum(1 for t in rejects if t >= tail_t)
        return {
            "qps": round(len(done) / wall, 1),
            "completed": len(done),
            "rejects": len(rejects),
            "reject_rate": round(len(rejects)
                                 / max(len(done) + len(rejects), 1), 4),
            "tail_reject_rate": round(
                tail_rej / max(tail_done + tail_rej, 1), 4),
            "errors": len(errors),
            "error_samples": errors[:3],
        }

    pool_kw = dict(max_batch_size=max_batch, max_queue_delay_ms=2,
                   queue_capacity=queue_cap, attempt_timeout_s=30.0)

    # ---- fixed-size leg: the reference-era deployment, shedding
    fixed_pool = serving.ReplicaPool(model_dir, replicas=1,
                                     name="fleet-fixed", **pool_kw)
    legs = {"fixed": leg_record(*drive(fixed_pool))}
    fixed_pool.close()

    # ---- autoscaled leg: same schedule, the controller absorbs it
    auto_pool = serving.ReplicaPool(
        model_dir, replicas=1, name="fleet-auto", autoscale=True,
        min_replicas=1, max_replicas=max_replicas,
        autoscale_kw=dict(interval_s=0.05, scale_up_cooldown_s=0.2,
                          scale_down_cooldown_s=0.3, down_idle_s=0.5),
        **pool_kw)
    wall, done, rejects, errors = drive(auto_pool)
    scaler = auto_pool._autoscaler
    rec = leg_record(wall, done, rejects, errors)
    rec.update({
        "scale_ups": scaler.scale_ups,
        "scale_up_latency_s": (round(scaler.last_scale_up_s, 3)
                               if scaler.last_scale_up_s is not None
                               else None),
        "peak_replicas": auto_pool.live_replica_count(),
    })
    # contraction: idle drains back to min without failing anything
    t_shrink = time.perf_counter()
    while auto_pool.live_replica_count() > 1 \
            and time.perf_counter() - t_shrink < 30:
        time.sleep(0.1)
    rec["final_replicas"] = auto_pool.live_replica_count()
    rec["scale_downs"] = scaler.scale_downs
    legs["autoscaled"] = rec
    auto_pool.close()
    shutil.rmtree(model_dir, ignore_errors=True)

    _emit({
        "metric": "serving_fleet_autoscale_qps",
        "value": legs["autoscaled"]["qps"],
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "clients": n_clients, "seconds": seconds,
        "max_replicas": max_replicas, "queue_capacity": queue_cap,
        "layers": n_layers, "hidden": hidden,
        "legs": legs,
        "total_errors": sum(l["errors"] for l in legs.values()),
        "device": str(jax.devices()[0])})


# fwd FLOPs per 224x224 image (2x the usual MACs figure — VGG16's famous
# "15.5G" is MACs, so fwd = 31e9); models build_train supports but this
# table lacks still bench (mfu reported null)
_IMAGE_MODELS = {
    # fwd FLOPs/image at 224^2/1000 classes (train ~ 3x fwd), each
    # MEASURED with XLA cost_analysis on the network AS IMPLEMENTED in
    # models/image_classification.py (is_test forward, 2026-07-31 —
    # same methodology as the r4 resnet50 audit, which also matches
    # per-conv shape sums): resnet50 8.14e9, resnet101 1.541e10,
    # resnet152 2.307e10, vgg16 3.011e10, alexnet (legacy 96-filter
    # unpadded-conv1 ungrouped variant) 1.852e9, googlenet v1 (aux
    # heads off) 2.734e9.
    "resnet50": (3 * 8.2e9, "resnet50_imagenet_train_throughput"),
    "resnet101": (3 * 15.4e9, "resnet101_imagenet_train_throughput"),
    "resnet152": (3 * 23.1e9, "resnet152_imagenet_train_throughput"),
    "vgg16": (3 * 30.1e9, "vgg16_imagenet_train_throughput"),
    "alexnet": (3 * 1.85e9, "alexnet_imagenet_train_throughput"),
    "googlenet": (3 * 2.73e9, "googlenet_imagenet_train_throughput"),
}


def bench_ckpt():
    """BENCH_CKPT=1: checkpointing overhead. Trains the same small Adam
    MLP three ways — no checkpointing, SYNCHRONOUS save every E steps
    (save blocks until the snapshot is published), ASYNC save every E
    steps (capture-only on the training thread, write on the manager's
    background thread) — and reports steps/s plus the training-loop STALL
    each mode paid to checkpointing (time blocked inside save calls) and
    the background save latency. One JSON line; the async-vs-sync stall
    gap is the number the subsystem exists to create.

    Knobs: BENCH_STEPS (timed steps), BENCH_CKPT_EVERY (save period E),
    BENCH_CKPT_DIM (MLP width — scales checkpoint bytes), BENCH_BATCH,
    BENCH_WARMUP."""
    import shutil
    import tempfile

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.core.utils import device_fetch_barrier

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "40")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    dim = int(os.environ.get("BENCH_CKPT_DIM", "256"))
    every = max(1, int(os.environ.get("BENCH_CKPT_EVERY", "5")))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=dim, act="tanh")
        h = fluid.layers.fc(input=h, size=dim, act="tanh")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        # Adam: 2 moments per param — checkpoint bytes ~3x params, the
        # realistic ratio a real trainer snapshots
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.rand(batch, dim).astype("float32"))
    ys = jnp.asarray(rng.rand(batch, 1).astype("float32"))
    jax.block_until_ready((xs, ys))
    feed = {"x": xs, "y": ys}
    exe = fluid.Executor(fluid.TPUPlace())

    results = {}
    for mode in ("none", "sync", "async"):
        ckdir = tempfile.mkdtemp(prefix="bench_ckpt_%s_" % mode)
        scope = fluid.Scope()
        mgr = None
        if mode != "none":
            mgr = CheckpointManager(ckdir, max_to_keep=3,
                                    async_save=(mode == "async"),
                                    max_in_flight=2)
        handles, stall, drain = [], 0.0, 0.0
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(warmup):
                exe.run(main_prog, feed=feed, fetch_list=[loss])
            out = None
            t0 = time.perf_counter()
            for i in range(1, steps + 1):
                out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                if mgr is not None and i % every == 0:
                    ts = time.perf_counter()
                    handles.append(mgr.save(i, program=main_prog,
                                            scope=scope,
                                            wait=(mode == "sync")))
                    stall += time.perf_counter() - ts
            device_fetch_barrier(out)
            loop_dt = time.perf_counter() - t0
            if mgr is not None:
                td = time.perf_counter()
                mgr.wait()
                drain = time.perf_counter() - td
                mgr.close()
        writes = [h.write_seconds for h in handles
                  if h.write_seconds is not None]
        results[mode] = {
            "steps_per_sec": round(steps / loop_dt, 2),
            "stall_ms": round(stall * 1e3, 3),
            "drain_ms": round(drain * 1e3, 3),
            "save_latency_ms": round(
                1e3 * sum(writes) / len(writes), 3) if writes else None,
            "saves": len(handles),
        }
        shutil.rmtree(ckdir, ignore_errors=True)

    _emit({
        "metric": "ckpt_async_steps_per_sec",
        "value": results["async"]["steps_per_sec"],
        "unit": "steps/sec",
        "vs_baseline": None,
        "batch": batch, "dim": dim, "steps": steps, "every": every,
        "modes": results,
        "device": str(jax.devices()[0]),
    })


def bench_sharded():
    """BENCH_SHARDED=1: ZeRO-style sharded weight update vs the
    replicated data-parallel baseline (parallel/plan.py,
    ARCHITECTURE.md §21). Trains the same Adam MLP twice on an N-device
    mesh from identical init — replicated update state vs
    `sharded_weight_update=True` — and reports steps/s for both, the
    per-chip update-state bytes each plan's memory accounting prices
    (the 1/N the sharding exists to buy), and the max absolute fetch
    divergence between the two loss streams (must be 0: sharding the
    update never changes the math). One JSON line.

    Knobs: BENCH_STEPS (timed steps), BENCH_WARMUP, BENCH_BATCH (global
    batch, split over the mesh), BENCH_SHARDED_DIM (MLP width — scales
    the update-state bytes), BENCH_SHARDED_DEVICES (mesh size, default
    every visible device)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.parallel.mesh import make_mesh

    n = int(os.environ.get("BENCH_SHARDED_DEVICES",
                           str(len(jax.devices()))))
    if n < 2:
        _emit(_error_line(
            "BENCH_SHARDED needs a multi-device mesh (%d visible); on "
            "CPU run under XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N" % n))
        sys.stdout.flush()
        os._exit(2)
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    if batch % n:
        batch = ((batch + n - 1) // n) * n  # divisibility contract
    steps = max(1, int(os.environ.get("BENCH_STEPS", "30")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    dim = int(os.environ.get("BENCH_SHARDED_DIM", "256"))

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 5
    startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=dim, act="tanh")
        h = fluid.layers.fc(input=h, size=dim, act="tanh")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        # Adam: the 2-moments-per-param update state the sharding halves
        # per doubling of the mesh — the realistic ZeRO target
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    rng = np.random.RandomState(0)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.rand(batch, 1).astype("float32")
    feed = {"x": xs, "y": ys}
    mesh = make_mesh({"dp": n}, jax.devices()[:n])
    exe = fluid.Executor(fluid.TPUPlace())

    results, mem, losses = {}, {}, {}
    init = None
    for mode in ("replicated", "sharded"):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            if init is None:
                # REAL copies, not np.asarray views: on the CPU backend
                # np.asarray of a jax array is zero-copy, and the
                # donated in-place update frees the viewed buffer —
                # the "identical init" would silently mutate under the
                # second leg (found as a warm-compile-cache-only bench
                # failure: cache hits shifted allocator reuse timing)
                init = {nm: np.array(scope.get(nm), copy=True)
                        for nm in scope.names()}
            else:
                for nm, v in init.items():
                    scope.set(nm, v)
            scope._rng_counter = 0
            pexe = fluid.ParallelExecutor(
                main_program=main_prog, loss_name=loss.name, mesh=mesh,
                sharded_weight_update=(mode == "sharded"))
            mem[mode] = pexe.plan.memory_report()
            for _ in range(warmup):
                pexe.run([loss.name], feed=feed)
            handles = []
            t0 = time.perf_counter()
            for _ in range(steps):
                handles.append(pexe.run([loss.name], feed=feed,
                                        return_numpy=False)[0])
            device_fetch_barrier(handles[-1:])
            dt = time.perf_counter() - t0
            # materialize AFTER the clock: the per-step losses feed the
            # divergence check, not the throughput number
            losses[mode] = [float(np.ravel(np.asarray(h))[0])
                            for h in handles]
            results[mode] = round(steps / dt, 2)
            assert all(np.isfinite(v) for v in losses[mode]), \
                "non-finite loss in %s leg" % mode

    divergence = max(abs(a - b) for a, b in
                     zip(losses["replicated"], losses["sharded"]))
    upd_r = mem["replicated"]["update_state"]["per_chip_bytes"]
    upd_s = mem["sharded"]["update_state"]["per_chip_bytes"]
    _emit({
        "metric": "sharded_update_steps_per_sec",
        "value": results["sharded"],
        "unit": "steps/sec",
        "vs_baseline": None,
        "devices": n, "batch": batch, "dim": dim, "steps": steps,
        "replicated_steps_per_sec": results["replicated"],
        "sharded_steps_per_sec": results["sharded"],
        "update_state_bytes_per_chip": {
            "replicated": upd_r, "sharded": upd_s,
            "ratio": round(upd_s / max(upd_r, 1), 4)},
        "params_bytes_per_chip": {
            "replicated": mem["replicated"]["params"]["per_chip_bytes"],
            "sharded": mem["sharded"]["params"]["per_chip_bytes"]},
        "fetch_divergence": divergence,
        "final_loss": losses["sharded"][-1],
        "device": str(jax.devices()[0]),
    })


def bench_tp():
    """BENCH_TP=1: tensor-parallel training as a Plan (parallel/plan.py
    tp_axis, ARCHITECTURE.md §23). Trains the same Adam MLP from
    identical init at mesh-1 and at tp=2/tp=4 ({'dp': 1, 'tp': n}
    meshes, auto row/col per-family specs, gather placement) and
    reports steps/s per leg, the per-chip PARAM bytes each plan's
    memory accounting prices (the 1/tp the intra-layer sharding buys —
    the "bigger than one chip" number), and the max absolute fetch
    divergence of each TP leg against the mesh-1 leg. The gather
    placement's contract is divergence EXACTLY 0.0: weights live
    sharded at rest and all-gather on use, so the math is the
    replicated math (test_bench_tp_smoke gates it). One JSON line.

    Knobs: BENCH_STEPS (timed steps), BENCH_WARMUP, BENCH_BATCH,
    BENCH_TP_DIM (MLP width — scales the at-rest param bytes),
    BENCH_TP_LEGS (comma list of tp sizes, default "1,2,4")."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.parallel.mesh import make_mesh

    legs_cfg = [int(v) for v in
                os.environ.get("BENCH_TP_LEGS", "1,2,4").split(",")]
    if 1 not in legs_cfg:
        legs_cfg = [1] + legs_cfg  # mesh-1 is the divergence baseline
    need = max(legs_cfg)
    if len(jax.devices()) < need:
        _emit(_error_line(
            "BENCH_TP legs %r need %d devices (%d visible); on CPU run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=N"
            % (legs_cfg, need, len(jax.devices()))))
        sys.stdout.flush()
        os._exit(2)
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "30")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    dim = int(os.environ.get("BENCH_TP_DIM", "256"))

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 5
    startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=dim, act="tanh")
        h = fluid.layers.fc(input=h, size=dim, act="tanh")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(batch, dim).astype("float32"),
            "y": rng.rand(batch, 1).astype("float32")}
    exe = fluid.Executor(fluid.TPUPlace())

    results, mem, losses = {}, {}, {}
    init = None
    for n in legs_cfg:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            if init is None:
                # REAL copies (not views of donated buffers — see
                # bench_sharded for the war story)
                init = {nm: np.array(scope.get(nm), copy=True)
                        for nm in scope.names()}
            else:
                for nm, v in init.items():
                    scope.set(nm, v)
            scope._rng_counter = 0
            mesh = make_mesh({"dp": 1, "tp": n}, jax.devices()[:n])
            pexe = fluid.ParallelExecutor(
                main_program=main_prog, loss_name=loss.name, mesh=mesh,
                tp_axis="tp")
            mem[n] = pexe.plan.memory_report()
            for _ in range(warmup):
                pexe.run([loss.name], feed=feed)
            handles = []
            t0 = time.perf_counter()
            for _ in range(steps):
                handles.append(pexe.run([loss.name], feed=feed,
                                        return_numpy=False)[0])
            device_fetch_barrier(handles[-1:])
            dt = time.perf_counter() - t0
            losses[n] = [float(np.ravel(np.asarray(h))[0])
                         for h in handles]
            results[n] = round(steps / dt, 2)
            assert all(np.isfinite(v) for v in losses[n]), \
                "non-finite loss in tp=%d leg" % n

    divergence = max((abs(a - b)
                      for n in legs_cfg if n != 1
                      for a, b in zip(losses[1], losses[n])),
                     default=0.0)
    tp_max = max(legs_cfg)
    par_1 = mem[1]["params"]["replicated_per_chip_bytes"]
    _emit({
        "metric": "tp_train_steps_per_sec",
        "value": results[tp_max],
        "unit": "steps/sec",
        "vs_baseline": None,
        "devices": tp_max, "batch": batch, "dim": dim, "steps": steps,
        "legs": {str(n): {
            "steps_per_sec": results[n],
            "params_bytes_per_chip": mem[n]["params"]["per_chip_bytes"],
            "params_ratio": round(
                mem[n]["params"]["per_chip_bytes"] / max(par_1, 1), 4),
        } for n in legs_cfg},
        "fetch_divergence": divergence,
        "final_loss": losses[tp_max][-1],
        "tp_placement": "gather",
        "device": str(jax.devices()[0]),
    })


def bench_resil():
    """BENCH_RESIL=1: numerical-guard overhead. Trains the deep-narrow
    smoke MLP four ways — guards off/on x single-step/steps=K — and
    reports steps/s for each plus the two overhead percentages. The
    guards add per-grad all-finite reductions (fused into the backward)
    plus ONE lax.cond gating every persistable update; the number this
    leg exists to defend is overhead < 10% on both legs
    (test_bench_resil_smoke asserts it). Batch defaults to 256: guard
    cost is proportional to STATE traffic while step cost scales with
    batch compute, so a degenerate tiny-batch toy would report a
    state/compute ratio no real trainer has.

    Knobs: BENCH_STEPS, BENCH_WARMUP, BENCH_BATCH, BENCH_RESIL_LAYERS,
    BENCH_RESIL_HIDDEN, BENCH_MULTISTEP (K for the multi-step leg),
    BENCH_RESIL_REPEATS (timed rounds; per-leg min taken).

    Deflake discipline (this leg gates a RATIO on a shared CI box):
    the four legs are timed in INTERLEAVED rounds — every round times
    plain/guarded/multi/multi-guarded back-to-back, and each leg keeps
    its min across rounds. A host-contention burst that lands inside
    one round slows every leg of that round together and the min drops
    the whole round, instead of (the old sequential-blocks layout)
    landing entirely inside ONE leg's timing block and inventing
    overhead the guards never had — the tier-1 flake noted in PR 9/10
    verification."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.resilience import install_numeric_guards

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "64")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    n_layers = int(os.environ.get("BENCH_RESIL_LAYERS", "10"))
    hidden = int(os.environ.get("BENCH_RESIL_HIDDEN", "64"))
    k = max(2, int(os.environ.get("BENCH_MULTISTEP", "8")))
    # five rounds by default (was three): the PR-10-era flake analysis
    # showed a single contention burst can survive three mins on a
    # loaded CI box; with five, the min has slack to drop two bad rounds
    repeats = max(1, int(os.environ.get("BENCH_RESIL_REPEATS", "5")))

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.rand(batch, hidden).astype("float32"))
    ys = jnp.asarray(rng.rand(batch, 1).astype("float32"))
    jax.block_until_ready((xs, ys))
    feed = {"x": xs, "y": ys}

    def build(guarded):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                            startup):
            x = fluid.layers.data(name="x", shape=[hidden],
                                  dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = x
            for _ in range(n_layers):
                h = fluid.layers.fc(input=h, size=hidden, act="relu")
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=p, label=y))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        if guarded:
            install_numeric_guards(main_prog, loss=loss)
        return main_prog, startup, loss

    exe = fluid.Executor(fluid.TPUPlace())

    # build + warm all four legs FIRST (each keeps its own live scope,
    # so training state persists across the interleaved rounds)
    legs = {}
    for name, guarded, multistep in (("plain", False, 1),
                                     ("guarded", True, 1),
                                     ("multi", False, k),
                                     ("multi_guarded", True, k)):
        main_prog, startup, loss = build(guarded)
        run_kw = {"steps": multistep, "fetch_reduce": "last"} \
            if multistep > 1 else {}
        outer = max(1, -(-steps // multistep))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(warmup):
                exe.run(main_prog, feed=feed, fetch_list=[loss], **run_kw)
        legs[name] = {"prog": main_prog, "loss": loss, "scope": scope,
                      "run_kw": run_kw, "outer": outer,
                      "multistep": multistep, "best": None, "out": None}

    # per-call materialization (return_numpy default): the realistic
    # trainer pattern — a loop that reads its loss every dispatch.
    # Comparing an ASYNC unguarded loop against the guard's mandatory
    # per-dispatch flag sync would charge the guard for the loop style,
    # not the guard work.
    for _ in range(repeats):
        for leg in legs.values():
            with fluid.scope_guard(leg["scope"]):
                t0 = time.perf_counter()
                for _ in range(leg["outer"]):
                    leg["out"] = exe.run(leg["prog"], feed=feed,
                                         fetch_list=[leg["loss"]],
                                         **leg["run_kw"])
                dt = time.perf_counter() - t0
            leg["best"] = dt if leg["best"] is None \
                else min(leg["best"], dt)
    for name, leg in legs.items():
        assert np.isfinite(np.asarray(leg["out"][0])).all(), \
            "non-finite loss in %s leg" % name

    def rate(leg):
        return leg["outer"] * leg["multistep"] / leg["best"]

    plain_off = rate(legs["plain"])
    plain_on = rate(legs["guarded"])
    multi_off = rate(legs["multi"])
    multi_on = rate(legs["multi_guarded"])

    def overhead(off, on):
        return round((off / on - 1.0) * 100.0, 2)

    _emit({
        "metric": "resil_guarded_steps_per_sec",
        "value": round(plain_on, 2),
        "unit": "steps/sec",
        "vs_baseline": None,
        "batch": batch, "layers": n_layers, "hidden": hidden,
        "steps": steps, "multistep": k, "repeats": repeats,
        "plain_steps_per_sec": round(plain_off, 2),
        "guarded_steps_per_sec": round(plain_on, 2),
        "multistep_steps_per_sec": round(multi_off, 2),
        "multistep_guarded_steps_per_sec": round(multi_on, 2),
        "overhead_pct_plain": overhead(plain_off, plain_on),
        "overhead_pct_multistep": overhead(multi_off, multi_on),
        "device": str(jax.devices()[0]),
    })


def bench_sentinel():
    """BENCH_SENTINEL=1: training-health monitoring overhead
    (ARCHITECTURE.md §29). Trains the deep-narrow smoke MLP with the
    sentinel's guard configuration (guards + the grad-norm stat channel)
    and times four legs:

        baseline        the gn-channel program, nothing watching it
        sentinel        same PROGRAM + TrainingSentinel.observe per step
                        (loss z-score + grad-norm z over the stat tap)
        sentinel_canary same + one CanaryChecker dispatch every
                        BENCH_SDC_EVERY steps (the SDC cadence cost)
        nochannel       guards WITHOUT the stat channel (informational:
                        what install_numeric_guards(grad_norm=True)
                        itself adds in-graph)

    The number this leg exists to defend is overhead_pct_sentinel <= 3%
    (test_bench_sentinel_smoke asserts it): the monitor reads a loss the
    loop already fetched and a grad norm that rode an existing transfer,
    so its cost is host arithmetic on two floats. baseline and sentinel
    deliberately run the SAME program (two scopes, one executable) so
    the gated ratio isolates exactly that monitoring cost — XLA:CPU
    run-to-run executable layout variance between two separately
    compiled programs was measured at +-5% on this smoke model, which
    would drown a 3% gate in compile-lottery noise. The in-graph channel
    cost (two executables, unavoidably noisy at smoke scale) is emitted
    as overhead_pct_channel for the benchd TPU tier to track, not gated.

    Knobs: BENCH_STEPS, BENCH_WARMUP, BENCH_BATCH, BENCH_RESIL_LAYERS,
    BENCH_RESIL_HIDDEN, BENCH_SDC_EVERY (canary cadence, default 16),
    BENCH_SENTINEL_REPEATS (timed rounds; per-leg min taken).

    Same deflake discipline as bench_resil (this leg also gates a
    ratio on a shared CI box): the legs are timed in INTERLEAVED
    rounds, each keeping its min across rounds, so a host-contention
    burst slows a whole round together and the min drops the round."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.resilience import install_numeric_guards
    from paddle_tpu.resilience.sdc import CanaryChecker
    from paddle_tpu.resilience.sentinel import TrainingSentinel

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "64")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    n_layers = int(os.environ.get("BENCH_RESIL_LAYERS", "10"))
    hidden = int(os.environ.get("BENCH_RESIL_HIDDEN", "64"))
    sdc_every = max(1, int(os.environ.get("BENCH_SDC_EVERY", "16")))
    repeats = max(1, int(os.environ.get("BENCH_SENTINEL_REPEATS", "5")))

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.rand(batch, hidden).astype("float32"))
    ys = jnp.asarray(rng.rand(batch, 1).astype("float32"))
    jax.block_until_ready((xs, ys))
    feed = {"x": xs, "y": ys}

    def build(grad_norm):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                            startup):
            x = fluid.layers.data(name="x", shape=[hidden],
                                  dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = x
            for _ in range(n_layers):
                h = fluid.layers.fc(input=h, size=hidden, act="relu")
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=p, label=y))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        install_numeric_guards(main_prog, loss=loss, grad_norm=grad_norm)
        return main_prog, startup, loss

    exe = fluid.Executor(fluid.TPUPlace())

    # detection intentionally lobotomized: the leg measures MONITORING
    # cost, and a real verdict (a z spike, or the divergence trend — a
    # converged loss oscillating around 1e-5 trips a 3x-median factor
    # honestly) would divert a round into recovery bookkeeping
    def fresh_sentinel():
        return TrainingSentinel(window=64, warmup=8, z_threshold=1e9,
                                divergence_patience=10 ** 9)

    canary = CanaryChecker(shape=(64, 64), iters=2)
    canary.record_reference()

    gn_prog, gn_startup, gn_loss = build(True)
    nc_prog, nc_startup, nc_loss = build(False)
    legs = {}
    for name, prog, startup, loss, monitored, with_canary in (
            ("baseline", gn_prog, gn_startup, gn_loss, False, False),
            ("sentinel", gn_prog, gn_startup, gn_loss, True, False),
            ("sentinel_canary", gn_prog, gn_startup, gn_loss, True, True),
            ("nochannel", nc_prog, nc_startup, nc_loss, False, False)):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(warmup):
                exe.run(prog, feed=feed, fetch_list=[loss])
        legs[name] = {"prog": prog, "loss": loss, "scope": scope,
                      "monitored": monitored, "canary": with_canary,
                      "best": None, "out": None}

    for _ in range(repeats):
        for leg in legs.values():
            sentinel = fresh_sentinel() if leg["monitored"] else None
            with fluid.scope_guard(leg["scope"]):
                t0 = time.perf_counter()
                for i in range(steps):
                    out = exe.run(leg["prog"], feed=feed,
                                  fetch_list=[leg["loss"]])
                    leg["out"] = out
                    if sentinel is not None:
                        gn = exe.last_stats.get("grad_norm")
                        err = sentinel.observe(
                            float(np.asarray(out[0]).reshape(-1)[0]),
                            grad_norm=None if gn is None
                            else float(np.asarray(gn)), step=i)
                        assert err is None, err
                    if leg["canary"] and (i + 1) % sdc_every == 0:
                        canary.check()
                dt = time.perf_counter() - t0
            leg["best"] = dt if leg["best"] is None \
                else min(leg["best"], dt)
    for name, leg in legs.items():
        assert np.isfinite(np.asarray(leg["out"][0])).all(), \
            "non-finite loss in %s leg" % name

    baseline = steps / legs["baseline"]["best"]
    monitored = steps / legs["sentinel"]["best"]
    canaried = steps / legs["sentinel_canary"]["best"]
    nochannel = steps / legs["nochannel"]["best"]

    def overhead(off, on):
        return round((off / on - 1.0) * 100.0, 2)

    _emit({
        "metric": "sentinel_steps_per_sec",
        "value": round(monitored, 2),
        "unit": "steps/sec",
        "vs_baseline": None,
        "batch": batch, "layers": n_layers, "hidden": hidden,
        "steps": steps, "repeats": repeats, "sdc_every": sdc_every,
        "baseline_steps_per_sec": round(baseline, 2),
        "sentinel_steps_per_sec": round(monitored, 2),
        "canary_steps_per_sec": round(canaried, 2),
        "nochannel_steps_per_sec": round(nochannel, 2),
        "overhead_pct_sentinel": overhead(baseline, monitored),
        "overhead_pct_canary": overhead(baseline, canaried),
        "overhead_pct_channel": overhead(nochannel, baseline),
        "canary_checks": int(canary.checks),
        "device": str(jax.devices()[0]),
    })


def _ccache_build_trainer(fluid, dim, layers):
    """The restartable training model both compile-cache children share:
    deep-narrow (dispatch/compile-bound, the cold-start victim), Adam so
    the checkpoint carries realistic state."""
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(input=h, size=dim, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main_prog, startup, loss


def _ccache_child(kind):
    """One cold-or-warm process start, measured from inside (import and
    device-init time excluded — the cache can't help those; what it
    kills is trace+lower+compile). Prints one JSON line with wall times
    and the always-on compile_cache counters: `compiles` = fresh
    compiles this process paid (each one stores an artifact),
    `aot_hits` = compiles replaced by disk loads."""
    import paddle_tpu as fluid
    from paddle_tpu.core.compile_cache import aot_stats

    dim = int(os.environ.get("BENCH_CCACHE_DIM", "64"))
    layers = int(os.environ.get("BENCH_CCACHE_LAYERS", "10"))
    rng = np.random.RandomState(0)

    if kind == "serving":
        from paddle_tpu.serving import InferenceEngine
        buckets = [int(b) for b in os.environ.get(
            "BENCH_CCACHE_BUCKETS", "1,2,4,8").split(",")]
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                            startup):
            x = fluid.layers.data(name="x", shape=[dim],
                                  dtype="float32")
            h = x
            for _ in range(layers):
                h = fluid.layers.fc(input=h, size=dim, act="relu")
            out = fluid.layers.fc(input=h, size=1)
        infer = main_prog.prune([out.name], for_test=True)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        engine = InferenceEngine(
            program=infer, feed_names=["x"], fetch_vars=[out],
            batch_buckets=buckets, warmup=False, validate=False)
        for name in scope.names():
            v = scope.get(name)
            if v is not None:
                engine._scope.set(name, v)
        t0 = time.perf_counter()
        engine.warmup()
        warmup_s = time.perf_counter() - t0
        # steady state stays bit-for-bit correct off the loaded artifacts
        got = engine.run_direct({"x": rng.rand(2, dim).astype("f")})[0]
        engine.close()
        print(json.dumps({
            "kind": kind, "warmup_s": round(warmup_s, 4),
            "buckets": buckets,
            "check": float(np.asarray(got[out.name]).reshape(-1)[0]),
            **{k: v for k, v in aot_stats().items()
               if k in ("stores", "hits", "load_errors")}}))
        return 0

    if kind == "trainer":
        from paddle_tpu.checkpoint import CheckpointManager
        from paddle_tpu.core.utils import device_fetch_barrier
        ckdir = os.environ["BENCH_CCACHE_CKPT_DIR"]
        steps = int(os.environ.get("BENCH_CCACHE_STEPS", "8"))
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        main_prog, startup, loss = _ccache_build_trainer(fluid, dim,
                                                         layers)
        feed = {"x": rng.rand(batch, dim).astype("f"),
                "y": rng.rand(batch, 1).astype("f")}
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        mgr = CheckpointManager(ckdir, async_save=False)
        restored = None
        with fluid.scope_guard(scope):
            exe.run(startup)
            restored = mgr.restore(program=main_prog, scope=scope)
            # the number the cache exists to move: restart/rollback
            # re-entry pays trace+lower+compile before step one — or a
            # disk load
            t0 = time.perf_counter()
            out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            device_fetch_barrier(out)
            first_step_s = time.perf_counter() - t0
            for i in range(steps - 1):
                out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            device_fetch_barrier(out)
            total_s = time.perf_counter() - t0
            if restored is None:
                mgr.save(steps, program=main_prog, scope=scope,
                         wait=True)
        mgr.close()
        print(json.dumps({
            "kind": kind, "restored_step": restored,
            "first_step_s": round(first_step_s, 4),
            "total_s": round(total_s, 4),
            "loss": float(np.asarray(out[0]).reshape(-1)[0]),
            **{k: v for k, v in aot_stats().items()
               if k in ("stores", "hits", "load_errors")}}))
        return 0

    raise SystemExit("unknown BENCH_COMPILE_CACHE_CHILD=%r" % kind)


def bench_compile_cache():
    """BENCH_COMPILE_CACHE=1: the cold-start legs. Each scenario runs as
    a fresh subprocess twice against ONE persistent AOT cache dir — the
    first (cold) process pays every compile and publishes artifacts,
    the second (warm) process must show ZERO fresh compiles and a
    measured wall-time drop:

      (a) serving warmup over a bucket lattice (the ptpu_serve restart),
      (b) trainer restart + checkpoint-rollback re-entry (the
          resilience Supervisor's recovery path).

    One JSON line per scenario. Knobs: BENCH_CCACHE_DIM /
    BENCH_CCACHE_LAYERS (model size), BENCH_CCACHE_BUCKETS (lattice),
    BENCH_CCACHE_STEPS (trainer steps)."""
    import shutil
    import subprocess

    from paddle_tpu.core.compile_cache import repo_cache_dir

    # a fixed path under the checkout, emptied first: "cold" needs a
    # directory nothing has written, not one named by the clock or a pid
    workdir = os.path.join(repo_cache_dir(), "bench_ccache")
    shutil.rmtree(workdir, ignore_errors=True)
    aot_dir = os.path.join(workdir, "aot")
    ckpt_dir = os.path.join(workdir, "ckpt")
    os.makedirs(ckpt_dir)

    def run_child(kind):
        env = dict(os.environ)
        env.update({
            "BENCH_COMPILE_CACHE_CHILD": kind,
            "FLAGS_aot_cache_dir": aot_dir,
            # isolate jax's own HLO cache too, so "cold" is honest
            "JAX_COMPILATION_CACHE_DIR": os.path.join(workdir, "xla"),
            "BENCH_CCACHE_CKPT_DIR": ckpt_dir,
        })
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True,
            timeout=int(os.environ.get("BENCH_CCACHE_TIMEOUT", "600")))
        if out.returncode != 0:
            raise RuntimeError("compile-cache child %r failed:\n%s\n%s"
                               % (kind, out.stdout, out.stderr))
        return json.loads(out.stdout.strip().splitlines()[-1])

    try:
        for kind, metric, field in (
                ("serving", "compile_cache_serving_warmup", "warmup_s"),
                ("trainer", "compile_cache_trainer_restart",
                 "first_step_s")):
            cold = run_child(kind)
            warm = run_child(kind)
            speedup = (cold[field] / warm[field]) if warm[field] else None
            _emit({
                "metric": metric,
                # value must be a number (benchd schema); a zero warm
                # time (speedup indeterminate) reports 0.0, never None
                "value": round(speedup, 2) if speedup else 0.0,
                "unit": "x cold/warm %s" % field,
                "vs_baseline": None,
                "cold": cold, "warm": warm,
                "warm_recompiles": warm["stores"],
            })
            sys.stdout.flush()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    # The compile-cache leg runs each scenario in child processes that
    # need the chip, and a chip belongs to one process: this parent must
    # not have initialised a backend when it starts them (importing
    # bench and paddle_tpu initialises none — test_chip_smoke.py).
    child = os.environ.get("BENCH_COMPILE_CACHE_CHILD")
    if child:
        sys.exit(_ccache_child(child))
    if os.environ.get("BENCH_COMPILE_CACHE") == "1":
        try:
            bench_compile_cache()
        except Exception as e:  # noqa: BLE001 — one JSON error line
            _emit(_error_line(repr(e)))
            sys.stdout.flush()
            sys.exit(3)
        return
    # Persistent executable cache, placed by the one rule in
    # core/compile_cache.py. On only when warmup excludes compile time
    # from the measurement; warmup=0 is the documented compile-INCLUSIVE
    # mode, and a cache hit there would report near-zero compile cost
    # as throughput.
    from paddle_tpu.core.compile_cache import enable_persistent_cache
    from paddle_tpu.places import cpu_only_env
    if int(os.environ.get("BENCH_WARMUP", "5")) > 0:
        enable_persistent_cache()
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_only_env():
        # never emit CPU numbers dressed up as TPU data
        _emit(_error_line("expected a TPU but jax found platform %r; set "
                          "JAX_PLATFORMS=cpu to smoke-run on the CPU on "
                          "purpose" % platform))
        sys.exit(3)
    if os.environ.get("BENCH_SERVING") == "1":
        bench_serving()
        return
    if os.environ.get("BENCH_POOL") == "1":
        bench_pool()
        return
    if os.environ.get("BENCH_FLEET") == "1":
        bench_fleet()
        return
    if os.environ.get("BENCH_CKPT") == "1":
        bench_ckpt()
        return
    if os.environ.get("BENCH_RESIL") == "1":
        bench_resil()
        return
    if os.environ.get("BENCH_SENTINEL") == "1":
        bench_sentinel()
        return
    if os.environ.get("BENCH_SHARDED") == "1":
        bench_sharded()
        return
    if os.environ.get("BENCH_TP") == "1":
        bench_tp()
        return
    if os.environ.get("BENCH_PIPELINE") == "1":
        bench_pipeline()
        return
    if os.environ.get("BENCH_OBS") == "1":
        bench_obs()
        return
    if os.environ.get("BENCH_DECODE") == "1" \
            and os.environ.get("BENCH_MODEL", "") != "transformer":
        bench_decode()
        return
    model = os.environ.get("BENCH_MODEL", "resnet50")
    if model == "transformer":
        if os.environ.get("BENCH_DECODE") == "1":
            bench_transformer_decode()
        else:
            bench_transformer()
        return
    if model == "stacked_lstm":
        bench_stacked_lstm()
        return
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.utils import device_fetch_barrier
    from paddle_tpu.models.image_classification import build_train

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "20")))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    dtype = os.environ.get("BENCH_DTYPE", "bf16")  # bf16 | fp32
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    # smoke-run knobs (defaults = the headline config)
    hw = int(os.environ.get("BENCH_IMAGE_HW", "224"))
    class_dim = int(os.environ.get("BENCH_CLASS_DIM", "1000"))
    # feed modes: device (one-time transfer, chip-throughput headline) |
    # host (float32 batches through DoubleBufferReader — measures the
    # full pipeline incl. link bandwidth) | host_u8 (uint8 batches,
    # normalize on device: 4x less traffic — the feeder machinery
    # decoupled from link bandwidth, round-4 weak #5)
    feed_mode = os.environ.get("BENCH_FEED", "device")

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        image, label, avg_cost, acc = build_train(
            model=model, class_dim=class_dim, image_shape=(3, hw, hw),
            learning_rate=0.1, momentum=0.9, use_bf16=(dtype == "bf16"),
            uint8_input=(feed_mode == "host_u8"))
    if remat:  # trade FLOPs for activation memory (enables larger batch)
        fluid.memory_optimization_transpiler.enable_rematerialization(
            main_prog)

    place = fluid.TPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    if feed_mode in ("host", "host_u8"):
        # realistic input pipeline: numpy batches staged host→device by the
        # shipped DoubleBufferReader (core/readers.py) — the same code path
        # layers.double_buffer uses — so the copy overlaps the running step
        from itertools import count
        from paddle_tpu.core.readers import (DoubleBufferReader,
                                             IteratorReader)
        def make_image():
            if feed_mode == "host_u8":
                return (rng.rand(batch, 3, hw, hw) * 255).astype("uint8")
            return rng.rand(batch, 3, hw, hw).astype("float32")

        host_batches = [
            (make_image(),
             rng.randint(0, class_dim, (batch, 1)).astype("int32"))
            for _ in range(3)]
        reader = DoubleBufferReader(IteratorReader(
            lambda: (host_batches[i % len(host_batches)] for i in count())),
            capacity=2, place=place)

        def stage(_i):
            img, lbl = reader.next()
            return {"image": img, "label": lbl}

        feeds = None  # per-step, via prefetcher below
    else:
        # one-time host→device transfer; the timed loop feeds
        # device-resident arrays
        xs = jnp.asarray(rng.rand(batch, 3, hw, hw).astype("float32"))
        ys = jnp.asarray(rng.randint(0, class_dim, (batch, 1)).astype("int32"))
        jax.block_until_ready((xs, ys))
        feeds = {"image": xs, "label": ys}

    multistep = _multistep()
    if multistep > 1 and feed_mode != "device":
        # loud-failure rule: the host feed modes exist to measure the
        # input pipeline, but Executor.run(steps=K) REPLAYS an explicit
        # feed for all K steps — the reader would fire once per K-block,
        # crediting K steps of throughput to 1/K of the staging work.
        # (The in-graph-reader path measures the pipeline under the
        # loop honestly; bench.py doesn't build one yet.)
        _emit(_error_line(
            "BENCH_MULTISTEP>1 with BENCH_FEED=%s would replay one "
            "staged batch per K-step block and overstate pipeline "
            "throughput; use BENCH_FEED=device" % feed_mode))
        sys.exit(2)
    outer, total_steps = _step_plan(steps, multistep)
    run_kw = _run_kw(multistep)
    with fluid.scope_guard(scope):
        exe.run(startup)
        # warmup=0 is honored: the timed loop then includes compile time
        for _ in range(warmup):
            fd = stage(0) if feeds is None else feeds
            exe.run(main_prog, feed=fd, fetch_list=[avg_cost], **run_kw)
        t0 = time.perf_counter()
        for i in range(outer):
            fd = stage(i) if feeds is None else feeds
            out = exe.run(main_prog, feed=fd,
                          fetch_list=[avg_cost], return_numpy=False,
                          **run_kw)
        device_fetch_barrier(out)
        dt = time.perf_counter() - t0
        loss = np.asarray(out[0])
        assert np.isfinite(loss).all(), "non-finite loss"

    ips = batch * total_steps / dt
    headline = (hw == 224 and class_dim == 1000)
    # ResNet-50 fwd = 4.09 GMACs = 8.18e9 FLOPs @ 224^2 (the commonly
    # quoted "4.1 GFLOPs" is MACs); training ~ 3x fwd. Audited round 4:
    # per-conv program shapes sum to 8.178e9 and XLA cost_analysis counts
    # 8.14e9 fwd / 26.9e9 train — so 3*8.2e9 is the conservative
    # conv+fc-only floor. (The pre-round-4 constant 3*4.1e9 undercounted
    # MFU by 2x.) VGG16: 15.5 GFLOPs fwd.
    flops_per_image, metric = _IMAGE_MODELS.get(
        model, (None, "%s_imagenet_train_throughput" % model))
    rec = {
        "metric": metric,
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        # the 300 img/s V100 baseline is a ResNet-50 224x224/1000-class
        # number; other models/smoke configs must not masquerade as it
        "vs_baseline": round(ips / 300.0, 3)
        if headline and model == "resnet50" else None,
        "batch": batch,
        "dtype": dtype,
        "feed": feed_mode,
        "multistep": multistep,
        "device": str(jax.devices()[0]),
        "mfu": _mfu(ips * flops_per_image)
        if headline and flops_per_image else None,
        "peak_tflops": _peak_tflops(),
        "model": model,
        "loss": float(np.asarray(loss).reshape(-1)[0]),
    }
    if not headline:
        rec["image_hw"] = hw
        rec["class_dim"] = class_dim
    _emit(rec)


if __name__ == "__main__":
    main()
