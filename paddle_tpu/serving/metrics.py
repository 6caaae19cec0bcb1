"""Serving metrics: QPS, latency percentiles, batch occupancy, queue
depth, rejection/deadline counters.

One `ServingMetrics` per `InferenceEngine`. Writers are the request
threads (submit/reject) and the batcher worker (dispatch); readers are
`/metrics` (Prometheus text) and `/v1/models` (JSON) — all
under one lock, all O(window) worst case.

The batcher worker also threads every dispatch into
`profiler.record_run` (tag `serving/<model> b<batch>[xs<seq>]`) when the
profiler is active, so `profile_report()` shows training and serving
entries side by side in the same Event table.
"""
import collections
import threading
import time

__all__ = ["ServingMetrics", "DecodeMetrics"]


def _percentile(sorted_vals, q):
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class ServingMetrics(object):
    """Thread-safe counters + a bounded latency window.

    Occupancy bookkeeping distinguishes REQUESTS from ROWS: a batch of 5
    one-row requests padded into an 8-row bucket counts occupancy 5
    (requests/batch — the coalescing win) and row utilization 5/8 (how
    much of the compiled bucket carried real data).
    """

    def __init__(self, latency_window=2048):
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self.requests_total = 0        # accepted into the queue
        self.responses_total = 0       # scattered back successfully
        self.rejected_queue_full = 0   # fast backpressure rejections
        self.deadline_expired = 0      # dropped before batching
        self.errors_total = 0          # dispatch/scatter failures
        self.batches_total = 0         # device dispatches
        self.batch_requests_total = 0  # requests across all batches
        self.batch_rows_total = 0      # real rows across all batches
        self.bucket_rows_total = 0     # padded bucket rows dispatched
        self.warmup_compiles = 0       # buckets traced at startup
        self._latencies = collections.deque(maxlen=latency_window)
        self._queue_depth_fn = None    # live gauge, set by the batcher

    def bind_queue_depth(self, fn):
        self._queue_depth_fn = fn

    def on_submit(self):
        with self._lock:
            self.requests_total += 1

    def on_queue_full(self):
        with self._lock:
            self.rejected_queue_full += 1

    def on_deadline_expired(self, n=1):
        with self._lock:
            self.deadline_expired += n

    def on_error(self, n=1):
        with self._lock:
            self.errors_total += n

    def on_warmup_compile(self, n=1):
        with self._lock:
            self.warmup_compiles += n

    def on_batch(self, num_requests, num_rows, bucket_rows, latencies_s):
        """One dispatch scattered: latencies_s are per-request
        submit->scatter times (dispatch enqueued; D2H still pending —
        that cost is the caller's, paid per-request on materialize)."""
        with self._lock:
            self.batches_total += 1
            self.batch_requests_total += num_requests
            self.batch_rows_total += num_rows
            self.bucket_rows_total += bucket_rows
            self.responses_total += num_requests
            self._latencies.extend(latencies_s)

    def queue_depth(self):
        fn = self._queue_depth_fn
        return fn() if fn is not None else 0

    def snapshot(self):
        with self._lock:
            lat = sorted(self._latencies)
            elapsed = max(time.monotonic() - self._t0, 1e-9)
            batches = max(self.batches_total, 1)
            return {
                "uptime_s": round(elapsed, 3),
                "requests_total": self.requests_total,
                "responses_total": self.responses_total,
                "rejected_queue_full": self.rejected_queue_full,
                "deadline_expired": self.deadline_expired,
                "errors_total": self.errors_total,
                "batches_total": self.batches_total,
                "qps": round(self.responses_total / elapsed, 3),
                "mean_batch_occupancy":
                    round(self.batch_requests_total / batches, 3),
                "row_utilization":
                    round(self.batch_rows_total /
                          max(self.bucket_rows_total, 1), 4),
                "warmup_compiles": self.warmup_compiles,
                "queue_depth": self.queue_depth(),
                "latency_ms": {
                    "p50": round(_percentile(lat, 0.50) * 1e3, 3),
                    "p95": round(_percentile(lat, 0.95) * 1e3, 3),
                    "p99": round(_percentile(lat, 0.99) * 1e3, 3),
                    "window": len(lat),
                },
            }

    def render_prometheus(self, model="default"):
        """Prometheus text exposition for one model (the /metrics
        contract). Multi-model servers must use `render_prometheus_all`
        — concatenating per-model expositions would repeat each family's
        HELP/TYPE header, which Prometheus rejects as a whole scrape."""
        return render_prometheus_all({model: self})


class DecodeMetrics(object):
    """Counters for one decode step-loop (serving.DecodeEngine).

    The unit of work is the ITERATION (one fixed-shape step over all
    slots), not the request: occupancy is slots-carrying-streams per
    iteration (the continuous-batching win — admits refill slots
    mid-flight, so mean occupancy > 1 under concurrent load), the
    latency window holds inter-token gaps (wall time between a stream's
    consecutive tokens — the latency a generative client feels), and
    tokens/s is measured over a recent bounded window so the gauge
    tracks current load, not lifetime average.  Readers: the
    observability-registry decoder collector (`/metrics`) and
    `pool_state()`."""

    def __init__(self, latency_window=4096):
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self.streams_admitted = 0      # admitted into a slot
        self.streams_completed = 0     # retired after finishing
        self.streams_failed = 0        # retired with an error/deadline
        self.rejected_queue_full = 0   # pending-queue backpressure
        self.deadline_expired = 0      # per-stream deadline retires
        self.tokens_total = 0          # tokens delivered to streams
        self.iterations_total = 0      # step-loop dispatches
        self.occupied_rows_total = 0   # sum of occupied slots per iter
        self._inter_token = collections.deque(maxlen=latency_window)
        self._rate = collections.deque(maxlen=latency_window)  # (t, n)

    def on_admit(self, n=1):
        with self._lock:
            self.streams_admitted += n

    def on_queue_full(self):
        with self._lock:
            self.rejected_queue_full += 1

    def on_deadline_expired(self, n=1):
        with self._lock:
            self.deadline_expired += n
            self.streams_failed += n

    def on_stream_failed(self, n=1):
        with self._lock:
            self.streams_failed += n

    def on_stream_completed(self, n=1):
        with self._lock:
            self.streams_completed += n

    def on_iteration(self, occupied, tokens, inter_token_gaps_s=()):
        """One decode step delivered: `occupied` slots carried live
        streams, `tokens` tokens went out, `inter_token_gaps_s` are the
        per-stream gaps since each stream's previous token."""
        with self._lock:
            self.iterations_total += 1
            self.occupied_rows_total += occupied
            self.tokens_total += tokens
            self._inter_token.extend(inter_token_gaps_s)
            self._rate.append((time.monotonic(), tokens))

    def snapshot(self):
        with self._lock:
            gaps = sorted(self._inter_token)
            elapsed = max(time.monotonic() - self._t0, 1e-9)
            if len(self._rate) >= 2:
                span = max(self._rate[-1][0] - self._rate[0][0], 1e-9)
                recent = sum(n for _, n in self._rate) / span
            else:
                recent = self.tokens_total / elapsed
            iters = max(self.iterations_total, 1)
            return {
                "uptime_s": round(elapsed, 3),
                "streams_admitted": self.streams_admitted,
                "streams_completed": self.streams_completed,
                "streams_failed": self.streams_failed,
                "rejected_queue_full": self.rejected_queue_full,
                "deadline_expired": self.deadline_expired,
                "tokens_total": self.tokens_total,
                "iterations": self.iterations_total,
                "tokens_per_s": round(recent, 3),
                "mean_slot_occupancy":
                    round(self.occupied_rows_total / iters, 3),
                "inter_token_p50_ms":
                    round(_percentile(gaps, 0.50) * 1e3, 3),
                "inter_token_p99_ms":
                    round(_percentile(gaps, 0.99) * 1e3, 3),
                "inter_token_window": len(gaps),
            }


# (family, type, help, snapshot key) — one HELP/TYPE per family in the
# exposition, one labeled sample line per model
_FAMILIES = [
    ("requests_total", "counter", "accepted requests", "requests_total"),
    ("responses_total", "counter", "completed requests",
     "responses_total"),
    ("rejected_queue_full_total", "counter",
     "fast rejections due to a full queue (backpressure)",
     "rejected_queue_full"),
    ("deadline_expired_total", "counter",
     "requests dropped before batching: deadline passed",
     "deadline_expired"),
    ("errors_total", "counter", "dispatch failures", "errors_total"),
    ("batches_total", "counter", "device dispatches", "batches_total"),
    ("qps", "gauge", "responses per second since start", "qps"),
    ("mean_batch_occupancy", "gauge",
     "mean requests coalesced per dispatch", "mean_batch_occupancy"),
    ("row_utilization", "gauge", "real rows / padded bucket rows",
     "row_utilization"),
    ("queue_depth", "gauge", "requests waiting right now", "queue_depth"),
]


def _escape_label(value):
    """Prometheus exposition label escaping: backslash, double quote,
    newline — an unescaped quote in a model name would invalidate the
    whole scrape for every model on the server."""
    return str(value).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


# pool-level families, read from PoolMetrics.snapshot() (one labeled
# sample per pool; HELP/TYPE once, like everything else)
_POOL_FAMILIES = [
    ("pool_requests_total", "counter", "requests accepted by the pool",
     "requests_total"),
    ("pool_responses_total", "counter", "pool requests completed",
     "responses_total"),
    ("pool_errors_total", "counter",
     "client-visible pool failures (after failover exhausted)",
     "errors_total"),
    ("pool_retries_total", "counter",
     "failover resubmissions onto a different replica", "retries_total"),
    ("pool_hedges_total", "counter", "tail-hedge duplicate attempts",
     "hedges_total"),
    ("pool_rejected_total", "counter",
     "admission/backpressure rejections (429s)", "rejected_queue_full"),
    ("pool_attempt_timeouts_total", "counter",
     "per-attempt timeouts (wedged-replica detections)",
     "attempt_timeouts_total"),
    ("pool_poisoned_results_total", "counter",
     "non-finite replica outputs caught before the client",
     "poisoned_results_total"),
    ("pool_reloads_total", "counter", "zero-downtime weight reloads",
     "reloads_total"),
    ("pool_ejections_total", "counter", "circuit-breaker ejections",
     "ejections_total"),
]


def render_prometheus_all(named_metrics, pools=None):
    """One valid exposition covering plain engines
    ({model: ServingMetrics}) and replica pools ({model: ReplicaPool}).
    A pool's replicas each emit one sample per serving family labeled
    {model, replica}; pool-level families (replica state gauge, retry /
    hedge / admission / reload counters, client latency) follow —
    HELP/TYPE still exactly once per family across everything."""
    entries = []    # (label_str, snapshot) for the per-engine families
    for name, m in sorted(named_metrics.items()):
        entries.append(('model="%s"' % _escape_label(name), m.snapshot()))
    pools = dict(pools or {})
    for name, pool in sorted(pools.items()):
        for ridx, m in sorted(pool.replica_metrics().items()):
            entries.append(('model="%s",replica="%s"'
                            % (_escape_label(name), ridx), m.snapshot()))
    lines = []
    for family, mtype, help_text, key in _FAMILIES:
        lines.append("# HELP ptpu_serving_%s %s" % (family, help_text))
        lines.append("# TYPE ptpu_serving_%s %s" % (family, mtype))
        for labels, s in entries:
            lines.append('ptpu_serving_%s{%s} %s' % (family, labels,
                                                     s[key]))
    lines.append("# HELP ptpu_serving_latency_ms request latency "
                 "percentiles (submit -> scatter)")
    lines.append("# TYPE ptpu_serving_latency_ms gauge")
    for labels, s in entries:
        for q in ("p50", "p95", "p99"):
            lines.append('ptpu_serving_latency_ms{%s,quantile="%s"} %s'
                         % (labels, q, s["latency_ms"][q]))
    if pools:
        from .pool import _STATE_GAUGE
        lines.append("# HELP ptpu_serving_replica_state replica health "
                     "(0=healthy, 1=degraded, 2=ejected; +4 when dead)")
        lines.append("# TYPE ptpu_serving_replica_state gauge")
        pool_replica_states = {name: pool.pool_state()["replicas"]
                               for name, pool in sorted(pools.items())}
        for name, reps in pool_replica_states.items():
            model = _escape_label(name)
            for r in reps:
                val = _STATE_GAUGE[r["state"]] + (4 if r["dead"] else 0)
                lines.append('ptpu_serving_replica_state{model="%s",'
                             'replica="%s"} %d' % (model, r["replica"],
                                                   val))
        # device ownership: one sample per (replica, device) — a
        # tensor-parallel replica spans M devices, so operators can see
        # exactly which chips each replica holds (ARCHITECTURE.md §23)
        lines.append("# HELP ptpu_serving_replica_device 1 for each "
                     "device in a replica's span (tensor-parallel "
                     "replicas span tp devices)")
        lines.append("# TYPE ptpu_serving_replica_device gauge")
        for name, reps in pool_replica_states.items():
            model = _escape_label(name)
            for r in reps:
                for dev in r.get("devices", ()):
                    lines.append(
                        'ptpu_serving_replica_device{model="%s",'
                        'replica="%s",device="%s"} 1'
                        % (model, r["replica"], _escape_label(dev)))
        psnaps = {name: pool.metrics.snapshot()
                  for name, pool in sorted(pools.items())}
        for family, mtype, help_text, key in _POOL_FAMILIES:
            lines.append("# HELP ptpu_serving_%s %s" % (family, help_text))
            lines.append("# TYPE ptpu_serving_%s %s" % (family, mtype))
            for name, s in psnaps.items():
                lines.append('ptpu_serving_%s{model="%s"} %s'
                             % (family, _escape_label(name), s[key]))
        lines.append("# HELP ptpu_serving_pool_latency_ms client-observed "
                     "pool latency percentiles (submit -> result, "
                     "failovers included)")
        lines.append("# TYPE ptpu_serving_pool_latency_ms gauge")
        for name, s in psnaps.items():
            for q in ("p50", "p95", "p99"):
                lines.append('ptpu_serving_pool_latency_ms{model="%s",'
                             'quantile="%s"} %s'
                             % (_escape_label(name), q,
                                s["latency_ms"][q]))
    return "\n".join(lines) + "\n"
