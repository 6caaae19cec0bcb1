"""Observability layer (ARCHITECTURE.md §24): flight recorder + trace
spans + the unified metrics registry.

The contract under test:
  * tracing is ALWAYS-ON and non-interfering — a concurrent pipelined
    serving run and a steps=K prefetch training run stay BIT-EXACT with
    the recorder on (vs run_direct / vs recorder-off), the
    `sync_stats()["on_dispatch_path"] == 0` discipline holds, and the
    ring stays bounded under sustained load;
  * the exported Chrome trace RECONSTRUCTS the pipeline: per-request
    queue -> formation -> dispatch -> window completion -> materialize
    spans linked by trace id, per-step host_io/dispatch children, and
    window-occupancy spans that never exceed the pipeline depth;
  * diagnostic bundles embed the recorder dump and `ptpu_doctor trace`
    renders it — a hang bundle shows the wedged step's OPEN spans;
  * the registry fronts the existing surfaces (profiler sync/cache
    counters, windows, batcher queues, supervisor events, checkpoint
    save latency, cluster heartbeats) through one Prometheus rendering,
    served standalone by `serve_metrics` for trainers and appended to
    the serving server's /metrics.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core.readers import EOFException
from paddle_tpu.observability import registry as obsreg
from paddle_tpu.observability import trace

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test gets its own bounded ring; always-on is restored."""
    trace.set_enabled(True)
    trace.configure(capacity=4096)
    yield
    trace.set_enabled(True)
    trace.configure(capacity=4096)


# ---------------------------------------------------------------------------
# trace core
# ---------------------------------------------------------------------------

def test_span_nesting_dump_and_chrome_export():
    tr = trace.new_trace()
    with trace.span("outer", cat="t", trace=tr, k=1) as sp:
        with sp.child("inner"):
            pass
        trace.instant("mark", trace=tr, why="x")
    leak = trace.span("leaky", cat="t", trace=trace.new_trace())
    d = trace.dump()
    names = [e["name"] for e in d["events"]]
    assert names == ["inner", "mark", "outer"]  # children end first
    inner = d["events"][0]
    outer = d["events"][2]
    assert inner["trace"] == outer["trace"] == tr
    assert inner["parent"] == outer["span"]
    assert outer["args"]["k"] == 1
    # the un-ended span is OPEN, with its age
    assert [o["name"] for o in d["open"]] == ["leaky"]
    assert d["open"][0]["age_s"] >= 0
    ct = trace.export_chrome_trace(data=d)
    evs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"inner", "outer", "leaky"}
    leaky = [e for e in evs if e["name"] == "leaky"][0]
    assert leaky["args"]["open"] is True
    insts = [e for e in ct["traceEvents"] if e["ph"] == "i"]
    assert insts and insts[0]["name"] == "mark"
    # thread-name metadata present for viewers
    assert any(e["ph"] == "M" for e in ct["traceEvents"])
    leak.end()


def test_ring_bounded_under_sustained_load():
    trace.configure(capacity=256)
    for i in range(5000):
        trace.instant("tick", i=i)
    d = trace.dump()
    assert len(d["events"]) <= 256
    assert d["dropped"] >= 5000 - 256
    # newest events survive, oldest fell off
    assert d["events"][-1]["args"]["i"] == 4999


def test_disabled_recorder_is_noop():
    trace.set_enabled(False)
    sp = trace.span("x", trace=trace.new_trace())
    assert sp.child("y") is sp
    sp.end()
    trace.instant("z")
    # a fresh ring is on or off as the one before it: set_enabled is the
    # one switch (configure(enabled=) went with PR 51)
    assert trace.configure(capacity=64).enabled is False
    assert trace.span("x2") is sp
    trace.set_enabled(True)
    assert trace.dump()["events"] == []
    with pytest.raises(TypeError):
        trace.configure(capacity=64, enabled=True)


def test_end_open_closes_a_trace_not_others():
    t1, t2 = trace.new_trace(), trace.new_trace()
    a = trace.span("a", trace=t1)
    b = trace.span("b", trace=t2)
    trace.end_open(t1, error="Boom")
    d = trace.dump()
    assert [e["name"] for e in d["events"]] == ["a"]
    assert d["events"][0]["args"]["error"] == "Boom"
    assert [o["name"] for o in d["open"]] == ["b"]
    b.end()
    assert a._ended


def test_window_completion_error_reaches_on_complete(monkeypatch):
    """A device-side failure at the window's completion wait must reach
    on_complete as error= — the execute span of a FAILED batch must not
    render as a clean completion in the postmortem timeline."""
    import jax
    from paddle_tpu.core.dispatch import InflightWindow

    real = jax.block_until_ready

    class _Poisoned(object):
        pass

    def fake(arrays):
        if any(isinstance(a, _Poisoned) for a in arrays):
            raise RuntimeError("device exploded")
        return real(arrays)

    monkeypatch.setattr(jax, "block_until_ready", fake)
    got = {}
    done = threading.Event()

    def on_complete(**kw):
        got.update(kw)
        done.set()

    w = InflightWindow(1, tag="err-test")
    try:
        assert w.acquire(timeout=5)
        w.track([_Poisoned()], on_complete=on_complete)
        assert done.wait(5)
        assert got == {"error": "RuntimeError"}
        # the slot came back regardless — serving survives the batch
        assert w.acquire(timeout=5)
        w.release()
    finally:
        w.close(5)


def test_render_timeline_lists_open_spans():
    with trace.span("done", trace=trace.new_trace()):
        pass
    sp = trace.span("wedged/here", trace=trace.new_trace())
    text = trace.render_timeline(trace.dump())
    assert "done" in text
    assert "OPEN" in text and "wedged/here" in text
    sp.end()


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_render():
    reg = obsreg.MetricsRegistry()
    c = reg.counter("ptpu_test_events_total", "events")
    c.inc(**{"class": "numeric", "action": "skip"})
    c.inc(2, **{"class": "numeric", "action": "skip"})
    g = reg.gauge("ptpu_test_depth", "depth")
    g.set(3, window='we"ird\n')
    h = reg.histogram("ptpu_test_latency_seconds", "lat",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render_prometheus()
    assert '# TYPE ptpu_test_events_total counter' in text
    assert 'ptpu_test_events_total{action="skip",class="numeric"} 3' \
        in text
    # label escaping: quote and newline survive as escapes
    assert 'window="we\\"ird\\n"' in text
    assert 'ptpu_test_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'ptpu_test_latency_seconds_bucket{le="1.0"} 2' in text
    assert 'ptpu_test_latency_seconds_bucket{le="+Inf"} 3' in text
    assert 'ptpu_test_latency_seconds_count 3' in text
    # HELP/TYPE exactly once per family
    assert text.count("# TYPE ptpu_test_events_total") == 1
    # type conflicts are programming errors, not silent corruption
    with pytest.raises(ValueError):
        reg.gauge("ptpu_test_events_total")
    # snapshot mirrors the same data machine-readably
    snap = reg.snapshot()
    assert snap["ptpu_test_events_total"]["samples"] == [
        [{"action": "skip", "class": "numeric"}, 3.0]]


def test_registry_collector_and_broken_collector_isolated():
    reg = obsreg.MetricsRegistry()

    @reg.register_collector
    def _ok():
        return [("ptpu_test_coll", "gauge", "x", [({"a": "b"}, 7)])]

    @reg.register_collector
    def _broken():
        raise RuntimeError("unreadable surface")

    text = reg.render_prometheus()
    assert 'ptpu_test_coll{a="b"} 7' in text  # broken one skipped


def test_default_registry_fronts_profiler_and_windows():
    from paddle_tpu.core.dispatch import InflightWindow
    profiler.reset_profiler()
    profiler.note_sync("test/obs_tag")
    w = InflightWindow(2, tag="obs-test")
    try:
        text = obsreg.REGISTRY.render_prometheus()
        assert 'ptpu_host_syncs_total{tag="test/obs_tag"} 1' in text
        assert "ptpu_window_depth" in text and "obs-test" in text
        assert "ptpu_trace_ring_events" in text
    finally:
        w.close(1.0)
        profiler.reset_profiler()


def test_profiler_snapshot_and_json_report():
    profiler.reset_profiler()
    profiler.record_run("obs_entry", 0.5)
    profiler.record_run("obs_entry", 0.25, compiled=True)
    profiler.note_sync("obs/sync")
    snap = profiler.snapshot()
    assert set(snap) == {"entries", "sync_stats", "cache_stats",
                         "device_ops"}
    assert snap["device_ops"] is None   # no trace was read since the reset
    e = snap["entries"]["obs_entry"]
    assert e["calls"] == 2 and e["runs"] == 1 and e["compiles"] == 1
    assert e["total"] == 0.5 and e["min"] == 0.5 and e["ave"] == 0.5
    assert snap["sync_stats"]["by_tag"]["obs/sync"] == 1
    assert snap["cache_stats"]["compiles"] == 1
    # profile_report(json=True) IS the snapshot, and it JSON-serializes
    assert profiler.profile_report(json=True) == snap
    json.dumps(snap)
    profiler.reset_profiler()


def test_metrics_http_endpoint_and_textfile(tmp_path):
    reg = obsreg.MetricsRegistry()
    reg.counter("ptpu_test_served_total", "x").inc(5)
    srv = obsreg.serve_metrics(port=0, registry=reg)
    try:
        url = "http://127.0.0.1:%d" % srv.port
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=10).read().decode()
        assert "ptpu_test_served_total 5" in body
        hz = urllib.request.urlopen(url + "/healthz", timeout=10)
        assert hz.status == 200
    finally:
        srv.close()
    path = obsreg.write_textfile(str(tmp_path / "metrics.prom"),
                                 registry=reg)
    with open(path) as f:
        assert "ptpu_test_served_total 5" in f.read()


# ---------------------------------------------------------------------------
# serving: the acceptance leg — trace reconstructs, results bit-exact
# ---------------------------------------------------------------------------

def _save_mlp(tmp_path, feat=8, classes=6, seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=classes, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = os.path.join(str(tmp_path), "mlp")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    return model_dir, feat


def test_pipelined_serving_trace_reconstructs_and_stays_bit_exact(
        tmp_path):
    """THE serving acceptance leg: 24 concurrent mixed-row requests
    through the depth-2 pipeline with the recorder always-on. Results
    bit-exact vs run_direct at each recorded bucket; zero dispatch-path
    syncs; and the exported trace reconstructs every request's
    queue -> formation -> dispatch -> window completion -> materialize
    timeline, with window occupancy never exceeding the depth."""
    from paddle_tpu import serving
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="obs", max_batch_size=8,
        batch_buckets=[1, 2, 4, 8], max_queue_delay_ms=4,
        pipeline_depth=2)
    try:
        profiler.reset_profiler()
        trace.clear()
        rng = np.random.RandomState(0)
        feeds = [rng.rand(1 + (i % 4), feat).astype("float32")
                 for i in range(24)]
        results, lock = {}, threading.Lock()

        def client(i):
            fut = engine.submit({"x": feeds[i]})
            out = fut.result(60).numpy()
            with lock:
                results[i] = (out, fut.bucket)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(feeds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        engine.drain(30)
        deadline = time.monotonic() + 10  # completion thread closes the
        while time.monotonic() < deadline:  # execute spans off-thread
            if not trace.dump()["open"]:
                break
            time.sleep(0.02)

        # 1) tracing never added a dispatch-path host sync
        assert profiler.sync_stats()["on_dispatch_path"] == 0

        # 2) per-request timeline reconstructs from the dump
        d = trace.dump()
        by_name = {}
        for ev in d["events"]:
            by_name.setdefault(ev["name"], []).append(ev)
        req_traces = {e["trace"] for e in by_name["serving/request"]}
        assert len(req_traces) == 24
        queue_traces = {e["trace"] for e in by_name["serving/queue"]}
        assert req_traces <= queue_traces

        def batch_traces(name):
            out = set()
            for ev in by_name.get(name, ()):
                out.update(ev["args"]["traces"])
            return out

        for stage in ("serving/formed_wait", "serving/dispatch",
                      "serving/pad_h2d", "serving/enqueue",
                      "serving/execute"):
            assert req_traces <= batch_traces(stage), stage
        mat_traces = {e["trace"] for e in by_name["serving/materialize"]}
        assert req_traces <= mat_traces

        # 3) window occupancy: overlapping execute spans <= depth
        execs = [(e["ts"], e["ts"] + e["dur"])
                 for e in by_name["serving/execute"]]
        assert execs
        for s0, e0 in execs:
            overlap = sum(1 for s1, e1 in execs if s1 < e0 and e1 > s0)
            assert overlap <= 2, "window occupancy exceeded depth"

        # 3b) cross-layer correlation: each batch's trace (scoped
        # ambient around the dispatch) is inherited by the engine's
        # pad/enqueue spans AND the Executor's exec/step span — the
        # device enqueue is attributable to its batch, not an
        # uncorrelated train-looking trace
        batch_traces = {e["trace"] for e in by_name["serving/execute"]}
        for stage in ("serving/pad_h2d", "serving/enqueue",
                      "exec/step"):
            covered = {e["trace"] for e in by_name.get(stage, ())}
            assert batch_traces <= covered, stage

        # 4) the chrome export carries the same spans
        ct = trace.export_chrome_trace(data=d)
        names = {e["name"] for e in ct["traceEvents"]}
        assert "serving/request" in names and "serving/execute" in names

        # 5) bit-exactness vs run_direct at each recorded bucket
        for i, (out, bucket) in results.items():
            ref, _ = engine.run_direct({"x": feeds[i]},
                                       batch_bucket=bucket[0],
                                       seq_bucket=bucket[1])
            for name in ref:
                np.testing.assert_array_equal(out[name], ref[name],
                                              err_msg="req %d" % i)
    finally:
        profiler.reset_profiler()
        engine.close()


# ---------------------------------------------------------------------------
# training: the acceptance leg — steps=K prefetch, recorder on vs off
# ---------------------------------------------------------------------------

def _make_recordio(tmp_path, n=12, batch=4, feat=6, seed=0):
    rng = np.random.RandomState(seed)
    data = [(rng.rand(batch, feat).astype("float32"),
             rng.rand(batch, 1).astype("float32")) for _ in range(n)]

    def reader():
        for rec in data:
            yield rec

    path = str(tmp_path / "obs.recordio")
    fluid.recordio_writer.convert_reader_to_recordio_file(path, reader)
    return path


def _train_to_eof(path, steps, feat=6):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        r = fluid.layers.open_recordio_file(
            path, shapes=[[-1, feat], [-1, 1]],
            dtypes=["float32", "float32"], lod_levels=[0, 0])
        x, y = fluid.layers.read_file(r)
        h = fluid.layers.fc(input=x, size=16, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.3)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    outs = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        while True:
            try:
                o = exe.run(main, fetch_list=[loss], steps=steps,
                            prefetch=True)
                outs.append(np.asarray(o[0]))
            except EOFException:
                break
        state = {n: np.asarray(scope.get(n)) for n in scope.names()
                 if hasattr(scope.get(n), "dtype")}
    return outs, state


def test_training_prefetch_steps_k_trace_bit_exact_vs_recorder_off(
        tmp_path):
    """THE training acceptance leg: a steps=K prefetch run with the
    recorder always-on is BIT-EXACT (fetch stream, params, Adam
    moments, dropout cursor) vs the same run with the recorder off,
    keeps zero dispatch-path syncs, and its exported trace reconstructs
    the per-step timeline — one exec/step trace per dispatch with
    host_io + dispatch children, plus the prefetch staging spans
    overlapping on the background thread."""
    path = _make_recordio(tmp_path, n=12)
    profiler.reset_profiler()
    trace.configure(capacity=4096)
    trace.clear()
    o_on, s_on = _train_to_eof(path, steps=3)
    d = trace.dump()
    assert profiler.sync_stats()["on_dispatch_path"] == 0
    profiler.reset_profiler()

    trace.set_enabled(False)
    o_off, s_off = _train_to_eof(path, steps=3)
    trace.set_enabled(True)

    # bit-exact vs recorder-off
    assert len(o_on) == len(o_off) >= 2
    for a, b in zip(o_on, o_off):
        np.testing.assert_array_equal(a, b)
    assert set(s_on) == set(s_off)
    for k in s_on:
        np.testing.assert_array_equal(s_on[k], s_off[k])

    # per-step timeline reconstructs: one clean steps=3 trace per
    # successful dispatch (the startup run is steps=1; the final EOF
    # attempt ends its step span with error=EOFException — filtered)
    steps_evs = [e for e in d["events"] if e["name"] == "exec/step"]
    full = [e for e in steps_evs if e["args"].get("steps") == 3
            and "error" not in (e["args"] or {})]
    assert len(full) == len(o_on)
    eof = [e for e in steps_evs
           if (e["args"] or {}).get("error") == "EOFException"]
    assert len(eof) == 1  # end-of-data is visible in the timeline too
    for ev in full:
        tr = ev["trace"]
        kids = {e["name"] for e in d["events"]
                if e["trace"] == tr and e["parent"] is not None}
        assert "exec/host_io" in kids and "exec/dispatch" in kids
    # prefetch staging ran on its own thread and was recorded
    stages = [e for e in d["events"]
              if e["name"] == "exec/prefetch_stage"]
    assert stages and all("prefetch" in e["tid"] for e in stages)


# ---------------------------------------------------------------------------
# checkpoint, supervisor, fleet surfaces
# ---------------------------------------------------------------------------

def test_checkpoint_save_records_span_and_latency_histogram(tmp_path):
    from paddle_tpu.checkpoint import CheckpointManager
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(x=p)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    hist = obsreg.REGISTRY.histogram("ptpu_checkpoint_save_seconds")
    before = hist.count()
    with fluid.scope_guard(scope):
        exe.run(startup)
        trace.clear()
        mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
        mgr.save(1, program=main, scope=scope)
        mgr.close()
    names = [e["name"] for e in trace.dump()["events"]]
    assert "checkpoint/capture" in names
    assert "checkpoint/write" in names
    assert hist.count() == before + 1
    text = obsreg.REGISTRY.render_prometheus()
    assert "ptpu_checkpoint_save_seconds_bucket" in text
    assert 'ptpu_checkpoint_saves_total{status="ok"}' in text


def test_supervisor_events_land_in_counter_and_recorder():
    from paddle_tpu import resilience as rz
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(x=p)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ctr = obsreg.REGISTRY.counter("ptpu_supervisor_events_total")
    with fluid.scope_guard(scope):
        exe.run(startup)
        sup = rz.Supervisor(exe, main, scope=scope, policies={
            "dispatch": [rz.retry(times=1), rz.abort()]})
        try:
            before = ctr.value(**{"class": "dispatch",
                                  "action": "retry"})
            trace.clear()
            with rz.FaultPlan(["dispatch_exc@0"]):
                out = sup.run_step(
                    feed={"x": np.ones((2, 2), "float32")},
                    fetch_list=[loss])
            assert out is not None  # retried clean
            assert ctr.value(**{"class": "dispatch",
                                "action": "retry"}) == before + 1
            names = [e["name"] for e in trace.dump()["events"]]
            assert "resilience/dispatch:retry" in names
        finally:
            sup.close()


def test_cluster_heartbeat_gauges_and_status_cli(tmp_path):
    from paddle_tpu.resilience.heartbeat import HeartbeatWriter
    from paddle_tpu.resilience.cluster import write_plan
    cdir = str(tmp_path / "cluster")
    for wid, step in (("w0", 10), ("w1", 7)):
        hb = HeartbeatWriter(cdir, wid)
        hb.update(status="ok", step=step, gen=3, gen_acked=3)
    write_plan(cdir, {"gen": 3, "phase": "run", "num_workers": 2,
                      "world": {"w0": {}, "w1": {}}})

    # registry collector: steps-behind derived from the front-runner;
    # every family carries the cluster label (two watched clusters with
    # overlapping worker ids must not collide into duplicate series)
    reg = obsreg.MetricsRegistry()
    obsreg.watch_cluster(cdir, registry=reg)
    text = reg.render_prometheus()
    lbl = 'cluster="cluster",worker="w%d"'
    assert 'ptpu_cluster_worker_step{%s} 10' % (lbl % 0) in text
    assert 'ptpu_cluster_worker_steps_behind{%s} 3' % (lbl % 1) in text
    assert 'ptpu_cluster_worker_generation{%s} 3' % (lbl % 1) in text
    assert 'ptpu_cluster_worker_beat_age_seconds{%s}' % (lbl % 0) in text
    assert 'ptpu_cluster_worker_alive{%s} 1' % (lbl % 0) in text

    # a DEPARTED worker's stale high step must not pin the front-runner
    # (steps-behind would read permanent false lag on healthy workers)
    HeartbeatWriter(cdir, "w9").update(status="left", step=100)
    text = reg.render_prometheus()
    assert ('ptpu_cluster_worker_steps_behind{cluster="cluster",'
            'worker="w1"} 3') in text

    # a worker that never reported a step has UNKNOWN lag: absent
    # sample, not a fake caught-up 0 a lag alert would sleep through
    HeartbeatWriter(cdir, "w2").update(status="joining")
    text = reg.render_prometheus()
    assert ('ptpu_cluster_worker_steps_behind{cluster="cluster",'
            'worker="w2"}') not in text
    assert 'ptpu_cluster_worker_step{cluster="cluster",worker="w2"} -1' \
        in text

    # unwatch drops the collector (teardown for cycling cluster dirs)
    obsreg.unwatch_cluster(cdir, registry=reg)
    assert "ptpu_cluster_worker_step" not in reg.render_prometheus()

    # two DIFFERENT dirs sharing a basename disambiguate their cluster
    # label (duplicate series would invalidate the whole scrape)
    d1 = str(tmp_path / "jobA" / "el")
    d2 = str(tmp_path / "jobB" / "el")
    HeartbeatWriter(d1, "w0").update(status="ok", step=1)
    HeartbeatWriter(d2, "w0").update(status="ok", step=2)
    reg2 = obsreg.MetricsRegistry()
    obsreg.watch_cluster(d1, registry=reg2)
    obsreg.watch_cluster(d2, registry=reg2)
    text = reg2.render_prometheus()
    lines = [l for l in text.splitlines()
             if l.startswith("ptpu_cluster_worker_step{")]
    assert len(lines) == 2 and len(set(lines)) == 2
    assert len({l.split("}")[0] for l in lines}) == 2  # distinct labels

    # the CLI fleet table over the same heartbeats
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptpu_elastic.py"),
         "status", "--cluster-dir", cdir, "--json"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["plan"]["gen"] == 3 and doc["plan"]["phase"] == "run"
    workers = {w["worker"]: w for w in doc["workers"]}
    assert workers["w0"]["step"] == 10
    assert workers["w1"]["steps_behind"] == 3
    assert workers["w0"]["gen_acked"] == 3
    # human table too
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptpu_elastic.py"),
         "status", "--cluster-dir", cdir],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert out2.returncode == 0
    assert "WORKER" in out2.stdout and "w1" in out2.stdout


def test_serving_server_metrics_includes_registry(tmp_path):
    """/metrics on the serving HTTP server = serving families + the
    runtime registry, one valid exposition (HELP/TYPE once each)."""
    from paddle_tpu import serving
    from paddle_tpu.serving.server import ModelServer
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(model_dir, name="m",
                                     max_batch_size=4,
                                     pipeline_depth=2)
    server = ModelServer(engine, port=0).start()
    try:
        engine.infer({"x": np.ones((1, feat), "float32")})
        body = urllib.request.urlopen(
            "http://%s/metrics" % server.address,
            timeout=10).read().decode()
        assert "ptpu_serving_requests_total" in body
        assert "ptpu_window_depth" in body        # registry families
        assert "ptpu_host_syncs_total" in body
        assert "ptpu_trace_ring_events" in body
        for line in body.splitlines():
            if line.startswith("# TYPE"):
                assert body.count(line + "\n") <= 1 or \
                    body.rstrip().endswith(line), line
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# the hang postmortem: bundle embeds the dump, doctor renders it
# ---------------------------------------------------------------------------

def test_watchdog_bundle_embeds_open_spans_and_doctor_renders(tmp_path):
    """THE postmortem acceptance leg: a real watchdog trip (slow_step
    past the deadline) leaves the wedged step's spans OPEN; the bundle
    embeds the recorder dump; `ptpu_doctor trace <bundle>` renders the
    timeline and flags the open spans."""
    from paddle_tpu import resilience as rz
    from paddle_tpu.resilience.watchdog import write_bundle
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(x=p)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 2), "float32")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])  # compiled
        trace.clear()
        with rz.FaultPlan(["slow_step@1:5.0"]) as plan:
            plan.set_step(1)
            with pytest.raises(rz.DispatchTimeoutError) as ei:
                exe.run(main, feed=feed, fetch_list=[loss], timeout=0.4)
            # the wedged worker's step span is OPEN right now — capture
            # the bundle exactly like the Supervisor's hang path does
            d_now = trace.dump()
            open_names = {o["name"] for o in d_now["open"]}
            assert "exec/step" in open_names
            bundle = write_bundle(str(tmp_path / "bundles"),
                                  "hang watchdog tripped",
                                  fault_class="hang", step=1,
                                  program=main, feed=feed, scope=scope,
                                  error=ei.value)
    with open(os.path.join(bundle, "bundle.json")) as f:
        meta = json.load(f)
    assert "trace" in meta
    assert any(o["name"] == "exec/step" for o in meta["trace"]["open"])

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptpu_doctor.py"),
         "trace", bundle, "--out", str(tmp_path / "chrome.json")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OPEN" in out.stdout and "exec/step" in out.stdout
    with open(str(tmp_path / "chrome.json")) as f:
        chrome = json.load(f)
    assert any(e.get("args", {}).get("open") for e in
               chrome["traceEvents"])
    # a bundle without a recorder dump degrades readably (exit 2)
    del meta["trace"]
    legacy = str(tmp_path / "legacy")
    os.makedirs(legacy)
    with open(os.path.join(legacy, "bundle.json"), "w") as f:
        json.dump(meta, f)
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptpu_doctor.py"),
         "trace", legacy],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert out2.returncode == 2
