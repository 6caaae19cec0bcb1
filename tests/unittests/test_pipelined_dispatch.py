"""Pipelined dispatch (ARCHITECTURE.md §22): continuous batching in
serving and host/device prefetch overlap in training.

The contract under test:
  * serving with pipeline_depth >= 2 returns results BIT-IDENTICAL to
    `run_direct` at the recorded bucket, under concurrent mixed-row
    clients, with deadline expiries and a hard engine kill mid-window —
    and drain/close semantics hold for both queues (request + formed);
  * Executor.run(prefetch=True) / ParallelExecutor.run(prefetch=True)
    produce bit-identical fetch streams and final state to the serial
    prepass, for feed-fed, reader-fed and steps=K runs;
  * staged pops ROLL BACK EXACTLY when anything other than the matching
    dispatch lands between prefetch and dispatch: an injected reader
    fault, a cluster fence (barrier hook raise), a checkpoint capture,
    or a signature change — the stream then replays bit-exactly;
  * no premature host syncs on the hot dispatch paths (profiler sync
    counter regression: `sync_stats()["on_dispatch_path"] == 0`).
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import executor as exe_mod
from paddle_tpu.core.dispatch import InflightWindow, rollback_all_staged
from paddle_tpu.core.readers import DoubleBufferReader, EOFException, \
    IteratorReader


# ---------------------------------------------------------------------------
# serving: pipelined bit-exactness, kills, deadlines, drain/close
# ---------------------------------------------------------------------------

def _save_mlp(tmp_path, feat=8, classes=6, seed=3):
    import os
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


def test_pipelined_serving_bit_exact_concurrent_mixed_rows(tmp_path):
    """24 concurrent mixed-row requests through the depth-2 pipeline,
    each bit-identical to run_direct at the bucket its future records;
    a sprinkle of already-expired deadlines lands mid-window and must
    404 cleanly without perturbing neighbours."""
    from paddle_tpu import serving
    from paddle_tpu.serving.batcher import DeadlineExceededError
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="pipe", max_batch_size=8,
        batch_buckets=[1, 2, 4, 8], max_queue_delay_ms=4,
        pipeline_depth=2)
    try:
        assert engine.pipeline_depth == 2
        assert engine._batcher._window is not None
        rng = np.random.RandomState(0)
        feeds = [rng.rand(1 + (i % 4), feat).astype("float32")
                 for i in range(24)]
        results, errors = {}, {}
        lock = threading.Lock()

        def client(i):
            try:
                # every 6th request carries an absurd deadline so some
                # expiries land between formation and dispatch
                dl = 0.01 if i % 6 == 5 else None
                fut = engine.submit({"x": feeds[i]}, deadline_ms=dl)
                out = fut.result(60).numpy()
                with lock:
                    results[i] = (out, fut.bucket)
            except Exception as e:  # noqa: BLE001 — judged below
                with lock:
                    errors[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(feeds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, e in errors.items():
            assert isinstance(e, DeadlineExceededError), (i, e)
        assert len(results) >= 16  # deadline victims only
        for i, (out, bucket) in results.items():
            ref, _ = engine.run_direct({"x": feeds[i]},
                                       batch_bucket=bucket[0],
                                       seq_bucket=bucket[1])
            for name in ref:
                np.testing.assert_array_equal(out[name], ref[name],
                                              err_msg="req %d" % i)
        # the window actually saw the traffic
        assert engine._batcher._window.stats()["completed"] >= 1
    finally:
        engine.close()


def test_pipelined_serving_kill_mid_window(tmp_path):
    """close(drain=False) while a burst is in flight: every future
    completes (result OR typed error), nothing hangs, and requests
    caught in the FORMED queue fail with ServingClosedError too."""
    from paddle_tpu import serving
    from paddle_tpu.serving.batcher import (ServingClosedError,
                                            ServingError)
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="kill", max_batch_size=4,
        batch_buckets=[1, 2, 4], max_queue_delay_ms=50,
        pipeline_depth=2, queue_capacity=512)
    rng = np.random.RandomState(1)
    futures = []
    for i in range(64):
        futures.append(engine.submit(
            {"x": rng.rand(1, feat).astype("float32")}))
    engine.close(drain=False)
    done = ok = 0
    for f in futures:
        try:
            f.result(30).numpy()
            ok += 1
        except ServingError:
            pass
        except TimeoutError:
            raise AssertionError("future hung across a hard close")
        done += 1
    assert done == len(futures)
    # with a 50ms coalescing window and an immediate kill, most of the
    # burst must have been failed-fast, not served
    assert ok < len(futures)


def test_pipelined_drain_and_close_complete_everything(tmp_path):
    """close(drain=True) after a burst: every single future resolves
    with a result (both queues + the in-flight window drained)."""
    from paddle_tpu import serving
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="drain", max_batch_size=4,
        batch_buckets=[1, 2, 4], max_queue_delay_ms=20,
        pipeline_depth=3, queue_capacity=512)
    rng = np.random.RandomState(2)
    futures = [engine.submit({"x": rng.rand(1, feat).astype("float32")})
               for _ in range(40)]
    assert engine.drain(timeout=60)       # non-closing drain converges
    assert all(f.done() for f in futures)
    engine.close()                         # idempotent with the drain
    for f in futures:
        f.result(1).numpy()


def test_depth_two_dispatches_the_next_batch_before_a_completion_and_no_third(
        tmp_path, monkeypatch):
    """What continuous batching is, as an order of events and not a
    latency at a load point: with pipeline_depth=2 batch n+1 is dispatched
    while batch n still holds its window slot, a third batch waits for a
    slot, and it goes when the first completes. The window's completion
    thread is held at its one device wait so that the order is not left
    to how fast a CPU steps."""
    import jax
    from paddle_tpu import serving
    from paddle_tpu.observability import trace
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="order", max_batch_size=1, batch_buckets=[1],
        max_queue_delay_ms=0, pipeline_depth=2)
    release = threading.Event()
    real = jax.block_until_ready

    def held(arrays):
        if threading.current_thread().name.startswith("ptpu-window-"):
            assert release.wait(60)
        return real(arrays)

    def names(spans):
        return sorted(s["name"] for s in spans
                      if s["name"] in ("serving/dispatch", "serving/execute",
                                       "serving/window_wait"))

    try:
        monkeypatch.setattr(jax, "block_until_ready", held)
        trace.configure(capacity=4096)
        rng = np.random.RandomState(0)
        futures = [engine.submit({"x": rng.rand(1, feat).astype("float32")})
                   for _ in range(3)]
        # two batches dispatched and in the window, the third waiting
        want = ["serving/execute", "serving/execute", "serving/window_wait"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                names(trace.dump()["open"]) != want:
            time.sleep(0.01)
        held_now = trace.dump()
        assert names(held_now["open"]) == want
        assert names(held_now["events"]).count("serving/dispatch") == 2
        release.set()
        for f in futures:
            f.result(60).numpy()
        engine.drain(30)
        deadline = time.monotonic() + 10    # execute spans close off-thread
        while time.monotonic() < deadline and trace.dump()["open"]:
            time.sleep(0.01)
        events = trace.dump()["events"]
        dispatch = sorted(e["ts"] for e in events
                          if e["name"] == "serving/dispatch")
        done = sorted(e["ts"] + e["dur"] for e in events
                      if e["name"] == "serving/execute")
        assert len(dispatch) == len(done) == 3
        assert dispatch[1] < done[0] <= dispatch[2]
    finally:
        release.set()
        engine.close()


def test_serial_mode_still_available(tmp_path):
    """pipeline_depth=0 keeps the PR-3 serial loop (the bench baseline
    and a conservative fallback) — same results, no window."""
    from paddle_tpu import serving
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="serial", max_batch_size=4, pipeline_depth=0)
    try:
        assert engine._batcher._window is None
        x = np.random.RandomState(3).rand(2, feat).astype("float32")
        out = engine.infer({"x": x})
        ref, _ = engine.run_direct({"x": x}, batch_bucket=2)
        for name in ref:
            np.testing.assert_array_equal(out[name], ref[name])
    finally:
        engine.close()


def test_no_premature_sync_on_serving_dispatch_path(tmp_path):
    """The no-premature-sync regression gate: a pipelined burst runs
    with the profiler's sync counter armed; every host sync observed on
    the dispatch path (the batcher's dispatch worker, marked with
    profiler.dispatch_path()) fails the test. Materialization happens
    afterwards, on the client thread, where it belongs."""
    from paddle_tpu import serving
    model_dir, feat = _save_mlp(tmp_path)
    engine = serving.InferenceEngine(
        model_dir, name="nosync", max_batch_size=4,
        batch_buckets=[1, 2, 4], max_queue_delay_ms=2, pipeline_depth=2)
    rng = np.random.RandomState(4)
    profiler.reset_profiler()  # sync counting is always-on; start clean
    try:
        futures = [engine.submit(
            {"x": rng.rand(1, feat).astype("float32")})
            for _ in range(24)]
        assert engine.drain(timeout=60)
        stats = profiler.sync_stats()
        assert stats["on_dispatch_path"] == 0, stats
        # clients materialize off-path — counted, but not against the
        # dispatch path
        for f in futures:
            f.result(10).numpy()
        stats = profiler.sync_stats()
        assert stats["by_tag"].get("serving/materialize", 0) >= 24
        assert stats["on_dispatch_path"] == 0, stats
    finally:
        profiler.reset_profiler()
        engine.close()


# ---------------------------------------------------------------------------
# training: prefetch bit-exactness + rollback invariants
# ---------------------------------------------------------------------------

def _make_recordio(tmp_path, n=12, batch=4, feat=6, seed=0,
                   name="pipe.recordio"):
    rng = np.random.RandomState(seed)
    data = [(rng.rand(batch, feat).astype("float32"),
             rng.rand(batch, 1).astype("float32")) for _ in range(n)]

    def reader():
        for rec in data:
            yield rec

    path = str(tmp_path / name)
    fluid.recordio_writer.convert_reader_to_recordio_file(path, reader)
    return path


def _build_reader_trainer(path, feat=6, seed=7, double_buffer=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        r = fluid.layers.open_recordio_file(
            path, shapes=[[-1, feat], [-1, 1]],
            dtypes=["float32", "float32"], lod_levels=[0, 0])
        if double_buffer:
            r = fluid.layers.create_double_buffer_reader(r, capacity=2)
        x, y = fluid.layers.read_file(r)
        h = fluid.layers.fc(input=x, size=16, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.3)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _state(scope):
    return {n: np.asarray(scope.get(n)) for n in scope.names()
            if hasattr(scope.get(n), "dtype")}


def _train_to_eof(path, prefetch, steps=1, double_buffer=False,
                  barrier=None, stop_after=None):
    """Run the reader-fed trainer to EOF (or `stop_after` successful
    runs); returns (fetch stream, final state, per-run errors)."""
    main, startup, loss = _build_reader_trainer(
        path, double_buffer=double_buffer)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    outs, errors = [], []
    with fluid.scope_guard(scope):
        exe.run(startup)
        while True:
            if stop_after is not None and len(outs) >= stop_after:
                break
            try:
                o = exe.run(main, fetch_list=[loss], steps=steps,
                            prefetch=prefetch)
                outs.append(np.asarray(o[0]))
            except EOFException:
                break
            except Exception as e:  # noqa: BLE001 — fault legs judge it
                if barrier is None and not getattr(
                        e, "_reader_fault", False):
                    raise
                errors.append(e)
        state = _state(scope)
    return outs, state, errors


@pytest.mark.parametrize("steps,double_buffer", [(1, False), (3, False),
                                                 (1, True), (4, True)])
def test_training_prefetch_bit_exact(tmp_path, steps, double_buffer):
    """Prefetched host-io prepass == serial prepass, bit for bit: fetch
    stream, params, Adam moments and the dropout seed cursor — plain
    and steps=K, with and without a double-buffer chain."""
    path = _make_recordio(tmp_path, n=12)
    o_ser, s_ser, _ = _train_to_eof(path, prefetch=False, steps=steps,
                                    double_buffer=double_buffer)
    o_pre, s_pre, _ = _train_to_eof(path, prefetch=True, steps=steps,
                                    double_buffer=double_buffer)
    assert len(o_ser) == len(o_pre) and len(o_ser) >= 2
    for a, b in zip(o_ser, o_pre):
        np.testing.assert_array_equal(a, b)
    assert sorted(s_ser) == sorted(s_pre)
    for n in s_ser:
        np.testing.assert_array_equal(s_ser[n], s_pre[n], err_msg=n)


def test_training_prefetch_feed_fed_identical(tmp_path):
    """A feed-fed (readerless) program under prefetch=True is exactly
    the serial path — the prefetcher never arms (nothing to stage)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.dropout(
            fluid.layers.fc(input=x, size=8, act="tanh"),
            dropout_prob=0.2)
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feed = {"x": np.random.RandomState(0).rand(4, 4).astype("f"),
            "y": np.random.RandomState(1).rand(4, 1).astype("f")}

    def run(prefetch):
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            outs = [np.asarray(exe.run(main, feed=feed,
                                       fetch_list=[loss],
                                       prefetch=prefetch)[0])
                    for _ in range(4)]
            assert exe._prefetcher is None  # never armed: no read ops
            return outs, _state(scope)

    o1, s1 = run(False)
    o2, s2 = run(True)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    for n in s1:
        np.testing.assert_array_equal(s1[n], s2[n], err_msg=n)


def test_reader_fault_between_prefetch_and_dispatch_rolls_back(tmp_path):
    """An injected reader fault fires ON THE PREFETCH THREAD (keyed on
    the reader's own delivered-record counter); the error surfaces at
    the next run() with the staged pops refunded — so the whole stream
    (before, the faulted position, and after) is bit-identical to the
    serial run under the same one-shot fault."""
    from paddle_tpu import resilience as rz
    path = _make_recordio(tmp_path, n=10)

    def leg(prefetch):
        with rz.FaultPlan(["reader_exc@5"]):
            return _train_to_eof(path, prefetch=prefetch, barrier=object())

    o_ser, s_ser, e_ser = leg(False)
    o_pre, s_pre, e_pre = leg(True)
    # the fault fired exactly once in each leg, at the same position
    assert len(e_ser) == 1 and len(e_pre) == 1
    assert getattr(e_ser[0], "_reader_fault", False)
    assert getattr(e_pre[0], "_reader_fault", False)
    # one-shot fault consumed NOTHING: all 10 records trained in both
    # legs (the prefetch leg refunded its staged pops before re-raising)
    assert len(o_ser) == len(o_pre) == 10
    for a, b in zip(o_ser, o_pre):
        np.testing.assert_array_equal(a, b)
    for n in s_ser:
        np.testing.assert_array_equal(s_ser[n], s_pre[n], err_msg=n)


def test_fence_between_prefetch_and_dispatch_consumes_nothing(tmp_path):
    """A cluster fence (barrier hook raise) landing AFTER a block was
    prefetched refunds the staged pops: the fenced attempt consumes no
    records and no rng, and the continued run is bit-identical to a
    never-fenced serial run — the PR-7 fence-consumes-nothing invariant
    surviving the overlap."""
    path = _make_recordio(tmp_path, n=8)

    class Fenced(RuntimeError):
        pass

    o_ref, s_ref, _ = _train_to_eof(path, prefetch=False)

    main, startup, loss = _build_reader_trainer(path)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    outs = []
    calls = {"n": 0}

    def barrier(point, **kw):
        calls["n"] += 1
        if calls["n"] == 4:  # fence lands before the 4th dispatch —
            raise Fenced()   # its block is already staged by then

    prev = exe_mod._barrier_hook
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe_mod._barrier_hook = barrier
        try:
            fenced = 0
            while True:
                try:
                    o = exe.run(main, fetch_list=[loss], prefetch=True)
                    outs.append(np.asarray(o[0]))
                except Fenced:
                    fenced += 1  # retry the same step, like a resharded
                    continue     # cohort replaying the fenced attempt
                except EOFException:
                    break
        finally:
            exe_mod._barrier_hook = prev
        state = _state(scope)
    assert fenced == 1
    assert len(outs) == len(o_ref)
    for a, b in zip(o_ref, outs):
        np.testing.assert_array_equal(a, b)
    for n in s_ref:
        np.testing.assert_array_equal(s_ref[n], state[n], err_msg=n)


def test_checkpoint_capture_quiesces_staged_pops(tmp_path):
    """CheckpointManager.save between prefetched steps refunds the
    staged next block BEFORE recording reader positions: resuming from
    the snapshot replays the stream bit-identically to the uninterrupted
    run (the staged-but-untrained records are not skipped)."""
    from paddle_tpu.checkpoint import CheckpointManager
    path = _make_recordio(tmp_path, n=10)
    ckpt = str(tmp_path / "ckpt")

    # uninterrupted reference
    o_ref, s_ref, _ = _train_to_eof(path, prefetch=False)

    # prefetch leg: snapshot after 4 steps (a block for step 5 is staged)
    main, startup, loss = _build_reader_trainer(path)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    outs = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        mgr = CheckpointManager(ckpt, async_save=False)
        for _ in range(4):
            outs.append(np.asarray(
                exe.run(main, fetch_list=[loss], prefetch=True)[0]))
        mgr.save(4, program=main, scope=scope)
        mgr.close()
        # keep training the original to EOF
        while True:
            try:
                outs.append(np.asarray(
                    exe.run(main, fetch_list=[loss], prefetch=True)[0]))
            except EOFException:
                break
        state = _state(scope)
    assert len(outs) == len(o_ref)
    for a, b in zip(o_ref, outs):
        np.testing.assert_array_equal(a, b)
    for n in s_ref:
        np.testing.assert_array_equal(s_ref[n], state[n], err_msg=n)

    # resume leg: restore the snapshot into a fresh world and finish
    main2, startup2, loss2 = _build_reader_trainer(path)
    exe2 = fluid.Executor(fluid.CPUPlace())
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup2)
        mgr2 = CheckpointManager(ckpt, async_save=False)
        assert mgr2.restore(program=main2, scope=scope2) == 4
        mgr2.close()
        resumed = []
        while True:
            try:
                resumed.append(np.asarray(
                    exe2.run(main2, fetch_list=[loss2], prefetch=True)[0]))
            except EOFException:
                break
        state2 = _state(scope2)
    np.testing.assert_array_equal(np.asarray(resumed),
                                  np.asarray(o_ref[4:]))
    for n in s_ref:
        np.testing.assert_array_equal(s_ref[n], state2[n], err_msg=n)


def test_signature_change_refunds_staged_block(tmp_path):
    """Alternating steps=1 / steps=K (different prefetch signature every
    call) forces a refund-and-inline-prepass each time — the stream must
    stay in order and bit-identical to the serial alternation."""
    path = _make_recordio(tmp_path, n=12)

    def leg(prefetch):
        main, startup, loss = _build_reader_trainer(path)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        outs = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            try:
                while True:
                    outs.append(np.asarray(exe.run(
                        main, fetch_list=[loss], steps=1,
                        prefetch=prefetch)[0]))
                    outs.append(np.asarray(exe.run(
                        main, fetch_list=[loss], steps=2,
                        fetch_reduce="last", prefetch=prefetch)[0]))
            except EOFException:
                pass
            return outs, _state(scope)

    o1, s1 = leg(False)
    o2, s2 = leg(True)
    assert len(o1) == len(o2)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    for n in s1:
        np.testing.assert_array_equal(s1[n], s2[n], err_msg=n)


def test_staged_error_for_other_signature_does_not_leak(tmp_path):
    """A staged EOF parked by a steps=K kick (too few records left for
    a whole K-block) must not fail a later steps=1 tail pass through
    the same executor: the mismatched error block consumed nothing and
    is discarded, the tail pass runs its own inline prepass and trains
    the remaining records — bit-identical to the serial alternation."""
    path = _make_recordio(tmp_path, n=7)  # 3 K=2 blocks + a 1-record tail

    def leg(prefetch):
        main, startup, loss = _build_reader_trainer(path)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        outs = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            # exactly 3 K=2 blocks: in the prefetch leg the 3rd run's
            # kick hits EOF staging the 4th block (1 record left) and
            # PARKS the error — which belongs to the steps=2 signature
            for _ in range(3):
                outs.append(np.asarray(exe.run(
                    main, fetch_list=[loss], steps=2,
                    fetch_reduce="last", prefetch=prefetch)[0]))
            # tail: drain the remainder with steps=1 — the parked
            # steps=2 EOF must be discarded (it consumed nothing), not
            # raised against this mismatched signature
            try:
                while True:
                    outs.append(np.asarray(exe.run(
                        main, fetch_list=[loss], prefetch=prefetch)[0]))
            except EOFException:
                pass
            return outs, _state(scope)

    o_ser, s_ser = leg(False)
    o_pre, s_pre = leg(True)
    assert len(o_ser) == len(o_pre) == 4  # 3 K-blocks + 1 tail record
    for a, b in zip(o_ser, o_pre):
        np.testing.assert_array_equal(a, b)
    for n in s_ser:
        np.testing.assert_array_equal(s_ser[n], s_pre[n], err_msg=n)


def test_no_premature_sync_on_training_dispatch_path(tmp_path):
    """A reader-fed prefetch loop with return_numpy=False, wrapped in
    profiler.dispatch_path(): zero host syncs on the loop thread (the
    prefetcher's H2D and the final materialization are elsewhere)."""
    path = _make_recordio(tmp_path, n=8)
    main, startup, loss = _build_reader_trainer(path)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    handles = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        # reset AFTER startup (its return_numpy materialization counts)
        profiler.reset_profiler()
        try:
            with profiler.dispatch_path():
                while True:
                    try:
                        handles.append(exe.run(
                            main, fetch_list=[loss], prefetch=True,
                            return_numpy=False)[0])
                    except EOFException:
                        break
            stats = profiler.sync_stats()
            assert stats["on_dispatch_path"] == 0, stats
            # materialization happens off the marked path
            vals = [np.asarray(h) for h in handles]
            assert len(vals) == 8
        finally:
            profiler.reset_profiler()


def test_parallel_executor_prefetch_bit_exact(tmp_path):
    """ParallelExecutor.run(prefetch=True) == serial prepass bit-for-bit
    (records pop + shard-place on the staging thread)."""
    path = _make_recordio(tmp_path, n=8, batch=8)  # 8 virtual devices

    def leg(prefetch):
        main, startup, loss = _build_reader_trainer(path)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        outs = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            pexe = fluid.ParallelExecutor(loss_name=loss.name,
                                          main_program=main)
            try:
                while True:
                    outs.append(np.asarray(pexe.run(
                        [loss], prefetch=prefetch)[0]))
            except EOFException:
                pass
            return outs, _state(scope)

    o1, s1 = leg(False)
    o2, s2 = leg(True)
    assert len(o1) == len(o2) == 8
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    for n in s1:
        np.testing.assert_array_equal(s1[n], s2[n], err_msg=n)


# ---------------------------------------------------------------------------
# units: InflightWindow, pin_place, quiesce hook
# ---------------------------------------------------------------------------

def test_inflight_window_bounds_and_accounts():
    import jax.numpy as jnp
    w = InflightWindow(2, tag="unit/window")
    try:
        assert w.acquire(timeout=1) and w.acquire(timeout=1)
        assert not w.acquire(timeout=0.05)   # window full
        w.track([jnp.ones(4)])               # completion frees a slot
        assert w.acquire(timeout=5)
        w.release()                          # failed-dispatch path
        w.track([])                          # empty dispatch completes
        deadline = time.monotonic() + 5
        while w.stats()["completed"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert w.stats()["completed"] == 2
        assert w.acquire(timeout=5)          # all slots recycled
    finally:
        w.close(timeout=5)
    with pytest.raises(ValueError):
        InflightWindow(0)


def test_double_buffer_pin_place_stages_to_device(tmp_path):
    """pin_place: the staging worker device_puts to the pinned dispatch
    device (H2D off the main thread); an explicit constructor place
    always wins; pins propagate through decorator chains."""
    import jax
    place = fluid.CPUPlace()

    def creator():
        for i in range(4):
            yield (np.full((2, 3), i, dtype="float32"),)

    r = DoubleBufferReader(IteratorReader(creator), capacity=2)
    try:
        assert r._place is None
        r.pin_place(place)
        assert r._place is place
        rec = r.next()
        assert isinstance(rec[0], jax.Array)
        assert rec[0].devices() == {place.device()}
        r.pin_place(fluid.TPUPlace())   # later pins never override
        assert r._place is place
    finally:
        r.close()
    # explicit constructor place beats any pin
    r2 = DoubleBufferReader(IteratorReader(creator), capacity=2,
                            place=place)
    try:
        r2.pin_place(fluid.TPUPlace())
        assert r2._place is place
    finally:
        r2.close()
    # chains forward the pin to the buffering decorator
    from paddle_tpu.core.readers import MultiPassReader
    inner = DoubleBufferReader(IteratorReader(creator), capacity=2)
    outer = MultiPassReader(inner, 2)
    try:
        outer.pin_place(place)
        assert inner._place is place
    finally:
        inner.close()


def test_rollback_all_staged_is_idempotent(tmp_path):
    """The quiesce hook is safe to call with nothing staged, with a
    foreign scope filter, and twice in a row."""
    rollback_all_staged()
    rollback_all_staged(scope=fluid.Scope())
    path = _make_recordio(tmp_path, n=6)
    main, startup, loss = _build_reader_trainer(path)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, fetch_list=[loss], prefetch=True)
        # a block for step 2 is staged; a FOREIGN scope filter must not
        # touch it...
        rollback_all_staged(scope=fluid.Scope())
        # ...and the matching-scope quiesce refunds it (twice = no-op)
        rollback_all_staged(scope=scope)
        rollback_all_staged(scope=scope)
        # the stream continues in order after the refund
        out = np.asarray(exe.run(main, fetch_list=[loss])[0])
        assert np.isfinite(out).all()
