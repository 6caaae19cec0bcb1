"""Iteration-level continuous batching for autoregressive decode
(ARCHITECTURE.md §27): DecodeEngine/DecodeBatcher serve a state-carrying
decode-step program with one batch-row slot per stream, admitting new
sequences into free slots and retiring finished ones BETWEEN decode
iterations at one fixed compiled shape.

The contract under test is bit-exactness under slot reuse: each stream's
token sequence must equal a solo decode of that stream (the
bucket-lattice invariant at a fixed shape — row results depend only on
that row's values — plus reset-on-admit rewriting EVERY slot var's row).
Plus the lifecycle edges: incremental token delivery, admit/retire
mid-decode (trace-span evidence), typed deadline/queue-full/closed
errors, hard close without hanging, drain completing all streams.

Everything runs on CPU with a tiny greedy argmax feedback decoder — the
control shape of generative decode without the model bulk.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.observability import trace

SLOTS, D, V, EOS = 4, 8, 16, 0


def build_decoder(slots=SLOTS, seed=7):
    """A decode-step program: carried token/hidden rows per slot, greedy
    argmax feedback, finished = (token == EOS). One Executor.run = one
    decode iteration for every slot at the fixed [slots] shape."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        tok = fluid.layers.create_global_var([slots, 1], 0, "int64",
                                             persistable=True, name="tok")
        h = fluid.layers.create_global_var([slots, D], 0.0, "float32",
                                           persistable=True, name="h")
        ctx = fluid.layers.create_global_var([slots, D], 0.0, "float32",
                                             persistable=True, name="ctx")
        x = fluid.layers.cast(tok, "float32")
        z = fluid.layers.fc(input=fluid.layers.concat([x, h, ctx], axis=1),
                            size=D, act="tanh")
        logits = fluid.layers.fc(input=z, size=V)
        nxt = fluid.layers.reshape(fluid.layers.argmax(logits, axis=1),
                                   shape=[slots, 1])
        fin = fluid.layers.equal(
            nxt, fluid.layers.fill_constant([slots, 1], "int64", EOS))
        fluid.layers.assign(nxt, output=tok)
        fluid.layers.assign(z, output=h)
    return main, startup, nxt, fin


def make_engine(name, slots=SLOTS, **kw):
    main, startup, nxt, fin = build_decoder(slots=slots)
    return serving.DecodeEngine(program=main, startup_program=startup,
                                token_var=nxt, finished_var=fin,
                                max_slots=slots, name=name, **kw)


def stream_feed(i, rng):
    return {"tok": np.array([i % (V - 1) + 1], dtype="int64"),
            "ctx": rng.randn(D).astype("float32")}


@pytest.fixture(scope="module")
def eng():
    e = make_engine("dec-test", default_max_new_tokens=12)
    yield e
    e.close(drain=False)


@pytest.fixture(scope="module")
def solo(eng):
    s = eng.solo_clone(name="dec-test-solo")
    yield s
    s.close(drain=False)


def toks(result):
    return np.asarray(result).reshape(-1)


def test_slot_vars_inferred_from_program_state(eng):
    # tok/h are written persistables (state_out), ctx a slot-shaped
    # read-only persistable — all three must be admit-rewritten rows
    assert sorted(eng.slot_vars) == ["ctx", "h", "tok"]
    d = eng.describe()
    assert d["mode"] == "decode" and d["max_slots"] == SLOTS
    assert {s["name"]: s["row_shape"] for s in d["slot_vars"]} == {
        "tok": [1], "h": [D], "ctx": [D]}


def test_mixed_streams_bit_exact_vs_solo(eng, solo):
    """More concurrent streams than slots, mixed token budgets: forces
    pending-queue waits, retires mid-flight, and slot REUSE by later
    streams. Every stream must match its solo decode bit-for-bit."""
    rng = np.random.RandomState(0)
    feeds = [stream_feed(i, rng) for i in range(7)]
    budgets = [3 + (i * 2) % 7 for i in range(7)]
    before = eng.decode_stats()
    streams = [None] * len(feeds)

    def client(i):
        streams[i] = eng.submit(feeds[i], max_new_tokens=budgets[i])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(feeds))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = [toks(s.result(60)) for s in streams]
    for i, g in enumerate(got):
        want = toks(solo.decode(feeds[i], max_new_tokens=budgets[i]))
        np.testing.assert_array_equal(g, want, err_msg="stream %d" % i)
        assert len(g) <= budgets[i]

    after = eng.decode_stats()
    done = after["streams_completed"] - before["streams_completed"]
    assert done == len(feeds)
    # iteration SHARING is the whole point: strictly fewer iterations
    # than serial (sum of lengths), at least the longest stream's count
    iters = after["iterations"] - before["iterations"]
    assert max(len(g) for g in got) <= iters < sum(len(g) for g in got)
    assert after["mean_slot_occupancy"] > 1.0


def test_incremental_delivery_and_admit_mid_decode(eng, solo, monkeypatch):
    """Tokens arrive per ITERATION (not at stream end), and a stream
    submitted while another decodes is admitted at an iteration boundary
    mid-flight — proven by the decode_step span that carries both
    stream ids after earlier steps carried only the first. The step loop
    is let through one iteration at a time until B is in the queue, so
    that "mid-decode" is an order of events and not a race between this
    thread and ten CPU iterations (it lost that race beside five other
    test workers)."""
    trace.clear()
    rng = np.random.RandomState(1)
    fa, fb = stream_feed(3, rng), stream_feed(9, rng)
    real_step, let = eng._batcher._step, threading.Semaphore(0)

    def step():
        assert let.acquire(timeout=60)
        return real_step()

    monkeypatch.setattr(eng._batcher, "_step", step)
    a = eng.submit(fa, max_new_tokens=10)
    let.release()                           # A's first iteration, no more
    first = a.next_token(timeout=30)        # delivered before A is done
    assert first is not None and not a.done()
    a_count_at_b = a.token_count()
    b = eng.submit(fb, max_new_tokens=4)
    monkeypatch.setattr(eng._batcher, "_step", real_step)
    let.release()                           # the step the loop waits in
    got_a = toks(a.result(60))
    got_b = toks(b.result(60))
    assert a_count_at_b < len(got_a)        # B arrived mid-decode of A
    np.testing.assert_array_equal(got_a[0], np.asarray(first).reshape(-1))
    np.testing.assert_array_equal(
        got_a, toks(solo.decode(fa, max_new_tokens=10)))
    np.testing.assert_array_equal(
        got_b, toks(solo.decode(fb, max_new_tokens=4)))

    deadline = time.monotonic() + 10        # execute spans close async
    while time.monotonic() < deadline and trace.dump()["open"]:
        time.sleep(0.02)
    events = trace.dump()["events"]
    steps = [e for e in events if e["name"] == "serving/decode_step"]
    ids = {a.stream_id, b.stream_id}
    shared = [e for e in steps if ids <= set(e["args"]["streams"])]
    alone = [e for e in steps
             if set(e["args"]["streams"]) == {a.stream_id}]
    assert shared and alone, "no iteration carried both streams"
    admits = [e for e in events if e["name"] == "serving/decode_admit"]
    assert {e["args"]["stream"] for e in admits} >= ids
    # per-stream root spans exist and the step spans link their traces
    roots = {e["trace"] for e in events if e["name"] == "serving/stream"}
    assert {a.trace, b.trace} <= roots
    step_traces = set()
    for e in steps:
        step_traces.update(e["args"]["traces"])
    assert {a.trace, b.trace} <= step_traces


def test_pending_deadline_expires_typed(eng):
    """A stream whose deadline passes while it waits for a slot fails
    with DeadlineExceededError at an iteration boundary; the resident
    streams are untouched."""
    rng = np.random.RandomState(2)
    residents = [eng.submit(stream_feed(i, rng), max_new_tokens=8)
                 for i in range(SLOTS)]
    victim = eng.submit(stream_feed(11, rng), max_new_tokens=4,
                        deadline_ms=1)
    with pytest.raises(serving.DeadlineExceededError):
        victim.result(30)
    for s in residents:
        assert len(toks(s.result(60))) >= 1


def test_invalid_feed_rejected_typed(eng):
    with pytest.raises(serving.InvalidRequestError):
        eng.submit({"nonsense": np.zeros(3, dtype="float32")})
    with pytest.raises(serving.InvalidRequestError):
        eng.submit({"ctx": np.zeros(D + 1, dtype="float32")})


def test_drain_completes_all_streams(eng):
    rng = np.random.RandomState(3)
    streams = [eng.submit(stream_feed(i, rng), max_new_tokens=5)
               for i in range(6)]
    assert eng.drain(timeout=60)
    for s in streams:
        assert s.done()
        assert len(toks(s.result(1))) >= 1
    st = eng.decode_stats()
    assert st["occupied_slots"] == 0 and st["pending_streams"] == 0


def test_registry_exports_decode_gauges(eng):
    from paddle_tpu.observability.registry import REGISTRY
    text = REGISTRY.render_prometheus()
    assert "ptpu_decode_slots" in text
    # registry names carry a uniquifying #N suffix per live decoder
    assert 'decoder="dec-test' in text
    assert "ptpu_decode_tokens_total" in text


def test_queue_full_and_hard_close_typed_no_hang():
    """A saturated decode engine rejects typed at submit; close with
    drain=False fails BOTH pending and resident streams typed, without
    hanging, and already-delivered tokens stay readable."""
    e = make_engine("dec-close", slots=2, queue_capacity=1,
                    default_max_new_tokens=4096)
    try:
        rng = np.random.RandomState(4)
        # admission happens on the worker thread at iteration boundaries,
        # so wait for each resident to occupy its slot before the next
        # submit — otherwise the not-yet-admitted first resident fills
        # the capacity-1 pending queue and the second submit rejects
        residents = []
        for i in range(2):
            residents.append(e.submit(stream_feed(i, rng)))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if e.decode_stats()["occupied_slots"] == i + 1:
                    break
                time.sleep(0.01)
            assert e.decode_stats()["occupied_slots"] == i + 1
        pending = e.submit(stream_feed(7, rng))
        with pytest.raises(serving.QueueFullError):
            e.submit(stream_feed(8, rng))
        # let the residents decode a few iterations first
        while residents[0].token_count() < 3:
            time.sleep(0.005)
        t0 = time.monotonic()
        e.close(drain=False)
        assert time.monotonic() - t0 < 10, "hard close hung"
        for s in residents + [pending]:
            with pytest.raises(serving.ServingClosedError):
                s.result(5)
        # the partial prefix a client already consumed stays readable
        assert residents[0].token_count() >= 3
        assert len(residents[0].tokens()) == residents[0].token_count()
        with pytest.raises(serving.ServingClosedError):
            e.submit(stream_feed(9, rng))
    finally:
        e.close(drain=False)


def test_solo_clone_shares_weights_not_state(eng, solo):
    """The solo reference must share the engine's weights (so comparing
    against it is meaningful) without sharing slot state (so a busy
    engine can't leak rows into the reference)."""
    rng = np.random.RandomState(5)
    f = stream_feed(6, rng)
    a = toks(solo.decode(f, max_new_tokens=6))
    b = toks(solo.decode(f, max_new_tokens=6))  # repeat: deterministic
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        a, toks(eng.decode(f, max_new_tokens=6)))
