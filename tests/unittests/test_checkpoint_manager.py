"""paddle_tpu.checkpoint — the fault-tolerance + bit-exact-resume
contract (ARCHITECTURE.md §16).

Headline guarantees under test:
  * training N steps straight through == train K, "crash", resume from
    the step-K snapshot, train N-K more — bit-identical params, optimizer
    moments, fetches; for SGD and Adam, plain and steps=K multi-step,
    feed-fed and reader-fed mid-epoch, with dropout (seed cursor).
  * kill -9 at ANY point during a save never yields an unloadable latest
    checkpoint (fault-injection sweep in a subprocess).
  * a bit-flipped snapshot file is detected by hash verification and
    skipped; retention prunes by max_to_keep/keep_every_n_steps.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.checkpoint import (CheckpointManager, RetentionPolicy,
                                   find_valid_snapshot, list_steps,
                                   load_manifest, verify_snapshot)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _build(optimizer="adam", dropout=False, seed=5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="tanh")
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.3)
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        if optimizer == "adam":
            # decaying LR: resume must restore @LR_DECAY_COUNTER@ too
            lr = fluid.layers.exponential_decay(0.01, 4, 0.7)
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
        else:
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _persisted(scope):
    from paddle_tpu.core.readers import ReaderBase
    return {n: np.asarray(scope.get(n)) for n in scope.names()
            if not isinstance(scope.get(n), ReaderBase)}


def _assert_state_equal(a, b):
    assert set(a) == set(b), (sorted(set(a) ^ set(b)))
    for n, va in a.items():
        np.testing.assert_array_equal(
            va, b[n], err_msg="state %r diverged after resume" % n)


# ------------------------------------------------------ bit-exact resume --
@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_bit_exact_resume_feed(tmp_path, optimizer):
    """Straight-through vs crash-at-K + resume: identical params AND
    optimizer state AND fetches, with dropout in the graph so the seed
    cursor restore is load-bearing."""
    r = np.random.RandomState(7)
    w = r.randn(6, 1).astype("f")
    data = [r.rand(16, 6).astype("f") for _ in range(8)]
    main, startup, loss = _build(optimizer, dropout=True)
    exe = fluid.Executor(fluid.CPUPlace())

    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        fetches_a = []
        for i, xb in enumerate(data):
            if i == 4:
                with CheckpointManager(str(tmp_path)) as mgr:
                    mgr.save(4, program=main, scope=scope_a).result(60)
            l, = exe.run(main, feed={"x": xb, "y": xb @ w},
                         fetch_list=[loss])
            fetches_a.append(np.asarray(l))
        final_a = _persisted(scope_a)

    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe.run(startup)
        with CheckpointManager(str(tmp_path)) as mgr:
            assert mgr.restore(program=main, scope=scope_b) == 4
        fetches_b = []
        for xb in data[4:]:
            l, = exe.run(main, feed={"x": xb, "y": xb @ w},
                         fetch_list=[loss])
            fetches_b.append(np.asarray(l))
        final_b = _persisted(scope_b)

    _assert_state_equal(final_a, final_b)
    for fa, fb in zip(fetches_a[4:], fetches_b):
        np.testing.assert_array_equal(fa, fb)


def _reader_program(tmp_path, batches=16, double_buffer=False):
    def gen():
        r = np.random.RandomState(3)
        for _ in range(batches):
            xs = r.rand(4, 6).astype("float32")
            yield xs, xs[:, :1].copy()

    path = str(tmp_path / "data.recordio")
    fluid.recordio_writer.convert_reader_to_recordio_file(path, gen)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        rdr = fluid.layers.open_recordio_file(
            filename=path, shapes=[[-1, 6], [-1, 1]], lod_levels=[0, 0],
            dtypes=["float32", "float32"])
        if double_buffer:
            # decorator CHAIN: only the outermost reader's state must be
            # recorded; the inner recordio reader replays through it
            rdr = fluid.layers.double_buffer(rdr)
        x, y = fluid.layers.read_file(rdr)
        h = fluid.layers.fc(input=x, size=8, act="tanh")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("steps_k,double_buffer",
                         [(1, False), (4, False), (1, True)])
def test_bit_exact_resume_reader_mid_epoch(tmp_path, steps_k,
                                           double_buffer):
    """Reader-fed training, checkpoint MID-epoch (reader position != 0),
    plain and steps=K multi-step, flat and double-buffer-chained: the
    resumed run consumes exactly the records the straight-through run
    would have (with a chain, only the OUTERMOST reader's state is
    recorded and the inner one replays through it)."""
    main, startup, loss = _reader_program(tmp_path,
                                          double_buffer=double_buffer)
    exe = fluid.Executor(fluid.CPUPlace())
    ck = str(tmp_path / "ck")
    total_calls = 12 // max(steps_k, 1) if steps_k > 1 else 10
    split = total_calls // 2
    run_kw = {"steps": steps_k} if steps_k > 1 else {}

    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        fetches_a = []
        for i in range(total_calls):
            if i == split:
                with CheckpointManager(ck, async_save=False) as mgr:
                    mgr.save(split, program=main, scope=scope_a)
            l, = exe.run(main, fetch_list=[loss], **run_kw)
            fetches_a.append(np.asarray(l))
        final_a = _persisted(scope_a)

    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe.run(startup)  # fresh readers at position 0
        with CheckpointManager(ck) as mgr:
            assert mgr.restore(program=main, scope=scope_b) == split
        fetches_b = []
        for _ in range(total_calls - split):
            l, = exe.run(main, fetch_list=[loss], **run_kw)
            fetches_b.append(np.asarray(l))
        final_b = _persisted(scope_b)

    _assert_state_equal(final_a, final_b)
    for fa, fb in zip(fetches_a[split:], fetches_b):
        np.testing.assert_array_equal(fa, fb)


def test_reader_state_dict_roundtrip_mid_k_block(tmp_path):
    """Satellite: ReaderBase.state_dict/load_state_dict alone (no
    manager) — mid-stream and mid-K-block positions round-trip, a failed
    next_many refunds the position, and DoubleBufferReader re-stages to
    the recorded depth."""
    from paddle_tpu.core.readers import (DoubleBufferReader,
                                         EOFException, IteratorReader)

    def creator():
        return iter([(np.full((2,), i, "f"),) for i in range(10)])

    r = IteratorReader(creator)
    for _ in range(3):
        r.next()
    st = r.state_dict()
    assert st["consumed"] == 3
    # a failed K-block must not move the recorded position
    with pytest.raises(EOFException):
        r.next_many(8)
    assert r.state_dict()["consumed"] == 3

    r2 = IteratorReader(creator)
    r2.load_state_dict(st)
    np.testing.assert_array_equal(r2.next()[0], np.full((2,), 3, "f"))

    # DoubleBuffer: staged-but-undelivered records are NOT consumed, and
    # the staging depth survives the round trip
    db = DoubleBufferReader(IteratorReader(creator), capacity=2)
    db.next(), db.next()
    db.ensure_staging_depth(4)
    st = db.state_dict()
    assert st["consumed"] == 2 and st["capacity"] == 4
    db.close()
    db2 = DoubleBufferReader(IteratorReader(creator), capacity=2)
    db2.load_state_dict(st)
    assert db2._capacity == 4
    np.testing.assert_array_equal(np.asarray(db2.next()[0]),
                                  np.full((2,), 2, "f"))
    db2.close()


def test_host_pipeline_skip_decorator():
    """reader.skip: the host-side resume twin of load_state_dict. Only
    the FIRST (resume) epoch is partial — later epochs of the same
    wrapped creator replay the full stream."""
    import paddle_tpu.reader as reader
    creator = lambda: iter(range(10))  # noqa: E731
    wrapped = reader.skip(creator, 4)
    assert list(wrapped()) == [4, 5, 6, 7, 8, 9]
    assert list(wrapped()) == list(range(10))
    assert list(reader.skip(creator, 12)()) == []


# ------------------------------------------------------------ torn write --
_VICTIM = textwrap.dedent("""
    import os, sys
    import numpy as np
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, %(repo)r)
    import paddle_tpu as fluid
    from paddle_tpu.checkpoint import CheckpointManager
    d = sys.argv[1]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 4).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        mgr = CheckpointManager(d)               # ASYNC writer thread
        mgr.save(1, program=main, scope=scope).result(60)  # known-good
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        os.environ["PTPU_CKPT_FAULT_AT"] = sys.argv[2]   # arm the kill
        h = mgr.save(2, program=main, scope=scope)
        h.result(60)   # the SIGKILL lands on the background writer;
        mgr.close()    # it kills the whole process, mid-async-save
    print("SURVIVED")
""")


def test_torn_write_never_corrupts_latest(tmp_path):
    """kill -9 at EVERY injection point of the write protocol: load must
    always find a valid snapshot — the previous one if the kill landed
    before the publishing rename, the new one if after. The sweep runs
    until the victim survives (fault point past the last crossing)."""
    script = tmp_path / "victim.py"
    script.write_text(_VICTIM % {"repo": REPO})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("PTPU_CKPT_FAULT_AT", None)
    saw_kill = saw_old = saw_new = False
    for n in range(0, 30):
        d = str(tmp_path / ("ck%d" % n))
        cp = subprocess.run(
            [sys.executable, str(script), d, str(n)], env=env,
            capture_output=True, text=True, timeout=600)
        killed = cp.returncode == -9
        found = find_valid_snapshot(d)
        assert found is not None, \
            "fault@%d left NO loadable snapshot: %s%s" % (n, cp.stdout,
                                                          cp.stderr)
        step, path = found
        assert not verify_snapshot(path)
        assert step in (1, 2), step
        saw_kill |= killed
        saw_old |= killed and step == 1
        saw_new |= killed and step == 2
        if not killed:
            assert "SURVIVED" in cp.stdout, cp.stdout + cp.stderr
            assert step == 2
            break
    else:
        pytest.fail("victim never survived: fault sweep too short")
    # the sweep must actually have exercised both recovery regimes
    assert saw_kill and saw_old and saw_new


# --------------------------------------------------- retention + hashes --
def test_retention_policy_and_gc(tmp_path):
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(1)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        with CheckpointManager(str(tmp_path), max_to_keep=2,
                               keep_every_n_steps=4,
                               async_save=False) as mgr:
            for s in range(1, 11):
                mgr.save(s, program=main, scope=scope)
            steps = mgr.steps()
    # newest 2 plus every 4th survive
    assert steps == [4, 8, 9, 10]

    # pure policy math
    pol = RetentionPolicy(max_to_keep=3)
    assert pol.to_delete([1, 2, 3, 4, 5]) == [1, 2]
    assert pol.to_delete([1, 2, 3, 4, 5], protect=(1,)) == [2]
    assert RetentionPolicy(max_to_keep=None).to_delete(range(100)) == []


def test_bit_flip_detected_and_skipped(tmp_path):
    """Hash verification: a flipped byte in any snapshot file makes that
    snapshot invalid; restore walks back to the previous valid one."""
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        with CheckpointManager(str(tmp_path), async_save=False) as mgr:
            exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                    fetch_list=[loss])
            mgr.save(1, program=main, scope=scope)
            exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                    fetch_list=[loss])
            mgr.save(2, program=main, scope=scope)

    victim = None
    for name, entry in load_manifest(str(tmp_path / "step_2")).items():
        if entry.get("is_param"):
            victim = str(tmp_path / "step_2" / entry["file"])
            break
    with open(victim, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        byte = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([byte[0] ^ 0xFF]))

    problems = verify_snapshot(str(tmp_path / "step_2"))
    assert problems and "hash mismatch" in problems[0]
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        with CheckpointManager(str(tmp_path)) as mgr:
            assert mgr.latest_step() == 1
            assert mgr.restore(program=main, scope=scope2) == 1
            # PINNING the corrupt step must raise, not silently start
            # fresh (and a pinned missing step likewise)
            with pytest.raises(ValueError):
                mgr.restore(program=main, scope=scope2, step=2)
            with pytest.raises(ValueError):
                mgr.restore(program=main, scope=scope2, step=99)

    # a corrupted manifest is caught too
    mpath = str(tmp_path / "step_2" / "manifest.json")
    with open(mpath, "a") as f:
        f.write(" ")
    assert verify_snapshot(str(tmp_path / "step_2"))


def test_corrupt_snapshot_json_is_skipped_not_crash(tmp_path):
    """snapshot.json is the root of the hash tree: its OWN corruption —
    torn to invalid JSON, deleted outright, or bit-flipped while staying
    valid JSON (caught by its self-hash) — must read as "invalid
    snapshot" (walk back to the previous valid one), never crash out of
    the load path and never silently downgrade to unhashed legacy
    trust."""
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(11)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        with CheckpointManager(str(tmp_path), async_save=False) as mgr:
            for s in (1, 2, 3, 4):
                mgr.save(s, program=main, scope=scope)
    # step_4: torn to invalid JSON
    (tmp_path / "step_4" / "snapshot.json").write_text("{ torn json")
    problems = verify_snapshot(str(tmp_path / "step_4"))
    assert problems and "snapshot.json" in problems[0]
    # step_3: tampered but still valid JSON — self-hash catches it
    spath = tmp_path / "step_3" / "snapshot.json"
    meta = json.loads(spath.read_text())
    meta["seed_cursor"] = meta["seed_cursor"] + 1
    spath.write_text(json.dumps(meta, indent=1, sort_keys=True))
    problems = verify_snapshot(str(tmp_path / "step_3"))
    assert problems and "content hash" in problems[0]
    # step_2: snapshot.json deleted — hashed manifest proves this is a
    # manager snapshot, so it must NOT pass as a legacy layout
    (tmp_path / "step_2" / "snapshot.json").unlink()
    problems = verify_snapshot(str(tmp_path / "step_2"))
    assert problems and "missing its snapshot.json" in problems[0]
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        with CheckpointManager(str(tmp_path)) as mgr:
            assert mgr.restore(program=main, scope=scope2) == 1


def test_orphaned_resave_park_is_recovered(tmp_path):
    """A kill between the two renames of a SAME-STEP re-save leaves the
    old snapshot parked as step_<N>.old.<pid> and no step_<N>: restore
    must rename it back (once the writer pid is dead) instead of losing
    the only copy of that step."""
    from paddle_tpu.checkpoint.snapshot import clean_stale_tmp
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(12)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        with CheckpointManager(str(tmp_path), async_save=False) as mgr:
            mgr.save(5, program=main, scope=scope)
        want = _persisted(scope)
    # simulate the kill window: step_5 parked under a dead writer's pid
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()  # reaped: os.kill(p.pid, 0) now raises ProcessLookupError
    os.rename(str(tmp_path / "step_5"),
              str(tmp_path / ("step_5.old.%d" % p.pid)))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        with CheckpointManager(str(tmp_path)) as mgr:
            assert mgr.restore(program=main, scope=scope2) == 5
        got = {n: np.asarray(scope2.get(n)) for n in want}
        _assert_state_equal(want, got)
    assert clean_stale_tmp(str(tmp_path)) == []  # nothing left to sweep


def test_failed_async_save_raises_at_next_save(tmp_path, monkeypatch):
    """An unobserved background save failure surfaces at the NEXT save()
    call — a trainer that ignores its SaveHandles must not run for days
    while every write fails — and completed handles are pruned so
    _pending stays bounded."""
    from paddle_tpu.analysis import ProgramVerificationError
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(13)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        with CheckpointManager(str(tmp_path)) as mgr:
            for s in (1, 2, 3):
                mgr.save(s, program=main, scope=scope)
            mgr.wait()
            assert len(mgr._pending) == 0  # drained via wait
            h = mgr.save(4, program=main, scope=scope)
            h.result(60)
            mgr.save(5, program=main, scope=scope).result(60)
            assert len(mgr._pending) <= 1  # done handles pruned
            main.global_block().append_op(
                type="definitely_not_an_op", inputs={}, outputs={},
                infer_shape=False)
            monkeypatch.setenv("FLAGS_validate_program", "1")
            bad = mgr.save(6, program=main, scope=scope)
            # don't touch `bad`: the failure must still surface
            import time
            for _ in range(100):
                if bad.done():
                    break
                time.sleep(0.05)
            with pytest.raises(ProgramVerificationError):
                mgr.save(7, program=main, scope=scope)
            assert mgr._pending == []  # failed handle consumed, 7 not queued


# -------------------------------------------------------- legacy shims --
def test_legacy_shim_partial_layout(tmp_path):
    """Satellite regression: the legacy pre-manager layout — step dirs
    written by old save_checkpoint (unhashed manifest, no snapshot.json),
    LATEST absent or stale — loads the newest COMPLETE snapshot instead
    of raising, and the legacy API signatures keep working."""
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        # fabricate the OLD layout: save_persistables into step dirs by
        # hand (what pre-manager save_checkpoint did), no LATEST at all
        fluid.io.save_persistables(exe, str(tmp_path / "step_3"), main)
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        fluid.io.save_persistables(exe, str(tmp_path / "step_7"), main)
        want = _persisted(scope)

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        assert fluid.io.load_checkpoint(exe, str(tmp_path), main) == 7
        got = {n: np.asarray(scope2.get(n)) for n in want}
        _assert_state_equal(want, got)

    # stale LATEST pointing at a missing step: still resolves newest
    (tmp_path / "LATEST").write_text("99")
    scope3 = fluid.Scope()
    with fluid.scope_guard(scope3):
        exe.run(startup)
        assert fluid.io.load_checkpoint(exe, str(tmp_path), main) == 7

    # a torn legacy dir (missing file) is skipped for the older complete one
    m = load_manifest(str(tmp_path / "step_7"))
    os.remove(str(tmp_path / "step_7" / next(iter(m.values()))["file"]))
    scope4 = fluid.Scope()
    with fluid.scope_guard(scope4):
        exe.run(startup)
        assert fluid.io.load_checkpoint(exe, str(tmp_path), main) == 3


def test_legacy_shim_empty_and_missing_dir(tmp_path):
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert fluid.io.load_checkpoint(exe, str(tmp_path), main) is None
        assert fluid.io.load_checkpoint(
            exe, str(tmp_path / "nope"), main) is None


# ------------------------------------------------- verifier + manifest --
def test_validate_program_at_save(tmp_path, monkeypatch):
    """Satellite: FLAGS_validate_program arms the PR-2 static verifier on
    the program RECORDED in the snapshot — a program that can't be
    re-lowered is a failed save, not a resume-time surprise."""
    from paddle_tpu.analysis import ProgramVerificationError
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        # poison the program AFTER running: an op type nothing registers
        main.global_block().append_op(
            type="definitely_not_an_op", inputs={}, outputs={},
            infer_shape=False)
        monkeypatch.setenv("FLAGS_validate_program", "1")
        with CheckpointManager(str(tmp_path), async_save=False) as mgr:
            with pytest.raises(ProgramVerificationError):
                mgr.save(1, program=main, scope=scope)
        # the failed save must not have published anything
        assert find_valid_snapshot(str(tmp_path)) is None
        # async path: the error surfaces on the handle / wait()
        with CheckpointManager(str(tmp_path)) as mgr2:
            h = mgr2.save(1, program=main, scope=scope)
            with pytest.raises(ProgramVerificationError):
                h.result(60)
            mgr2._pending[:] = []  # consumed via the handle above
        monkeypatch.delenv("FLAGS_validate_program")


def test_manifest_tags_accumulator_owners(tmp_path):
    """Satellite: optimizer accumulators are manifest-tagged to their
    owner param; beta-pow style globals carry owner='' (never
    pattern-matched to a param)."""
    main, startup, loss = _build("adam")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(5)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        with CheckpointManager(str(tmp_path), async_save=False) as mgr:
            mgr.save(1, program=main, scope=scope)
    manifest = load_manifest(str(tmp_path / "step_1"))
    params = [n for n, e in manifest.items() if e.get("is_param")]
    moments = {n: e for n, e in manifest.items()
               if n.startswith(("moment1_", "moment2_"))}
    assert moments, "Adam moments missing from the snapshot"
    for n, e in moments.items():
        assert e.get("owner") in params, (n, e)
    betas = {n: e for n, e in manifest.items()
             if n.startswith(("beta1_pow", "beta2_pow"))}
    assert betas and all(e.get("owner") == "" for e in betas.values())


def test_async_save_backpressure_and_capture_isolation(tmp_path):
    """Async semantics: values captured at save() time are what lands on
    disk even though training keeps mutating the scope (donation-immune
    device copies), and in-flight saves are bounded."""
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(6)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(16, 6).astype("f")
        exe.run(main, feed={"x": xb, "y": xb[:, :1]}, fetch_list=[loss])
        param = main.all_parameters()[0].name
        with CheckpointManager(str(tmp_path), max_in_flight=1) as mgr:
            at_save = np.asarray(scope.get(param)).copy()
            h = mgr.save(1, program=main, scope=scope)
            # keep training while the writer works
            for _ in range(5):
                exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                        fetch_list=[loss])
            path = h.result(60)
            assert h.write_seconds is not None
        entry = load_manifest(path)[param]
        np.testing.assert_array_equal(
            np.load(os.path.join(path, entry["file"])), at_save)
        # training DID move past the captured value
        assert not np.array_equal(np.asarray(scope.get(param)), at_save)


@pytest.mark.parametrize("mode", ["async", "sync"])
def test_save_returns_before_the_write_only_when_async(tmp_path,
                                                       monkeypatch, mode):
    """What async saving is for, as an order of events and not a stall in
    milliseconds: an async save() hands the snapshot to the writer thread
    and returns while that thread still holds the write; a sync save (the
    manager's, or wait=True on an async manager's) returns only after the
    snapshot is published."""
    import threading
    from paddle_tpu.checkpoint import manager as mgr_mod
    main, startup, loss = _build("sgd")
    exe = fluid.Executor(fluid.CPUPlace())
    events, held = [], threading.Event()
    in_write = threading.Event()
    real_write = mgr_mod._snap.write_snapshot

    def write(*args, **kwargs):
        events.append("write begins")
        in_write.set()
        if mode == "async":          # the writer waits for save()'s return
            assert held.wait(60)
        path = real_write(*args, **kwargs)
        events.append("write ends")
        return path

    monkeypatch.setattr(mgr_mod._snap, "write_snapshot", write)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with CheckpointManager(str(tmp_path),
                               async_save=(mode == "async")) as mgr:
            h = mgr.save(1, program=main, scope=scope)
            if mode == "async":
                assert in_write.wait(60)     # the writer has the snapshot
                assert not h.done()          # and save() is already back
            events.append("save returns")
            held.set()
            path = h.result(60)
            if mode == "async":
                # wait=True on the same manager is the sync contract
                mgr.save(2, program=main, scope=scope, wait=True)
                events.append("waited save returns")
    if mode == "async":
        assert events == ["write begins", "save returns", "write ends",
                          "write begins", "write ends",
                          "waited save returns"]
    else:
        assert events == ["write begins", "write ends", "save returns"]
        assert h.done()
    assert verify_snapshot(path) == []


# ----------------------------------------------------- serving + tools --
def test_engine_from_checkpoint(tmp_path):
    """The serving engine loads the newest valid training snapshot as a
    servable model, bit-matching the training-side forward pass; a
    corrupted newest snapshot falls back to the previous valid one."""
    from paddle_tpu.serving.engine import InferenceEngine
    main, startup, loss = _build("sgd")
    pred_name = None
    for op in main.global_block().ops:
        if op.type == "mean":
            break
    # the fc output feeding square_error_cost is the servable fetch
    for op in main.global_block().ops:
        if op.type == "square_error_cost":
            pred_name = op.inputs["X"][0]
    assert pred_name
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(8)
    scope = fluid.Scope()
    ck = str(tmp_path / "ck")
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        with CheckpointManager(ck, async_save=False) as mgr:
            exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                    fetch_list=[loss])
            mgr.save(1, program=main, scope=scope)
            exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                    fetch_list=[loss])
            mgr.save(2, program=main, scope=scope)

    eng = InferenceEngine.from_checkpoint(
        ck, fetch_list=[pred_name], batch_buckets=[4], max_batch_size=4)
    try:
        assert eng.checkpoint_step == 2
        assert eng.feed_names == ["x"]
        q = r.rand(3, 6).astype("f")
        out, bucket = eng.run_direct({"x": q})
        infer = main.prune([pred_name], for_test=True)
        with fluid.scope_guard(scope):
            ref, = exe.run(infer, feed={"x": np.concatenate(
                [q, np.zeros((1, 6), "f")])}, fetch_list=[pred_name])
        np.testing.assert_array_equal(out[pred_name],
                                      np.asarray(ref)[:3])
    finally:
        eng.close()

    # corrupt step_2 -> engine serves step_1
    m = load_manifest(os.path.join(ck, "step_2"))
    victim = next(e["file"] for e in m.values() if e.get("is_param"))
    with open(os.path.join(ck, "step_2", victim), "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    eng2 = InferenceEngine.from_checkpoint(
        ck, fetch_list=[pred_name], batch_buckets=[4], max_batch_size=4,
        warmup=False)
    try:
        assert eng2.checkpoint_step == 1
    finally:
        eng2.close()


def test_ptpu_ckpt_cli_and_pplint(tmp_path):
    """Satellite: the ptpu_ckpt CLI (inspect/verify/gc) and pplint over a
    checkpoint dir, end to end in subprocesses."""
    main, startup, loss = _build("adam")
    exe = fluid.Executor(fluid.CPUPlace())
    r = np.random.RandomState(9)
    scope = fluid.Scope()
    ck = str(tmp_path / "ck")
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = r.rand(4, 6).astype("f")
        with CheckpointManager(ck, async_save=False) as mgr:
            for s in (1, 2, 3):
                exe.run(main, feed={"x": xb, "y": xb[:, :1]},
                        fetch_list=[loss])
                mgr.save(s, program=main, scope=scope)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))

    def run(tool, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", tool)]
            + list(args), env=env, capture_output=True, text=True,
            timeout=600)

    cp = run("ptpu_ckpt.py", "inspect", ck, "--json")
    assert cp.returncode == 0, cp.stderr
    rec = json.loads(cp.stdout)
    assert rec["step"] == 3 and rec["num_vars"] > 0
    assert rec["seed_cursor"] is not None
    assert any(e.get("owner") for e in rec["vars"].values())

    assert run("ptpu_ckpt.py", "verify", ck).returncode == 0
    # dry-run: would-delete = findings (exit 1), and deletes nothing
    cp = run("ptpu_ckpt.py", "gc", ck, "--max-to-keep", "1", "--dry-run")
    assert cp.returncode == 1, cp.stdout + cp.stderr
    assert [s for s, _ in list_steps(ck)] == [1, 2, 3]
    cp = run("ptpu_ckpt.py", "gc", ck, "--max-to-keep", "1")
    assert cp.returncode == 0, cp.stderr
    assert [s for s, _ in list_steps(ck)] == [3]
    cp = run("ptpu_ckpt.py", "gc", ck, "--max-to-keep", "1", "--dry-run")
    assert cp.returncode == 0, cp.stdout + cp.stderr

    # pplint lints the recorded program of the newest valid snapshot
    cp = run("pplint.py", ck)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "0 error(s)" in cp.stdout

    # corruption: verify exits 1 and names the bad snapshot
    m = load_manifest(os.path.join(ck, "step_3"))
    victim = next(iter(m.values()))["file"]
    with open(os.path.join(ck, "step_3", victim), "r+b") as f:
        f.write(b"\xde\xad")
    cp = run("ptpu_ckpt.py", "verify", ck)
    assert cp.returncode == 1
    assert "CORRUPT" in cp.stdout
