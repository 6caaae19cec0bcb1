"""Persistent AOT compile-artifact cache (core/compile_cache.py).

The contract under test, in order of how much it matters:
  1. correctness is never at stake — a cache hit is BIT-IDENTICAL to a
     fresh compile, and every failure mode (torn entry, bit flip, hand
     edit, call-time rejection) falls back to a fresh compile;
  2. a warm process start pays ZERO fresh compiles (the subprocess leg,
     asserted via the profiler counter);
  3. invalidation is structural: jax version / device / program edits /
     trace-env flags are inside the hashed key, so a changed environment
     MISSES rather than loads a stale artifact.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _build_model(hidden=16, layers=3, seed_layer=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[hidden], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(input=h, size=hidden, act="relu")
        if seed_layer:
            h = fluid.layers.dropout(h, dropout_prob=0.3)
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(hidden=16, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, hidden).astype("float32"),
            "y": rng.rand(batch, 1).astype("float32")}


@pytest.fixture
def aot_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "aot")
    monkeypatch.setenv("FLAGS_aot_cache_dir", d)
    cc.reset_aot_stats()
    cc._warned.clear()  # warn-once dedup is per-process; tests assert
    yield d             # on warnings, so each starts fresh
    cc.reset_aot_stats()
    cc._warned.clear()


def _train(main, startup, loss, n=3, feed=None, **run_kw):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = feed or _feed()
    outs = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(n):
            outs.append(exe.run(main, feed=feed, fetch_list=[loss],
                                **run_kw)[0])
    return outs


# ------------------------------------------------------------ happy path --
def test_hit_is_bit_identical_and_skips_compiles(aot_dir):
    main, startup, loss = _build_model()
    cold = _train(main, startup, loss)
    assert cc.aot_stats()["stores"] == 2  # startup + main

    # a REBUILT byte-identical program in a fresh executor = the restart
    # shape of the problem (content-hash key, not per-process uids)
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model()
    warm = _train(main2, startup2, loss2)
    st = cc.aot_stats()
    assert st["hits"] == 2 and st["stores"] == 0, st
    assert st["saved_s"] > 0
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_multistep_key_and_hit(aot_dir, monkeypatch):
    monkeypatch.setenv("FLAGS_multistep_unroll", "0")  # cheap compile
    main, startup, loss = _build_model()
    cold = _train(main, startup, loss, n=1, steps=4, fetch_reduce="stack")
    assert cc.aot_stats()["stores"] == 2
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model()
    warm = _train(main2, startup2, loss2, n=1, steps=4,
                  fetch_reduce="stack")
    assert cc.aot_stats()["hits"] == 2, cc.aot_stats()
    assert np.array_equal(cold[0], warm[0])
    # a different K is a different artifact, never a wrong-shaped hit
    cc.reset_aot_stats()
    main3, startup3, loss3 = _build_model()
    _train(main3, startup3, loss3, n=1, steps=2, fetch_reduce="stack")
    st = cc.aot_stats()
    assert st["hits"] == 1 and st["stores"] == 1, st  # startup hits only


def test_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("FLAGS_aot_cache_dir", raising=False)
    monkeypatch.setattr(cc, "_aot_default_dir", None)
    cc.reset_aot_stats()
    main, startup, loss = _build_model()
    _train(main, startup, loss)
    st = cc.aot_stats()
    assert st == {"hits": 0, "misses": 0, "stores": 0,
                  "store_errors": 0, "load_errors": 0, "saved_s": 0.0}
    # explicit empty = off even when a default was enabled
    monkeypatch.setattr(cc, "_aot_default_dir", str(tmp_path / "dflt"))
    monkeypatch.setenv("FLAGS_aot_cache_dir", "")
    assert cc.active_aot_cache_dir() is None
    monkeypatch.delenv("FLAGS_aot_cache_dir")
    assert cc.active_aot_cache_dir() == str(tmp_path / "dflt")


# ------------------------------------------------------------ invalidation
def test_program_edit_re_keys(aot_dir):
    main, startup, loss = _build_model(layers=2)
    _train(main, startup, loss)
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model(layers=3)  # edited model
    _train(main2, startup2, loss2)
    st = cc.aot_stats()
    # startup differs too (one more fc init): nothing may hit
    assert st["hits"] == 0 and st["stores"] == 2, st


def test_trace_env_flag_re_keys(aot_dir, monkeypatch):
    main, startup, loss = _build_model()
    _train(main, startup, loss)
    cc.reset_aot_stats()
    # a trace-time env flag flip must miss, not serve the other config
    monkeypatch.setenv("FLAGS_flash_min_seq", "64")
    main2, startup2, loss2 = _build_model()
    _train(main2, startup2, loss2)
    st = cc.aot_stats()
    assert st["hits"] == 0 and st["stores"] == 2, st


def test_stale_jax_version_never_loads(aot_dir):
    """A jax upgrade changes the hashed key (miss), and a hand-edited
    entry claiming the current version for foreign bytes fails the
    key-material check — either way the stale artifact never loads."""
    main, startup, loss = _build_model()
    cold = _train(main, startup, loss)
    entries = cc.list_entries(aot_dir)
    assert len(entries) == 2
    # simulate "written by another jax": rewrite the recorded version
    for path, meta in entries:
        meta["key"]["jax_version"] = "0.0.1-other"
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model()
    with pytest.warns(RuntimeWarning, match="not loadable"):
        warm = _train(main2, startup2, loss2)
    st = cc.aot_stats()
    assert st["hits"] == 0 and st["load_errors"] >= 1, st
    assert st["stores"] == 2  # re-published fresh artifacts
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_corrupt_payload_skipped_with_warning(aot_dir):
    """The acceptance bit-flip case: a flipped artifact byte fails the
    sha256 check BEFORE deserialization (the payload is a pickle — the
    hash gate is what makes loading it safe), warns, and compiles
    fresh with identical results."""
    main, startup, loss = _build_model()
    cold = _train(main, startup, loss)
    flipped = 0
    for path, meta in cc.list_entries(aot_dir):
        p = os.path.join(path, "payload.bin")
        blob = bytearray(open(p, "rb").read())
        blob[len(blob) // 2] ^= 0x40
        open(p, "wb").write(bytes(blob))
        flipped += 1
    assert flipped == 2
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model()
    with pytest.warns(RuntimeWarning, match="sha256 mismatch"):
        warm = _train(main2, startup2, loss2)
    st = cc.aot_stats()
    assert st["hits"] == 0 and st["load_errors"] == 2, st
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_torn_meta_skipped(aot_dir):
    main, startup, loss = _build_model()
    cold = _train(main, startup, loss)
    for path, _ in cc.list_entries(aot_dir):
        with open(os.path.join(path, "meta.json"), "w") as f:
            f.write('{"format_version": 1, "key_ha')  # torn write
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model()
    with pytest.warns(RuntimeWarning, match="not loadable"):
        warm = _train(main2, startup2, loss2)
    assert cc.aot_stats()["hits"] == 0
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_unserializable_program_skips_cache(aot_dir, monkeypatch):
    """A program the desc format can't hash runs exactly as before —
    in-process jit cache only, one warning, no store attempts."""
    from paddle_tpu.core import program_desc
    def boom(program):
        raise ValueError("not serializable (test)")
    monkeypatch.setattr(program_desc, "program_to_bytes", boom)
    cc._program_hash_cache.clear()
    main, startup, loss = _build_model()
    with pytest.warns(RuntimeWarning, match="not serializable"):
        _train(main, startup, loss)
    st = cc.aot_stats()
    assert st["stores"] == 0 and st["hits"] == 0 and st["misses"] == 0
    cc._program_hash_cache.clear()


# ------------------------------------------------- seeding / determinism --
def test_seeded_program_hit_replays_rng_stream(aot_dir):
    """Dropout rides the per-run seed argument, not the artifact: a
    cached executable must produce the same per-step stream a fresh
    compile would for the same seed cursor."""
    main, startup, loss = _build_model(seed_layer=True)
    cold = _train(main, startup, loss, n=4)
    cc.reset_aot_stats()
    main2, startup2, loss2 = _build_model(seed_layer=True)
    warm = _train(main2, startup2, loss2, n=4)
    assert cc.aot_stats()["hits"] == 2
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_restart_from_a_checkpoint_reenters_from_the_cache(aot_dir,
                                                          tmp_path):
    """The trainer's restart (and the supervisor's rollback re-entry): a
    rebuilt program in a fresh executor restores the saved step and takes
    its next one from the cache (no fresh compile), bit-identical to the
    run that was never interrupted."""
    from paddle_tpu.checkpoint import CheckpointManager
    feed = _feed()

    def trainer():
        main, startup, loss = _build_model()
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        return main, loss, exe, scope

    def steps(main, loss, exe, scope, n):
        with fluid.scope_guard(scope):
            return [exe.run(main, feed=feed, fetch_list=[loss])[0]
                    for _ in range(n)]

    main, loss, exe, scope = trainer()
    steps(main, loss, exe, scope, 4)
    with CheckpointManager(str(tmp_path / "ckpt"), async_save=False) as mgr:
        mgr.save(4, program=main, scope=scope)
    want = steps(main, loss, exe, scope, 2)       # steps 5 and 6, unbroken

    cc.reset_aot_stats()
    main2, loss2, exe2, scope2 = trainer()
    with CheckpointManager(str(tmp_path / "ckpt"), async_save=False) as mgr:
        assert mgr.restore(program=main2, scope=scope2) == 4
    got = steps(main2, loss2, exe2, scope2, 2)
    st = cc.aot_stats()
    assert st["stores"] == 0 and st["hits"] == 2 and st["load_errors"] == 0
    for a, b in zip(want, got):
        assert np.array_equal(a, b)


# ------------------------------------------------------------ cross-process
_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache as cc

main, startup = fluid.Program(), fluid.Program()
with fluid.unique_name.guard(), fluid.program_guard(main, startup):
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    h = fluid.layers.fc(input=h, size=16, act="relu")
    p = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(
        x=fluid.layers.square_error_cost(input=p, label=y))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

rng = np.random.RandomState(0)
feed = {"x": rng.rand(8, 16).astype("f"),
        "y": rng.rand(8, 1).astype("f")}
exe = fluid.Executor(fluid.CPUPlace())
scope = fluid.Scope()
profiler.reset_profiler()
profiler._active = True  # counters only; no jax trace dir side effects
outs = []
with fluid.scope_guard(scope):
    exe.run(startup)
    for i in range(3):
        outs.append(exe.run(main, feed=feed, fetch_list=[loss])[0])
profiler._active = False
print(json.dumps({
    "fetches": [float(o.reshape(-1)[0]) for o in outs],
    "profiler": profiler.cache_stats(),
    "aot": cc.aot_stats(),
}))
"""


def test_cross_process_cache_hit_zero_compiles(aot_dir):
    """THE acceptance test: run a program, restart in a fresh process
    with the same cache dir — zero new compiles (profiler counter) and
    bit-identical fetches."""
    def run_child():
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({"JAX_PLATFORMS": "cpu",
                    "FLAGS_aot_cache_dir": aot_dir})
        out = subprocess.run(
            [sys.executable, "-c", _CHILD % {"repo": REPO}], env=env,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run_child()
    assert cold["profiler"]["compiles"] == 2       # startup + main
    assert cold["aot"]["stores"] == 2
    warm = run_child()
    assert warm["profiler"]["compiles"] == 0, warm  # ZERO new compiles
    assert warm["profiler"]["aot_hits"] == 2
    assert warm["profiler"]["saved_s"] > 0
    assert warm["aot"]["hits"] == 2 and warm["aot"]["stores"] == 0
    assert warm["fetches"] == cold["fetches"]      # bit-identical


# ------------------------------------------------------------- satellites --
def test_profile_report_shows_cache_columns(aot_dir):
    main, startup, loss = _build_model()
    _train(main, startup, loss)
    main2, startup2, loss2 = _build_model()
    profiler.reset_profiler()
    profiler._active = True
    try:
        _train(main2, startup2, loss2)
    finally:
        profiler._active = False
    report = profiler.profile_report()
    profiler.reset_profiler()
    assert "AOTHit" in report and "Saved(s)" in report
    assert "compile cache:" in report
    stats_line = [l for l in report.splitlines()
                  if l.startswith("compile cache:")][0]
    assert "2 AOT hits" in stats_line and "0 compiles" in stats_line


def test_gc_retention(aot_dir):
    main, startup, loss = _build_model()
    _train(main, startup, loss)
    entries = cc.list_entries(aot_dir)
    assert len(entries) == 2
    # age everything: would-delete under a zero-day window
    for path, meta in entries:
        meta["created_at"] = meta["created_at"] - 7 * 86400
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)
    doomed, kept = cc.gc_aot_cache(aot_dir, max_age_days=1.0,
                                   dry_run=True)
    assert len(doomed) == 2 and not kept
    assert len(cc.list_entries(aot_dir)) == 2  # dry run deletes nothing
    doomed, kept = cc.gc_aot_cache(aot_dir, max_age_days=1.0)
    assert len(doomed) == 2
    assert cc.list_entries(aot_dir) == []
    # size budget: keep newest entries under the cap
    main2, startup2, loss2 = _build_model()
    _train(main2, startup2, loss2)
    doomed, kept = cc.gc_aot_cache(aot_dir, max_total_mb=1e-6,
                                   dry_run=True)
    assert doomed  # budget smaller than any entry: all would go


def test_ptpu_cache_cli(aot_dir):
    """Subprocess leg: inspect --json, verify (0 clean / 1 corrupt),
    gc --dry-run exit semantics — the ptpu_ckpt contract."""
    main, startup, loss = _build_model()
    _train(main, startup, loss)
    tool = os.path.join(REPO, "tools", "ptpu_cache.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"

    def run(*args):
        return subprocess.run([sys.executable, tool] + list(args),
                              env=env, capture_output=True, text=True,
                              timeout=300)

    out = run("inspect", aot_dir, "--json")
    assert out.returncode == 0, out.stderr
    record = json.loads(out.stdout)
    assert len(record["entries"]) == 2
    import jax
    for e in record["entries"]:
        assert e["jax_version"] == jax.__version__
        assert e["platform"] == "cpu"
        assert e["size_bytes"] > 0 and e["program_sha256"]

    assert run("verify", aot_dir).returncode == 0
    # flip one payload byte: verify must exit 1 and name the entry
    path, _ = cc.list_entries(aot_dir)[0]
    p = os.path.join(path, "payload.bin")
    blob = bytearray(open(p, "rb").read())
    blob[10] ^= 0x01
    open(p, "wb").write(bytes(blob))
    out = run("verify", aot_dir)
    assert out.returncode == 1 and "CORRUPT" in out.stdout

    # gc: dry-run with findings exits 1, real gc exits 0 and deletes
    out = run("gc", aot_dir, "--max-age-days", "0", "--dry-run")
    assert out.returncode == 1 and "would delete: 2" in out.stdout
    assert len(cc.list_entries(aot_dir)) == 2
    out = run("gc", aot_dir, "--max-age-days", "0")
    assert out.returncode == 0
    assert cc.list_entries(aot_dir) == []
    # empty dir now: verify/inspect stay clean, bad path exits 2
    assert run("verify", aot_dir).returncode == 0
    assert run("inspect", os.path.join(aot_dir, "nope")).returncode == 2


def test_unusable_compiled_entry_falls_back_to_retrace(aot_dir):
    """With the cache on, entries are fixed-aval Compiled objects; one
    that rejects the live arguments at call time (aval drift the
    donating jit would have absorbed by retracing) must fall back to a
    fresh retracing compile, discard the disk entry, and produce the
    right answer — never surface the raw aval TypeError."""
    import jax
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _feed(batch=8)
    with fluid.scope_guard(scope):
        exe.run(startup)
        want = exe.run(main, feed=feed, fetch_list=[loss])[0]

        # plant a REAL Compiled with the wrong avals (compiled for
        # batch=4) into the in-process entry for the batch=8 key
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(main, feed=_feed(batch=4), fetch_list=[loss],
                 scope=scope)
        wrong = next(e[0] for k, e in exe2._cache.items()
                     if k[3] == (loss.name,))
        assert isinstance(wrong, jax.stages.Compiled)
        key8 = next(k for k in exe._cache if k[3] == (loss.name,))
        good = exe._cache[key8]
        exe._cache[key8] = (wrong,) + good[1:]

        cc._warned.clear()
        with pytest.warns(RuntimeWarning, match="unusable"):
            out = exe.run(main, feed=feed, fetch_list=[loss])
        # the fallback retraced and dispatched the REAL batch-8 args
        assert out[0].shape == want.shape
        assert np.isfinite(out[0]).all()
        assert cc.aot_stats()["load_errors"] >= 1
        # next run: plain warm call on the replaced entry
        out2 = exe.run(main, feed=feed, fetch_list=[loss])
        assert np.isfinite(out2[0]).all()


def test_serving_warmup_through_aot_cache(aot_dir):
    """The serving cold-start path: a second engine over the same model
    warms its whole bucket lattice from disk — zero fresh compiles —
    and serves bit-identical results."""
    from paddle_tpu.serving import InferenceEngine

    def build_engine():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main,
                                                            startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            out = fluid.layers.fc(input=h, size=2)
        infer = main.prune([out.name], for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        engine = InferenceEngine(
            program=infer, feed_names=["x"], fetch_vars=[out],
            batch_buckets=[1, 2, 4], warmup=False, validate=False)
        for name in scope.names():
            if scope.get(name) is not None:
                engine._scope.set(name, scope.get(name))
        return engine, out.name

    e1, fetch = build_engine()
    e1.warmup()
    req = {"x": np.random.RandomState(0).rand(2, 6).astype("f")}
    want = e1.run_direct(req)[0]
    e1.close()
    stores = cc.aot_stats()["stores"]
    assert stores >= 3  # one artifact per bucket

    cc.reset_aot_stats()
    e2, fetch = build_engine()
    e2.warmup()
    st = cc.aot_stats()
    assert st["stores"] == 0 and st["hits"] >= 3, st
    got = e2.run_direct(req)[0]
    e2.close()
    assert np.array_equal(want[fetch], got[fetch])


# ------------------------------------------------- devices and signatures --
def test_aot_artifact_binds_to_the_executors_own_device(aot_dir):
    """jax 0.9.0's deserialize_and_load binds an artifact to EVERY local
    device unless told otherwise (the five seed failures: "expected 8
    shards"); the executor passes its place's device, so a warm hit on a
    device other than the first works and lands there."""
    import jax

    class ThirdCpu(fluid.CPUPlace):
        device_id = 3

    def run():
        main, startup, loss = _build_model()
        exe = fluid.Executor(ThirdCpu())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            out = [exe.run(main, feed=_feed(), fetch_list=[loss])[0]
                   for _ in range(2)]
            where = {d for n in scope.names()
                     if isinstance(scope.get(n), jax.Array)
                     for d in scope.get(n).devices()}
        return out, where

    cold, _ = run()
    cc.reset_aot_stats()
    warm, where = run()
    assert cc.aot_stats()["hits"] == 2 and cc.aot_stats()["stores"] == 0
    assert where == {jax.devices("cpu")[3]}
    for a, b in zip(cold, warm):
        assert np.array_equal(a, b)


def test_committed_feeds_compile_the_step_once():
    """Feeds staged with jax.device_put(x, device) are committed and
    commit the step's outputs; the executor commits the state with them,
    or step 2 (whose state is those outputs) would lower under another
    argument signature and XLA would compile the program twice."""
    import jax

    compiles = []

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    dev = exe.place.device()
    feed = {k: jax.device_put(v, dev) for k, v in _feed().items()}
    assert all(v.committed for v in feed.values())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            assert compiles                    # step 1 compiled
            del compiles[:]
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles == []
