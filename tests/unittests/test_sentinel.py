"""Training-health sentinel + SDC quarantine (ARCHITECTURE.md §29).

Headline guarantees under test:
  * the robust-statistics layer: median/MAD z-scores warm up before
    judging, survive the spikes they detect (uncontaminated baseline),
    grad-norm checks are one-sided, divergence needs sustained drift.
  * the grad-norm stat channel: `install_numeric_guards(grad_norm=True)`
    lands the global grad norm in `Executor.last_stats` after every
    dispatch — single-step and max-folded across a steps=K scan — with
    zero extra host syncs (it rides the packed guard-flag transfer).
  * rollback_skip_data is the PaLM remedy, bit-exact: an injected
    `loss_spike` in a multi-fault chaos run (reader NaN + reader
    exception + spike, one seeded stream) rolls back and routes the
    readers past the fault window, and the final params equal a clean
    run over the same surviving records, dropout and all.
  * the SDC canary: digests are stable check over check, a fault-plan
    `bitflip` is convicted on the exact check (and device) the plan
    names, the reference digest travels in state_dict, and the
    Supervisor escalates the conviction as fault class "sdc" carrying
    the typed cause.
  * the cluster quarantine protocol: a faulted heartbeat naming an
    `sdc_device` gets that device into `plan.json`'s quarantine list,
    the member's budget shrinks (or the member drops entirely), and
    `DeviceLayout` builds the training mesh around the convicted chip.

The end-to-end bitflip leg (real ptpu_elastic cohort, real quarantine,
training completing on the reduced mesh) is `multiproc`-marked beside
its host-death siblings in the slow suite.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import resilience as rz
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.checkpoint.manager import skip_reader_records
from paddle_tpu.resilience import cluster as cl
from paddle_tpu.resilience import heartbeat as hb
from paddle_tpu.resilience.sdc import CanaryChecker, SilentCorruptionError
from paddle_tpu.resilience.sentinel import (DivergenceError,
                                            LossSpikeError, RobustWindow,
                                            TrainingSentinel)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
TOOL = os.path.join(REPO, "tools", "ptpu_elastic.py")

EXE = fluid.Executor(fluid.CPUPlace())
R = np.random.RandomState(11)
DATA = [R.rand(8, 6).astype("f") for _ in range(16)]


def _feed_fn(i):
    return {"x": DATA[i % len(DATA)], "y": DATA[i % len(DATA)][:, :1]}


_CACHE = {}


def _feed_setup(grad_norm=False, guarded=True):
    """A guarded feed-fed Adam trainer; grad_norm=True adds the stat
    channel, guarded=False installs nothing (one cached program per
    mode)."""
    key = ("feed_gn" if grad_norm else "feed") if guarded else "feed_plain"
    if key not in _CACHE:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        startup.random_seed = 5
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="tanh")
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=p, label=y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        if guarded:
            rz.install_numeric_guards(main, loss=loss, grad_norm=grad_norm)
        _CACHE[key] = (main, startup, loss)
    return _CACHE[key]


def _reader_setup(tmp_factory):
    """A guarded reader-fed trainer with dropout (seed cursor
    load-bearing) over a 64-record recordio stream."""
    if "reader" not in _CACHE:
        root = tmp_factory.mktemp("sentinel_reader")

        def gen():
            r = np.random.RandomState(3)
            for _ in range(64):
                xs = r.rand(4, 6).astype("float32")
                yield xs, xs[:, :1].copy()

        path = str(root / "data.recordio")
        fluid.recordio_writer.convert_reader_to_recordio_file(path, gen)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 9
        startup.random_seed = 9
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            rdr = fluid.layers.open_recordio_file(
                filename=path, shapes=[[-1, 6], [-1, 1]],
                lod_levels=[0, 0], dtypes=["float32", "float32"])
            x, y = fluid.layers.read_file(rdr)
            h = fluid.layers.fc(input=x, size=8, act="tanh")
            h = fluid.layers.dropout(h, dropout_prob=0.2)
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=p, label=y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        rz.install_numeric_guards(main, loss=loss)
        _CACHE["reader"] = (main, startup, loss)
    return _CACHE["reader"]


def _persisted(scope):
    from paddle_tpu.core.readers import ReaderBase
    return {n: np.asarray(scope.get(n)).copy() for n in scope.names()
            if not isinstance(scope.get(n), ReaderBase)
            and scope.get(n) is not None}


def _assert_state_equal(a, b):
    assert set(a) == set(b), sorted(set(a) ^ set(b))
    for n in a:
        np.testing.assert_array_equal(
            a[n], b[n], err_msg="state %r diverged" % n)


def _live_reader(sup):
    states = sup._reader_states()
    assert len(states) == 1
    return states[0]


# ------------------------------------------------------------ sentinel --
def test_robust_window_warmup_and_outlier_resistance():
    """No verdicts before `warmup` samples (a 3-point median is noise),
    and the baseline is ROBUST: with the window stuffed by clean
    samples, one huge value scores an enormous z — but pushing it
    moves the median by at most one rank, so the NEXT clean sample
    still scores small (mean/stddev would have been dragged)."""
    w = RobustWindow(window=16, warmup=8)
    for i in range(7):
        assert w.zscore(100.0) is None  # warmup: no baseline yet
        w.push(1.0 + 0.01 * i)
    assert not w.ready
    w.push(1.07)
    assert w.ready
    assert abs(w.zscore(1.04)) < 3.0
    assert w.zscore(1e6) > 1e3
    # contaminate deliberately: the median barely moves
    med0 = w.median()
    w.push(1e6)
    assert abs(w.median() - med0) < 0.1
    assert abs(w.zscore(1.04)) < 5.0
    # state roundtrip
    w2 = RobustWindow(window=16, warmup=8)
    w2.load_state_dict(w.state_dict())
    assert w2.median() == w.median() and len(w2) == len(w)
    w2.reset()
    assert len(w2) == 0 and w2.zscore(1.0) is None


def test_sentinel_loss_spike_and_clean_baseline():
    """A x1000 loss after a steady window returns LossSpikeError (not
    raises — the Supervisor decides); the spiked sample is never folded
    in, so the window still judges the next samples off the CLEAN
    baseline. Non-finite host losses are spikes with infinite z."""
    s = TrainingSentinel(window=32, warmup=8, z_threshold=8.0)
    r = np.random.RandomState(0)
    for i in range(12):
        assert s.observe(1.0 + 0.01 * r.rand(), step=i) is None
    err = s.observe(1000.0, step=12)
    assert isinstance(err, LossSpikeError)
    assert err.metric == "loss" and err.step == 12
    assert err.zscore > 8.0 and err.value == 1000.0
    assert s.spikes == 1
    # baseline uncontaminated: the next ordinary sample is clean
    assert s.observe(1.005, step=13) is None
    # a second spike still trips (the first never entered the window)
    assert isinstance(s.observe(900.0, step=14), LossSpikeError)
    # non-finite at the host (guards off / unwatched loss)
    err = s.observe(float("nan"), step=15)
    assert isinstance(err, LossSpikeError) and err.zscore == float("inf")
    st = s.status()
    assert st["spikes"] == 3 and st["samples"] == 13
    assert st["z"] is None  # inf is not JSON-able: masked to None


def test_sentinel_grad_blowup_one_sided():
    """The grad-norm check trips on blowups only: a COLLAPSING norm is
    convergence, not a fault."""
    s = TrainingSentinel(window=32, warmup=8, z_threshold=8.0,
                         grad_z_threshold=6.0)
    r = np.random.RandomState(1)
    for i in range(12):
        assert s.observe(1.0, grad_norm=2.0 + 0.05 * r.rand(),
                         step=i) is None
    # collapse: far below the window, but one-sided => clean
    assert s.observe(1.0, grad_norm=1e-6, step=12) is None
    err = s.observe(1.0, grad_norm=1e6, step=13)
    assert isinstance(err, LossSpikeError)
    assert err.metric == "grad_norm" and err.zscore > 6.0
    # a non-finite norm that slipped past the device guards
    err = s.observe(1.0, grad_norm=float("inf"), step=14)
    assert isinstance(err, LossSpikeError) and err.metric == "grad_norm"


def test_sentinel_divergence_needs_sustained_drift():
    """Drift the z-score is blind to (every step near its neighbors,
    the window walking away from the best median) trips DivergenceError
    only after `divergence_patience` consecutive bad steps; a dip back
    under the factor resets the trend."""
    s = TrainingSentinel(window=8, warmup=4, z_threshold=50.0,
                         divergence_factor=2.0, divergence_patience=6)
    r = np.random.RandomState(2)

    def sample(i):
        # 0.02/step drift under 0.2-wide jitter: each sample sits a few
        # MADs off its window at most, while the median walks away
        return 1.0 + 0.02 * i + 0.2 * r.rand()

    out, tripped_at = None, None
    for i in range(200):
        out = s.observe(sample(i), step=i)
        if out is not None:
            tripped_at = i
            break
    assert isinstance(out, DivergenceError), out
    assert out.value > 2.0 * out.best
    assert tripped_at > 40  # drift, detected late — not a one-off spike
    assert s.spikes == 0    # never mistaken for a bad batch
    # state roundtrip preserves the trend bookkeeping
    s3 = TrainingSentinel(window=8, warmup=4, z_threshold=50.0,
                          divergence_factor=2.0, divergence_patience=6)
    s3.load_state_dict(s.state_dict())
    assert s3.state_dict() == s.state_dict()
    s3.reset()
    assert s3.state_dict()["loss_win"] == {"values": []}


def test_grad_norm_stat_channel(tmp_path):
    """grad_norm=True: the global grad norm rides the packed guard-flag
    vector (a "stat" channel, max-folded across steps=K) into
    Executor.last_stats — finite, positive, present after every
    dispatch, and the K-block's value is the max over its steps."""
    main, startup, loss = _feed_setup(grad_norm=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        EXE.run(main, feed=_feed_fn(0), fetch_list=[loss])
        g1 = EXE.last_stats.get("grad_norm")
        assert g1 is not None and np.isfinite(g1) and float(g1) > 0
        # steps=K (same feed every in-block step — stacked per-step
        # feeds are reader machinery): one dispatch, stat max-folded
        EXE.run(main, feed=_feed_fn(1), fetch_list=[loss], steps=4,
                fetch_reduce="last")
        gk = EXE.last_stats.get("grad_norm")
        assert gk is not None and np.isfinite(gk) and float(gk) > 0
    # the sentinel consumes exactly this channel
    s = TrainingSentinel(window=8, warmup=4)
    for i in range(6):
        assert s.observe(1.0, grad_norm=float(g1), step=i) is None
    assert isinstance(
        s.observe(1.0, grad_norm=float(g1) * 1e8, step=6),
        LossSpikeError)


@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("watched", ["guards", "sentinel"])
def test_watching_a_trainer_adds_no_host_read_to_a_step(watched, steps):
    """What "the sentinel is on everywhere" may cost, as a count and not a
    share of a CPU's milliseconds: a watched step records the spans an
    unwatched one records, one exec/d2h among them (the fetch the caller
    asked for), and books the one host sync it books. The guards' flags
    and the grad-norm statistic ride the dispatch's own outputs; observe()
    is host arithmetic on values already fetched. Plain and steps=K."""
    from paddle_tpu import profiler
    from paddle_tpu.observability import trace

    run_kw = {"steps": steps, "fetch_reduce": "last"} if steps > 1 else {}
    sentinel = TrainingSentinel(window=8, warmup=4)

    def steady_step(main, startup, loss, observe):
        """(span names, host syncs by tag) of one run after a warm one."""
        with fluid.scope_guard(fluid.Scope()):
            EXE.run(startup)
            EXE.run(main, feed=_feed_fn(0), fetch_list=[loss], **run_kw)
            trace.configure(capacity=4096)
            profiler.reset_profiler()
            out = EXE.run(main, feed=_feed_fn(1), fetch_list=[loss],
                          **run_kw)
            if observe:
                gn = EXE.last_stats["grad_norm"]
                assert sentinel.observe(
                    float(np.asarray(out[0]).reshape(-1)[0]),
                    grad_norm=float(np.asarray(gn)), step=0) is None
            names = [ev["name"] for ev in trace.dump()["events"]]
            return names, profiler.sync_stats()["by_tag"]

    plain_names, plain_syncs = steady_step(*_feed_setup(guarded=False),
                                           observe=False)
    names, syncs = steady_step(*_feed_setup(grad_norm=watched == "sentinel"),
                               observe=watched == "sentinel")
    assert plain_names.count("exec/d2h") == 1
    assert plain_syncs == {"executor/return_numpy": 1}
    assert names == plain_names
    assert syncs == plain_syncs


# -------------------------------------------------------- fault kinds --
def test_fault_plan_parses_sentinel_kinds():
    """loss_spike@N[:mag] / grad_blowup@N / bitflip@N[:device] parse,
    one-shot by default, with the documented magnitude defaults."""
    from paddle_tpu.resilience.faults import _spike_mag
    p = rz.FaultPlan.from_env(
        "loss_spike@3:50;grad_blowup@5;bitflip@1:1")
    kinds = sorted(e.kind for e in p.entries)
    assert kinds == ["bitflip", "grad_blowup", "loss_spike"]
    assert all(not e.repeat for e in p.entries)
    by_kind = {e.kind: e for e in p.entries}
    assert _spike_mag(by_kind["loss_spike"]) == 50.0
    assert _spike_mag(by_kind["grad_blowup"]) == 1e6
    assert by_kind["bitflip"].arg == 1.0
    with pytest.raises(ValueError):
        rz.FaultPlan(["bit_flip@1"])  # typo'd kinds fail loudly


def test_loss_spike_feed_seam_is_finite_and_one_shot():
    """The feed-seam loss_spike scales every float feed by a FINITE
    magnitude (no guard trip — only statistics can see it) exactly
    once."""
    main, startup, loss = _feed_setup()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        vals = []
        with rz.FaultPlan(["loss_spike@1:100"]) as plan:
            for i in range(3):
                plan.set_step(i)
                out, = EXE.run(main, feed=_feed_fn(0), fetch_list=[loss])
                vals.append(float(np.asarray(out).reshape(-1)[0]))
        assert all(np.isfinite(v) for v in vals)
        # the spiked step's loss is orders of magnitude off its
        # neighbors; the step after is back near baseline
        assert vals[1] > 100.0 * max(vals[0], vals[2])


# ---------------------------------------------------------- SDC canary --
def test_canary_digest_stable_and_reference_travels():
    """Five healthy checks: one stable digest (fixed input, fixed
    program, same device). The reference travels in state_dict so a
    restore compares against the ORIGINAL healthy reading."""
    c = CanaryChecker(shape=(32, 32), seed=1, iters=2)
    ref = c.record_reference()
    for _ in range(4):
        assert c.check() == ref
    assert c.checks == 5 and c.mismatches == 0
    assert c.status()["reference"] == ref
    c2 = CanaryChecker(shape=(32, 32), seed=1, iters=2)
    c2.load_state_dict(c.state_dict())
    assert c2.reference == ref and c2.checks == 5
    assert c2.check() == ref  # compares against the carried reference
    # a different seed is a DIFFERENT canary: digest differs
    assert CanaryChecker(shape=(32, 32), seed=2,
                         iters=2).record_reference() != ref
    with pytest.raises(ValueError):
        CanaryChecker(shape=(32, 16))  # y @ y.T needs square


def test_bitflip_convicts_exact_check_then_healthy():
    """bitflip@2: checks 0 (reference) and 1 pass, check 2 raises the
    typed conviction naming the device, and — one-shot — check 3 is
    healthy again. The flip is ONE bit of one element: invisible to
    finiteness guards, fatal to the digest."""
    c = CanaryChecker(shape=(32, 32), seed=0, iters=2)
    with rz.FaultPlan(["bitflip@2"]):
        ref = c.record_reference()      # check 0
        assert c.check() == ref          # check 1
        with pytest.raises(SilentCorruptionError) as ei:
            c.check()                    # check 2: convicted
        assert ei.value.device_index == 2 % len(c.devices())
        assert ei.value.expected == ref and ei.value.got != ref
        assert c.mismatches == 1
        assert c.check() == ref          # one-shot: healthy again
    # verdict history records the mismatch for the status surface
    assert [v["ok"] for v in c.verdicts] == [True, True, False, True]


def test_supervisor_sdc_abort_carries_cause(tmp_path):
    """Supervisor + sdc_every=1: the canary runs after each completed
    step; a bitflip conviction routes through fault class "sdc" whose
    default chain is abort — TrainingAborted carries the typed cause
    (the elastic worker reads device_index off it to escalate)."""
    main, startup, loss = _feed_setup()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        sup = rz.Supervisor(
            EXE, main, scope=scope,
            sdc=CanaryChecker(shape=(16, 16), iters=1), sdc_every=1)
        try:
            with rz.FaultPlan(["bitflip@1"]):
                with pytest.raises(rz.TrainingAborted) as ei:
                    sup.train(6, feed_fn=_feed_fn, fetch_list=[loss])
        finally:
            sup.close()
    assert isinstance(ei.value.cause, SilentCorruptionError)
    assert ei.value.cause.device_index == 1 % len(sup.sdc.devices())
    acts = [(e["class"], e["action"]) for e in sup.events]
    assert ("sdc", "abort") in acts
    # the conviction happened AFTER a completed step, not instead of it
    assert sup.step >= 1


# ------------------------------------------------- skip-window machinery --
def test_skip_reader_records_unit(tmp_path_factory):
    """skip_reader_records advances a live reader by exactly N records
    (per-reader dict or flat int), and EOF propagates instead of being
    swallowed (end of data ends the caller's loop cleanly)."""
    from paddle_tpu.core.readers import EOFException
    main, startup, loss = _reader_setup(tmp_path_factory)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        EXE.run(main, fetch_list=[loss])  # opens the live reader
        sup = rz.Supervisor(EXE, main, scope=scope)
        try:
            name, state = _live_reader(sup)
        finally:
            sup.close()
        at = int(state._consumed)
        assert skip_reader_records(scope, [name], 5) == 5
        assert int(state._consumed) == at + 5
        assert skip_reader_records(scope, {name: 0}, {name: 3}) == 3
        assert int(state._consumed) == at + 8
        with pytest.raises(EOFException):
            skip_reader_records(scope, [name], 10_000)


def test_checkpoint_restore_skip_records(tmp_path, tmp_path_factory):
    """restore(skip_records=K) lands reader positions at snapshot + K:
    the from-scratch-resume side of the rollback_skip_data equality."""
    main, startup, loss = _reader_setup(tmp_path_factory)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        for _ in range(4):
            EXE.run(main, fetch_list=[loss])
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        try:
            mgr.save(4, program=main, scope=scope)
            for _ in range(3):
                EXE.run(main, fetch_list=[loss])  # drift past the save
            sup = rz.Supervisor(EXE, main, scope=scope)
            try:
                name, state = _live_reader(sup)
            finally:
                sup.close()
            assert int(state._consumed) == 7
            assert mgr.restore(program=main, scope=scope, step=4,
                               skip_records=2) == 4
            state = scope.get(name)
            assert int(state._consumed) == 4 + 2
        finally:
            mgr.close()


# ------------------------------------------------- chaos soak: the claim --
def test_chaos_soak_rollback_skip_bit_exact(tmp_path, tmp_path_factory):
    """THE acceptance leg. One seeded reader stream, three composed
    faults after the step-8 snapshot — reader_nan@9 (guard trip, exact
    skip), reader_exc@10 (worker-thread fault, exact skip), and
    loss_spike@12 (finite x1000 batch only the sentinel can see). The
    spike triggers rollback_skip_data(skip=1): restore step 8, advance
    the stream past everything consumed since (records 8..13). Final
    params must be BIT-EXACT vs a clean run that trained records 0..7,
    skipped records 8..13, and continued on 14.. — the PaLM-style
    "resume over a stream that never contained those records"."""
    main, startup, loss = _reader_setup(tmp_path_factory)

    # ---- reference: clean run over the surviving stream ------------
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        EXE.run(startup)
        sup_a = rz.Supervisor(EXE, main, scope=scope_a)
        try:
            sup_a.train(8, fetch_list=[loss])
            name, state = _live_reader(sup_a)
            assert int(state._consumed) == 8
            assert skip_reader_records(scope_a, [name], 6) == 6
            sup_a.train(16, fetch_list=[loss])
        finally:
            sup_a.close()
        assert int(scope_a.get(name)._consumed) == 22
        final_a = _persisted(scope_a)

    # ---- chaos run: sentinel + composed faults ----------------------
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        EXE.run(startup)
        mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
        sentinel = TrainingSentinel(window=32, warmup=6, z_threshold=50.0)
        sup_b = rz.Supervisor(
            EXE, main, scope=scope_b, checkpoint_manager=mgr,
            sentinel=sentinel,
            policies={
                "numeric": [rz.skip_batch(times=2), rz.abort()],
                "reader": [rz.skip_batch(times=2), rz.abort()],
                "loss_spike": [rz.rollback_skip_data(times=2, skip=1),
                               rz.abort()],
            })
        plan = rz.FaultPlan(["reader_nan@9", "reader_exc@10",
                             "loss_spike@12"]).arm()
        try:
            sup_b.train(16, fetch_list=[loss], checkpoint_every=8)
        finally:
            plan.disarm()
            sup_b.close()
            mgr.close()
        final_b = _persisted(scope_b)

    acts = [(e["class"], e["action"]) for e in sup_b.events]
    assert ("numeric", "skip_batch") in acts     # reader_nan@9
    assert ("reader", "skip_batch") in acts      # reader_exc@10
    assert ("loss_spike", "rollback") in acts    # restore step 8
    assert ("loss_spike", "rollback_skip") in acts
    skip_ev = [e for e in sup_b.events
               if e["action"] == "rollback_skip"][0]
    assert "skipped 6 records" in skip_ev["detail"]
    assert sentinel.spikes == 1  # exactly the injected spike, no noise
    assert sup_b.step == 16
    _assert_state_equal(final_a, final_b)


def test_rollback_skip_feed_fed_degrades_to_rollback(tmp_path):
    """A feed-fed program has no reader streams to route around: the
    action degrades to a plain rollback with a logged note, and the
    caller's feed_fn decides what the restored step sees."""
    main, startup, loss = _feed_setup()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        sup = rz.Supervisor(
            EXE, main, scope=scope, checkpoint_manager=mgr,
            sentinel=TrainingSentinel(window=16, warmup=4,
                                      z_threshold=50.0),
            policies={"loss_spike": [rz.rollback_skip_data(times=1),
                                     rz.abort()]})
        plan = rz.FaultPlan(["loss_spike@6:1000"]).arm()
        try:
            sup.train(10, feed_fn=_feed_fn, fetch_list=[loss],
                      checkpoint_every=4)
        finally:
            plan.disarm()
            sup.close()
            mgr.close()
    ev = [e for e in sup.events if e["action"] == "rollback_skip"]
    assert ev and "no in-graph readers" in ev[0]["detail"]
    assert sup.step == 10


# ----------------------------------------------------------- quarantine --
def test_assign_world_subtracts_quarantine(tmp_path):
    """The coordinator's device-budget split subtracts each member's
    quarantined devices; a fully-quarantined member is dropped and the
    budget re-splits over the survivors with contiguous ranks."""
    coord = cl.ClusterCoordinator(str(tmp_path), num_workers=2,
                                  total_device_count=4)
    coord.quarantine = {"w0": [1]}
    world = coord._assign_world(["w0", "w1"])
    assert world["w0"]["local_device_count"] == 1
    assert world["w1"]["local_device_count"] == 2
    assert sorted(w["rank"] for w in world.values()) == [0, 1]
    # full quarantine: the member drops, the survivor takes the budget
    coord.quarantine = {"w0": [0, 1]}
    world = coord._assign_world(["w0", "w1"])
    assert sorted(world) == ["w1"]
    assert world["w1"] == {"rank": 0, "local_device_count": 4}
    # every device everywhere convicted: nothing to assign
    coord.quarantine = {"w0": [0, 1], "w1": [0, 1, 2, 3]}
    assert coord._assign_world(["w0", "w1"]) == {}


def test_device_layout_builds_around_quarantine():
    """DeviceLayout.skip_local_devices: JSON roundtrip, filtered
    local_devices, and a LOUD refusal when quarantine leaves fewer
    usable devices than the layout wants."""
    import jax
    lay = cl.DeviceLayout(local_device_count=1, skip_local_devices=[0])
    assert lay.to_json()["skip_local_devices"] == [0]
    back = cl.DeviceLayout.from_json(lay.to_json())
    assert back == lay and back.skip_local_devices == (0,)
    assert "quarantined" in repr(back)
    assert jax.devices()[0] not in lay.local_devices()
    # every device convicted: the mesh refuses loudly, never shrinks
    # silently under the cohort's divisibility contract
    all_q = cl.DeviceLayout(
        local_device_count=1,
        skip_local_devices=range(len(jax.devices())))
    assert all_q.local_devices() == []
    with pytest.raises(ValueError) as ei:
        all_q.local_mesh()
    assert "quarantined" in str(ei.value)
    # no quarantine: key absent from JSON (older plans stay readable)
    assert "skip_local_devices" not in \
        cl.DeviceLayout(local_device_count=1).to_json()


def test_coordinator_quarantines_sdc_device(tmp_path):
    """A faulted heartbeat naming `sdc_device` quarantines that device:
    "quarantine" event, the list in every subsequent plan, and the
    member's mesh budget reduced in the rescale — per-DEVICE surgery,
    not a whole-host fence-out."""
    from paddle_tpu.checkpoint.snapshot import write_snapshot
    from tests.unittests.test_elastic_cluster import (FakeWorker,
                                                      _coord_thread,
                                                      _wait_event)
    d = str(tmp_path)
    write_snapshot(cl.default_checkpoint_dir(d), 5,
                   [("a", {}, np.zeros(2, "f"))], {"seed_cursor": 0})
    coord = cl.ClusterCoordinator(d, num_workers=2,
                                  heartbeat_timeout=2.0,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  total_device_count=4, allow_grow=False)
    a = FakeWorker(d, "wa").start()
    b = FakeWorker(d, "wb").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        gen = cl.read_plan(d)["gen"]
        # wb's canary convicted its local device 1
        b.fault(gen, fault="SilentCorruptionError('canary mismatch')",
                sdc_device=1)
        q = _wait_event(coord, "quarantine")
        assert q["worker"] == "wb" and q["device"] == 1
        ev = _wait_event(coord, "rescale")
        assert sorted(ev["survivors"]) == ["wa", "wb"]
        assert ev["quarantine"] == {"wb": [1]}
        plan = cl.read_plan(d)
        assert plan["quarantine"] == {"wb": [1]}
        assert plan["world"]["wb"]["local_device_count"] == 1
        assert plan["world"]["wa"]["local_device_count"] == 2
        a.finish()
        b.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        b.close()


def test_fleet_view_training_health_fields(tmp_path):
    """Heartbeats carry the WHY: sentinel z/spikes, canary status, the
    escalated fault repr and sdc_device ride fleet_view() — the single
    derivation `ptpu_elastic status` and the metrics collector share —
    and the cluster collector renders them as gauge families."""
    from paddle_tpu.observability import registry as obsreg
    d = str(tmp_path / "el")
    w = hb.HeartbeatWriter(d, "w0")
    w.update(status="fault", step=9,
             sentinel={"z": 1.5, "grad_z": None, "spikes": 2,
                       "samples": 40},
             sdc={"checks": 5, "mismatches": 1, "last_device": 1,
                  "reference": "abc"},
             fault="SilentCorruptionError('mismatch')", sdc_device=1)
    cl.write_plan(d, {"gen": 1, "phase": "run",
                      "world": {"w0": {"rank": 0}},
                      "quarantine": {"w0": [1]}})
    rows = hb.HeartbeatMonitor(d, timeout=5.0).fleet_view()
    assert len(rows) == 1
    r = rows[0]
    assert r["sentinel"]["spikes"] == 2 and r["sentinel"]["z"] == 1.5
    assert r["sdc"]["mismatches"] == 1
    assert r["sdc_device"] == 1 and "SilentCorruption" in r["fault"]
    reg = obsreg.MetricsRegistry()
    obsreg.watch_cluster(d, registry=reg)
    try:
        text = reg.render_prometheus()
        lbl = 'cluster="el",worker="w0"'
        assert 'ptpu_cluster_worker_loss_zscore{%s} 1.5' % lbl in text
        assert ('ptpu_cluster_worker_loss_spikes_total{%s} 2'
                % lbl) in text
        assert ('ptpu_cluster_worker_sdc_mismatches_total{%s} 1'
                % lbl) in text
        assert 'ptpu_cluster_quarantined_devices{%s} 1' % lbl in text
    finally:
        obsreg.unwatch_cluster(d, registry=reg)

    # the status CLI prints the same story: quarantine in the plan
    # line, per-worker columns, and the fault detail line
    out = subprocess.run(
        [sys.executable, TOOL, "status", "--cluster-dir", d, "--json"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["plan"]["quarantine"] == {"w0": [1]}
    w0 = [r for r in payload["workers"] if r["worker"] == "w0"][0]
    assert w0["sdc_device"] == 1 and w0["sentinel"]["spikes"] == 2


@pytest.mark.multiproc
@pytest.mark.slow  # subprocess cohort, beside its host-death siblings
def test_bitflip_quarantine_end_to_end(tmp_path):
    """THE quarantine acceptance leg: a real ptpu_elastic cohort (one
    worker, two virtual devices, canary every 2 steps) with bitflip
    armed to convict local device 1. The coordinator must quarantine
    exactly that device, reshard the worker onto the surviving 1-device
    mesh, and training must COMPLETE there — zero aborted steps, rc 0,
    the quarantine visible in the final plan."""
    d = str(tmp_path / "cluster")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("PTPU_FAULT_PLAN", None)
    cp = subprocess.run(
        [sys.executable, TOOL, "launch", "--cluster-dir", d,
         "--workers", "1", "--steps", "12", "--host-devices", "2",
         "--local-devices", "2", "--step-delay", "0.05",
         "--sdc-every", "2",
         "--fault-worker", "0", "--fault-plan", "bitflip@1:1",
         "--deadline", "240"],
        env=env, capture_output=True, text=True, timeout=420)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert '"quarantine"' in cp.stdout
    summary = json.loads(cp.stdout.strip().splitlines()[-1]
                         .split("done: ", 1)[1])
    assert summary["steps"]["w0"] == 12
    plan = cl.read_plan(d)
    assert plan["quarantine"] == {"w0": [1]}
    assert plan["world"]["w0"]["local_device_count"] == 1
