"""Elastic multi-host training (ARCHITECTURE.md §19): heartbeat
protocol, cluster plan, coordinator state machine (death -> fence ->
rollback -> reshard; join -> barrier-save -> grow; repeated death ->
abort with a merged bundle), the ElasticWorker loop, and the
`multiproc`-marked acceptance legs that prove the whole thing with real
OS processes and real SIGKILLs.

Coordinator-logic tests drive FAKE workers (threads speaking the
heartbeat/plan protocol, no jax) so every transition is fast and
deterministic; the multiproc legs then run the true end-to-end story:
kill one of two workers mid-run via `host_death@N`, watch the survivor
roll back and reshard onto the bigger per-worker mesh, compare its
post-rescale loss stream BIT-EXACT against a from-scratch run on the
small mesh restored from the same snapshot, and grow the cohort back
with a replacement worker with no aborted step.
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
from paddle_tpu.resilience import cluster as cl
from paddle_tpu.resilience import heartbeat as hb
from paddle_tpu.checkpoint.snapshot import write_snapshot

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
TOOL = os.path.join(REPO, "tools", "ptpu_elastic.py")


# ---------------------------------------------------------------- plan --
def test_plan_roundtrip_and_atomicity(tmp_path):
    d = str(tmp_path)
    assert cl.read_plan(d) is None
    p = cl.write_plan(d, {"gen": 1, "phase": "run",
                          "world": {"w0": {"rank": 0}}})
    assert p["wall_time"] > 0
    got = cl.read_plan(d)
    assert got["gen"] == 1 and got["phase"] == "run"
    # no tmp droppings after publish
    assert [e for e in os.listdir(d) if ".tmp." in e] == []


# ----------------------------------------------------------- heartbeat --
def test_heartbeat_writer_and_monitor(tmp_path):
    d = str(tmp_path)
    w = hb.HeartbeatWriter(d, "wA", interval=0.05)
    w.start()
    try:
        mon = hb.HeartbeatMonitor(d, timeout=5.0)
        deadline = time.monotonic() + 5
        while "wA" not in mon.poll():
            assert time.monotonic() < deadline
            time.sleep(0.01)
        view = mon.poll()["wA"]
        assert view["alive"] and view["status"] == "joining"
        w.update(status="ok", step=7, gen_acked=3)
        view = mon.poll()["wA"]
        assert view["step"] == 7 and view["gen_acked"] == 3
        # a worker that never registered is dead-by-absence
        assert mon.dead_workers(expected=["ghost"]) == ["ghost"]
    finally:
        w.close()
    # terminal status: stale but NOT dead (finished workers stop beating)
    mon_fast = hb.HeartbeatMonitor(d, timeout=0.01)
    time.sleep(0.05)
    assert mon_fast.poll()["wA"]["status"] == "left"
    assert mon_fast.poll()["wA"]["alive"]


def test_heartbeat_staleness_is_death(tmp_path):
    d = str(tmp_path)
    w = hb.HeartbeatWriter(d, "wB", interval=10.0)
    w.start()
    w.update(status="ok")
    w.close(status=None)  # stop beating, NO terminal word: a crash
    # pid is this (alive) process, so only staleness can catch it
    mon = hb.HeartbeatMonitor(d, timeout=0.2)
    time.sleep(0.4)
    assert mon.dead_workers() == ["wB"]


def test_heartbeat_stall_fault_key(tmp_path):
    """heartbeat_stall@N: fires on the step cursor, silences beat()
    for `arg` seconds (forever without one); the training loop itself
    is untouched."""
    d = str(tmp_path)
    w = hb.HeartbeatWriter(d, "wC", interval=10.0)
    plan = rz.FaultPlan(["heartbeat_stall@2:0.4"])
    with plan:
        plan.set_step(1)
        plan._executor_hook("dispatch")
        assert w.beat()            # not yet: wrong step
        plan.set_step(2)
        plan._executor_hook("dispatch")
        assert plan.heartbeat_stalled()
        assert not w.beat()        # silenced
        time.sleep(0.5)
        assert w.beat()            # finite stall expired
    # parsing: registry knows the new kinds, one-shot default
    p2 = rz.FaultPlan.from_env("host_death@5;heartbeat_stall@3")
    kinds = sorted(e.kind for e in p2.entries)
    assert kinds == ["heartbeat_stall", "host_death"]
    assert all(not e.repeat for e in p2.entries)


_HOST_DEATH_VICTIM = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, @REPO@)
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import resilience as rz
main, startup = fluid.Program(), fluid.Program()
with fluid.unique_name.guard(), fluid.program_guard(main, startup):
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    p = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(x=p)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
    plan = rz.FaultPlan.from_env().arm()
    xb = np.zeros((2, 4), "f")
    for i in range(8):
        plan.set_step(i)
        exe.run(main, feed={"x": xb}, fetch_list=[loss])
        print("STEP_%d_DONE" % i, flush=True)
print("SURVIVED")
"""


def test_host_death_kills_at_exact_step(tmp_path):
    """host_death@3 SIGKILLs the worker BEFORE step 3 consumes
    anything: steps 0-2 complete, step 3 never reports, rc is -9."""
    script = tmp_path / "victim.py"
    script.write_text(_HOST_DEATH_VICTIM.replace("@REPO@", repr(REPO)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PTPU_FAULT_PLAN="host_death@3")
    cp = subprocess.run([sys.executable, str(script)], env=env,
                        capture_output=True, text=True, timeout=600)
    assert cp.returncode == -9, (cp.returncode, cp.stdout, cp.stderr)
    assert "STEP_2_DONE" in cp.stdout
    assert "STEP_3_DONE" not in cp.stdout and "SURVIVED" not in cp.stdout


# ------------------------------------------- coordinator (fake workers) --
class FakeWorker(object):
    """Speaks the heartbeat/plan protocol without training: joins, acks
    fences (optionally with a saved_step), reports ok/done on run
    plans. `die()` stops beating with no terminal word — a crash."""

    def __init__(self, cluster_dir, wid, ack_fences=True,
                 saved_step=None):
        self.cluster_dir = str(cluster_dir)
        self.w = hb.HeartbeatWriter(cluster_dir, wid, interval=0.05)
        self.ack_fences = ack_fences
        self.saved_step = saved_step
        self.status_on_run = "ok"
        self._stop = threading.Event()
        self._seen = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self):
        self.w.start()
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(0.02):
            p = cl.read_plan(self.cluster_dir)
            if not p or p["gen"] == self._seen:
                continue
            self._seen = p["gen"]
            if p["phase"] == "fence" \
                    and self.w.worker_id in p.get("world", {}):
                if self.ack_fences:
                    fields = {"status": "fenced", "gen_acked": p["gen"],
                              "saved_step": None}
                    # the barrier save falls to the fence world's
                    # ACTING rank 0 (same rule as ElasticWorker)
                    me = p["world"][self.w.worker_id]
                    ranks = [int(v.get("rank", 1 << 30))
                             for v in p["world"].values()]
                    if p.get("save_step") \
                            and self.saved_step is not None \
                            and me.get("rank") == min(ranks):
                        fields["saved_step"] = self.saved_step
                    self.w.update(**fields)
            elif p["phase"] == "run" \
                    and self.w.worker_id in p.get("world", {}):
                self.w.update(status=self.status_on_run, gen=p["gen"],
                              step=p.get("restore_step") or 0)

    def finish(self):
        self.status_on_run = "done"
        self.w.update(status="done")

    def fault(self, gen, fault="DispatchTimeout", **why):
        """Report a fault in generation `gen`, once this worker's own ack
        of that generation's run plan has landed: the ack writes
        status_on_run and, coming second, would take the fault back."""
        deadline = time.monotonic() + 10
        while self.w.snapshot()["gen"] != gen \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        self.w.update(status="fault", gen=gen, fault=fault, **why)

    def die(self):
        self._stop.set()
        self._thread.join(1.0)
        self.w.close(status=None)  # no terminal word: a crash

    def leave(self):
        self._stop.set()
        self._thread.join(1.0)
        self.w.close(status="left")  # orderly departure, NOT done

    def close(self):
        self._stop.set()
        self._thread.join(1.0)
        self.w.close()


def _run_coord(coord, box, deadline):
    try:
        box["summary"] = coord.run(deadline=deadline)
    except cl.ClusterAborted as e:
        box["abort"] = e
    except Exception as e:  # noqa: BLE001 — surfaced by the test
        box["error"] = e


def _coord_thread(coord, deadline=30):
    box = {}
    t = threading.Thread(target=_run_coord, args=(coord, box, deadline),
                         daemon=True)
    t.start()
    return t, box


def _wait_event(coord, name, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ev = [e for e in coord.events if e["event"] == name]
        if ev:
            return ev[-1]
        time.sleep(0.02)
    raise AssertionError("no %r event; got %r"
                         % (name, [e["event"] for e in coord.events]))


def test_coordinator_death_fence_rollback_reshard(tmp_path):
    """One of two fake workers dies: fence -> survivors ack -> run plan
    pinning the newest valid snapshot, survivor's local mesh GROWN to
    the full device budget."""
    d = str(tmp_path)
    ck = cl.default_checkpoint_dir(d)
    write_snapshot(ck, 7, [("a", {}, np.zeros(2, "f"))],
                   {"seed_cursor": 0})
    coord = cl.ClusterCoordinator(d, num_workers=2, heartbeat_timeout=0.6,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  total_device_count=4, allow_grow=False)
    a = FakeWorker(d, "wa").start()
    b = FakeWorker(d, "wb").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        plan = cl.read_plan(d)
        assert plan["phase"] == "run" and plan["restore_step"] == 7
        assert plan["world"]["wa"]["local_device_count"] == 2
        b.die()
        ev = _wait_event(coord, "rescale")
        assert ev["survivors"] == ["wa"] and ev["restore_step"] == 7
        plan = cl.read_plan(d)
        # reshard: the survivor now owns the WHOLE device budget
        assert plan["world"] == {"wa": {"rank": 0,
                                        "local_device_count": 4}}
        a.finish()
        t.join(10)
        assert "summary" in box, box
        names = [e["event"] for e in coord.events]
        assert names[:2] == ["formed", "detected"]
        assert "fence" in names and "fenced" in names
    finally:
        a.close()
        b.close()


def test_coordinator_worker_fault_rolls_back_same_size(tmp_path):
    """A worker-side cluster fault (escalated DispatchTimeoutError):
    the cohort fences and rolls back together, nobody is dropped."""
    d = str(tmp_path)
    ck = cl.default_checkpoint_dir(d)
    write_snapshot(ck, 4, [("a", {}, np.zeros(2, "f"))],
                   {"seed_cursor": 0})
    coord = cl.ClusterCoordinator(d, num_workers=2, heartbeat_timeout=2.0,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  allow_grow=False)
    a = FakeWorker(d, "wa").start()
    b = FakeWorker(d, "wb").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        gen = cl.read_plan(d)["gen"]
        b.fault(gen)
        ev = _wait_event(coord, "rescale")
        assert sorted(ev["survivors"]) == ["wa", "wb"]
        assert ev["restore_step"] == 4
        a.finish()
        b.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        b.close()


def test_coordinator_grow_at_step_barrier(tmp_path):
    """A joiner appears: fence with save_step, rank 0 acks with the
    step it snapshotted, the grown world pins exactly that step — no
    rollback, no aborted step."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=1, heartbeat_timeout=2.0,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  total_device_count=4)
    a = FakeWorker(d, "wa", saved_step=9).start()
    t, box = _coord_thread(coord)
    c = None
    try:
        _wait_event(coord, "formed")
        assert cl.read_plan(d)["world"]["wa"]["local_device_count"] == 4
        c = FakeWorker(d, "wc").start()
        ev = _wait_event(coord, "grow")
        assert ev["restore_step"] == 9
        plan = cl.read_plan(d)
        assert sorted(plan["world"]) == ["wa", "wc"]
        # the budget re-splits over the grown cohort
        assert plan["world"]["wa"]["local_device_count"] == 2
        assert plan["restore_step"] == 9
        a.finish()
        c.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        if c is not None:
            c.close()


def test_coordinator_repeated_death_aborts_with_merged_bundle(tmp_path):
    """Death during recovery past the rescale budget: the coordinator
    aborts with ONE merged bundle — its events, every worker's last
    heartbeat, the plan history, and each worker's own bundles."""
    d = str(tmp_path)
    # a worker-side PR-5 bundle that must be merged in
    wdir = os.path.join(d, "bundles", "wb", "bundle_step3")
    os.makedirs(wdir)
    with open(os.path.join(wdir, "bundle.json"), "w") as f:
        json.dump({"reason": "hang watchdog tripped"}, f)
    coord = cl.ClusterCoordinator(d, num_workers=2, heartbeat_timeout=0.5,
                                  poll_interval=0.02, fence_timeout=1.0,
                                  max_rescales=1, allow_grow=False)
    a = FakeWorker(d, "wa", ack_fences=False).start()  # never acks
    b = FakeWorker(d, "wb").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        b.die()  # rescale 1: fence; wa never acks -> budget exhausted
        t.join(20)
        assert "abort" in box, box
        e = box["abort"]
        assert e.bundle and os.path.isdir(e.bundle)
        with open(os.path.join(e.bundle, "bundle.json")) as f:
            meta = json.load(f)
        assert meta["events"] and meta["heartbeats"]
        assert any(p["phase"] == "fence" for p in meta["plans"])
        assert os.path.exists(os.path.join(
            e.bundle, "workers", "wb", "bundle_step3", "bundle.json"))
        assert cl.read_plan(d)["phase"] == "abort"
    finally:
        a.close()
        b.close()


def test_member_that_left_is_rescaled_out(tmp_path):
    """A member that departs with terminal status 'left' (worker-side
    failure path) is not coming back: the coordinator must rescale it
    out, not wait on its 'done' forever."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=2, heartbeat_timeout=5.0,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  allow_grow=False)
    a = FakeWorker(d, "wa").start()
    b = FakeWorker(d, "wb").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        b.leave()
        ev = _wait_event(coord, "rescale")
        assert ev["survivors"] == ["wa"]
        a.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        b.close()


def test_stale_plan_cleared_on_coordinator_init(tmp_path):
    """Reusing a cluster dir (the resume flow): a previous run's plan
    must not leak into the new coordinator's numbering."""
    d = str(tmp_path)
    cl.write_plan(d, {"gen": 9, "phase": "done", "world": {}})
    cl.ClusterCoordinator(d, num_workers=1)
    assert cl.read_plan(d) is None


def test_grow_save_falls_to_acting_rank0(tmp_path):
    """Rank 0 dies during the grow fence: the restarted fence's lowest
    surviving rank performs the barrier save, so the grow still pins
    the CURRENT step instead of degrading into a rollback."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=2, heartbeat_timeout=0.6,
                                  poll_interval=0.02, fence_timeout=5.0,
                                  total_device_count=4)
    a = FakeWorker(d, "wa", ack_fences=False, saved_step=7).start()
    b = FakeWorker(d, "wb", saved_step=5).start()
    t, box = _coord_thread(coord)
    c = None
    try:
        _wait_event(coord, "formed")
        c = FakeWorker(d, "wc").start()
        _wait_event(coord, "fence")   # the grow barrier is up
        a.die()                       # rank 0 dies mid-fence
        ev = _wait_event(coord, "grow", timeout=20)
        # wb (rank 1, now the acting rank 0) saved step 5 — NOT a
        # fallback to the newest snapshot
        assert ev["restore_step"] == 5
        plan = cl.read_plan(d)
        assert sorted(plan["world"]) == ["wb", "wc"]
        b.finish()
        c.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        b.close()
        if c is not None:
            c.close()


def test_fence_restarts_when_survivor_dies_mid_fence(tmp_path):
    """Death DURING recovery, budget available: the fence restarts with
    the remaining cohort instead of hanging on a dead ack."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=3, heartbeat_timeout=0.5,
                                  poll_interval=0.02, fence_timeout=4.0,
                                  max_rescales=4, allow_grow=False)
    a = FakeWorker(d, "wa").start()
    b = FakeWorker(d, "wb", ack_fences=False).start()
    c = FakeWorker(d, "wc").start()
    t, box = _coord_thread(coord)
    try:
        _wait_event(coord, "formed")
        c.die()                      # triggers rescale
        _wait_event(coord, "fence")
        b.die()                      # dies while the fence waits on it
        ev = _wait_event(coord, "rescale", timeout=20)
        assert ev["survivors"] == ["wa"]
        refences = [e for e in coord.events if e["event"] == "refence"]
        assert refences and "wb" in refences[-1]["dropped"]
        a.finish()
        t.join(10)
        assert "summary" in box, box
    finally:
        a.close()
        b.close()
        c.close()


# ------------------------------------------------- worker (in-process) --
def _tiny_build(layout):
    del layout
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 13
    startup.random_seed = 13
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        p = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    rng = np.random.RandomState(2)
    data = [rng.rand(4, 4).astype("f") for _ in range(8)]

    def feed_fn(i):
        xb = data[i % len(data)]
        return {"x": xb, "y": xb[:, :1].copy()}

    return {"main": main, "startup": startup, "loss": loss,
            "feed_fn": feed_fn}


def test_elastic_worker_end_to_end_single(tmp_path):
    """One ElasticWorker under a live coordinator, in-process: forms,
    trains to completion, records results, publishes the final
    snapshot, and the coordinator reports done."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=1,
                                  heartbeat_timeout=30.0,
                                  poll_interval=0.02,
                                  local_device_count=2)
    t, box = _coord_thread(coord, deadline=240)
    worker = cl.ElasticWorker(d, "w0", _tiny_build, checkpoint_every=2)
    out = worker.run(5)
    t.join(60)
    assert "summary" in box, box
    assert box["summary"]["steps"] == {"w0": 5}
    assert out["steps"] == 5 and out["generations"] == 1
    rows = [json.loads(l) for l in
            open(os.path.join(d, "results_w0.jsonl"))]
    assert [r["step"] for r in rows] == list(range(5))
    from paddle_tpu.checkpoint import find_valid_snapshot
    found = find_valid_snapshot(cl.default_checkpoint_dir(d))
    assert found is not None and found[0] == 5  # final published state


def test_worker_hang_escalates_to_cluster_rollback(tmp_path):
    """A wedged dispatch (slow_step vs the watchdog): the worker's
    local chain aborts (hangs are cluster faults — cohort state is
    indeterminate), the fault is escalated through the heartbeat, the
    coordinator fences and rolls the cohort back at the SAME size, and
    training finishes — with the worker's PR-5 diagnostic bundle on
    disk."""
    d = str(tmp_path)
    coord = cl.ClusterCoordinator(d, num_workers=1,
                                  heartbeat_timeout=30.0,
                                  poll_interval=0.02,
                                  local_device_count=2)
    t, box = _coord_thread(coord, deadline=240)
    worker = cl.ElasticWorker(d, "w0", _tiny_build, checkpoint_every=2,
                              watchdog_timeout=1.0)
    plan = rz.FaultPlan(["slow_step@3:30.0"]).arm()
    try:
        out = worker.run(6)
    finally:
        plan.disarm()
    t.join(60)
    assert "summary" in box, box
    assert out["steps"] == 6 and out["generations"] == 2
    ev = next(e for e in coord.events if e["event"] == "rescale")
    assert ev["survivors"] == ["w0"]       # nobody dropped: a rollback
    assert ev["restore_step"] == 2         # newest snapshot pre-wedge
    det = next(e for e in coord.events if e["event"] == "detected")
    assert det["faulted"] == ["w0"] and det["dead"] == []
    # the local abort captured a bundle before escalating
    broot = os.path.join(d, "bundles", "w0")
    assert os.path.isdir(broot) and os.listdir(broot)
    # every step completed exactly once in the final history
    rows = _load_results(d, "w0")
    final_gen = max(r["gen"] for r in rows)
    assert sorted(r["step"] for r in rows if r["gen"] == final_gen) \
        == [2, 3, 4, 5]                    # replay from the rollback
    assert sorted({r["step"] for r in rows}) == list(range(6))


# ----------------------------------------------------- multiproc legs --
def _spawn_worker(wid, cluster_dir, steps, fault=None, step_delay=0.3,
                  host_devices=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
                         % host_devices)
    if fault:
        env["PTPU_FAULT_PLAN"] = fault
    else:
        env.pop("PTPU_FAULT_PLAN", None)
    p = subprocess.Popen(
        [sys.executable, TOOL, "worker", "--cluster-dir", cluster_dir,
         "--worker-id", wid, "--steps", str(steps),
         "--checkpoint-every", "3", "--sharded-weight-update",
         "--step-delay", str(step_delay)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    # reap on exit so a SIGKILL'd worker can't linger as a zombie the
    # monitor would read as alive
    threading.Thread(target=p.wait, daemon=True).start()
    return p


def _load_results(cluster_dir, wid):
    path = os.path.join(cluster_dir, "results_%s.jsonl" % wid)
    return [json.loads(l) for l in open(path)]


# The from-scratch small-mesh reference runs in its OWN process with the
# workers' exact device environment (4 virtual XLA:CPU devices): the
# device count shapes XLA's intra-op reduction partitioning, so an
# 8-device test process computing on a 4-device sub-mesh matches only to
# ~1e-8, not bit-exact — and bit-exact is the claim under test.
_REF_SCRIPT = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, @REPO@)
import numpy as np
import importlib.util
spec = importlib.util.spec_from_file_location("_t", @TOOL@)
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)
import jax
import paddle_tpu as fluid
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.parallel import DeviceLayout
from paddle_tpu.parallel.mesh import make_mesh
ckpt, restore, upto = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
layout = DeviceLayout(local_device_count=4)
built = tool.demo_build(layout)
scope = fluid.Scope()
with fluid.scope_guard(scope):
    fluid.Executor(fluid.CPUPlace()).run(built["startup"])
    mgr = CheckpointManager(ckpt, async_save=False)
    got = mgr.restore(program=built["main"], scope=scope, step=restore,
                      layout=layout)
    assert got == restore, (got, restore)
    mgr.close()
    pexe = fluid.ParallelExecutor(
        main_program=built["main"],
        mesh=make_mesh({"dp": 4}, jax.devices()[:4]),
        sharded_weight_update=True)
    for i in range(restore, upto):
        v, = pexe.run([built["loss"].name], feed=built["feed_fn"](i))
        print("ROW " + json.dumps(
            {"step": i, "value": float(np.asarray(v).reshape(-1)[0])}))
"""


def _reference_stream(tmp_path, ckpt_dir, restore, upto):
    script = tmp_path / "reference.py"
    script.write_text(_REF_SCRIPT.replace("@REPO@", repr(REPO))
                      .replace("@TOOL@", repr(TOOL)))
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("PTPU_FAULT_PLAN", None)
    cp = subprocess.run(
        [sys.executable, str(script), ckpt_dir, str(restore), str(upto)],
        env=env, capture_output=True, text=True, timeout=600)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    rows = [json.loads(l.split("ROW ", 1)[1])
            for l in cp.stdout.splitlines() if l.startswith("ROW ")]
    return {r["step"]: r["value"] for r in rows}


@pytest.mark.multiproc
@pytest.mark.slow  # subprocess cohort: out of the fast tier-1 leg;
#                    runs in the default (slow-inclusive) suite and via
#                    `pytest -m multiproc`
def test_kill_a_host_rescale_bit_exact_and_grow(tmp_path):
    """THE acceptance leg. 2 workers x 2 devices (cluster budget 4);
    `host_death@6` SIGKILLs w1 mid-run. The survivor is fenced, rolls
    back to the newest valid snapshot, reshards onto the full 4-device
    mesh, and finishes training; its post-rescale loss stream is
    BIT-EXACT vs a from-scratch run on the 4-device mesh restored from
    the same snapshot. A replacement worker then joins and the mesh
    grows back at a step barrier with no aborted step."""
    d = str(tmp_path)
    steps = 80  # paced (step_delay) so the replacement's jax import
    #             lands well before the survivor finishes
    coord = cl.ClusterCoordinator(
        d, num_workers=2, heartbeat_timeout=3.0, poll_interval=0.05,
        fence_timeout=60.0, total_device_count=4)
    t, box = _coord_thread(coord, deadline=420)
    procs = [_spawn_worker("w0", d, steps),
             _spawn_worker("w1", d, steps, fault="host_death@6")]
    try:
        resc = _wait_event(coord, "rescale", timeout=120)
        assert resc["survivors"] == ["w0"], resc
        restore = resc["restore_step"]
        assert restore is not None and 0 <= restore <= 8
        # the dead host is gone for real
        assert procs[1].wait(timeout=60) == -9
        # replacement join -> grow
        procs.append(_spawn_worker("w2", d, steps))
        grow = _wait_event(coord, "grow", timeout=120)
        assert grow["joiners"] == ["w2"]
        t.join(180)
        assert "summary" in box, (box, coord.events)
        summary = box["summary"]
        assert sorted(summary["world"]) == ["w0", "w2"]
        assert summary["steps"] == {"w0": steps, "w2": steps}
        assert procs[0].wait(timeout=60) == 0
        assert procs[2].wait(timeout=60) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # ---- bit-exactness vs a from-scratch small-mesh resume ----------
    rows0 = _load_results(d, "w0")
    post = {}
    for r in rows0:
        if r["gen"] >= resc["gen"]:
            assert r["step"] not in post, \
                "step %d recorded twice post-rescale" % r["step"]
            post[r["step"]] = r["value"]
    assert sorted(post) == list(range(restore, steps))

    # the small-mesh (post-rescale, pre-grow) window vs a from-scratch
    # 4-device run restored from the same snapshot — bit-exact
    G = grow["restore_step"]
    assert restore < G <= steps
    ref = _reference_stream(tmp_path, cl.default_checkpoint_dir(d),
                            restore, G)
    small_mesh = {s: v for s, v in post.items() if s < G}
    assert small_mesh == ref, \
        "post-rescale stream diverged from the from-scratch " \
        "small-mesh resume"

    # ---- grow joined with no aborted step ---------------------------
    pre_grow = [r["step"] for r in rows0
                if resc["gen"] <= r["gen"] < grow["gen"]]
    post_grow = [r["step"] for r in rows0 if r["gen"] >= grow["gen"]]
    assert pre_grow and post_grow
    assert max(pre_grow) + 1 == min(post_grow) == G
    # the joiner's stream is bit-identical to the survivor's
    rows2 = {r["step"]: r["value"] for r in _load_results(d, "w2")
             if r["gen"] >= grow["gen"]}
    assert rows2 == {s: post[s] for s in rows2}
    # and the cohort agreed before the death too
    rows1 = {r["step"]: r["value"] for r in _load_results(d, "w1")}
    assert rows1 == {s: v for s, v in
                     {r["step"]: r["value"] for r in rows0
                      if r["gen"] < resc["gen"]}.items() if s in rows1}
    assert sorted(rows1) == list(range(6))  # killed AT step 6 exactly


@pytest.mark.multiproc
@pytest.mark.slow  # see test_kill_a_host_rescale_bit_exact_and_grow
def test_ptpu_elastic_cli_heartbeat_stall_leg(tmp_path):
    """The launcher end to end, with the OTHER death mode: a worker
    whose heartbeats stall (training continues!) is declared dead on
    missed heartbeats alone, fenced out, and the cohort finishes
    without it. Exercises `ptpu_elastic launch` exactly as an operator
    would run it."""
    d = str(tmp_path / "cluster")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("PTPU_FAULT_PLAN", None)
    cp = subprocess.run(
        [sys.executable, TOOL, "launch", "--cluster-dir", d,
         "--workers", "2", "--steps", "24", "--host-devices", "2",
         "--local-devices", "2", "--step-delay", "0.15",
         "--heartbeat-timeout", "1.2",
         "--fault-worker", "1", "--fault-plan", "heartbeat_stall@4",
         "--deadline", "240"],
        env=env, capture_output=True, text=True, timeout=420)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert '"rescale"' in cp.stdout
    summary = json.loads(cp.stdout.strip().splitlines()[-1]
                         .split("done: ", 1)[1])
    assert summary["steps"]["w0"] == 24
    assert summary["rescales"] >= 1
