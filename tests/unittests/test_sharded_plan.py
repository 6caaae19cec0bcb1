"""ShardingPlan (parallel/plan.py, ARCHITECTURE.md §21): sharded
data-parallel training as a first-class compile-time plan.

The contracts under test:
  * mesh-size-1 plan is BIT-exact vs the replicated path (SGD and
    Adam + LR decay, plain and steps=K) — sharding the weight update
    must never change the math;
  * non-dividing param dims fall back to replicated with a logged
    reason, never a crash;
  * the plan joins the persistent AOT compile-cache key: changed plan =
    new key, identical rebuild = identical key;
  * sharded snapshots reshard-restore through the plan bit-exactly
    (restore(layout=ShardingPlan) places state straight into the new
    world's layout);
  * guards/gating (PR-5) compose with sharded update state;
  * the canonical sorted-param order contract in backward/optimizer.
"""
import logging
import os

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.parallel import DeviceLayout, ShardingPlan
from paddle_tpu.parallel.mesh import make_mesh, P

EXE = fluid.Executor(fluid.CPUPlace())
R = np.random.RandomState(4)
XS = R.rand(16, 12).astype("float32")
YS = (XS.sum(1, keepdims=True) * 0.1).astype("float32")


def _mesh(n, axes=None):
    return make_mesh(axes or {"dp": n}, jax.devices()[:n])


def _build(opt="sgd", seed=11, dim=12, width=16, dropout=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=width, act="tanh")
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.2)
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        if opt == "sgd":
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        elif opt == "adam_decay":
            lr = fluid.layers.exponential_decay(0.01, 2, 0.9)
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
        else:
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _init_like(scope, init):
    for n, v in init.items():
        scope.set(n, v)
    scope._rng_counter = 0


# --------------------------------------------------------------------------
# mesh-size-1 bit-exactness (acceptance): the plan path vs today's
# replicated single-device path, plain and steps=K
# --------------------------------------------------------------------------
@pytest.mark.parametrize("opt", ["sgd", "adam_decay"])
def test_mesh1_plan_bit_exact_vs_replicated(opt, monkeypatch):
    monkeypatch.setenv("FLAGS_multistep_unroll", "0")  # scan path in CI
    steps_k = 3

    # ONE program for both runs: dropout masks derive from op uids, so
    # bit-exactness is asserted between executors, not between rebuilds
    main, startup, loss = _build(opt, dropout=True)

    # reference: plain Executor, 3 single steps + 3 more (the K block)
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        EXE.run(startup)
        init = {n: np.array(s1.get(n), copy=True)
                for n in s1.names()}
        s1._rng_counter = 0  # same seed stream as the plan run below
        ref = [np.asarray(EXE.run(main, feed={"x": XS, "y": YS},
                                  fetch_list=[loss])[0]).copy()
               for _ in range(3 + steps_k)]
        ref_state = {n: np.asarray(s1.get(n)).copy() for n in s1.names()}

    # mesh-size-1 sharded plan (the plan exists; every spec degenerates
    # to replicated because the shard axis has size 1)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        EXE.run(startup)
        _init_like(s2, init)
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name,
                                      mesh=_mesh(1),
                                      sharded_weight_update=True)
        assert len(pexe.plan) > 0
        assert not any(e.sharded for e in pexe.plan)
        got = [np.asarray(pexe.run([loss.name],
                                   feed={"x": XS, "y": YS})[0]).copy()
               for _ in range(3)]
        stacked = pexe.run([loss.name], feed={"x": XS, "y": YS},
                           steps=steps_k, fetch_reduce="stack")[0]
        got += [np.asarray(stacked)[i].copy() for i in range(steps_k)]
        got_state = {n: np.asarray(s2.get(n)).copy() for n in s2.names()}

    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(a, b, err_msg="step %d" % i)
    assert set(ref_state) == set(got_state)
    for n in ref_state:
        np.testing.assert_array_equal(ref_state[n], got_state[n],
                                      err_msg=n)


def test_mesh_n_sharded_training_loss_parity():
    """Mesh size N: replicated vs sharded update land the same losses
    and state (bit-equal on XLA:CPU — elementwise update math plus the
    same reduction tree either way)."""
    outs, states = {}, {}
    for tag, kw in (("repl", {}), ("shard",
                                   {"sharded_weight_update": True})):
        main, startup, loss = _build("adam")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            EXE.run(startup)
            if tag == "repl":
                init = {n: np.array(scope.get(n), copy=True)
                        for n in scope.names()}
            else:
                _init_like(scope, init)
            pexe = fluid.ParallelExecutor(main_program=main,
                                          loss_name=loss.name,
                                          mesh=_mesh(8), **kw)
            outs[tag] = [np.asarray(pexe.run(
                [loss.name], feed={"x": XS, "y": YS})[0]).copy()
                for _ in range(4)]
            states[tag] = {n: np.asarray(scope.get(n)).copy()
                           for n in scope.names()}
    for a, b in zip(outs["repl"], outs["shard"]):
        np.testing.assert_array_equal(a, b)
    for n in states["repl"]:
        np.testing.assert_array_equal(states["repl"][n],
                                      states["shard"][n], err_msg=n)


# --------------------------------------------------------------------------
# partitioner: non-dividing dims fall back replicated, with a reason
# --------------------------------------------------------------------------
def test_non_dividing_dims_fall_back_replicated_logged(caplog):
    main, startup, loss = _build(width=13)  # 13 % 8 != 0
    with caplog.at_level(logging.INFO, logger="paddle_tpu.parallel.plan"):
        plan = ShardingPlan.build(main, _mesh(8), shard_update=True)
    # the 12x13 fc weight shards (dim0 12 % 8 != 0 -> no; careful: dim0
    # is 12) — walk the entries instead of guessing: every non-dividing
    # param must be replicated AND carry a reason; dividing ones shard
    for e in plan:
        if e.kind != "param":
            continue
        if e.shape and e.shape[0] % 8 == 0 and int(
                np.prod(e.shape)) >= 8:
            assert e.sharded, e
        else:
            assert not e.sharded, e
            assert e.reason, e
    assert any("replicated" in r.message for r in caplog.records)
    # and the program still RUNS under the partial plan — never a crash
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name,
                                      mesh=_mesh(8), plan=plan)
        v, = pexe.run([loss.name], feed={"x": XS, "y": YS})
        assert np.isfinite(np.asarray(v)).all()


def test_plan_determinism_overrides_and_grad_constraints():
    """Identical rebuilds give identical digests (restart-stability);
    explicit overrides win and pin exactly one var; grad constraints
    cover exactly the sharded params."""
    def build():
        return _build("adam", seed=3)

    main1, _, _ = build()
    main2, _, _ = build()
    p1 = ShardingPlan.build(main1, _mesh(8), shard_update=True)
    p2 = ShardingPlan.build(main2, _mesh(8), shard_update=True)
    assert p1.digest() == p2.digest()
    assert p1.to_json() == p2.to_json()

    sharded_params = [e.name for e in p1
                      if e.kind == "param" and e.sharded]
    assert sharded_params
    from paddle_tpu.core.framework import GRAD_SUFFIX
    assert sorted(p1.grad_constraints()) == sorted(
        n + GRAD_SUFFIX for n in sharded_params)

    # override: pin one param replicated — plan differs, spec honored,
    # its accumulators keep their own (replicated-follow) decision
    pinned = sharded_params[0]
    p3 = ShardingPlan.build(main1, _mesh(8), shard_update=True,
                            overrides={pinned: P()})
    assert p3.digest() != p1.digest()
    assert p3.entries[pinned].override
    assert p3.spec_for(pinned) == P()
    assert pinned not in [e.name.replace(GRAD_SUFFIX, "")
                          for e in p3 if e.kind == "gradient"]


def test_plan_memory_accounting_ratio():
    main, _, _ = _build("adam", dim=16, width=32)
    n = 8
    plan = ShardingPlan.build(main, _mesh(n), shard_update=True)
    rep_plan = ShardingPlan.build(main, _mesh(n), shard_update=False)
    m, mr = plan.memory_report(), rep_plan.memory_report()
    assert mr["update_state"]["per_chip_bytes"] == \
        mr["update_state"]["replicated_per_chip_bytes"]
    # the ZeRO ratio: per-chip update state <= (1/N + eps) of replicated
    # (eps = the un-shardable [1] beta pows + any non-dividing var)
    ratio = m["update_state"]["per_chip_bytes"] / \
        m["update_state"]["replicated_per_chip_bytes"]
    assert ratio <= 1.0 / n + 0.05, ratio
    assert m["params"]["per_chip_bytes"] < \
        m["params"]["replicated_per_chip_bytes"]
    assert m["sharded_vars"] and m["replicated_vars"]
    assert "describe" and "update state/chip" in plan.describe()


# --------------------------------------------------------------------------
# the plan joins the AOT compile-cache key
# --------------------------------------------------------------------------
def test_plan_round_trips_through_aot_cache_key():
    from paddle_tpu.core import compile_cache

    def key_for(plan, program):
        h, _ = compile_cache.aot_entry_key(
            program, (("x", (16, 12), "float32"),), ("loss",), (),
            (1, None, False, ()), jax.devices()[0],
            extra={"executor": "parallel", "num_devices": 8,
                   "plan": plan.to_json()})
        return h

    main1, _, _ = _build("adam", seed=5)
    main2, _, _ = _build("adam", seed=5)  # identical rebuild
    mesh = _mesh(8)
    sharded1 = ShardingPlan.build(main1, mesh, shard_update=True)
    sharded2 = ShardingPlan.build(main2, mesh, shard_update=True)
    repl = ShardingPlan.build(main1, mesh, shard_update=False)
    pinned = ShardingPlan.build(
        main1, mesh, shard_update=True,
        overrides={sorted(main1._accumulator_owner.values())[-1]: P()})

    # identical rebuild -> identical key (restart-stable: canonical
    # param order makes the program bytes equal, deterministic
    # partitioner makes the plan equal)
    assert key_for(sharded1, main1) == key_for(sharded2, main2)
    # changed plan -> new key, program untouched
    assert key_for(repl, main1) != key_for(sharded1, main1)
    assert key_for(pinned, main1) != key_for(sharded1, main1)


def test_plan_keys_aot_cache_entries_on_disk(tmp_path, monkeypatch):
    """Integration: two different plans over the SAME program store two
    distinct AOT artifacts; a fresh executor under the first plan hits
    the existing entry instead of adding a third."""
    monkeypatch.setenv("FLAGS_aot_cache_dir", str(tmp_path))

    def entries():
        return sorted(d for d in os.listdir(str(tmp_path))
                      if d.startswith("aot_"))

    main, startup, loss = _build("sgd", seed=9)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)  # the startup compile stores its own entry
        base = set(entries())
        feed = {"x": XS, "y": YS}
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name, mesh=_mesh(8),
                                      sharded_weight_update=True)
        pexe.run([loss.name], feed=feed)
        after_sharded = set(entries()) - base
        assert len(after_sharded) == 1
        pexe2 = fluid.ParallelExecutor(main_program=main,
                                       loss_name=loss.name,
                                       mesh=_mesh(8))
        pexe2.run([loss.name], feed=feed)
        # replicated plan = different key
        assert len(set(entries()) - base) == 2
        pexe3 = fluid.ParallelExecutor(main_program=main,
                                       loss_name=loss.name,
                                       mesh=_mesh(8),
                                       sharded_weight_update=True)
        pexe3.run([loss.name], feed=feed)
        # same plan = same key = disk hit, no third entry
        assert len(set(entries()) - base) == 2
        assert after_sharded <= set(entries())


# --------------------------------------------------------------------------
# snapshots: capture sharded, reshard through the plan, resume bit-exact
# --------------------------------------------------------------------------
def test_sharded_snapshot_reshard_resume_bit_exact(tmp_path):
    """Train sharded on N=4, snapshot (specs ride the manifest, the
    layout records the shard axis), restore through the M=2 world's
    ShardingPlan, continue — bit-identical across two independent
    restore+continue runs, with state placed exactly per the new plan."""
    main, startup, loss = _build("adam", dropout=True, seed=21)
    data = [R.rand(8, 12).astype("f") for _ in range(8)]

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name, mesh=_mesh(4),
                                      sharded_weight_update=True)
        for i in range(3):
            pexe.run([loss.name], feed={"x": data[i],
                                        "y": data[i][:, :1]})
        ck = str(tmp_path / "ck")
        mgr = CheckpointManager(ck, async_save=False)
        mgr.save(3, program=main, scope=scope,
                 layout=DeviceLayout(local_device_count=4,
                                     shard_axis="dp"))
        mgr.close()

    plan2 = ShardingPlan.build(main, _mesh(2), shard_update=True)

    def resume():
        s = fluid.Scope()
        with fluid.scope_guard(s):
            EXE.run(startup)
            mgr = CheckpointManager(ck, async_save=False)
            assert mgr.restore(program=main, scope=s, step=3,
                               layout=plan2) == 3
            mgr.close()
            # placement IS the plan's: a sharded param sits split over
            # the 2-device mesh, a replicated one whole
            for e in plan2:
                if e.kind == "gradient":
                    continue
                v = s.get(e.name)
                if v is None:
                    continue
                assert isinstance(v, jax.Array), e.name
                assert v.sharding.spec == plan2.sharding_for(
                    e.name).spec, e.name
            pexe = fluid.ParallelExecutor(main_program=main,
                                          loss_name=loss.name,
                                          plan=plan2)
            out = [np.asarray(pexe.run(
                [loss.name], feed={"x": data[i],
                                   "y": data[i][:, :1]})[0]).copy()
                for i in range(3, 6)]
            return out, {n: np.asarray(s.get(n)).copy()
                         for n in s.names()}, s.seed_state()

    la, sa, ca = resume()
    lb, sb, cb = resume()
    assert ca == cb
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)
    for n in sa:
        np.testing.assert_array_equal(sa[n], sb[n], err_msg=n)

    # layout-target restore (DeviceLayout, adapted recorded specs) lands
    # the same VALUES — plan-target restore differs in placement only
    s = fluid.Scope()
    with fluid.scope_guard(s):
        EXE.run(startup)
        mgr = CheckpointManager(ck, async_save=False)
        mgr.restore(program=main, scope=s, step=3,
                    layout=DeviceLayout(local_device_count=2))
        mgr.close()
        s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        EXE.run(startup)
        mgr = CheckpointManager(ck, async_save=False)
        mgr.restore(program=main, scope=s2, step=3, layout=plan2)
        mgr.close()
    for n in s.names():
        np.testing.assert_array_equal(np.asarray(s.get(n)),
                                      np.asarray(s2.get(n)), err_msg=n)


# --------------------------------------------------------------------------
# guards (PR-5) compose with the sharded plan
# --------------------------------------------------------------------------
def test_numeric_guards_gate_sharded_update():
    import paddle_tpu.resilience as rz
    from paddle_tpu.core.executor import NumericalGuardError

    main, startup, loss = _build("adam")
    rz.install_numeric_guards(main, loss=loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        EXE.run(startup)
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name, mesh=_mesh(8),
                                      sharded_weight_update=True)
        pexe.run([loss.name], feed={"x": XS, "y": YS})
        before = {n: np.asarray(scope.get(n)).copy()
                  for n in scope.names()}
        bad = XS.copy()
        bad[0, 0] = np.nan
        with pytest.raises(NumericalGuardError):
            pexe.run([loss.name], feed={"x": bad, "y": YS})
        # the gate made the poisoned step a no-op on the SHARDED state
        for n, v in before.items():
            np.testing.assert_array_equal(v, np.asarray(scope.get(n)),
                                          err_msg=n)


# --------------------------------------------------------------------------
# DeviceLayout shard axis + _adapt_spec on a dedicated update axis
# --------------------------------------------------------------------------
def test_device_layout_shard_axis_json_roundtrip():
    la = DeviceLayout(local_device_count=4,
                      mesh_axes={"dp": 2, "zero": 2}, shard_axis="zero")
    rt = DeviceLayout.from_json(la.to_json())
    assert rt == la
    assert rt.shard_axis == "zero"
    assert rt.resolved_shard_axis() == "zero"
    # default: no named axis -> update state follows the batch axis
    d = DeviceLayout(local_device_count=2)
    assert d.shard_axis is None
    assert d.resolved_shard_axis() == "dp"
    assert DeviceLayout.from_json(d.to_json()).shard_axis is None
    # pre-shard_axis snapshots (no key at all) parse fine
    old = {k: v for k, v in d.to_json().items() if k != "shard_axis"}
    assert DeviceLayout.from_json(old).shard_axis is None
    with pytest.raises(ValueError, match="shard_axis"):
        DeviceLayout(local_device_count=2, shard_axis="zero")


def test_adapt_spec_drops_or_redivides_shard_axis():
    from paddle_tpu.checkpoint.manager import _adapt_spec

    # recorded under a dp×zero mesh, restored onto dp-only: the zero
    # axis is dropped -> replicated on that dim
    mesh_dp = _mesh(2)
    assert tuple(_adapt_spec(["zero", None], mesh_dp, (8, 3))) \
        == (None, None)
    # restored onto a mesh that still has the axis at a dividing size:
    # the sharding survives re-divided
    mesh_dz = _mesh(4, {"dp": 2, "zero": 2})
    assert tuple(_adapt_spec(["zero", None], mesh_dz, (8, 3))) \
        == ("zero", None)
    # non-dividing under the new size: replicated
    assert tuple(_adapt_spec(["zero"], mesh_dz, (7,))) == (None,)


def test_dedicated_shard_axis_trains_and_matches():
    """A dp×zero mesh: batch over 'dp', update state over 'zero' — the
    plan shards params/moments over the dedicated axis and numerics
    match the replicated run."""
    mesh = _mesh(8, {"dp": 2, "zero": 4})
    main, startup, loss = _build("adam", seed=13)
    plan = ShardingPlan.build(main, mesh, shard_axis="zero",
                              shard_update=True)
    assert plan.shard_axis == "zero"
    assert any(e.spec == P("zero") for e in plan if e.kind == "param")

    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        EXE.run(startup)
        init = {n: np.array(s1.get(n), copy=True)
                for n in s1.names()}
        pexe = fluid.ParallelExecutor(main_program=main,
                                      loss_name=loss.name, mesh=mesh)
        base = [np.asarray(pexe.run([loss.name],
                                    feed={"x": XS, "y": YS})[0]).copy()
                for _ in range(3)]
    main2, startup2, loss2 = _build("adam", seed=13)
    plan2 = ShardingPlan.build(main2, mesh, shard_axis="zero",
                               shard_update=True)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        EXE.run(startup2)
        _init_like(s2, init)
        pexe = fluid.ParallelExecutor(main_program=main2,
                                      loss_name=loss2.name, plan=plan2)
        got = [np.asarray(pexe.run([loss2.name],
                                   feed={"x": XS, "y": YS})[0]).copy()
               for _ in range(3)]
    for a, b in zip(base, got):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# donating multi-device executables through jax's persistent HLO cache.
# An older jax corrupted donated buffers after deserializing them and the
# ParallelExecutor opted those compiles out of the cache; on jax 0.9.0 the
# fault is gone (PR 21: 12 warm runs bit-identical), so they
# cache like everything else — and a warm load must train bit-identically.
# --------------------------------------------------------------------------
def test_donating_pe_compile_round_trips_through_jax_hlo_cache(tmp_path):
    from jax._src import compilation_cache as _cc

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs

    def train():
        main, startup, loss = _build("sgd", seed=17)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            EXE.run(startup)
            pexe = fluid.ParallelExecutor(main_program=main,
                                          loss_name=loss.name,
                                          mesh=_mesh(8),
                                          sharded_weight_update=True)
            return [np.asarray(pexe.run([loss.name],
                                        feed={"x": XS, "y": YS})[0]).copy()
                    for _ in range(6)]

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        _cc.reset_cache()  # re-latch "cache used" against the new dir
        jax.clear_caches()  # so cold and warm compile the same set
        cold = train()
        stored = len(os.listdir(str(tmp_path)))
        assert stored >= 2          # startup + the donating sharded step
        jax.clear_caches()          # forget the in-process executables
        warm = train()
        assert len(os.listdir(str(tmp_path))) == stored  # all were hits
        for a, b in zip(cold, warm):
            assert np.array_equal(a, b)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        _cc.reset_cache()


# --------------------------------------------------------------------------
# canonical order (the restart-stability satellite)
# --------------------------------------------------------------------------
def test_canonical_update_order_is_sorted_by_param_name():
    """Params CREATED in non-sorted order still get their update ops —
    and their accumulators — in sorted-name order, so program bytes and
    the plan walk are restart-stable regardless of construction order."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=8,
                            param_attr=fluid.ParamAttr(name="z.w"))
        h = fluid.layers.fc(input=h, size=8,
                            param_attr=fluid.ParamAttr(name="a.w"))
        loss = fluid.layers.mean(h)
        _, pairs = fluid.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    names = [p.name for p, _ in pairs]
    assert names == sorted(names), names
    upd = [op.inputs["Param"][0] for op in main.global_block().ops
           if op.type == "momentum"]
    assert upd == sorted(upd), upd
    # accumulator creation followed the same order: velocities' unique
    # counters ascend with the sorted param walk
    owner = main._accumulator_owner
    vel = sorted(a for a in owner if "velocity" in a)
    assert [owner[a] for a in vel] == sorted(owner[a] for a in vel)
