"""The judged bench.py must keep producing its one-JSON-line contract.

One subprocess run of bench.py in the tiny smoke config on CPU (host-feed
fp32 — exercises the DoubleBufferReader staging, the explicit-CPU device
gate, and the JSON record in a single fast compile; the bf16/AMP
compile path is covered in-process by test_mixed_precision.py). Guards the
driver-facing artifact against regressions the unit suite wouldn't see.
"""
import json
import math
import pytest
import os
import statistics
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def test_bench_json_contract():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_BATCH": "2", "BENCH_STEPS": "1", "BENCH_WARMUP": "0",
        "BENCH_IMAGE_HW": "32", "BENCH_CLASS_DIM": "10",
        "BENCH_DTYPE": "fp32", "BENCH_FEED": "host",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "resnet50_imagenet_train_throughput"
    assert rec["value"] > 0
    assert rec["unit"] == "images/sec/chip"
    assert rec["feed"] == "host" and rec["dtype"] == "fp32"
    # smoke config must NOT claim a baseline comparison
    assert rec["vs_baseline"] is None
    assert rec["image_hw"] == 32 and rec["class_dim"] == 10
    assert "loss" in rec and rec["loss"] == rec["loss"]  # finite


def test_bench_multistep_smoke():
    """The BENCH_MULTISTEP=K leg of bench.py: one subprocess run on CPU
    with tiny shapes through Executor.run(steps=8), so the multi-step
    bench path can't silently rot. FLAGS_multistep_unroll=0 pins the
    lax.scan lowering — one copy of the step in the module keeps the
    compile comparable to the single-step smoke (the CPU-default full
    unroll compiles K copies and belongs in a perf sweep, not CI)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_BATCH": "2", "BENCH_STEPS": "8", "BENCH_WARMUP": "1",
        "BENCH_IMAGE_HW": "32", "BENCH_CLASS_DIM": "10",
        "BENCH_DTYPE": "fp32", "BENCH_FEED": "device",
        "BENCH_MULTISTEP": "8", "FLAGS_multistep_unroll": "0",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "resnet50_imagenet_train_throughput"
    assert rec["value"] > 0
    # the JSON line must record the multistep setting (BENCH_LOG lines
    # are unlabeled otherwise and a K=8 number could masquerade as K=1)
    assert rec["multistep"] == 8
    assert rec["vs_baseline"] is None
    assert "loss" in rec and rec["loss"] == rec["loss"]


def test_bench_serving_smoke():
    """The BENCH_SERVING leg: one subprocess run on CPU with a tiny MLP
    through the real InferenceEngine + batcher. The acceptance gates ride
    here: coalescing must actually coalesce (mean batch occupancy > 1)
    and closed-loop throughput must beat the serial batch=1 baseline —
    otherwise the serving runtime is a queue with extra steps."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_SERVING": "1",
        "BENCH_SERVING_REQUESTS": "128", "BENCH_SERVING_SERIAL": "32",
        "BENCH_SERVING_CLIENTS": "16", "BENCH_SERVING_MAX_BATCH": "8",
        # deep-and-narrow: dispatch-bound, so the coalescing win is a
        # multiple, not a margin host noise can flip (see bench_serving)
        "BENCH_SERVING_HIDDEN": "64", "BENCH_SERVING_LAYERS": "10",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serving_throughput"
    assert rec["unit"] == "requests/sec/chip"
    assert rec["vs_baseline"] is None
    assert rec["mean_batch_occupancy"] > 1.0
    assert rec["value"] > rec["serial_qps"] > 0
    assert rec["open_qps"] > 0
    for k in ("closed_p50_ms", "closed_p95_ms", "closed_p99_ms",
              "open_p50_ms", "open_p95_ms", "open_p99_ms",
              "row_utilization"):
        assert rec[k] >= 0


def test_bench_pipeline_smoke():
    """The BENCH_PIPELINE leg: one subprocess run on CPU driving the
    same open-loop schedule through the serial and pipelined batchers
    and the same recordio trainer through the serial and prefetched
    prepass. The gates are the CORRECTNESS half of the acceptance
    criteria — both divergences exactly 0.0 and every request/step
    completed; the speed half (pipelined beats serial) needs hardware
    where host and device overlap at all, i.e. the TPU sweep tier, not
    this one-core CI box where both legs timeshare one core."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_PIPELINE": "1",
        "BENCH_PIPELINE_REQUESTS": "64",
        "BENCH_PIPELINE_RECORDS": "16",
        "BENCH_PIPELINE_FEAT": "512",
        "BENCH_SERVING_HIDDEN": "64", "BENCH_SERVING_LAYERS": "4",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "pipeline_dispatch_open_qps"
    assert rec["unit"] == "requests/sec/chip"
    assert "error" not in rec
    # bit-exactness gates: pipelined serving == run_direct probe,
    # prefetched training == serial prepass, exactly
    assert rec["serving_divergence"] == 0.0
    assert rec["train_divergence"] == 0.0
    # all work completed and was measured
    assert rec["value"] > 0 and rec["serial_open_qps"] > 0
    assert rec["train_steps"] == 16
    assert rec["train_serial_steps_s"] > 0
    assert rec["train_prefetch_steps_s"] > 0
    for k in ("serial_p50_ms", "serial_p99_ms",
              "pipelined_p50_ms", "pipelined_p99_ms"):
        assert rec[k] >= 0
    assert rec["pipeline_depth"] == 2


def test_bench_pool_smoke():
    """The BENCH_POOL leg: one subprocess run on CPU driving the same
    open-loop schedule through 1- and 2-replica pools with a mid-run
    replica kill (2-replica leg) and a mid-run zero-downtime reload
    (both legs). The acceptance gate rides here: ZERO client-visible
    errors across both events — otherwise the pool's availability story
    is decoration."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_POOL": "1",
        "BENCH_POOL_REQUESTS": "90", "BENCH_POOL_REPLICAS": "1,2",
        "BENCH_POOL_MAX_BATCH": "8", "BENCH_SERVING_LAYERS": "6",
        "BENCH_SERVING_HIDDEN": "64",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serving_pool_throughput"
    assert rec["unit"] == "requests/sec/chip"
    assert rec["vs_baseline"] is None
    assert rec["value"] > 0
    legs = rec["legs"]
    assert set(legs) == {"1", "2"}
    # the acceptance gate: zero errors across the kill AND the reload
    assert rec["total_errors"] == 0, rec
    for n, leg in legs.items():
        assert leg["errors"] == 0, leg
        assert leg["completed"] == 90
        assert leg["qps"] > 0
        assert leg["p99_ms"] >= leg["p50_ms"] >= 0
        assert any(e.startswith("reload@") for e in leg["events"])
    # the kill fired in the multi-replica leg only
    assert any(e.startswith("kill@") for e in legs["2"]["events"])
    assert not any(e.startswith("kill@") for e in legs["1"]["events"])


def test_bench_fleet_smoke():
    """The BENCH_FLEET leg: one subprocess run on CPU driving the same
    closed-loop load step through a FIXED 1-replica pool and an
    AUTOSCALED [1,3] pool. The acceptance gates ride here: the fixed
    pool sheds sustained 429s through the load's tail while the
    autoscaled pool's tail 429 rate returns to ~0 (the scale-up
    absorbed the step, riding warm engine builds), the contraction
    drains back to 1 replica, and NO leg fails an accepted request."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_FLEET": "1",
        "BENCH_FLEET_CLIENTS": "12", "BENCH_FLEET_SECONDS": "2.5",
        "BENCH_FLEET_MAX_REPLICAS": "3", "BENCH_FLEET_QUEUE_CAP": "4",
        "BENCH_SERVING_LAYERS": "6", "BENCH_SERVING_HIDDEN": "64",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serving_fleet_autoscale_qps"
    assert rec["unit"] == "requests/sec/chip"
    assert rec["vs_baseline"] is None
    assert rec["value"] > 0
    legs = rec["legs"]
    assert set(legs) == {"fixed", "autoscaled"}
    # zero accepted-request failures anywhere (429s are not errors:
    # they are the signal, retried by the clients)
    assert rec["total_errors"] == 0, rec
    # the fixed pool keeps shedding through the tail of the load step
    assert legs["fixed"]["tail_reject_rate"] > 0, legs["fixed"]
    # the autoscaled pool absorbed it: scale-up happened and the tail
    # 429 rate collapsed (~0; strictly below the fixed pool's)
    auto = legs["autoscaled"]
    assert auto["scale_ups"] >= 1, auto
    assert auto["scale_up_latency_s"] is not None
    assert auto["tail_reject_rate"] <= 0.05, auto
    assert auto["tail_reject_rate"] < legs["fixed"]["tail_reject_rate"]
    # contraction: drained back to the fixed floor after the load
    assert auto["final_replicas"] == 1, auto
    assert auto["scale_downs"] >= 1, auto


def test_bench_ckpt_smoke():
    """The BENCH_CKPT leg: one subprocess run on CPU comparing no
    checkpointing vs sync saves vs async saves. The acceptance gate rides
    here: async checkpointing must stall the training loop LESS than
    synchronous saves of the same snapshots — otherwise the background
    writer is decoration. Sized so the gap is a multiple (the sync stall
    includes materialize+hash+fsync of an Adam-sized snapshot; the async
    stall is capture only)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_CKPT": "1",
        "BENCH_STEPS": "20", "BENCH_CKPT_EVERY": "4",
        "BENCH_CKPT_DIM": "128", "BENCH_BATCH": "8", "BENCH_WARMUP": "1",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "ckpt_async_steps_per_sec"
    assert rec["unit"] == "steps/sec"
    assert rec["value"] > 0
    modes = rec["modes"]
    assert set(modes) == {"none", "sync", "async"}
    assert modes["sync"]["saves"] == modes["async"]["saves"] == 5
    assert modes["none"]["stall_ms"] == 0.0
    # the headline gate: async checkpointing stalls training less than
    # synchronous saves of identical snapshots
    assert modes["async"]["stall_ms"] < modes["sync"]["stall_ms"], modes
    assert modes["sync"]["save_latency_ms"] > 0
    assert modes["async"]["save_latency_ms"] > 0


def test_bench_compile_cache_smoke():
    """The BENCH_COMPILE_CACHE leg: cold vs warm process start for (a)
    serving warmup over a bucket lattice and (b) trainer restart +
    rollback re-entry, against one persistent AOT artifact cache dir.
    The acceptance gate rides here: the WARM process must pay ZERO
    fresh compiles (every executable loads from disk) and its measured
    wall time must drop. Results must also be bit-identical across the
    cold/warm serving runs (same check scalar)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_COMPILE_CACHE": "1",
        "BENCH_CCACHE_DIM": "32", "BENCH_CCACHE_LAYERS": "6",
        "BENCH_CCACHE_BUCKETS": "1,2,4", "BENCH_CCACHE_STEPS": "4",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    recs = {r["metric"]: r for r in lines}
    assert set(recs) == {"compile_cache_serving_warmup",
                         "compile_cache_trainer_restart"}
    for rec in recs.values():
        # THE gate: a warm start recompiles nothing, loads everything
        assert rec["warm_recompiles"] == 0, rec
        assert rec["warm"]["hits"] > 0 and rec["warm"]["load_errors"] == 0
        assert rec["cold"]["hits"] == 0 and rec["cold"]["stores"] > 0
        assert rec["value"] > 1.0, rec  # measured wall-time drop
    serving = recs["compile_cache_serving_warmup"]
    assert serving["cold"]["check"] == serving["warm"]["check"]
    trainer = recs["compile_cache_trainer_restart"]
    assert trainer["cold"]["restored_step"] is None
    assert trainer["warm"]["restored_step"] == 4  # rollback re-entry


def test_bench_resil_smoke():
    """The BENCH_RESIL leg: one subprocess run on CPU comparing guards
    off vs on, single-step and steps=K. The leg has to run and emit its
    record with all four rates and both overhead figures; how large the
    overhead is gets judged on the chip — a CPU-timed bound proves
    nothing about it (ROADMAP C1)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_RESIL": "1",
        "BENCH_STEPS": "16", "BENCH_WARMUP": "2",
        "BENCH_RESIL_REPEATS": "1",
        # lax.scan lowering for the K=8 leg (same reasoning as
        # test_bench_multistep_smoke: the CPU-default unroll compiles
        # K copies and belongs in a perf sweep, not CI)
        "FLAGS_multistep_unroll": "0",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "resil_guarded_steps_per_sec"
    assert rec["unit"] == "steps/sec"
    assert rec["value"] > 0
    assert rec["vs_baseline"] is None
    for k in ("plain_steps_per_sec", "guarded_steps_per_sec",
              "multistep_steps_per_sec",
              "multistep_guarded_steps_per_sec"):
        assert rec[k] > 0
    for k in ("overhead_pct_plain", "overhead_pct_multistep"):
        assert np.isfinite(rec[k])


def test_bench_sentinel_smoke():
    """The BENCH_SENTINEL leg: one subprocess run on CPU measuring the
    training-health sentinel (ARCHITECTURE.md §29). The acceptance gate
    rides here: watching a trainer — the loss robust z-score plus the
    grad-norm stat riding the guard-flag vector — must cost <= 3%
    steps/s, or "the sentinel is on everywhere" dies in review. The
    bench isolates that ratio by running baseline and monitored legs on
    the SAME compiled program (only host-side monitoring differs), so
    the 3% gate is not hostage to the +-5% executable-layout lottery
    between two separately compiled programs; the in-graph channel cost
    is emitted (overhead_pct_channel) for the benchd t2g tier, not
    gated. Same anti-flake treatment as test_bench_resil_smoke:
    interleaved min-of-five rounds in-process, best of three attempts
    here."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_SENTINEL": "1",
        "BENCH_STEPS": "48", "BENCH_WARMUP": "2",
        "BENCH_SENTINEL_REPEATS": "5",
        "FLAGS_multistep_unroll": "0",
    })
    best = None
    for attempt in range(3):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stdout + out.stderr
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "sentinel_steps_per_sec"
        assert rec["unit"] == "steps/sec"
        assert rec["value"] > 0
        assert rec["vs_baseline"] is None
        for k in ("baseline_steps_per_sec", "sentinel_steps_per_sec",
                  "canary_steps_per_sec", "nochannel_steps_per_sec"):
            assert rec[k] > 0
        # the canary cadence actually ran (48 steps / every 16 = 3 per
        # round x 5 rounds, + the startup reference)
        assert rec["canary_checks"] >= 3
        if best is None or (rec["overhead_pct_sentinel"]
                            < best["overhead_pct_sentinel"]):
            best = rec
        if best["overhead_pct_sentinel"] <= 3.0:
            break
    # THE gate: monitoring is host arithmetic on two already-fetched
    # floats — <= 3% or the always-on story is fiction
    assert best["overhead_pct_sentinel"] <= 3.0, best


def test_bench_tp_smoke():
    """The BENCH_TP leg: one subprocess run on an 8-virtual-device CPU
    mesh training the same Adam MLP at mesh-1 and tp=2/tp=4 under the
    plan's auto row/col tensor-parallel specs (gather placement). The
    acceptance gates ride here: fetch divergence EXACTLY 0.0 (weights
    shard at rest and all-gather on use, so TP is a memory layout
    change, never a numerics change) and per-chip PARAM bytes at
    ratio <= ~(1/tp + eps) of the mesh-1 leg (eps = the replicated
    biases + the non-dividing final head) — the number behind the
    "serve models bigger than one chip" claim."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_TP": "1",
        "BENCH_STEPS": "8", "BENCH_WARMUP": "1",
        "BENCH_TP_DIM": "64", "BENCH_BATCH": "32",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "tp_train_steps_per_sec"
    assert rec["unit"] == "steps/sec"
    assert rec["vs_baseline"] is None
    assert rec["tp_placement"] == "gather"
    legs = rec["legs"]
    assert set(legs) == {"1", "2", "4"}
    for n, leg in legs.items():
        assert leg["steps_per_sec"] > 0, leg
        assert leg["params_bytes_per_chip"] > 0
    # THE gates: bit-exactness and the per-chip memory ratio
    assert rec["fetch_divergence"] == 0.0, rec
    for n in (2, 4):
        assert legs[str(n)]["params_ratio"] <= 1.0 / n + 0.05, legs
    assert np.isfinite(rec["final_loss"])


def test_bench_sharded_smoke():
    """The BENCH_SHARDED leg: one subprocess run on an 8-virtual-device
    CPU mesh comparing the replicated update against the ZeRO-style
    sharded plan. The acceptance gates ride here: the sharded plan's
    per-chip update-state bytes must be <= ~(1/N + eps) of the
    replicated path (eps = the un-shardable [1] optimizer-global
    scalars), and the two loss streams must not diverge AT ALL —
    sharding the weight update is a memory/speed layout change, never a
    numerics change. Width pinned to 64: at wider layers XLA:CPU's
    reduce-scatter and all-reduce reduction trees genuinely differ by
    1 ulp (measured, deterministic), which the chaotic training
    trajectory amplifies — that is a backend rounding artifact, not a
    plan bug, and the bit-exact claim is gated where the trees
    coincide. (A warm persistent HLO cache used to make this leg
    nondeterministically WRONG — donating multi-device executables
    deserialized from jax's cache corrupt donated buffers; the
    ParallelExecutor now opts its donating compiles out, see
    compile_cache.donating_multidevice_compile_guard — so this gate
    also regression-tests that fix under the bench's default-on
    cache.)"""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_SHARDED": "1",
        "BENCH_STEPS": "16", "BENCH_WARMUP": "2",
        "BENCH_SHARDED_DIM": "64", "BENCH_BATCH": "64",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "sharded_update_steps_per_sec"
    assert rec["unit"] == "steps/sec"
    assert rec["vs_baseline"] is None
    assert rec["devices"] == 8
    assert rec["sharded_steps_per_sec"] > 0
    assert rec["replicated_steps_per_sec"] > 0
    b = rec["update_state_bytes_per_chip"]
    assert b["replicated"] > 0
    # the ZeRO ratio: <= 1/N + eps per-chip update state
    assert b["sharded"] <= b["replicated"] * (1.0 / 8 + 0.05), b
    assert rec["fetch_divergence"] == 0.0, rec
    assert np.isfinite(rec["final_loss"])


@pytest.mark.slow
def test_bench_transformer_decode_smoke():
    """The decode bench mode the sweep runs unattended: one subprocess
    run on CPU at tiny dims must emit the JSON contract line with the
    emitted-token unit."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", BENCH_MODEL="transformer",
               BENCH_DECODE="1", BENCH_BATCH="2", BENCH_SEQ="16",
               BENCH_BEAM="2", BENCH_STEPS="1", BENCH_WARMUP="1",
               BENCH_LAYERS="2", BENCH_DMODEL="64")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "transformer_cached_decode_throughput"
    assert rec["unit"] == "emitted tokens/sec/chip"
    assert rec["value"] > 0


def test_bench_decode_smoke():
    """The BENCH_DECODE continuous-batching leg (no BENCH_MODEL): one
    subprocess run on CPU at tiny dims through the real DecodeEngine.
    The acceptance gates ride here: divergence_vs_solo must be exactly
    0.0 (the leg itself hard-fails otherwise — bit-exactness per stream
    is the contract, not a tolerance) and mean slot occupancy > 1 (the
    open loop must actually SHARE iterations across streams; occupancy
    pinned at 1 means admits only ever landed in an empty batch and
    continuous batching never engaged)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_DECODE": "1",
        "BENCH_DECODE_STREAMS": "16", "BENCH_DECODE_SLOTS": "4",
        "BENCH_DECODE_TOKENS": "8", "BENCH_DECODE_HIDDEN": "32",
        "BENCH_DECODE_VOCAB": "64", "BENCH_DECODE_LAYERS": "2",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "decode_continuous_tokens_per_sec"
    assert rec["unit"] == "tokens/sec/chip"
    assert rec["vs_baseline"] is None
    assert rec["divergence_vs_solo"] == 0.0
    assert rec["mean_slot_occupancy"] > 1.0
    assert rec["value"] > 0 and rec["serial_tokens_per_s"] > 0
    assert rec["iterations"] > 0 and rec["tokens"] > 0
    for k in ("inter_token_p50_ms", "inter_token_p99_ms"):
        assert rec[k] >= 0


def test_bench_obs_smoke():
    """The BENCH_OBS leg and the always-on flight recorder's cost
    (ARCHITECTURE.md §24). The bench's JSON line must prove the recorder
    was live (spans_recorded > 0), that tracing added no dispatch-path host
    syncs (sync_on_dispatch == 0, read from profiler.snapshot()) and report
    both overheads as finite numbers. What "always-on" may cost is held by
    two deterministic checks, in this process: the events a steady
    Executor.run records are at most the eight an exec/step is made of,
    and a span's open/close pair costs under SPAN_PAIR_LIMIT_US. (A 5 %
    wall-clock gate over the bench's millisecond steps stood here until PR
    35: on a CPU that six test workers share it failed on trees that
    touched neither the recorder nor the executors.)"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "BENCH_OBS": "1",
        "BENCH_OBS_ROUNDS": "2",
        "BENCH_OBS_STEPS": "24",
        "BENCH_OBS_REQUESTS": "24",
    })
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "observability_overhead"
    assert rec["unit"] == "steps/sec/chip"
    assert "error" not in rec
    assert rec["value"] > 0
    assert rec["train_sps_on"] > 0 and rec["train_sps_off"] > 0
    assert rec["serving_p99_on_ms"] > 0
    # the recorder was live, and stayed sync-free on dispatch paths
    assert rec["spans_recorded"] > 0
    assert rec["sync_on_dispatch"] == 0
    assert math.isfinite(rec["train_overhead"])
    assert math.isfinite(rec["serving_overhead"])

    import paddle_tpu as fluid
    from paddle_tpu.observability import trace
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(x=fluid.layers.fc(input=x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((4, 8), "float32")}
        exe.run(main, feed=feed, fetch_list=[loss])
        trace.configure(capacity=4096)
        exe.run(main, feed=feed, fetch_list=[loss])
    steady = [ev["name"] for ev in trace.dump()["events"]]
    assert len(steady) <= 8 and set(steady) <= {
        "exec/step", "exec/prepare", "exec/host_io", "exec/lookup",
        "exec/dispatch", "exec/jit_call", "exec/writeback", "exec/d2h"}

    def pair_us(n=10000):
        trace.configure(capacity=4096)
        t0 = time.perf_counter()
        for _ in range(n):
            trace.span("bench/pair").end()
        return 1e6 * (time.perf_counter() - t0) / n
    assert statistics.median(pair_us() for _ in range(5)) \
        < SPAN_PAIR_LIMIT_US


# five times what the parent's recorder read on this box (3.05 us a pair,
# median of five, PR 35; this tree 3.3 with the profiler's is_enabled check)
SPAN_PAIR_LIMIT_US = 15.0
