"""Rehearsals and tests of the benchmark itself, all on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The whole command runs as a child process on a tiny configuration that
lives in this directory (tests/tiny), which proves that a configuration, a
traffic mix and a per-layer metric are added by files and manifest entries
alone. Nothing here touches the TPU library, at import or in a test.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny", "manifest.json")
TRACE = os.path.join(HERE, "recorded", "dp4_boundary.xplane.pb")


def _run(workload, *extra, devices=1, seconds=1, seed=5):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
               % devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", TINY, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds)] + list(extra),
        env=env, capture_output=True, text=True, timeout=600)
    return proc


def _last(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ rehearsals --
def test_cpu_without_rehearse_is_refused():
    """No accelerator: another exit code than 0 and no result line."""
    proc = _run("tiny_t16", "--trace", "0")
    assert proc.returncode == 2
    assert "{" not in proc.stdout.strip().splitlines()[-1]
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_one_device(trace):
    proc = _run("tiny_t16", "--rehearse", "--trace", str(trace))
    out = _last(proc)
    assert "bench: platform: cpu" in proc.stdout
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["device"]["platform"] == "cpu"
    # a CPU run reports counts and never a time, a rate or a share of a
    # device: the manifest's program_counter metrics and no other
    names = set(out["metrics"])
    if trace:
        assert names == {"compile_requests", "cache_hit_share",
                         "window_steps"}
        assert out["metrics"]["window_steps"]["value"] == out["attempted"]
    else:
        assert names == set()
    assert "busy_s" not in out["device"] and "breakdown" not in out


@pytest.mark.parametrize("workload", ["tiny_dp4", "tiny_dp4_zero"])
def test_rehearsal_four_devices(workload):
    """The dp=4 path on four virtual CPU devices, with replicated weights
    and with `sharded_weight_update`: state and batch on four distinct
    devices, or the run is not correct."""
    proc = _run(workload, "--rehearse", "--trace", "0", devices=4)
    out = _last(proc)
    assert out["correct"] is True
    assert out["device"]["count"] == 4
    assert "on 4 distinct device(s)" in proc.stdout


def test_four_chip_cell_on_one_device_is_refused():
    proc = _run("tiny_dp4", "--rehearse", "--trace", "0", devices=1)
    assert proc.returncode == 2
    assert "asks for 4 chip(s)" in proc.stderr


def test_rehearsal_steps_per_call():
    """run(steps=2): every step of a call is counted and checked."""
    out = _last(_run("tiny_k2", "--rehearse", "--trace", "0"))
    assert out["correct"] is True and out["attempted"] % 4 == 0


def test_rehearsal_host_u8():
    """uint8 batches through the program's DoubleBufferReader; three batches
    in rotation, so the loss needs some rounds to fall."""
    out = _last(_run("tiny_hostu8", "--rehearse", "--trace", "0", seconds=5))
    assert out["correct"] is True and out["attempted"] > 0


def test_second_run_finds_every_program_in_the_cache(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR places the cache, jax keeps only
    compiles of a second or more; the benchmark keeps them all, so only a
    checkout's first run compiles."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    shares = [_last(_run("tiny_t16", "--rehearse", "--trace", "1",
                         seconds=0.2, seed=seed))["metrics"][
        "cache_hit_share"]["value"] for seed in (1, 2)]
    assert shares == [0.0, 100.0]


def test_same_seed_same_inputs_other_seed_other_inputs():
    lines = []
    for seed in (5, 5, 6):
        proc = _run("tiny_t16", "--rehearse", seconds=0.2, seed=seed)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines.append(next(ln for ln in proc.stdout.splitlines()
                          if "bench: correct:" in ln).split(" -> ")[0])
    assert "reference" in lines[0]
    assert lines[0] == lines[1] != lines[2]


# ------------------------------------------------ `correct` can fail ----
@pytest.mark.parametrize("mutant", ["zeroed", "no_causal", "unscaled"])
def test_broken_attention_is_not_correct(mutant):
    """The cell with its attention broken on purpose (tests/mutant.py): the
    first-step loss stays within its tolerance of the reference, as it sits
    near ln(V) whatever the forward does, and the logits do not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "mutant.py"), mutant,
         "--manifest", TINY, "--workload", "tiny_t16", "--rehearse",
         "--seconds", "0.2", "--seed", "5"],
        env=env, capture_output=True, text=True, timeout=600)
    out = _last(proc)
    assert out["correct"] is False and out["failed"] == 0
    line = next(ln for ln in proc.stdout.splitlines()
                if "bench: correct:" in ln)
    verdicts = json.loads(line.rpartition("verdicts ")[2])
    assert verdicts.pop("reference") is False and all(verdicts.values())
    errors = dict(part.split(" off by ") for part in line.split(
        "value): ")[1].split(";")[0].split(", "))
    assert float(errors["loss"].split()[0]) < 0.02
    assert float(errors["logits"].split()[0]) > 0.2


def test_normalised_error():
    from benchmark import checks
    want = np.array([[1.0, -4.0], [2.0, 0.0]])
    assert checks.normalised_error(want, want) == 0.0
    assert checks.normalised_error(want + [[0, 0], [0, 1]], want) == 0.25
    assert checks.normalised_error([2.2], 2.0) == pytest.approx(0.1)
    bad = want.copy()
    bad[0, 0] = np.nan
    assert not checks.normalised_error(bad, want) <= 1e9


# -------------------------------------------------------- the manifest ----
def test_manifest_resolves_every_cell():
    from benchmark import manifest
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for w in m["workloads"]:
        cell = manifest.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                  w["name"])
        e2e = {e["name"] for e, _ in cell.metrics["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics["per_layer"]
        for _, reader in (cell.metrics["end_to_end"]
                          + cell.metrics["per_layer"]):
            assert callable(reader.read)
        moved = {e["moves"] for e, _ in cell.metrics["per_layer"]}
        assert moved <= e2e, (w["name"], moved - e2e)


def test_unknown_device_kind_is_an_error():
    from benchmark import manifest
    assert manifest.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.peak_for("cpu")


def test_memory_peak_adds_what_the_runtime_reserves():
    """The v5e runtime books a step's temporaries as reserved, not in use
    (0.44 GiB in use beside 8.56 reserved for ResNet-50): both count, the
    fullest device decides, and a backend without the numbers gives None."""
    from benchmark import cell

    class Dev(object):
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats
    full = Dev({"peak_bytes_in_use": 475917312,
                "peak_bytes_reserved": 9187262464})
    less = Dev({"peak_bytes_in_use": 475917312, "peak_bytes_reserved": 5})
    assert cell._memory_peak([less, full]) == 475917312 + 9187262464
    assert cell._memory_peak([Dev({"peak_bytes_in_use": 7})]) == 7
    assert cell._memory_peak([full, Dev(None)]) is None


# ------------------------------------------------- operations a sample ----
def _config(name):
    from benchmark import manifest
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    mod = manifest.load_module(os.path.join(ROOT, "benchmark", "configs",
                                            name + ".py"))
    return cfg, mod


def test_resnet50_operations_against_the_hand_count():
    """He et al. Table 1 gives 3.8e9 multiply-adds for the 50-layer net
    with stride on the first 1x1 of a block; this program (and the fluid
    benchmark) strides the 3x3, which makes it 4.09e9: 8.18 GFLOP forward,
    24.5 GFLOP an image trained."""
    cfg, mod = _config("resnet50")
    macs = sum(ci * co * k * k * hw * hw for ci, co, k, hw in mod._convs(cfg))
    assert abs(macs - 4.089e9) < 0.005e9
    assert len(mod._convs(cfg)) == 54       # 53 convolutions + classifier
    assert abs(mod.ops_per_sample(cfg, {}) - 24.53e9) < 0.03e9


@pytest.mark.parametrize("seq_len,want", [(256, 386.1e6), (2048, 551.3e6)])
def test_transformer_operations_against_the_hand_count(seq_len, want):
    """6 x (6 x 28 d^2 + d V) weights' operations = 362.5e6, and
    6 layers x 30 T d for attention (full + causal half + full, 12 T d a
    full attention)."""
    cfg, mod = _config("transformer_base")
    d, v, n = 512, 32000, 6
    by_hand = 6 * (n * 28 * d * d + d * v) + n * 30 * seq_len * d
    got = mod.ops_per_sample(cfg, {"seq_len": seq_len})
    assert got == by_hand
    assert abs(got - want) < 0.1e6


# ------------------------------------------ references against the program --
def _program_and_reference(mod, cfg, traffic, seed=3):
    """(the first step's fetches of the program with AMP switched off, the
    reference's): both float32, so they agree closely or the reference is
    not the program's architecture."""
    import jax
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        fetches = mod.build(fluid, cfg, traffic)
    main.enable_mixed_precision(False)
    batch = mod.make_batch(cfg, traffic, jax.random.key(seed))
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = [np.asarray(scope.get(p.name))
                  for p in main.global_block().all_parameters()]
        got = exe.run(main, feed=batch, fetch_list=list(fetches.values()))
    want = mod.reference(cfg, traffic, params, batch)
    assert sorted(want) == sorted(fetches)
    return dict(zip(fetches, got)), want


def test_transformer_reference_agrees_with_the_program():
    _, mod = _config("transformer_base")
    with open(os.path.join(HERE, "tiny", "configs",
                           "transformer_tiny.json")) as f:
        cfg = json.load(f)
    got, want = _program_and_reference(
        mod, cfg, {"batch": 3, "seq_len": 16, "feed": "device"})
    # float32 on both sides: summation order only. A forward without label
    # smoothing or the sqrt(d) scale is off by more than 1e-2
    from benchmark import checks
    assert got["logits"].shape == (3, 16, 64)       # the vocabulary is 64
    for name in ("loss", "logits"):
        assert checks.normalised_error(got[name], want[name]) <= 1e-4, name


def test_resnet_reference_agrees_with_the_program():
    cfg, mod = _config("resnet50")
    cfg = dict(cfg, image_hw=96, class_dim=10)
    got, want = _program_and_reference(
        mod, cfg, {"batch": 4, "feed": "device"})
    # float32 on both sides through 53 batch-normed convolutions
    from benchmark import checks
    assert checks.normalised_error(got["loss"], want["loss"]) <= 1e-4


# ------------------------------------------------------ trace reduction ----
class _Ev(object):
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line(object):
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane(object):
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_reduction_on_a_hand_made_trace():
    """One device, 100 ns window: fusion 0-40 (a while 10-30 nested with a
    20 ns child), a Mosaic call 50-60, an all-reduce 60-80 with nothing
    beside it, idle 40-50 (in run_call) and 80-90 (in block_sync), a last
    op 90-100."""
    from benchmark import trace_reduce
    ops = _Line("XLA Ops", [
        _Ev("fusion.1", 0, 40), _Ev("while.2", 10, 20),
        _Ev("fusion.3", 10, 20),
        _Ev('%fn.7 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %fusion.1), '
            'custom_call_target="tpu_custom_call"', 50, 10),
        _Ev("%all-reduce.4 = f32[64]{0} all-reduce(f32[64]{0} %fusion.3), "
            "channel_id=1", 60, 20),
        _Ev('%custom-call.5 = f32[4]{0} custom-call(f32[4]{0} %fn.7), '
            'custom_call_target="ConcatBitcast"', 90, 10)])
    host = _Line("python", [_Ev("bench/run_call", 35, 13),
                            _Ev("bench/block_sync", 75, 20),
                            _Ev("other", 0, 100)])
    s = trace_reduce.reduce_planes(iter([    # ProfileData's is one-shot too
        _Plane("/device:TPU:0", [ops, _Line("Steps", [_Ev("1", 0, 100)])]),
        _Plane("/host:CPU", [host])]))
    ns = 1e-9
    assert s["planes"] == ["/device:TPU:0"]
    assert abs(s["window_s"] - 100 * ns) < 1e-15
    assert abs(s["busy_s"] - 80 * ns) < 1e-15
    assert abs(s["category_s"]["xla"] - 50 * ns) < 1e-15   # while: self 0
    assert abs(s["category_s"]["pallas"] - 10 * ns) < 1e-15
    assert abs(s["collective_s"] - 20 * ns) < 1e-15
    assert abs(s["collective_exposed_s"] - 20 * ns) < 1e-15
    assert s["n_ops"] == 6
    assert dict(map(tuple, s["top_ops"]))[
        "fn.7 custom-call tpu_custom_call"] == 10 * ns
    assert s["top_ops"][0][0] in ("fusion.1", "fusion.3",
                                  "all-reduce.4 all-reduce")
    assert sorted(g[0] for g in s["idle_gaps"][:2]) == [
        "bench/block_sync", "bench/run_call"]
    totals = {k: round(v, 15) for k, v in s["idle_gaps"]
              if k.startswith("total:")}
    assert totals == {"total:bench/run_call": 8 * ns,     # 40-48 of 40-50
                      "total:none": 2 * ns,
                      "total:bench/block_sync": 10 * ns}


def test_reduction_counts_an_overlapped_collective_once():
    """An asynchronous all-reduce: `-start` 100-102 and `-done` 130-140 on
    the ops line, a fusion 102-130 between them, and the whole flight
    100-140 as one span on the async line (with a copy, which is ignored).
    A second collective, 150-160, sits inside a `while` 145-170 that
    computes nothing beside it: a parent does not hide its child."""
    from benchmark import trace_reduce
    flight = ("%all-reduce-start.9 = f32[64]{0} all-reduce-start(f32[64]{0} "
              "%fusion.8), channel_id=2")
    ops = _Line("XLA Ops", [
        _Ev(flight, 100, 2), _Ev("fusion.10", 102, 28),
        _Ev("%all-reduce-done.9 = f32[64]{0} all-reduce-done(f32[64]{0} "
            "%all-reduce-start.9)", 130, 10),
        _Ev("while.11", 145, 25),
        _Ev("%all-gather.12 = f32[64]{0} all-gather(f32[16]{0} %fusion.10), "
            "channel_id=3", 150, 10)])
    flights = _Line("Async XLA Ops", [
        _Ev(flight, 100, 40),
        _Ev("%copy-start.3 = f32[4]{0} copy-start(f32[4]{0} %p)", 90, 60)])
    s = trace_reduce.reduce_planes([_Plane("/device:TPU:0", [ops, flights])])
    ns = 1e-9
    assert s["n_ops"] == 5
    assert abs(s["window_s"] - 70 * ns) < 1e-15
    assert abs(s["busy_s"] - 65 * ns) < 1e-15
    assert abs(s["collective_s"] - 50 * ns) < 1e-15         # 40 once, + 10
    assert abs(s["collective_exposed_s"] - 22 * ns) < 1e-15  # 2 + 10 + 10
    assert abs(s["category_s"]["collective"] - 22 * ns) < 1e-15
    assert abs(s["category_s"]["xla"] - 43 * ns) < 1e-15    # 28 + while 15


def test_device_ms_a_step_reads_the_traced_window():
    """Self time of a category over the steps of the traced window; nothing
    without a trace (a --trace 0 run) or without a device operation."""
    from benchmark import readers
    record = {"trace": {"busy_s": 0.9, "category_s": {"xla": 0.6,
                                                      "pallas": 0.3}},
              "window": {"attempted": 30}}
    read = readers.category_ms_per_step
    assert read(record, "xla") == pytest.approx(20.0)
    assert read(record, "pallas") == pytest.approx(10.0)
    assert read(record, "collective") == 0.0
    assert read(dict(record, trace=None), "xla") is None
    idle = dict(record, trace={"busy_s": 0.0, "category_s": {}})
    assert read(idle, "xla") is None


def test_reduction_without_a_device_plane_gives_nothing():
    from benchmark import trace_reduce
    s = trace_reduce.reduce_planes([_Plane("/host:CPU", [])])
    assert s["busy_s"] == 0.0 and s["top_ops"] == []


def test_reduction_on_the_recorded_trace():
    """8 ms of resnet50_train_dp4 recorded on a four-chip v5e host
    (tests/recorded/README.txt): the numbers the reduction gave when it was
    recorded, so that a change to the reduction shows."""
    from benchmark import trace_reduce
    with open(os.path.join(HERE, "recorded",
                           "dp4_boundary.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_file(TRACE)
    assert got["planes"] == want["planes"] and len(got["planes"]) == 4
    assert got["n_ops"] == want["n_ops"]
    for key in ("window_s", "busy_s", "collective_s",
                "collective_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["category_s"] == pytest.approx(want["category_s"], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert 0 < got["collective_s"] < got["category_s"]["xla"]
    assert "pallas" not in got["category_s"]        # ResNet has no kernel
    assert got["top_ops"][0][0] == want["top_op"][0]
    assert [g[0] for g in got["idle_gaps"]] == [
        g[0] for g in want["idle_gaps"]]
    assert got["idle_gaps"][0][0] == "bench/run_call"
