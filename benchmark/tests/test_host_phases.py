"""The five per-layer metrics that read the program's own phases (PR 35:
benchmark/program_reads.py and its readers), rehearsed on the CPU through
the whole command on a tiny cell. A manifest of this test's own
(tests/tiny_host_phases) lists them beside the tiny configuration and
traffic that tests/tiny has; nothing here touches the TPU library."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "tiny_host_phases", "manifest.json")
COUNTERS = {"jaxpr_trace_s", "mlir_lower_s", "compile_or_load_s"}
SPANS = {"jit_call_ms", "executor_host_ms"}


def _rehearse(workload, devices, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
               % devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", MANIFEST, "--workload", workload, "--rehearse",
         "--seed", "3000000019", "--seconds", "1", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,devices", [("tiny_t16", 1),
                                              ("tiny_dp4", 4)])
def test_a_traced_rehearsal_prints_the_three_counters(workload, devices):
    """Through Executor and through ParallelExecutor. A CPU run prints the
    `program_counter` metrics and no time of a device or a span."""
    stdout, out = _rehearse(workload, devices, 1)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    got = out["metrics"]
    assert set(got) == COUNTERS | {"compile_requests"}
    for name in COUNTERS:
        assert got[name]["unit"] == "s" and got[name]["value"] > 0
    # no more than the spans they lie in, which set-up's line prints
    line = next(ln for ln in stdout.splitlines() if "bench: set-up" in ln)
    spans = dict(zip(line.split(": ", 2)[2].split()[0::2],
                     line.split(": ", 2)[2].split()[1::2]))
    assert sum(got[n]["value"] for n in COUNTERS) <= \
        float(spans["startup"]) + float(spans["first_step"].rstrip(";")) \
        + 0.02      # the line rounds each span to 10 ms


def test_untraced_the_line_has_none_of_them():
    _, out = _rehearse("tiny_t16", 1, 0)
    assert not (COUNTERS | SPANS) & set(out["metrics"])


def test_the_manifest_lists_the_five_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = ["jaxpr_trace_s", "mlir_lower_s", "compile_or_load_s",
             "jit_call_ms", "executor_host_ms"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    tail = [by_name[n] for n in names]      # later PRs may add behind them
    assert all("workloads" not in m for m in tail)
    assert [m["moves"] for m in tail] == ["setup_s"] * 3 + ["mfu"] * 2
    for m in tail:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
