"""tools/ptpu_bench.py — the CLI surface of paddle_tpu.benchd, run as
subprocesses on CPU the way CI runs it.

The store is seeded with one measured line and one error placeholder
(a run that died before measuring): `ptpu_bench gate` over it must exit
0 — the placeholder is a failed run, not a regression — while a
synthetic 20% throughput drop against the measured baseline must exit 1.
"""
import json
import os
import subprocess
import sys

from paddle_tpu.benchd.store import BenchStore

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
CLI = os.path.join(REPO, "tools", "ptpu_bench.py")

BASE = {"metric": "resnet50_imagenet_train_throughput", "value": 1000.0,
        "unit": "images/sec/chip", "batch": 64, "device": "TPU v5 lite0"}


def _seed(tmp_path):
    store = BenchStore(tmp_path / "bench_store")
    store.append(dict(BASE), source="run1", ts=1.0)
    store.append(dict(BASE, value=0.0,
                      error="device init did not return"),
                 source="run2", ts=2.0)


def _run(tmp_path, *argv):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    return subprocess.run(
        [sys.executable, CLI, "--store", str(tmp_path / "bench_store")]
        + list(argv),
        env=env, capture_output=True, text=True, timeout=300)


def _fresh(tmp_path, value):
    fresh = tmp_path / "fresh.jsonl"
    fresh.write_text(json.dumps(dict(BASE, value=value)) + "\n")
    return str(fresh)


def test_bench_gate_smoke(tmp_path):
    """Self-gate: the newest entry is an error placeholder, which skips
    and is shown; nothing regresses — exit 0."""
    _seed(tmp_path)
    out = _run(tmp_path, "gate")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 regression(s)" in out.stdout
    assert "error placeholders" in out.stdout


def test_bench_gate_synthetic_regression(tmp_path):
    """A 20% throughput drop in the same config must FAIL the gate (exit
    1) against the measured baseline — the same store that just exited
    0 on the error placeholder."""
    _seed(tmp_path)
    out = _run(tmp_path, "gate", "--fresh", _fresh(tmp_path, 800.0),
               "--json")
    assert out.returncode == 1, out.stdout + out.stderr
    report = json.loads(out.stdout)
    (verdict,) = report["verdicts"]
    assert verdict["verdict"] == "regression"
    assert verdict["baseline_source"] == "run1"
    assert verdict["baseline"] == 1000.0


def test_bench_gate_fresh_improvement_passes(tmp_path):
    _seed(tmp_path)
    out = _run(tmp_path, "gate", "--fresh", _fresh(tmp_path, 1200.0),
               "--json")
    assert out.returncode == 0, out.stdout + out.stderr
    assert json.loads(out.stdout)["counts"]["improvement"] == 1


def test_bench_gate_bad_fresh_file_is_usage_error(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"metric": "m"}\n')   # no value/unit
    out = _run(tmp_path, "gate", "--fresh", str(bad))
    assert out.returncode == 2, out.stdout + out.stderr


def test_bench_status_reports_last_good(tmp_path):
    """`ptpu_bench status`: the measured line is the key's last-good
    baseline; the placeholder counts as an error, never as a value."""
    _seed(tmp_path)
    out = _run(tmp_path, "status", "--json")
    assert out.returncode == 0, out.stdout + out.stderr
    status = json.loads(out.stdout)
    assert status["store"] == {"records": 2, "errors": 1}
    (slot,) = status["last_good"].values()
    assert slot == {"value": 1000.0, "source": "run1"}
