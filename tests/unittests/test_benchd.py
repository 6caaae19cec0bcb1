"""paddle_tpu.benchd — record schema, bench store and the regression
gate (ARCHITECTURE.md §28). Everything here reads and writes files
under tmp_path; nothing initialises a device."""
import importlib.util
import os

import pytest

from paddle_tpu.benchd import gate as benchd_gate
from paddle_tpu.benchd import schema
from paddle_tpu.benchd.store import BenchStore

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

GOOD = {"metric": "m_x", "value": 10.0, "unit": "u/s",
        "batch": 64, "device": "TPU v5 lite0"}


def _rec(**kw):
    rec = dict(GOOD)
    rec.update(kw)
    return rec


# ------------------------------------------------------------- schema --

def test_schema_validates_and_rejects():
    assert schema.validate_record(GOOD) == []
    assert schema.validate_record({"metric": "m"})          # no value/unit
    assert schema.validate_record(_rec(value=float("nan")))
    assert schema.validate_record(_rec(value=True))         # bool != number
    assert schema.validate_record(_rec(error=""))           # empty error
    assert schema.validate_record(_rec(vs_baseline="high"))
    assert schema.validate_record("not a dict")
    with pytest.raises(ValueError):
        schema.check_record(_rec(unit=""))
    assert schema.check_record(GOOD) is GOOD


def test_schema_error_rule_and_device_kind():
    assert not schema.is_error(GOOD)
    assert schema.is_error(_rec(error="wedged"))
    # chip index stripped: chips of one kind share baselines
    assert schema.device_kind({"device": "TPU v5 lite0"}) == "TPU v5 lite"
    assert schema.device_kind({"device": "TPU v5 lite1"}) == "TPU v5 lite"
    assert schema.device_kind({"device": "TFRT_CPU_0"}) == "cpu"
    assert schema.device_kind({}) == "unknown"


def test_config_digest_keys_configs_not_measurements():
    # same config, different measured value -> same key
    assert schema.config_digest(_rec(value=10.0)) \
        == schema.config_digest(_rec(value=99.0))
    # different config -> different key (a batch-512 line must never
    # gate against a batch-64 baseline)
    assert schema.config_digest(_rec(batch=512)) \
        != schema.config_digest(GOOD)
    # floats are measurements, not config
    assert schema.config_digest(_rec(mfu=0.31)) \
        == schema.config_digest(GOOD)


# -------------------------------------------------------------- store --

def test_store_append_and_last_good_skips_errors(tmp_path):
    s = BenchStore(tmp_path / "store")
    s.append(_rec(value=100.0), ts=1.0)
    s.append(_rec(value=110.0), ts=2.0)
    # the baseline rule, enforced: an error placeholder is never a
    # baseline, however new
    s.append(_rec(value=0.0, error="device init failed"), ts=3.0)
    lg = s.last_good("m_x")
    assert lg["record"]["value"] == 110.0
    assert s.summary()["errors"] == 1
    # before_seq: a fresh line never resolves itself as baseline
    assert s.last_good("m_x", before_seq=1)["record"]["value"] == 100.0
    assert s.last_good("m_x", before_seq=0) is None


def test_store_rejects_malformed_and_survives_corruption(tmp_path):
    s = BenchStore(tmp_path / "store")
    with pytest.raises(ValueError):
        s.append({"metric": "m", "value": 1.0})  # no unit
    s.append(GOOD)
    with open(s.path, "a") as f:
        f.write("{torn line\n")                  # crash mid-write
    s.append(_rec(value=11.0))
    assert len(s.entries()) == 2                 # readable after any kill


# --------------------------------------------------------------- gate --

def _gate_fresh(rec, **env_kw):
    env = {"metric": rec["metric"],
           "device_kind": schema.device_kind(rec),
           "digest": schema.config_digest(rec), "record": rec}
    env.update(env_kw)
    return env


def test_gate_verdicts(tmp_path):
    s = BenchStore(tmp_path / "store")
    s.append(_rec(value=100.0), ts=1.0)
    run = benchd_gate.run_gate
    # 25% down on the same config: regression, exit 1
    rep = run(s, fresh=[_gate_fresh(_rec(value=75.0))])
    assert [v["verdict"] for v in rep["verdicts"]] == ["regression"]
    assert rep["exit_code"] == 1
    # within the ±10% band: ok
    assert run(s, fresh=[_gate_fresh(_rec(value=95.0))])[
        "exit_code"] == 0
    # 30% up: improvement (still exit 0)
    rep = run(s, fresh=[_gate_fresh(_rec(value=130.0))])
    assert rep["counts"]["improvement"] == 1 and rep["exit_code"] == 0
    # error placeholder: skipped per the baseline rule, never failed
    rep = run(s, fresh=[_gate_fresh(_rec(value=0.0, error="wedged"))])
    assert rep["counts"]["error-skipped"] == 1 and rep["exit_code"] == 0
    # unknown config: no-baseline pass — cross-config ratios are
    # context, never verdicts
    rep = run(s, fresh=[_gate_fresh(_rec(value=1.0, batch=512))])
    assert rep["counts"]["no-baseline"] == 1 and rep["exit_code"] == 0


def test_gate_min_of_repeats(tmp_path):
    """One noisy repeat must not fail a healthy config: the best of the
    fresh repeats is the representative."""
    s = BenchStore(tmp_path / "store")
    s.append(_rec(value=100.0), ts=1.0)
    fresh = [_gate_fresh(_rec(value=60.0)),     # noisy outlier
             _gate_fresh(_rec(value=98.0))]
    rep = benchd_gate.run_gate(s, fresh=fresh)
    v = rep["verdicts"][0]
    assert v["verdict"] == "within-noise" and v["repeats"] == 2
    assert rep["exit_code"] == 0


def test_gate_lower_is_better_direction():
    assert benchd_gate.metric_direction("anything", "images/sec") == 1
    assert benchd_gate.metric_direction("serving_p99_ms", "ms") == -1
    assert benchd_gate.metric_direction("new_latency", "ms") == -1


def test_gate_self_mode_skips_newest_errors(tmp_path):
    """Self-gate: the newest entry per key vs the last-good before it —
    an error placeholder newest (a run that died before measuring)
    passes, a real regression newest fails."""
    s = BenchStore(tmp_path / "store")
    s.append(_rec(value=100.0), ts=1.0)
    s.append(_rec(value=0.0, error="wedged"), ts=2.0)
    assert benchd_gate.run_gate(s)["exit_code"] == 0
    s.append(_rec(value=50.0), ts=3.0)
    rep = benchd_gate.run_gate(s)
    assert rep["exit_code"] == 1 and rep["regressions"] == 1


# ------------------------------------------------------- schema guard --

def _load_bench_module():
    spec = importlib.util.spec_from_file_location(
        "_bench_for_schema", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ERROR_MODES = [
    {}, {"BENCH_SERVING": "1"}, {"BENCH_POOL": "1"},
    {"BENCH_FLEET": "1"}, {"BENCH_CKPT": "1"}, {"BENCH_RESIL": "1"},
    {"BENCH_COMPILE_CACHE": "1"}, {"BENCH_SHARDED": "1"},
    {"BENCH_TP": "1"}, {"BENCH_PIPELINE": "1"}, {"BENCH_OBS": "1"},
    {"BENCH_DECODE": "1"},
    {"BENCH_MODEL": "transformer"},
    {"BENCH_MODEL": "transformer", "BENCH_DECODE": "1"},
    {"BENCH_MODEL": "stacked_lstm"},
]


@pytest.mark.parametrize("mode", _ERROR_MODES,
                         ids=["+".join(sorted(m)) or "default"
                              for m in _ERROR_MODES])
def test_every_error_line_matches_the_schema(mode, monkeypatch):
    """Every bench.py leg's failure placeholder validates against the
    ONE shared record schema — so the store can always ingest a failed
    window and the gate always classifies it as error-skipped."""
    for k in list(os.environ):
        if k.startswith("BENCH_"):
            monkeypatch.delenv(k, raising=False)
    for k, v in mode.items():
        monkeypatch.setenv(k, v)
    bench = _load_bench_module()
    rec = bench._error_line("synthetic failure")
    assert schema.validate_record(rec) == []
    assert schema.is_error(rec)
    assert rec["value"] == 0.0


def test_bench_success_emissions_go_through_emit():
    """Source guard: every metric-bearing emission in bench.py goes out
    through _emit (the schema check); raw print(json.dumps(...)) is
    reserved for the compile-cache child's intermediate non-record
    lines."""
    src = open(os.path.join(REPO, "bench.py")).read()
    raw_sites = [chunk.split("\n", 3)[:3] for chunk in
                 src.split("print(json.dumps(")[1:]]
    # only the two compile-cache child payloads (keyed "kind", not
    # "metric") plus the print inside _emit itself may bypass the guard
    non_emit = [site for site in raw_sites
                if "check_record(rec)" not in site[0]]
    assert len(non_emit) == 2, non_emit
    for site in non_emit:
        assert any('"kind"' in line for line in site), site
    assert src.count("_emit(") >= 30


# ------------------------------------------------- bench.py device rules --
class _FakeDevice(object):
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_tflops_known_unknown_and_cpu(monkeypatch):
    """The MFU denominator: keyed on device_kind; an accelerator missing
    from the table is an error (it used to read as a v5e), and on the
    CPU there is no peak — MFU is not measured, never a number."""
    import jax
    bench = _load_bench_module()
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
    assert bench._peak_tflops() == 197.0
    assert bench._mfu(19.7e12) == 0.1
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v9 mystery")])
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        bench._peak_tflops()
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "100")
    assert bench._peak_tflops() == 100.0            # explicit pin wins
    monkeypatch.delenv("BENCH_PEAK_TFLOPS")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("cpu", "cpu")])
    assert bench._peak_tflops() is None and bench._mfu(1e12) is None


def test_bench_main_refuses_a_host_without_a_tpu(monkeypatch, capsys):
    """No lock, no watchdog thread, no os._exit: main() looks at
    jax.devices() once and, unless the platform is tpu or the process is
    pinned to the CPU on purpose, prints one schema-valid error line and
    exits 3 without running a step."""
    import json
    import jax
    bench = _load_bench_module()
    for k in list(os.environ):
        if k.startswith("BENCH_"):
            monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("BENCH_WARMUP", "0")     # leave the cache alone
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("cpu", "cpu")])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 3
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert schema.validate_record(rec) == [] and schema.is_error(rec)
    assert "expected a TPU" in rec["error"]


def test_ptpu_bench_has_no_verb_that_touches_the_chip():
    """`run`, `daemon` and `reset-queue` went with the daemon: measuring
    is one process sent through the chip tool, not a CLI verb here."""
    import subprocess
    import sys
    cli = os.path.join(REPO, "tools", "ptpu_bench.py")
    for verb in ("run", "daemon", "reset-queue"):
        out = subprocess.run([sys.executable, cli, verb],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 2, (verb, out.stdout, out.stderr)
        assert "invalid choice" in out.stderr
