"""The driver's command, rehearsed on the CPU: one case a tiny cell.

`python benchmark/run.py` judges every PR on the chip (BENCHMARK.json,
PERF_LEDGER.jsonl). Its own tests live in benchmark/tests, outside what
tier-1 collects, so a program change that breaks the command's path was first
seen on the chip. Here every workload of every tiny manifest under
benchmark/tests walks that path as a child process (manifest -> configuration
module -> build -> set-up checks -> window -> the result line) and has to
come out correct. The manifests are found by glob: a `model_config` PR that
adds benchmark/tests/tiny_<name>/manifest.json is run with no edit here.

Nothing about speed: a CPU run gives counts, never a time or a rate.
"""
import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
# tiny_host_phases lists two of tiny's workloads again under other readers
REPEATS = ("tiny_host_phases",)
# --seconds where 1 is too few: tiny_hostu8's loss has to fall over three
# rotating batches, which takes two blocks of two steps. Its own test under
# benchmark/tests passes 5; beside five other test workers one block alone
# took 5.4 s, so four times that
SECONDS = {"tiny_hostu8": 20}


def _cells():
    cells = []
    for path in sorted(glob.glob(os.path.join(
            REPO, "benchmark", "tests", "tiny*", "manifest.json"))):
        if os.path.basename(os.path.dirname(path)) in REPEATS:
            continue
        with open(path) as f:
            manifest = json.load(f)
        cells += [pytest.param(path, w["name"], w["chips"], id=w["name"])
                  for w in manifest["workloads"]]
    return cells


@pytest.mark.parametrize("manifest,workload,chips", _cells())
def test_rehearsal(manifest, workload, chips, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % chips,
               # a cache of this run's own: nothing an earlier tree left
               # is loaded, and parallel cases write no file twice
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", workload,
         "--seed", "5", "--seconds", str(SECONDS.get(workload, 1)),
         "--rehearse", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, proc.stdout[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0, out
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
