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
# tiny_host_phases and tiny_build_phases list some of tiny's workloads again
# under other readers
REPEATS = ("tiny_host_phases", "tiny_build_phases")
BUILD_PHASES = os.path.join(REPO, "benchmark", "tests", "tiny_build_phases",
                            "manifest.json")
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


def _rehearse(manifest, workload, chips, tmp_path, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % chips,
               # a cache of this run's own: nothing an earlier tree left
               # is loaded, and parallel cases write no file twice
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", workload,
         "--seed", "5", "--seconds", str(SECONDS.get(workload, 1)),
         "--rehearse", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, proc.stdout[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0, out
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    return out


# The chain of child processes is three files' (this one,
# test_benchmark_rehearsal_b.py and _c.py, each with _cells()[i::FILES]):
# under `--dist loadfile` a file is one worker's from start to end, and the
# files of the fewest cases are handed out last, so one file of all the
# cells was the run's tail (ROADMAP C8, PR 73). This file, which has the
# traced case besides and so goes out before the other two, takes the share
# with tiny_hostu8 in it (52 s of the cells' 430 on the builder's machine).
# A new manifest's cells fall to the files by their place in the sorted
# list, with no edit.
FILES = 3


@pytest.mark.parametrize("manifest,workload,chips", _cells()[1::FILES])
def test_rehearsal(manifest, workload, chips, tmp_path):
    _rehearse(manifest, workload, chips, tmp_path)


def test_a_traced_rehearsal_prints_the_six_of_set_up(tmp_path):
    """PR 51's per-layer metrics are `program_counter`s, which a CPU run
    prints: under --trace 1 the line has all six, through
    ParallelExecutor on four devices here (test_build_phases.py runs the
    one-device workloads of the same manifest and holds the numbers to the
    set-up line)."""
    with open(BUILD_PHASES) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]]
    six = ["program_build_s", "infer_shape_s", "append_backward_s",
           "optimizer_pass_s", "package_import_s", "pallas_import_s"]
    assert listed[-6:] == six
    got = _rehearse(BUILD_PHASES, "tiny_dp4", 4, tmp_path, trace=1)["metrics"]
    assert set(six) <= set(got)
    assert all(got[n]["unit"] == "s" and got[n]["value"] >= 0 for n in six)
    assert got["program_build_s"]["value"] > got["infer_shape_s"]["value"] > 0
