"""`peak_hbm_gib` is a layer's metric (PR 55): listed once, under `per_layer`,
in BENCHMARK.json and in every tiny manifest, and found under layer_metrics/
by every cell. That what it moves (`mfu`) is reported by each of its cells is
test_benchmark.py's test_manifest_resolves_every_cell, for every entry. All
on the CPU, no array.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_memory_metric.py -q
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
NAME = "peak_hbm_gib"
MANIFESTS = [os.path.join(ROOT, "BENCHMARK.json")] + sorted(
    glob.glob(os.path.join(HERE, "tiny*", "manifest.json")))


def _ids(paths):
    return [os.path.relpath(p, ROOT) for p in paths]


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MANIFESTS, ids=_ids(MANIFESTS))
def test_memory_is_listed_once_and_under_per_layer(path):
    m = _load(path)
    held = [e["name"] for e in m["end_to_end"]]
    assert NAME not in held
    # the four that stay: a manifest has the throughput of its own cells
    assert set(held) <= {"images_per_s_per_chip", "tokens_per_s_per_chip",
                         "mfu", "setup_s"}
    assert {"mfu", "setup_s"} < set(held)
    # `host_clock`, not `program_counter`: cell.run reads only the latter in
    # a CPU rehearsal, and a CPU's memory is no device number
    entries = [e for e in m["per_layer"] if e["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "GiB", "better": "lower",
                        "source": "host_clock", "layer": "device",
                        "moves": "mfu"}]


def test_the_real_manifest_keeps_the_four_with_their_bounds():
    held = _load(MANIFESTS[0])["end_to_end"]
    assert [(e["name"], e["bound"]) for e in held] == [
        ("images_per_s_per_chip", 0.01), ("tokens_per_s_per_chip", 0.03),
        ("mfu", 0.03), ("setup_s", 0.1)]


@pytest.mark.parametrize("path", MANIFESTS, ids=_ids(MANIFESTS))
def test_every_cell_finds_the_reader_under_layer_metrics(path):
    from benchmark import manifest
    for w in _load(path)["workloads"]:
        cell = manifest.load_cell(path, w["name"])
        assert NAME not in [e["name"] for e, _ in cell.metrics["end_to_end"]]
        reader, = [r for e, r in cell.metrics["per_layer"]
                   if e["name"] == NAME]
        assert reader.__file__ == os.path.join(
            ROOT, "benchmark", "layer_metrics", NAME + ".py")
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "end_to_end", NAME + ".py"))


def test_the_reader_gives_gib_or_nothing():
    from benchmark import manifest
    reader = manifest.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))
    assert reader.read({"memory_peak_bytes": None}) is None
    assert reader.read({"memory_peak_bytes": 3 << 29}) == 1.5
