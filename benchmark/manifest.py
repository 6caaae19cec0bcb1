"""Reads BENCHMARK.json and finds, by name, the files that belong to one
cell: its configuration (sizes + code), its traffic mix, and the readers of
the metrics it reports. Nothing here knows a configuration, a traffic mix or
a metric by name; a later PR adds files and manifest entries and edits
nothing.

Where things are looked for, under every directory of `paths` in turn:
  configuration  the manifest entry's `file` (.json); its code is the file
                 its `module` key names, else the .py beside it
  traffic mix    <path>/traffic/<traffic>.json
  metric reader  <path>/end_to_end/<name>.py, <path>/layer_metrics/<name>.py
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path):
    """Import one file by path, under a name no package can clash with."""
    name = "_bench_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell(object):
    """One entry of `workloads`, resolved to its files."""

    def __init__(self, manifest, workload):
        self.name = workload["name"]
        self.chips = workload["chips"]
        self.run_seconds = manifest["run_seconds"]
        paths = [os.path.join(ROOT, p) for p in manifest["paths"]]
        entry = next(c for c in manifest["configs"]
                     if c["name"] == workload["config"])
        cfg_file = os.path.join(ROOT, entry["file"])
        self.config = _read_json(cfg_file)
        module = self.config.get("module")
        self.config_module = load_module(
            os.path.join(ROOT, module) if module
            else os.path.splitext(cfg_file)[0] + ".py")
        self.traffic = _read_json(_find(
            paths, "traffic", workload["traffic"] + ".json"))
        if self.traffic["chips"] != self.chips:
            raise ValueError(
                "cell %r asks for %d chip(s) but its traffic %r is written "
                "for %d" % (self.name, self.chips, workload["traffic"],
                            self.traffic["chips"]))
        # manifest key -> [(entry, reader module)] of the metrics this cell
        # reports: those with no `workloads` list, or with the cell on it
        self.metrics = {
            key: [(m, load_module(_find(paths, sub, m["name"] + ".py")))
                  for m in manifest[key]
                  if self.name in m.get("workloads", [self.name])]
            for key, sub in (("end_to_end", "end_to_end"),
                             ("per_layer", "layer_metrics"))}


def _find(paths, sub, filename):
    for p in paths:
        candidate = os.path.join(p, sub, filename)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError("no %s/%s under any of %r"
                            % (sub, filename, paths))


def load_cell(manifest_path, workload_name):
    manifest = _read_json(manifest_path)
    for w in manifest["workloads"]:
        if w["name"] == workload_name:
            return Cell(manifest, w)
    raise KeyError("no workload %r in %s (it has %s)" % (
        workload_name, manifest_path,
        ", ".join(w["name"] for w in manifest["workloads"])))


def peak_for(device_kind):
    """The published peaks of one chip of this kind. A kind that is not in
    the table is an error, never a default."""
    table = _read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    try:
        return table["by_device_kind"][device_kind]
    except KeyError:
        raise KeyError(
            "no peak for device_kind %r in benchmark/peaks.json (it has %s): "
            "add the published numbers with their source"
            % (device_kind, sorted(table["by_device_kind"]))) from None
