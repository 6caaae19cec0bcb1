"""The ONE bench record schema (ARCHITECTURE.md §28).

Every bench.py leg prints exactly one JSON record line per measurement;
the BenchStore ingests those lines and the regression gate compares
them.  This module is the shared contract all three sides validate
against, so a future leg cannot silently emit lines the store or gate
can't read (the schema-guard satellite of PR 19):

  required   metric (non-empty str)   what was measured
             value  (finite number)   the measurement (0.0 on error)
             unit   (non-empty str)   e.g. "images/sec/chip"
  optional   error  (non-empty str)   present IFF the line is a
                                      failure placeholder, never a
                                      measurement — the baseline
                                      rule: baselines skip any
                                      record carrying an "error" key.
             vs_baseline (number|None)
             everything else          leg-specific config/result detail

Store keying derives from here too:

  * `device_kind(record)`  — the hardware family ("TPU v5 lite",
    "cpu"), index digits stripped so chip 0 and chip 1 share baselines.
  * `config_digest(record)` — a digest over the record's CONFIG keys
    (strings / bools / ints — batch, dtype, feed, seq...), excluding
    measured values and floats, so repeat runs of one configuration
    land under one baseline key and a batch-512 line never gates
    against a batch-64 baseline.
"""
import hashlib
import json
import math
import re

__all__ = ["RECORD_KEYS", "validate_record", "check_record", "is_error",
           "config_digest", "device_kind"]

# the required surface; everything else in a record is leg detail
RECORD_KEYS = ("metric", "value", "unit")

# envelope/measurement keys that are NOT configuration: excluded from
# the config digest alongside every float (floats are measurements —
# loss, mfu, qps, p99... — config knobs are strings, bools and ints)
_NON_CONFIG_KEYS = frozenset((
    "metric", "value", "unit", "vs_baseline", "error",
    "device", "device_kind", "loss", "mfu", "peak_tflops",
    "ts", "source", "seq", "on_tpu", "speed_asserted",
))


def validate_record(rec):
    """Return a list of problem strings (empty = valid). Never raises —
    the ingest path classifies unparseable lines instead of dying on
    the first historical oddity."""
    problems = []
    if not isinstance(rec, dict):
        return ["record is %s, not a dict" % type(rec).__name__]
    metric = rec.get("metric")
    if not isinstance(metric, str) or not metric:
        problems.append("metric missing or not a non-empty str: %r"
                        % (metric,))
    value = rec.get("value")
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        problems.append("value missing or not a number: %r" % (value,))
    elif not math.isfinite(value):
        problems.append("value not finite: %r" % (value,))
    unit = rec.get("unit")
    if not isinstance(unit, str) or not unit:
        problems.append("unit missing or not a non-empty str: %r"
                        % (unit,))
    if "error" in rec:
        err = rec["error"]
        if not isinstance(err, str) or not err:
            problems.append("error key present but not a non-empty "
                            "str: %r" % (err,))
    if "vs_baseline" in rec:
        vb = rec["vs_baseline"]
        if vb is not None and (isinstance(vb, bool)
                               or not isinstance(vb, (int, float))):
            problems.append("vs_baseline not a number or None: %r"
                            % (vb,))
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        problems.append("record not JSON-serializable: %r" % (e,))
    return problems


def check_record(rec):
    """Raise ValueError on an invalid record (the emit-side guard:
    bench.py legs call this through `_emit` so a malformed line is a
    loud test failure, not a silently unreadable store entry)."""
    problems = validate_record(rec)
    if problems:
        raise ValueError("invalid bench record: %s (record=%r)"
                         % ("; ".join(problems), rec))
    return rec


def is_error(rec):
    """The baseline rule, machine-readable: a record carrying an
    "error" key is a failure placeholder, never a baseline."""
    return isinstance(rec, dict) and "error" in rec


def device_kind(rec):
    """Hardware family key: "TPU v5 lite0" -> "TPU v5 lite" (trailing
    chip index stripped — chips of one kind share baselines), anything
    CPU-ish -> "cpu", absent -> "unknown" (an error placeholder may
    never have initialized a device)."""
    dev = rec.get("device") if isinstance(rec, dict) else rec
    if not dev or not isinstance(dev, str):
        return "unknown"
    if "cpu" in dev.lower():
        return "cpu"
    return re.sub(r"[\s_]*\d+$", "", dev.strip()) or "unknown"


def config_digest(rec):
    """Digest of the record's configuration keys — str/bool/int values
    outside _NON_CONFIG_KEYS (floats are measurements, nested
    containers are result detail). Stable across repeat runs of one
    config; distinct across configs (batch, dtype, feed, seq...)."""
    cfg = {}
    for k in sorted(rec):
        if k in _NON_CONFIG_KEYS:
            continue
        v = rec[k]
        if isinstance(v, bool) or isinstance(v, (str, int)):
            cfg[k] = v
    blob = json.dumps(cfg, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]
