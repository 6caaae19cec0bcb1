"""paddle_tpu.benchd — bench record schema, bench store and the
perf-regression gate (ARCHITECTURE.md §28).

A chip run is one process on a fresh machine from which only an output
directory returns, so nothing here is resident: the records a run prints
are *measured* values, schema-checked on the way out, and compared
afterwards.

  * `schema`  — the ONE bench record schema (metric/value/unit/error)
                every bench.py leg's success and error lines validate
                against, and the store/gate read.
  * `store`   — `BenchStore`: append-only JSONL keyed by
                (metric, device_kind, config digest), `last_good()`
                baseline resolution that skips `"error"` records.
  * `gate`    — the perf-regression gate: fresh lines vs
                last-good-hardware baselines with per-metric relative
                noise bands and min-of-repeats, so perf regressions
                fail CI the way correctness does.

CLI: `tools/ptpu_bench.py` (gate / status).
"""
from .schema import (RECORD_KEYS, check_record, config_digest,
                     device_kind, is_error, validate_record)
from .store import BenchStore
from .gate import run_gate

__all__ = [
    "RECORD_KEYS", "validate_record", "check_record", "is_error",
    "config_digest", "device_kind",
    "BenchStore",
    "run_gate",
]
