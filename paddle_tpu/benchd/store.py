"""BenchStore: the append-only home for measured bench records
(ARCHITECTURE.md §28).

One JSONL file (`records.jsonl`) of envelopes:

    {"v": 1, "seq": N, "ts": <epoch s>, "source": "...",
     "metric": "...", "device_kind": "...", "digest": "...",
     "record": {<the bench.py JSON line, schema-checked>}}

Keying is (metric, device_kind, config digest) — see schema.py — so
repeat runs of one configuration accumulate under one baseline key and
`last_good()` never compares across configurations unless explicitly
asked to fall back.

`last_good()` enforces the baseline rule: any record carrying an
`"error"` key is a failure placeholder (a run that died before it
measured, a timeout), never a baseline — it reads as a failed run, not
as a 100% throughput regression.
"""
import fcntl
import json
import os
import time

from . import schema

__all__ = ["BenchStore"]

_RECORDS = "records.jsonl"


class BenchStore(object):
    def __init__(self, root):
        self.root = os.path.abspath(str(root))
        os.makedirs(self.root, exist_ok=True)
        self.path = os.path.join(self.root, _RECORDS)

    # ------------------------------------------------------------ append --
    def append(self, record, source="bench", ts=None):
        """Schema-check `record` and append one envelope line.  The
        whole read-count + write happens under an exclusive flock on
        the records file, so a daemon and a CLI appending concurrently
        can neither interleave half-lines nor duplicate seq numbers."""
        schema.check_record(record)
        env = {
            "v": 1,
            "ts": float(time.time() if ts is None else ts),
            "source": str(source),
            "metric": record["metric"],
            "device_kind": schema.device_kind(record),
            "digest": schema.config_digest(record),
            "record": record,
        }
        fd = os.open(self.path, os.O_CREAT | os.O_RDWR, 0o666)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            with open(self.path, "r") as f:
                env["seq"] = sum(1 for _ in f)
            line = json.dumps(env, sort_keys=True)
            os.lseek(fd, 0, os.SEEK_END)
            os.write(fd, (line + "\n").encode("utf-8"))
            os.fsync(fd)
        finally:
            os.close(fd)  # closes the fd's flock with it
        return env

    # -------------------------------------------------------------- read --
    def entries(self, metric=None, device_kind=None, digest=None,
                source_prefix=None):
        """Envelopes in append order, optionally filtered. Corrupt
        lines (a torn concurrent write survived a crash) are skipped,
        not fatal — the store must stay readable after any kill."""
        out = []
        try:
            with open(self.path, "r") as f:
                lines = f.readlines()
        except OSError:
            return out
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                env = json.loads(line)
            except ValueError:
                continue
            if not isinstance(env, dict) or "record" not in env:
                continue
            if metric is not None and env.get("metric") != metric:
                continue
            if device_kind is not None \
                    and env.get("device_kind") != device_kind:
                continue
            if digest is not None and env.get("digest") != digest:
                continue
            if source_prefix is not None and not str(
                    env.get("source", "")).startswith(source_prefix):
                continue
            out.append(env)
        return out

    def last_good(self, metric, device_kind=None, digest=None,
                  before_seq=None):
        """Newest entry for the key whose record does NOT carry an
        "error" key (the baseline rule) — or None.
        `before_seq` restricts to strictly-older entries so a fresh
        line never resolves itself as its own baseline."""
        best = None
        for env in self.entries(metric=metric, device_kind=device_kind,
                                digest=digest):
            if schema.is_error(env["record"]):
                continue
            if before_seq is not None and env.get("seq", 0) >= before_seq:
                continue
            if best is None or (env.get("ts", 0), env.get("seq", 0)) \
                    >= (best.get("ts", 0), best.get("seq", 0)):
                best = env
        return best

    def summary(self):
        """Status surface: counts plus per-(metric, device_kind) last
        good / error tallies."""
        entries = self.entries()
        per_key = {}
        errors = 0
        for env in entries:
            err = schema.is_error(env["record"])
            errors += bool(err)
            key = (env.get("metric"), env.get("device_kind"))
            slot = per_key.setdefault(key, {"records": 0, "errors": 0,
                                            "last_good": None})
            slot["records"] += 1
            slot["errors"] += bool(err)
            if not err:
                lg = slot["last_good"]
                if lg is None or (env.get("ts", 0), env.get("seq", 0)) \
                        >= (lg.get("ts", 0), lg.get("seq", 0)):
                    slot["last_good"] = env
        return {"records": len(entries), "errors": errors,
                "keys": per_key}
