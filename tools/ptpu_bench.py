#!/usr/bin/env python3
"""ptpu_bench — bench store and perf-regression gate (paddle_tpu.benchd).

    tools/ptpu_bench.py gate [--store DIR] [--fresh FILE.jsonl]
                        [--json]
        Perf-regression gate.  With --fresh, each line of FILE is a
        bench record gated against the store's last-good baseline for
        its (metric, device_kind, config) key; without it, the store
        self-gates its newest record per key.  Error placeholders skip,
        never fail.

    tools/ptpu_bench.py status [--store DIR] [--json]
        The store summarized: record and error-placeholder counts and
        the last-good value per (metric, device_kind) key.

The store defaults to <repo>/bench_store.  Neither verb initialises a
device backend: measuring is `python bench.py` (or `chip_smoke.py`),
one process on the chip; this CLI only reads what those runs printed.

Exit codes: 0 ok (gate: no regressions), 1 gate regression, 2 bad
invocation.
"""
import argparse
import json
import os
import sys

# reading records never needs the chip, and must not claim it
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _open_store(args):
    from paddle_tpu.benchd import BenchStore
    return BenchStore(args.store or os.path.join(_REPO, "bench_store"))


def _load_fresh(path):
    from paddle_tpu.benchd import schema
    fresh = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            # accept bare records or store envelopes
            if isinstance(rec, dict) and "record" in rec and "v" in rec:
                env, rec = rec, rec["record"]
            else:
                env = {"record": rec}
            schema.check_record(rec)
            env.setdefault("metric", rec.get("metric"))
            env.setdefault("device_kind", schema.device_kind(rec))
            env.setdefault("digest", schema.config_digest(rec))
            fresh.append(env)
    return fresh


def cmd_gate(args):
    from paddle_tpu.benchd import run_gate
    store = _open_store(args)
    fresh = None
    if args.fresh:
        try:
            fresh = _load_fresh(args.fresh)
        except (OSError, ValueError) as e:
            print("ptpu_bench gate: bad --fresh file: %s" % e,
                  file=sys.stderr)
            return 2
    report = run_gate(store, fresh=fresh)
    if args.json:
        print(json.dumps(report, indent=1, default=str))
    else:
        for v in report["verdicts"]:
            mark = {"regression": "FAIL", "improvement": "GOOD"}.get(
                v["verdict"], "ok")
            print("%-4s %s" % (mark, v["detail"]))
        print("gate: %d regression(s) across %d key(s)"
              % (report["regressions"], len(report["verdicts"])))
    return report["exit_code"]


def cmd_status(args):
    summ = _open_store(args).summary()
    out = {
        "store": {"records": summ["records"], "errors": summ["errors"]},
        "last_good": {
            "%s @ %s" % k: {
                "value": slot["last_good"]["record"]["value"],
                "source": slot["last_good"]["source"],
            }
            for k, slot in sorted(summ["keys"].items())
            if slot["last_good"] is not None},
    }
    if args.json:
        print(json.dumps(out, indent=1, default=str))
        return 0
    print("bench store: %d record(s), %d error placeholder(s)"
          % (summ["records"], summ["errors"]))
    print("last-good baselines:")
    for key, slot in sorted(out["last_good"].items()):
        print("  %-60s %s  (%s)" % (key, slot["value"], slot["source"]))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="ptpu_bench",
        description="bench store + perf-regression gate "
                    "(paddle_tpu.benchd)")
    p.add_argument("--store", default=None,
                   help="store dir (default <repo>/bench_store)")
    sub = p.add_subparsers(dest="cmd", required=True)

    gp = sub.add_parser("gate", help="perf-regression gate")
    gp.add_argument("--fresh", default=None,
                    help="JSONL of fresh records to gate (default: "
                         "self-gate the store's newest per key)")
    gp.add_argument("--json", action="store_true")
    gp.set_defaults(fn=cmd_gate)

    sp = sub.add_parser("status", help="store summary")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_status)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
