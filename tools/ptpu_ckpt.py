#!/usr/bin/env python3
"""ptpu_ckpt — operate on CheckpointManager checkpoint directories.

    tools/ptpu_ckpt.py inspect <ckpt-dir> [--step N] [--json]
        Manifest, step, seed cursor, reader states, per-file hashes of
        one snapshot (default: the newest valid one).

    tools/ptpu_ckpt.py verify <ckpt-dir>
        Hash-check EVERY published snapshot. Exit 1 if any snapshot's
        hash tree fails — the deploy-gate form: "is every checkpoint in
        this directory loadable?"

    tools/ptpu_ckpt.py gc <ckpt-dir> --max-to-keep N [--keep-every M]
                       [--dry-run]
        Apply a retention policy offline (the same engine the manager
        runs after each save) and sweep dead writers' tmp droppings.

Exit codes: 0 ok, 1 findings (corruption / would-delete in --dry-run
when nothing matches is still 0), 2 bad invocation.
"""
import argparse
import json
import os
import sys

# a checkpoint tool reads files and must never claim the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _human_size(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return "%.1f%s" % (n, unit) if unit != "B" else "%dB" % n
        n /= 1024.0


def cmd_inspect(args):
    from paddle_tpu.checkpoint import snapshot as snap
    found = snap.find_valid_snapshot(args.dir, step=args.step)
    if found is None:
        print("ptpu_ckpt: no %s snapshot under %s"
              % ("valid step_%s" % args.step if args.step is not None
                 else "valid", args.dir), file=sys.stderr)
        return 1
    step, path = found
    meta = snap.read_snapshot_meta(path)
    manifest = snap.load_manifest(path)
    record = {
        "step": step,
        "path": path,
        "legacy": bool(meta.get("legacy")),
        "seed_cursor": meta.get("seed_cursor"),
        "program_version": meta.get("program_version"),
        "program_sha256": (meta.get("program") or {}).get("sha256"),
        "reader_states": meta.get("reader_states") or {},
        "num_vars": len(manifest),
        "total_bytes": sum(
            os.path.getsize(os.path.join(path, e["file"]))
            for e in manifest.values()),
        "vars": {
            name: {"shape": e.get("shape"), "dtype": e.get("dtype"),
                   "is_param": e.get("is_param"),
                   "owner": e.get("owner"), "sha256": e.get("sha256")}
            for name, e in sorted(manifest.items())},
        "all_steps": [s for s, _ in snap.list_steps(args.dir)],
        "latest_pointer": snap.read_latest_pointer(args.dir),
    }
    if args.json:
        print(json.dumps(record, indent=1))
        return 0
    print("snapshot step_%d  (%s)" % (step, path))
    print("  legacy=%s seed_cursor=%s program_version=%s"
          % (record["legacy"], record["seed_cursor"],
             record["program_version"]))
    print("  %d vars, %s" % (record["num_vars"],
                             _human_size(record["total_bytes"])))
    for name, e in record["vars"].items():
        owner = ""
        if e.get("owner"):
            owner = "  <- %s" % e["owner"]
        elif e.get("owner") == "":
            owner = "  <- (optimizer global)"
        print("    %-40s %-12s %s%s"
              % (name, e.get("dtype"), e.get("shape"), owner))
    for rname, st in record["reader_states"].items():
        print("  reader %s: %s" % (rname, st))
    print("  steps on disk: %s  LATEST-> %s"
          % (record["all_steps"], record["latest_pointer"]))
    return 0


def cmd_verify(args):
    from paddle_tpu.checkpoint import snapshot as snap
    steps = snap.list_steps(args.dir)
    if not steps:
        print("ptpu_ckpt: no snapshots under %s" % args.dir,
              file=sys.stderr)
        return 1
    bad = 0
    for step, path in steps:
        problems = snap.verify_snapshot(path)
        if problems:
            bad += 1
            print("step_%d: CORRUPT" % step)
            for p in problems:
                print("    %s" % p)
        else:
            legacy = snap.read_snapshot_meta(path).get("legacy")
            print("step_%d: ok%s" % (step,
                                     " (legacy, unhashed)" if legacy
                                     else ""))
    print("ptpu_ckpt: %d/%d snapshot(s) verify" % (len(steps) - bad,
                                                   len(steps)))
    return 1 if bad else 0


def cmd_gc(args):
    from paddle_tpu.checkpoint import RetentionPolicy, apply_retention
    from paddle_tpu.checkpoint import snapshot as snap
    policy = RetentionPolicy(max_to_keep=args.max_to_keep,
                             keep_every_n_steps=args.keep_every)
    steps = [s for s, _ in snap.list_steps(args.dir)]
    doomed = policy.to_delete(steps)
    if args.dry_run:
        print("would delete: %s (keeping %s)"
              % (doomed, [s for s in steps if s not in doomed]))
        return 1 if doomed else 0  # documented: would-delete = findings
    deleted = apply_retention(args.dir, policy)
    print("deleted: %s (keeping %s)"
          % (deleted, [s for s, _ in snap.list_steps(args.dir)]))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ptpu_ckpt",
        description="inspect / verify / gc checkpoint directories")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("inspect", help="describe one snapshot")
    p.add_argument("dir")
    p.add_argument("--step", type=int, default=None,
                   help="pin a step (default: newest valid)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("verify", help="hash-check every snapshot")
    p.add_argument("dir")
    p.set_defaults(fn=cmd_verify)

    p = sub.add_parser("gc", help="apply a retention policy offline")
    p.add_argument("dir")
    p.add_argument("--max-to-keep", type=int, required=True)
    p.add_argument("--keep-every", type=int, default=None,
                   help="also keep every Nth step")
    p.add_argument("--dry-run", action="store_true")
    p.set_defaults(fn=cmd_gc)

    args = ap.parse_args(argv)
    if not os.path.isdir(args.dir):
        print("ptpu_ckpt: %s is not a directory" % args.dir,
              file=sys.stderr)
        return 2
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
