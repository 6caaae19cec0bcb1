#!/usr/bin/env python3
"""ptpu_cache — operate on the persistent AOT compile-artifact cache
(paddle_tpu/core/compile_cache.py).

    tools/ptpu_cache.py inspect <cache-dir> [--json]
        List every entry: key hash, artifact size, jax version,
        platform/device kind, program hash, multistep signature, compile
        seconds recorded, age.

    tools/ptpu_cache.py verify <cache-dir>
        Re-hash every entry's payload against its meta.json. Exit 1 if
        any entry is corrupt (torn write, bit flip, hand edit) — the
        deploy-gate form: "will every warm start actually load?"

    tools/ptpu_cache.py gc <cache-dir> [--max-age-days N]
                       [--max-total-mb N] [--dry-run]
        Apply retention (age window, then newest-first size budget —
        the checkpoint retention discipline) and sweep dead writers'
        tmp droppings. --dry-run exits 1 when it WOULD delete
        (ptpu_ckpt gc's documented contract).

Exit codes: 0 ok, 1 findings (corrupt entries / would-delete in
--dry-run), 2 bad invocation.
"""
import argparse
import json
import os
import sys
import time

# a cache tool reads files and must never claim the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _human_size(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return "%.1f%s" % (n, unit) if unit != "B" else "%dB" % n
        n /= 1024.0


def _entry_record(path, meta):
    from paddle_tpu.core import compile_cache as cc
    key = (meta or {}).get("key", {})
    return {
        "path": path,
        "key_hash": (meta or {}).get("key_hash",
                                     os.path.basename(path)[len("aot_"):]),
        "readable": meta is not None,
        "size_bytes": cc.entry_size_bytes(path),
        "payload_bytes": (meta or {}).get("payload_bytes"),
        "jax_version": key.get("jax_version"),
        "platform": key.get("platform"),
        "device_kind": key.get("device_kind"),
        "num_devices": key.get("num_devices"),
        "program_sha256": key.get("program_sha256"),
        "fetch_names": key.get("fetch_names"),
        "multi": key.get("multi"),
        "compile_seconds": (meta or {}).get("compile_seconds"),
        "created_at": (meta or {}).get("created_at"),
    }


def cmd_inspect(args):
    from paddle_tpu.core import compile_cache as cc
    entries = cc.list_entries(args.dir)
    records = [_entry_record(p, m) for p, m in entries]
    if args.json:
        print(json.dumps({
            "cache_dir": args.dir,
            "entries": records,
            "total_bytes": sum(r["size_bytes"] for r in records),
        }, indent=1))
        return 0
    if not records:
        print("ptpu_cache: no entries under %s" % args.dir)
        return 0
    now = time.time()
    for r in records:
        age = "?" if not r["created_at"] else \
            "%.1fh" % ((now - r["created_at"]) / 3600.0)
        print("%s  %-8s jax=%-8s %s/%s x%s  compile=%.2fs  age=%s%s"
              % (r["key_hash"][:16], _human_size(r["size_bytes"]),
                 r["jax_version"], r["platform"], r["device_kind"] or "-",
                 r["num_devices"], r["compile_seconds"] or 0.0, age,
                 "" if r["readable"] else "  [META UNREADABLE]"))
        print("    program=%s  fetch=%s  multi=%s"
              % ((r["program_sha256"] or "?")[:16],
                 ",".join(r["fetch_names"] or []) or "-", r["multi"]))
    print("ptpu_cache: %d entr%s, %s total"
          % (len(records), "y" if len(records) == 1 else "ies",
             _human_size(sum(r["size_bytes"] for r in records))))
    return 0


def cmd_verify(args):
    from paddle_tpu.core import compile_cache as cc
    entries = cc.list_entries(args.dir)
    if not entries:
        print("ptpu_cache: no entries under %s" % args.dir)
        return 0
    bad = 0
    for path, meta in entries:
        problems = cc.verify_entry(path)
        name = os.path.basename(path)
        if problems:
            bad += 1
            print("%s: CORRUPT" % name)
            for p in problems:
                print("    %s" % p)
        else:
            print("%s: ok" % name)
    print("ptpu_cache: %d/%d entr%s verify"
          % (len(entries) - bad, len(entries),
             "y" if len(entries) == 1 else "ies"))
    return 1 if bad else 0


def cmd_gc(args):
    from paddle_tpu.core import compile_cache as cc
    doomed, kept = cc.gc_aot_cache(
        args.dir, max_age_days=args.max_age_days,
        max_total_mb=args.max_total_mb, dry_run=args.dry_run)
    verb = "would delete" if args.dry_run else "deleted"
    print("%s: %d entr%s (%d kept)"
          % (verb, len(doomed), "y" if len(doomed) == 1 else "ies",
             len(kept)))
    for path in doomed:
        print("    %s" % os.path.basename(path))
    if args.dry_run:
        return 1 if doomed else 0  # documented: would-delete = findings
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ptpu_cache",
        description="inspect / verify / gc the AOT compile-artifact "
                    "cache")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("inspect", help="list entries with key metadata")
    p.add_argument("dir")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("verify", help="hash-check every entry")
    p.add_argument("dir")
    p.set_defaults(fn=cmd_verify)

    p = sub.add_parser("gc", help="apply retention to the cache")
    p.add_argument("dir")
    p.add_argument("--max-age-days", type=float, default=None)
    p.add_argument("--max-total-mb", type=float, default=None)
    p.add_argument("--dry-run", action="store_true")
    p.set_defaults(fn=cmd_gc)

    args = ap.parse_args(argv)
    if not os.path.isdir(args.dir):
        print("ptpu_cache: %s is not a directory" % args.dir,
              file=sys.stderr)
        return 2
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
