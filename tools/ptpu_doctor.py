#!/usr/bin/env python3
"""ptpu_doctor — inspect and replay resilience diagnostic bundles.

A bundle is what the Supervisor/watchdog captures when a training fault
escalates (resilience/watchdog.py write_bundle): the program, the
failing step's feeds and persistable state, the recent-metrics ring,
the event log, and every thread's stack at capture time.

    tools/ptpu_doctor.py inspect <bundle-dir> [--json]
        Human (or JSON) summary: reason, fault class, step, error,
        feed shapes, metrics ring, recovery events, thread stacks.

    tools/ptpu_doctor.py trace <bundle-dir | trace-dump.json>
            [--out chrome.json] [--last N]
        Render the flight-recorder timeline a bundle embeds
        (paddle_tpu.observability.trace, ARCHITECTURE.md §24): the
        recorded span ring in time order plus every span still OPEN at
        capture — for a hang bundle, the open spans ARE the answer to
        "what was the pipeline doing when it wedged". --out writes
        Chrome trace-event JSON for chrome://tracing / Perfetto.

    tools/ptpu_doctor.py replay <bundle-dir> [--fetch NAME ...]
        Re-run the RECORDED failing step offline: load the bundled
        program, put the bundled persistable state into a fresh scope,
        dispatch the bundled feeds once (guards and all, on CPU).
        Exit 1 when the fault REPRODUCES (same class of failure —
        that is the actionable result: the bundle alone demonstrates
        the bug); exit 0 when the step replays clean (the fault was
        environmental: preemption, a dying reader host, a flaky link).

Exit codes: 0 replayed clean / inspected, 1 fault reproduced,
2 bundle unreplayable (no program/feeds captured) or bad invocation.
"""
import argparse
import json
import os
import sys

# a diagnosis tool replays on the host and must never claim the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def cmd_inspect(args):
    from paddle_tpu.resilience.watchdog import read_bundle
    meta, program, feeds, state = read_bundle(args.bundle)
    if args.json:
        out = dict(meta)
        out["has_feeds"] = feeds is not None
        out["has_state"] = state is not None
        out["num_state_vars"] = 0 if state is None else len(state)
        print(json.dumps(out, indent=1, sort_keys=True))
        return 0
    print("bundle:      %s" % args.bundle)
    print("reason:      %s" % meta.get("reason"))
    print("fault class: %s" % meta.get("fault_class"))
    print("step:        %s" % meta.get("step"))
    print("error:       %s" % meta.get("error"))
    print("program:     %s" % ("recorded (v%s)" % meta.get(
        "program_version") if program is not None else "absent"))
    print("feeds:       %s" % (", ".join(
        "%s%s" % (n, s[0]) for n, s in sorted(
            meta.get("feed_shapes", {}).items())) or "absent"))
    print("state vars:  %d captured, %d unavailable"
          % (0 if state is None else len(state),
             len(meta.get("state_unavailable", []))))
    for ev in meta.get("events", [])[-8:]:
        print("event:       step %s %s:%s %s"
              % (ev.get("step"), ev.get("class"), ev.get("action"),
                 ev.get("error") or ""))
    for m in list(meta.get("metrics", []))[-5:]:
        print("metric:      %s" % m)
    for name in sorted(meta.get("thread_stacks", {})):
        print("thread:      %s" % name)
    return 0


def cmd_trace(args):
    from paddle_tpu.observability import trace as otrace
    target = args.bundle
    data = None
    if os.path.isdir(target):
        # a watchdog/supervisor bundle OR a cluster merged bundle —
        # both carry their recorder dump under "trace" in bundle.json
        meta_path = os.path.join(target, "bundle.json")
        if not os.path.exists(meta_path):
            print("ptpu_doctor: %r has no bundle.json" % target,
                  file=sys.stderr)
            return 2
        with open(meta_path) as f:
            meta = json.load(f)
        data = meta.get("trace")
        if data is None:
            print("TRACE UNSUPPORTED: bundle predates the flight "
                  "recorder (no 'trace' key in bundle.json)")
            return 2
    else:
        with open(target) as f:
            raw = json.load(f)
        # accept a bundle.json, a bare dump(), or nothing usable
        data = raw.get("trace") if "trace" in raw else raw
        if not isinstance(data, dict) or "events" not in data:
            print("ptpu_doctor: %r carries no recorder dump "
                  "(want a bundle dir, bundle.json, or a "
                  "trace.dump() JSON)" % target, file=sys.stderr)
            return 2
    if args.out:
        otrace.export_chrome_trace(args.out, data=data)
        print("chrome trace written: %s (load in chrome://tracing or "
              "https://ui.perfetto.dev)" % args.out)
    print(otrace.render_timeline(data, last=args.last))
    return 0


def cmd_replay(args):
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core.compile_cache import enable_persistent_cache
    from paddle_tpu.core.executor import NumericalGuardError
    from paddle_tpu.resilience.watchdog import read_bundle
    # a replay of a large training step pays the same compile the
    # wedged trainer did; the persistent cache makes repeat replays (and
    # a replay on the machine that trained) load it from disk instead
    enable_persistent_cache()
    meta, program, feeds, state = read_bundle(args.bundle)
    if program is None or feeds is None:
        print("REPLAY UNSUPPORTED: bundle carries %s" % (
            "no program" if program is None else
            "feed shapes only (reader-fed step; arrays not captured)"))
        return 2
    if meta.get("state_unavailable"):
        # a post-timeout capture with donated-and-gone buffers: a
        # replay against partial state would raise replay-ENVIRONMENT
        # errors and masquerade as a reproduction
        print("REPLAY UNSUPPORTED: %d state var(s) were unavailable at "
              "capture (%s...) — the bundle cannot re-create the "
              "failing step's inputs"
              % (len(meta["state_unavailable"]),
                 ", ".join(meta["state_unavailable"][:3])))
        return 2
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        for name, arr in (state or {}).items():
            scope.set(name, arr)
        fetch = list(args.fetch or [])
        try:
            # the replay rides the same watchdog it diagnoses: a
            # hang-class bundle that REPRODUCES must exit 1, not wedge
            # the doctor
            out = exe.run(program, feed=dict(feeds), fetch_list=fetch,
                          timeout=float(args.timeout))
        except fluid.DispatchTimeoutError as e:
            if meta.get("fault_class") == "hang":
                print("REPRODUCED: replaying step %s hung past %.0fs "
                      "(%s)" % (meta.get("step"), float(args.timeout), e))
                return 1
            print("REPLAY ERROR: replay hung past %.0fs but the bundle "
                  "records a %r fault" % (float(args.timeout),
                                          meta.get("fault_class")))
            return 2
        except Exception as e:  # noqa: BLE001 — classified below
            # the verdict requires the raise to MATCH the recorded
            # fault class: a numeric bundle reproduces only via the
            # numerical guard — any other raise here is a replay
            # problem, not a reproduction
            if meta.get("fault_class") == "numeric" and not isinstance(
                    e, NumericalGuardError):
                print("REPLAY ERROR: expected a numerical-guard trip "
                      "but replay raised %s: %s" % (type(e).__name__, e))
                return 2
            print("REPRODUCED: replaying step %s raised %s: %s"
                  % (meta.get("step"), type(e).__name__, e))
            return 1
    for name, v in zip(fetch, out):
        print("fetch %s = %s" % (name, np.asarray(v).reshape(-1)[:8]))
    print("CLEAN: step %s replayed without a fault (environmental "
          "failure — preemption, reader host, link?)" % meta.get("step"))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="ptpu_doctor")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("inspect", help="summarize a bundle")
    p.add_argument("bundle")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_inspect)
    p = sub.add_parser("trace", help="render a bundle's flight-recorder "
                                     "timeline")
    p.add_argument("bundle", help="bundle dir, bundle.json, or a "
                                  "trace dump JSON")
    p.add_argument("--out", default=None,
                   help="also write Chrome trace-event JSON here")
    p.add_argument("--last", default=60, type=int,
                   help="how many newest events to render (default 60)")
    p.set_defaults(fn=cmd_trace)
    p = sub.add_parser("replay", help="re-run the recorded failing step")
    p.add_argument("bundle")
    p.add_argument("--fetch", action="append", default=[],
                   help="var name(s) to fetch on a clean replay")
    p.add_argument("--timeout", default=300.0, type=float,
                   help="replay hang deadline in seconds (default 300)")
    p.set_defaults(fn=cmd_replay)
    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except FileNotFoundError as e:
        print("ptpu_doctor: %s" % e, file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
