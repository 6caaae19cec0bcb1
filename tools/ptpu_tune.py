#!/usr/bin/env python3
"""ptpu_tune — search and record execution configs (paddle_tpu.tuning).

    tools/ptpu_tune.py list [--store DIR] [--json]
        Every recorded config: signature, device, knobs, score,
        when/what was searched.

    tools/ptpu_tune.py show <signature> [--device KEY] [--store DIR]
                       [--json]
        One entry in full (device defaults to this host's cpu key).

    tools/ptpu_tune.py train-smoke [--store DIR] [--k 1,2,4,8]
                       [--steps 32] [--layers 12] [--hidden 32]
                       [--batch 16] [--json]
        Zero-to-tuned on the built-in dispatch-bound MLP: search
        multistep K on CPU, record the winner, print the result — the
        subprocess-tested path and the template for tuning a real model
        (see paddle_tpu.tuning.tune_training_multistep /
        tune_serving_batching for programs and serving engines).

Exit codes: 0 ok, 1 nothing found (list/show on empty store), 2 bad
invocation.
"""
import argparse
import json
import os
import sys

# a tuning CLI on the smoke model must never claim the chip; real-
# model tuning runs go through the python API on the target device
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _store(args):
    from paddle_tpu.tuning import TuningStore
    return TuningStore(root=args.store)


def cmd_list(args):
    entries = _store(args).entries()
    if args.json:
        print(json.dumps({"entries": entries}, indent=1))
        return 0 if entries else 1
    if not entries:
        print("ptpu_tune: no recorded configs")
        return 1
    for e in entries:
        print("%s  @ %s" % (e.get("signature"), e.get("device_key")))
        print("    knobs=%s  score=%s %s"
              % (e.get("knobs"), e.get("score"), e.get("score_unit")))
    return 0


def cmd_show(args):
    st = _store(args)
    dev = args.device
    if dev is None:
        import jax
        from paddle_tpu.tuning import device_key
        dev = device_key(jax.devices("cpu")[0])
    entry = st.get(args.signature, dev)
    if entry is None:
        print("ptpu_tune: no config for %r @ %r"
              % (args.signature, dev), file=sys.stderr)
        return 1
    print(json.dumps(entry, indent=1, sort_keys=True))
    return 0


def cmd_train_smoke(args):
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import tuning

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_prog,
                                                        startup):
        x = fluid.layers.data(name="x", shape=[args.hidden],
                              dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        for _ in range(args.layers):
            h = fluid.layers.fc(input=h, size=args.hidden, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(args.batch, args.hidden).astype("float32"),
            "y": rng.rand(args.batch, 1).astype("float32")}
    ks = [int(k) for k in args.k.split(",") if k.strip()]
    # scan lowering keeps the K>1 compiles cheap enough for a smoke CLI
    os.environ.setdefault("FLAGS_multistep_unroll", "0")
    store = (tuning.TuningStore(root=args.store) if args.store
             else tuning.TuningStore())
    result = tuning.tune_training_multistep(
        main_prog, startup, feed, [loss], k_candidates=ks,
        steps=args.steps, warmup=1, repeats=2, store=store,
        verbose=not args.json)
    record = {
        "signature": tuning.program_signature(main_prog),
        "best": result.best,
        "best_score": result.best_score,
        "score_unit": result.score_unit,
        "results": [{"knobs": k, "score": s, "error": e}
                    for k, s, e in result.results],
        "store_path": result.store_path,
    }
    print(json.dumps(record) if args.json
          else "recorded %s (%.1f %s) -> %s"
          % (result.best, result.best_score, result.score_unit,
             result.store_path))
    return 0


def cmd_kernels(args):
    """Kernel block-knob sweep (tuning.tune_kernels): per (op,
    shape-bucket, device_kind) tile search + the flash-vs-dense
    crossover, recorded so every later process dispatches at the tuned
    tiles (ops/kernel_config.py reads the store at trace time)."""
    if args.place == "tpu":
        # the module-level CPU pin must not leak into a hardware tune;
        # jax has not initialized yet (it imports lazily below)
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            del os.environ["JAX_PLATFORMS"]
    from paddle_tpu import tuning
    ops = tuple(o.strip() for o in args.ops.split(",") if o.strip())
    shapes = None
    if args.smoke:
        # tiny shapes: the subprocess-tested zero-to-tuned path (CPU
        # interpret mode; real sweeps drop --smoke and run on TPU)
        shapes = {"attn": [dict(b=1, h=1, d=8, t=16)],
                  "xent": [dict(n=16, v=64)],
                  "ln": [dict(n=16, d=32)],
                  "lstm": [dict(b=4, t=8, d=8)],
                  "seq": [dict(b=8, t=16)]}
    store = (tuning.TuningStore(root=args.store) if args.store
             else tuning.TuningStore())
    result = tuning.tune_kernels(
        ops=ops, shapes=shapes, repeats=args.repeats, store=store,
        include_crossover=not args.no_crossover,
        verbose=not args.json)
    record = {
        "entries": {sig: {"best": r.best, "best_score": r.best_score,
                          "score_unit": r.score_unit,
                          "store_path": r.store_path}
                    for sig, r in result["entries"].items()},
        "crossover": result["crossover"],
        "store": store.root,
    }
    if args.json:
        print(json.dumps(record))
    else:
        for sig, r in sorted(record["entries"].items()):
            print("%s -> %s (%.1f %s)" % (sig, r["best"], r["best_score"],
                                          r["score_unit"]))
        if record["crossover"] is not None:
            print("flash crossover -> min_seq=%d" % record["crossover"])
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ptpu_tune",
        description="search and record execution configs")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("list", help="every recorded config")
    p.add_argument("--store", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("show", help="one config in full")
    p.add_argument("signature")
    p.add_argument("--device", default=None,
                   help="device key 'platform/kind' (default: host cpu)")
    p.add_argument("--store", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_show)

    p = sub.add_parser("train-smoke",
                       help="tune multistep K on the built-in MLP")
    p.add_argument("--store", default=None)
    p.add_argument("--k", default="1,2,4,8")
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_train_smoke)

    p = sub.add_parser("kernels",
                       help="sweep pallas tile/block knobs per "
                            "(op, shape-bucket, device_kind)")
    p.add_argument("--store", default=None)
    p.add_argument("--ops", default="attn,xent,ln,lstm,seq")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--place", default="cpu", choices=["cpu", "tpu"],
                   help="tpu = tune on the real chip (the only numbers "
                        "worth recording for deploy; cpu interpret mode "
                        "exists for the smoke path)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes (seconds on CPU) — the tested "
                        "zero-to-tuned path")
    p.add_argument("--no-crossover", action="store_true",
                   help="skip the flash-vs-dense crossover measurement")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_kernels)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
