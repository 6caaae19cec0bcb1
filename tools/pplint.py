#!/usr/bin/env python3
"""pplint — static verifier for saved paddle_tpu / era-Fluid programs.

Runs the paddle_tpu/analysis pass pipeline (use-before-def, shape/dtype
consistency, unregistered ops, reader placement, feed/fetch carriers)
over a SERIALIZED program, without executing it — plus, on request, the
deployment tier (row-independence, sharding-consistency, dtype-flow,
decode-invariants, donation-safety) under a deployment context:

    tools/pplint.py <model-dir>              # save_inference_model /
                                             # save_reference_model dir
    tools/pplint.py <model-dir>/__model__    # a bare desc file
    tools/pplint.py <checkpoint-dir>         # CheckpointManager root:
                                             # lints the program recorded
                                             # in the newest VALID snapshot
    tools/pplint.py <ckpt>/step_100          # one snapshot (its program
                                             # hash-verified before lint)
    tools/pplint.py dir --deploy serving     # + row-independence etc.
                                             # under the serving context
    tools/pplint.py dir --deploy decode --max-slots 8
    tools/pplint.py dir --deploy training --plan plan.json
    tools/pplint.py dir --json               # machine-readable findings
    tools/pplint.py dir --fail-on warning    # CI severity threshold
    tools/pplint.py --all-models             # sweep the bundled model
                                             # zoo under every applicable
                                             # context (the tier-1 leg)

Accepted formats (auto-detected from the first bytes):
  * native versioned JSON desc (core/program_desc.py)        -> b'{'
  * round-1 legacy pickle                                    -> b'\\x80'
  * era-wire ProgramDesc protobuf (reference_format.py)      -> anything
    else; the wire-level feed/fetch carrier checks run BEFORE the desc
    is parsed, then the parsed program goes through the full pipeline.

Feed/fetch targets come from __model_meta__.json (native dirs) or the
era feed/fetch plumbing ops (strip_feed_fetch).

Exit codes:
  0  no findings at or above the --fail-on threshold
     (default threshold: error)
  1  findings at/above the threshold (details on stdout; in --json
     mode, as one JSON document)
  2  bad invocation / unreadable or unverifiable model artifact

--strict is kept as an alias for --fail-on warning.
"""
import argparse
import json
import os
import sys

# lint reads programs and must never claim the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _resolve_checkpoint_dir(path):
    """Map a checkpoint layout onto the program desc it records, or None
    when `path` is not a checkpoint. Accepts a checkpoint ROOT (step_<N>
    dirs / LATEST: the newest snapshot whose hash tree verifies wins,
    like CheckpointManager.restore) or one snapshot dir (snapshot.json:
    linted exactly as given — corruption is a hard error here, since the
    user pointed at THIS snapshot). Both paths verify only what the lint
    reads (structure, manifest hash, the program's own sha256) — array
    payloads are ptpu_ckpt verify's job, not GBs of reads for a lint."""
    from paddle_tpu.checkpoint import snapshot as snap
    if os.path.exists(os.path.join(path, snap.SNAPSHOT_FILE)):
        problems = snap.verify_snapshot_light(path)
        if problems:
            raise ValueError("corrupt snapshot %s: %s"
                             % (path, "; ".join(problems)))
        meta = snap.read_snapshot_meta(path)
    elif snap.list_steps(path) or os.path.exists(
            os.path.join(path, snap.LATEST_FILE)):
        # newest-first walk, but only as much hashing as the lint needs:
        # structure + manifest hash + the recorded program's own sha256
        # (verify_snapshot_light) — NOT every array file, which on a real
        # checkpoint is GBs of reads for zero lint value
        meta = None
        for _, cand in reversed(snap.list_steps(path)):
            if snap.verify_snapshot_light(cand):
                continue
            meta, path = snap.read_snapshot_meta(cand), cand
            break
        if meta is None:
            raise ValueError("checkpoint dir %s has no snapshot that "
                             "verifies" % path)
    else:
        return None
    prog = meta.get("program")
    if not prog:
        raise ValueError("snapshot %s records no program (legacy "
                         "io.save_checkpoint layout)" % path)
    return os.path.join(path, prog["file"])


def load_program(path, model_filename=None, allow_pickle=False):
    """-> (program, feed_names, fetch_names, wire_diagnostics)."""
    import paddle_tpu as fluid
    from paddle_tpu import reference_format as rf
    from paddle_tpu.analysis import check_wire_carriers

    meta_feeds = meta_fetches = None
    if os.path.isdir(path):
        ckpt_desc = _resolve_checkpoint_dir(path)
        if ckpt_desc is not None:
            # training-checkpoint program: no feed/fetch contract is
            # recorded; analysis falls back to the is_data convention
            path = ckpt_desc
        else:
            meta_path = os.path.join(path, "__model_meta__.json")
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    meta = json.load(f)
                meta_feeds, meta_fetches = (meta.get("feed"),
                                            meta.get("fetch"))
            path = os.path.join(path, model_filename or "__model__")
    with open(path, "rb") as f:
        raw = f.read()

    if raw[:1] == b"{":  # native versioned JSON desc
        program = fluid.Program.parse_from_string(raw)
        return program, meta_feeds, meta_fetches, []
    if raw[:1] == b"\x80":  # round-1 legacy pickle artifact
        # unpickling EXECUTES code from the file — never do that by
        # default in a lint tool whose whole job is inspecting artifacts
        # of unknown provenance
        if not allow_pickle:
            raise ValueError(
                "legacy pickle desc: unpickling executes code from the "
                "file; pass --allow-pickle only for artifacts you trust")
        import pickle
        program = pickle.loads(raw)
        return program, meta_feeds, meta_fetches, []
    # era-wire protobuf: carrier checks at the WIRE level first, then
    # parse (which strips the feed/fetch plumbing) and the layout adapter.
    # A malformation that also breaks parsing must still REPORT the wire
    # diagnostics that explain it, not vanish behind a load error.
    blocks = rf._parse_blocks(raw)
    wire_diags = check_wire_carriers(blocks)
    try:
        program = rf.parse_program_desc(blocks)
        feeds, fetches = rf.strip_feed_fetch(blocks)
        rf.adapt_sequence_layout(program, feeds)
    except Exception:
        if wire_diags:
            return None, None, None, wire_diags
        raise
    return program, meta_feeds or feeds, meta_fetches or fetches, wire_diags


def build_deploy_context(kind, program, feeds, fetches, plan_path=None,
                         max_slots=8, weights_dtype=None):
    """DeploymentContext for a SAVED program, mirroring what the engines
    derive at load: serving classifies each fetch by the engine's row
    policy (leading -1 = sliced rows), decode infers the slot vars from
    the executor's own state analysis, training arms a saved plan JSON
    through the device-free PlanView."""
    from paddle_tpu import analysis
    from paddle_tpu.core.utils import find_var
    if kind == "serving":
        row, whole = [], []
        for n in fetches or ():
            var = find_var(program, n)
            shape = list(getattr(var, "shape", None) or []) \
                if var is not None else []
            if (var is not None and not var.persistable and shape
                    and shape[0] == -1):
                row.append(n)
            else:
                whole.append(n)
        return analysis.DeploymentContext.for_serving(
            row_fetches=row, whole_fetches=whole,
            weights_dtype=weights_dtype)
    if kind == "decode":
        slots = analysis.infer_slot_vars(program, fetches, max_slots)
        return analysis.DeploymentContext.for_decode(
            slot_vars=slots, max_slots=max_slots,
            row_fetches=list(fetches or ()))
    if kind == "training":
        plan = None
        if plan_path:
            with open(plan_path) as f:
                plan = analysis.PlanView.from_json(json.load(f))
        return analysis.DeploymentContext.for_training(plan=plan)
    return analysis.DeploymentContext.generic()


def _diag_json(d):
    return {"severity": d.severity, "code": d.code, "message": d.message,
            "block": d.block_idx, "op": d.op_idx, "op_type": d.op_type,
            "vars": list(d.var_names), "hint": d.hint,
            "callstack": [list(fr) for fr in d.callstack]}


def _result_json(target, result):
    return {"target": target,
            "errors": len(result.errors),
            "warnings": len(result.warnings),
            "certificates": dict(result.certificates),
            "diagnostics": [_diag_json(d) for d in result.diagnostics]}


def _fails(result, fail_on):
    return bool(result.errors
                or (fail_on == "warning" and result.warnings))


def _lint_all_models(args):
    """Sweep the bundled model zoo: every model's training program under
    the generic deployment context AND under an auto-built ShardingPlan
    (1-device mesh — the plan/program coherence rules are device-count
    independent). One process, <15s: this is the tier-1 CI leg."""
    from paddle_tpu import analysis
    from paddle_tpu.models import zoo
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.plan import ShardingPlan

    mesh = make_mesh({"dp": 1})
    reports, bad = [], 0
    for name in zoo.names():
        main, _startup = zoo.build(name)
        contexts = [("generic", analysis.DeploymentContext.generic())]
        try:
            plan = ShardingPlan.build(main, mesh, shard_update=True)
            contexts.append(("training+plan",
                             analysis.DeploymentContext.for_training(
                                 plan=plan)))
        except Exception as e:  # pragma: no cover - partitioner gap
            print("pplint: %s: plan build failed (%s); generic only"
                  % (name, e), file=sys.stderr)
        for ckind, deploy in contexts:
            result = analysis.analyze(main, deploy=deploy)
            target = "%s[%s]" % (name, ckind)
            reports.append((target, result))
            if _fails(result, args.fail_on):
                bad += 1
    if args.json:
        print(json.dumps({"models": [_result_json(t, r)
                                     for t, r in reports]}, indent=2))
    else:
        for target, result in reports:
            for d in result:
                print("%s: %s" % (
                    target, d.format(with_callstack=not args.no_callstack)))
            print("pplint: %d error(s), %d warning(s) in %s"
                  % (len(result.errors), len(result.warnings), target))
    return 1 if bad else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="pplint", description="static verifier for saved programs")
    ap.add_argument("path", nargs="?", default=None,
                    help="model directory or program desc file")
    ap.add_argument("--model-filename", default=None,
                    help="desc filename inside a model dir "
                         "(default __model__)")
    ap.add_argument("--steps", type=int, default=1,
                    help="validate for Executor.run(steps=K) semantics")
    ap.add_argument("--deploy", default=None,
                    choices=["serving", "decode", "training", "generic"],
                    help="also run the deployment-pass tier under this "
                         "context (row-independence, sharding, dtype "
                         "flow, decode invariants, donation safety)")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="ShardingPlan JSON (plan.to_json()) to check "
                         "the program against (--deploy training)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="decode slot count for --deploy decode")
    ap.add_argument("--weights-dtype", default=None,
                    choices=["fp32", "bf16", "int8"],
                    help="serving weights dtype the deployment expects")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as one JSON document on stdout")
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warning"],
                    help="lowest severity that makes the exit code 1 "
                         "(default: error)")
    ap.add_argument("--all-models", action="store_true",
                    help="lint every bundled model zoo program under "
                         "all applicable deployment contexts")
    ap.add_argument("--strict", action="store_true",
                    help="alias for --fail-on warning")
    ap.add_argument("--no-callstack", action="store_true",
                    help="omit op creation stacks from output")
    ap.add_argument("--allow-pickle", action="store_true",
                    help="permit loading round-1 legacy pickle descs "
                         "(unpickling executes code — trusted files only)")
    args = ap.parse_args(argv)
    if args.strict:
        args.fail_on = "warning"

    if args.all_models:
        return _lint_all_models(args)
    if args.path is None:
        ap.error("need a model path (or --all-models)")

    try:
        program, feeds, fetches, wire_diags = load_program(
            args.path, args.model_filename,
            allow_pickle=args.allow_pickle)
    except Exception as e:
        print("pplint: cannot load %s: %s" % (args.path, e),
              file=sys.stderr)
        return 2

    from paddle_tpu import analysis
    if program is None:
        # wire carrier errors AND an unparseable desc: the diagnostics
        # are the explanation — report them instead of a bare load error
        result = analysis.AnalysisResult(wire_diags)
    else:
        deploy = None
        if args.deploy:
            try:
                deploy = build_deploy_context(
                    args.deploy, program, feeds, fetches,
                    plan_path=args.plan, max_slots=args.max_slots,
                    weights_dtype=args.weights_dtype)
            except Exception as e:
                print("pplint: cannot build %s deployment context: %s"
                      % (args.deploy, e), file=sys.stderr)
                return 2
        result = analysis.analyze(program, feed_names=feeds,
                                  fetch_names=fetches, steps=args.steps,
                                  deploy=deploy)
        result.diagnostics[:0] = wire_diags  # wire findings lead, in order

    if args.json:
        print(json.dumps(_result_json(args.path, result), indent=2))
    else:
        for d in result:
            print(d.format(with_callstack=not args.no_callstack))
        print("pplint: %d error(s), %d warning(s) in %s"
              % (len(result.errors), len(result.warnings), args.path))
    return 1 if _fails(result, args.fail_on) else 0


if __name__ == "__main__":
    sys.exit(main())
