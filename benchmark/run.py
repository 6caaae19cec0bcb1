"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the machine it is started on and prints,
as the last line of its standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, traced `breakdown`, and last
`compared` (what `correct` compared, each number beside its limit; the same
words end standard error). Earlier
lines (prefixed `bench:`) name the platform, the device kind and count, the
set-up's parts, the window's sample count and quartiles, and the checks.

Exit codes: 0 a result was printed; 2 no TPU, or fewer chips than the cell
asks for (nothing printed as a result); 1 anything else.

`--rehearse` walks the same path on the CPU (JAX_PLATFORMS=cpu) for the
tests: it prints `platform: cpu` and counts, and no device metric.
`--manifest` names another manifest than BENCHMARK.json (the tests' own).
`--keep-trace DIR` leaves the profiler's files in DIR.
"""
import time
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from benchmark import cell as runner
    from benchmark import manifest
    cell = manifest.load_cell(args.manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    try:
        result = runner.run(cell, args, T_PROCESS)
    except runner.NoChip as e:
        print("bench: %s" % e, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
