"""paddle_tpu.tuning — recorded autotuning of execution configs.

The compile tax has a sibling: the *default* tax. Every knob the
runtime exposes (multistep K, unroll policy, remat segment length,
guard granularity, the serving bucket lattice) ships with a default
that is right for some model on some device and measurably wrong for
others — PR 1 measured +65% from K alone on a dispatch-bound model.
This package closes the loop the TVM paper describes: *search* the
knobs against the bench harness (autotuner.py), *record* the winner per
(model signature, device) in a versioned on-disk store (store.py), and
*start at the tuned point* in production:

    # tune once (offline, or via tools/ptpu_tune.py)
    tuning.tune_training_multistep(main_prog, startup, feed, [loss],
                                   store=True)
    # every later process
    exe.run(main_prog, ..., apply_tuned=True)
    engine = InferenceEngine(model_dir, apply_tuned=True)

A recorded config never changes semantics silently: tuned `steps`
applies only to reader-fed programs where K steps consume K records
(Executor.run documents the rule), serving knobs apply only when the
caller did not pass explicit ones, and a store-version bump or device
change reads as "untuned" — defaults, the safe fallback.
"""
from .autotuner import (Autotuner, TuningResult, tune_kernels,
                        tune_serving_batching, tune_training_multistep)
from .store import (KNOWN_KNOBS, STORE_VERSION, TuningStore,
                    device_key, program_signature,
                    resolve_store_dir)

__all__ = [
    "Autotuner", "TuningResult", "TuningStore", "KNOWN_KNOBS",
    "STORE_VERSION", "device_key",
    "program_signature", "resolve_store_dir", "tune_kernels",
    "tune_serving_batching", "tune_training_multistep", "lookup_program",
    "apply_to_run",
]


def lookup_program(program, device, store=None):
    """The recorded config entry for (program content signature, device)
    or None. The Executor's apply_tuned=True gate."""
    st = store if isinstance(store, TuningStore) else TuningStore(
        root=store)
    return st.get(program_signature(program), device_key(device))


def apply_to_run(entry, program, steps, fetch_reduce="stack"):
    """Resolve one run's (steps, fetch_reduce, unroll_override) from a
    recorded entry.

    Tuned `steps` applies only when the caller left steps=1 AND the
    program is reader-fed: for an explicit-feed program, K device-side
    steps would re-train on the SAME batch K times — a semantic change
    no tuner is allowed to make. When tuned steps apply, a recorded
    fetch_reduce rides along if the caller left the default 'stack'
    (the tuner measured with it, and K-stacked fetches would surprise a
    caller expecting single-step values). multistep_unroll (when
    recorded) overrides the platform default for the lowered loop — a
    pure performance knob, always safe."""
    knobs = entry.get("knobs", {})
    tuned_steps = knobs.get("steps")
    if tuned_steps and int(tuned_steps) > 1 and steps == 1 and \
            _reader_fed(program):
        steps = int(tuned_steps)
        if knobs.get("fetch_reduce") and fetch_reduce == "stack":
            fetch_reduce = knobs["fetch_reduce"]
    unroll = knobs.get("multistep_unroll")
    return steps, fetch_reduce, (None if unroll is None else bool(unroll))


def _reader_fed(program):
    return any(op.type == "read"
               for op in program.global_block().ops)
