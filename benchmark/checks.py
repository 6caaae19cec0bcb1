"""What the configurations' `check` functions share. A configuration's own
file says what a right answer is: `check(cfg, first, want, scalars)` takes
the first step's fetches and the reference's ({name: numpy array}, the same
names) and the first fetch of every step of the run, and returns
({verdict: bool}, one line of what it found). cell.run adds the verdicts
that hold for every cell (finite, no compile in the window, placement)."""
import statistics

import numpy as np


def normalised_error(got, want):
    """max |got - want| over max |want|: the relative error of a scalar.
    NaN anywhere gives NaN, which is under no tolerance."""
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def training(cfg, first, want, losses):
    """A cell that trains on one repeated batch: every fetch of the first
    step within its tolerance (`reference.tolerance` of the configuration's
    .json, by fetch) of the float32 reference, and the loss lower in the
    run's last quarter than in its first."""
    tolerance = cfg["reference"]["tolerance"]
    errors = {name: normalised_error(first[name], want[name])
              for name in want}
    quarter = max(1, len(losses) // 4)
    verdicts = {
        "reference": all(errors[n] <= tolerance[n] for n in errors),
        "loss_fell": len(losses) > 1 and statistics.median(
            losses[-quarter:]) < statistics.median(losses[:quarter])}
    found = "first step against the float32 reference (largest error over " \
        "largest value): %s; loss %.6f (reference %.6f) -> %.4f over %d " \
        "steps" % (
            ", ".join("%s off by %.2e (tolerance %g)"
                      % (n, errors[n], tolerance[n]) for n in sorted(errors)),
            losses[0], float(np.ravel(want["loss"])[0]), losses[-1],
            len(losses))
    return verdicts, found
