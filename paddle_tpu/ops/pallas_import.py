"""The one place `jax.experimental.pallas` enters the process, timed.

Importing it costs 1.3 s on the benchmark's machines (PERF.md section 6,
PRs 48 and 50), which is why `import paddle_tpu` leaves it out and a kernel
module is imported inside the function that needs it. That puts the cost
inside whatever is running when the first such op is built or lowered
(shape inference under `build/program`, or jax's trace of the first step):
`ptpu_import_seconds{module="jax.experimental.pallas"}` says how much of
that was this import. Every kernel module takes `pl` and `pltpu` from here.
"""
import time

_t0 = time.perf_counter()
from jax.experimental import pallas as pl               # noqa: E402,F401
from jax.experimental.pallas import tpu as pltpu        # noqa: E402,F401
_seconds = time.perf_counter() - _t0

from ..observability.registry import note_import     # noqa: E402

note_import("jax.experimental.pallas", _seconds)
