"""The one place `jax.experimental.pallas` enters the process, timed, and
the one form a kernel's entry takes.

Importing it costs 1.3 s on the benchmark's machines (PERF.md section 6,
PRs 48 and 50), which is why `import paddle_tpu` leaves it out and a kernel
module is imported inside the function that needs it. That puts the cost
inside whatever is running when the first such op is built or lowered
(shape inference under `build/program`, or jax's trace of the first step):
`ptpu_import_seconds{module="jax.experimental.pallas"}` says how much of
that was this import. Every kernel module takes `pl` and `pltpu` from here.

The rule for whoever writes the next kernel module (PR 60; the model is
`ssd_kernels._fwd_call`): a function that holds a `pl.pallas_call` is a
`kernel_entry`, a `jax.jit` with everything but its arrays static. A
model's layers call it at one shape, jit keeps the trace under its
arguments, and a step traces the kernel's Python body (a hundred equations
a lane tile, unrolled) and lowers it to a Mosaic module once a shape and
not once a call site: the other sites `call` the one `func.func`, and XLA
inlines it. So (1) whatever the body would read from its module or from
`kernel_config` (a tile, `dispatch_platform`) the caller resolves and hands
in as a static argument: a trace is kept under its arguments and sees a
patched module only the first time; (2) the `jax.custom_vjp` stays
outside, its forward and backward rules calling the entries; (3) the entry
is the narrowest function that holds the call: what is inside it lowers
once, its locations start at the kernel's name, and the fluid op's scope
that `python -m paddle_tpu.profiler` reads is the call site's, which only
XLA's inlining writes before them (a Mosaic call keeps its own `name=`
either way, which is what the benchmark's readers match); a function a
test or a mutant replaces by name stays a plain function that calls the
entries.
`ptpu_kernel_body_traces_total{kernel}` counts the real traces.
"""
import functools
import time

import jax

_t0 = time.perf_counter()
from jax.experimental import pallas as pl               # noqa: E402,F401
from jax.experimental.pallas import tpu as pltpu        # noqa: E402,F401
_seconds = time.perf_counter() - _t0

from ..observability.registry import note_import, note_kernel_trace  # noqa: E402,E501

note_import("jax.experimental.pallas", _seconds)


_COSTS_ITS_BYTES = set()


def kernel_entry(kernel, costs_its_bytes=False, **static):
    """`jax.jit(fn, **static)` for a function that holds the pallas_call
    named `kernel`; its Python body counts itself in
    `ptpu_kernel_body_traces_total{kernel}` (module docstring).
    costs_its_bytes: the kernel is one read and one write of its operand
    and nothing dearer, so running it again costs what reading a kept
    result back would: what a recomputing loop asks before it keeps a
    kernel's outputs (`costs_its_bytes` below)."""
    if costs_its_bytes:
        _COSTS_ITS_BYTES.add(kernel)

    def entry(fn):
        @functools.wraps(fn)
        def body(*args, **kwargs):
            note_kernel_trace(kernel)
            return fn(*args, **kwargs)
        return jax.jit(body, **static)
    return entry


def costs_its_bytes(kernel):
    """Did the entry of the pallas_call named `kernel` say so?"""
    return kernel in _COSTS_ITS_BYTES
