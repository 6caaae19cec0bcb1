"""Device places.

Parity: paddle/fluid/platform/place.h (CPUPlace / CUDAPlace) — plus the
TPUPlace this framework exists for. A Place selects the JAX backend the
Executor dispatches to; TPUPlace is the default when TPU devices exist.
CUDAPlace is accepted as an alias for "the accelerator" so unmodified fluid
scripts run (the reference's CUDAPlace(0) becomes the TPU chip).
"""
import os
import sys

import jax


def cpu_only_env():
    """True when JAX_PLATFORMS explicitly restricts this process to CPU
    (the test suite, smoke runs) — the one case where an accelerator
    Place resolves to a CPU device instead of raising."""
    want = os.environ.get("JAX_PLATFORMS", "")
    parts = [p.strip() for p in want.split(",") if p.strip()]
    return bool(parts) and all(p == "cpu" for p in parts)


def require_accelerator(tool_name):
    """Loud-failure rule for anything that prints timings: exit unless
    jax's default device is an accelerator or the process is pinned to
    the CPU on purpose (JAX_PLATFORMS=cpu). Returns that device."""
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not cpu_only_env():
        sys.exit("%s: expected an accelerator but jax found only CPU "
                 "devices; refusing to emit CPU numbers (set "
                 "JAX_PLATFORMS=cpu to smoke-run on purpose)" % tool_name)
    return dev


class Place(object):
    backend = None

    def device(self):
        devs = jax.devices(self.backend) if self.backend else jax.devices()
        return devs[self.device_id if hasattr(self, "device_id") else 0]

    def __repr__(self):
        return type(self).__name__ + "()"


class CPUPlace(Place):
    backend = "cpu"


class TPUPlace(Place):
    """Native TPU execution (BASELINE.json north star: platform::TPUPlace).

    No accelerator and no such chip are errors: a TPUPlace never quietly
    becomes the CPU or chip 0. The single exception is a process pinned
    to the CPU by JAX_PLATFORMS=cpu (how the tests drive TPUPlace code),
    where the first CPU device stands in for a one-chip host."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    @staticmethod
    def devices():
        """The devices a TPUPlace indexes: every accelerator, or — only
        under JAX_PLATFORMS=cpu — the first CPU device."""
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if devs:
            return devs
        if not cpu_only_env():
            raise RuntimeError(
                "TPUPlace: jax found no accelerator (devices: %s); set "
                "JAX_PLATFORMS=cpu to run on the CPU on purpose"
                % (jax.devices(),))
        return jax.devices()[:1]

    def device(self):
        devs = self.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "TPUPlace(%d): this host has %d %s device(s)"
                % (self.device_id, len(devs), devs[0].platform))
        return devs[self.device_id]


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference scripts that say CUDAPlace(0) get the
    accelerator (TPU) — no GPU in the loop."""


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return any(d.platform not in ("cpu",) for d in jax.devices())
