"""Probe: NCHW vs NHWC conv layout cost on the real TPU for a ResNet-50-ish
stack of convs, fwd+bwd. Run standalone: python tools/layout_probe.py"""
import os
import sys
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from paddle_tpu.core.utils import device_fetch_barrier  # noqa: E402
from paddle_tpu.places import require_accelerator  # noqa: E402


def conv_stack(layout):
    dn = (layout, "OIHW" if layout == "NCHW" else "HWIO", layout)

    def apply(params, x):
        for w in params:
            x = lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding="SAME",
                dimension_numbers=dn)
            x = jnp.maximum(x, 0)
        return jnp.sum(x.astype(jnp.float32))

    return apply


def bench_layout(layout, batch=256, c=256, hw=14, k=3, depth=8, steps=20):
    rng = np.random.RandomState(0)
    if layout == "NCHW":
        x = jnp.asarray(rng.rand(batch, c, hw, hw).astype(np.float32),
                        dtype=jnp.bfloat16)
        ws = [jnp.asarray(rng.randn(c, c, k, k).astype(np.float32) * 0.05,
                          dtype=jnp.bfloat16) for _ in range(depth)]
    else:
        x = jnp.asarray(rng.rand(batch, hw, hw, c).astype(np.float32),
                        dtype=jnp.bfloat16)
        ws = [jnp.asarray(rng.randn(k, k, c, c).astype(np.float32) * 0.05,
                          dtype=jnp.bfloat16) for _ in range(depth)]
    apply = conv_stack(layout)
    grad = jax.jit(jax.grad(apply))
    g = grad(ws, x)
    device_fetch_barrier(g)
    t0 = time.perf_counter()
    for _ in range(steps):
        g = grad(ws, x)
    device_fetch_barrier(g)
    dt = (time.perf_counter() - t0) / steps
    flops = 2 * 3 * depth * batch * hw * hw * c * c * k * k  # fwd+bwd(2x)
    print("%s: %.2f ms/step, %.1f TFLOP/s" % (layout, dt * 1e3,
                                              flops / dt / 1e12))


if __name__ == "__main__":
    # refuse to print CPU timings dressed up as TPU data
    require_accelerator("layout_probe")
    for layout in ("NCHW", "NHWC"):
        bench_layout(layout)
