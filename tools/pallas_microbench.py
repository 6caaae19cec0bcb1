"""Kernel-level microbench: pallas flash attention and fused softmax-xent
vs their dense XLA counterparts, fwd+bwd, on the chip (one process; send
it through the chip tool). One JSON line per comparison: {"kernel": ...,
"dense_ms": ..., "fused_ms": ..., "speedup": ..., "shape": ...}.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _await():
    import jax
    from paddle_tpu.core.compile_cache import enable_persistent_cache
    from paddle_tpu.places import require_accelerator
    enable_persistent_cache()
    require_accelerator("pallas_microbench")
    return jax


def _time(fn, *args, iters=20, warmup=3):
    from paddle_tpu.core.utils import device_fetch_barrier
    for _ in range(warmup):
        out = fn(*args)
    device_fetch_barrier(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    device_fetch_barrier(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _attention_setup(b, t, h, d, causal, dtype):
    """Shared q/k/v construction + dense baseline so bench_attention and
    tune_attention_blocks stay comparable by construction."""
    import jax.numpy as jnp
    from paddle_tpu.parallel.ring_attention import attention_reference

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype("f") * 0.3,
                           dtype=dtype) for _ in range(3))

    def dense_fwd(q, k, v):
        return attention_reference(q, k, v, causal=causal)

    def dense_loss(q, k, v):
        return jnp.sum(dense_fwd(q, k, v).astype(jnp.float32))

    return q, k, v, dense_fwd, dense_loss


def bench_attention(b=8, t=2048, h=8, d=64, causal=True, dtype="bfloat16"):
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    q, k, v, _, dense_loss = _attention_setup(b, t, h, d, causal, dtype)

    def flash_loss(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))
    flash = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
    dms = _time(dense, q, k, v)
    fms = _time(flash, q, k, v)
    # flush per line: a timeout-kill must not discard measurements
    # already completed
    print(json.dumps({
        "kernel": "flash_attention_fwd_bwd", "dense_ms": round(dms, 3),
        "fused_ms": round(fms, 3), "speedup": round(dms / fms, 3),
        "shape": [b, t, h, d], "causal": causal, "dtype": dtype,
        "device": str(jax.devices()[0])}), flush=True)


def bench_softmax_xent(n=8192, v=32000):
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(n, v).astype("f"))
    labels = jnp.asarray(rng.randint(0, v, n).astype("i4"))

    def dense(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1))

    def fused(logits, labels):
        return jnp.sum(pk.softmax_xent(logits, labels))

    d = jax.jit(jax.grad(dense))
    f = jax.jit(jax.grad(fused))
    dms = _time(d, logits, labels)
    fms = _time(f, logits, labels)
    print(json.dumps({
        "kernel": "softmax_xent_fwd_bwd", "dense_ms": round(dms, 3),
        "fused_ms": round(fms, 3), "speedup": round(dms / fms, 3),
        "shape": [n, v], "device": str(jax.devices()[0])}), flush=True)


def tune_attention_blocks(b=8, t=2048, h=8, d=64, causal=True,
                          dtype="bfloat16"):
    """Sweep flash block_q/block_k against the dense baseline, timing the
    forward alone and fwd+bwd separately (the r4 microbench measured
    fwd+bwd at 0.75x dense with the 128/128 default — this isolates
    whether the forward tiling or the backward kernel is the regression)."""
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    q, k, v, dense_fwd, dense_loss = _attention_setup(b, t, h, d, causal,
                                                      dtype)
    dense_f = jax.jit(dense_fwd)
    dense_g = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))
    dfms = _time(dense_f, q, k, v)
    dgms = _time(dense_g, q, k, v)
    print(json.dumps({"kernel": "attention_dense_baseline",
                      "fwd_ms": round(dfms, 3), "fwdbwd_ms": round(dgms, 3),
                      "shape": [b, t, h, d], "causal": causal,
                      "device": str(jax.devices()[0])}), flush=True)

    for bq in (128, 256, 512):
        for bk in (128, 256, 512):
            if bq > t or bk > t:
                continue

            def flash_loss(q, k, v, bq=bq, bk=bk):
                return jnp.sum(pk.flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk)
                    .astype(jnp.float32))

            # fwd and fwd+bwd fail independently (e.g. a block config
            # whose backward kernel exceeds VMEM) — time them separately
            # so a bwd failure cannot discard a banked fwd number
            err = None
            try:
                ff = jax.jit(lambda q, k, v, bq=bq, bk=bk:
                             pk.flash_attention(q, k, v, causal=causal,
                                                block_q=bq, block_k=bk))
                ffms = _time(ff, q, k, v)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                ffms = None
                err = "fwd: " + str(e)[:140]
            try:
                fg = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
                fgms = _time(fg, q, k, v)
            except Exception as e:  # noqa: BLE001
                fgms = None
                err = (err + "; " if err else "") + "bwd: " + str(e)[:140]
            print(json.dumps({
                "kernel": "flash_tune", "block_q": bq, "block_k": bk,
                "fwd_ms": ffms and round(ffms, 3),
                "fwdbwd_ms": fgms and round(fgms, 3),
                "fwd_speedup": ffms and round(dfms / ffms, 3),
                "fwdbwd_speedup": fgms and round(dgms / fgms, 3),
                "error": err}), flush=True)


if __name__ == "__main__":
    # MB_* knobs shrink the config for smoke runs (CPU interpret mode is
    # orders of magnitude slower than the real kernel)
    if os.environ.get("MB_TUNE") == "1":
        tune_attention_blocks(b=int(os.environ.get("MB_B", "8")),
                              t=int(os.environ.get("MB_SEQ", "2048")),
                              h=int(os.environ.get("MB_H", "8")))
    elif os.environ.get("MB_SHAPES"):
        # MB_SHAPES=BxTxHxD[,BxTxHxD...]: attention fwd+bwd comparison
        # at each shape (one line per shape, cheapest-first ordering is
        # the caller's job)
        for spec in os.environ["MB_SHAPES"].split(","):
            b, t, h, d = (int(x) for x in spec.strip().split("x"))
            bench_attention(b=b, t=t, h=h, d=d)
    else:
        bench_attention(b=int(os.environ.get("MB_B", "8")),
                        t=int(os.environ.get("MB_SEQ", "2048")),
                        h=int(os.environ.get("MB_H", "8")))
        bench_softmax_xent(n=int(os.environ.get("MB_N", "8192")),
                           v=int(os.environ.get("MB_V", "32000")))
