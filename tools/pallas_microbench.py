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
    sweep_flash_blocks stay comparable by construction."""
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


_SWEEP_SHAPES = "64x2048x64x0,64x2048x64x1,64x4096x128x1"
_SWEEP_BLOCKS = tuple((bq, bk) for bq in (128, 256, 512, 1024)
                      for bk in (128, 256, 512, 1024))


def sweep_flash_blocks(shapes=_SWEEP_SHAPES, blocks=_SWEEP_BLOCKS,
                       dtype="bfloat16"):
    """ms a call of each of the three flash kernels alone, in the kernels'
    own [BH, T, D] layout, over block_q x block_k at the shapes the
    benchmark's cells run (BHxTxDxcausal), and each result's largest error
    against dense float32 attention on the same rounded inputs (first two
    head-sequences). A pair Mosaic refuses is a line with its error. Under
    jit a kernel whose result is dropped is dead code, so `_flash_bwd`'s
    dq alone times the dQ kernel and (dk, dv) alone the dK/dV kernel."""
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel.ring_attention import attention_reference

    for spec in shapes.split(","):
        bh, t, d, causal = (int(x) for x in spec.split("x"))
        causal, scale = bool(causal), 1.0 / float(np.sqrt(d))
        rng = np.random.RandomState(0)
        q, k, v, g = (jnp.asarray(rng.randn(bh, t, d).astype("f") * 0.5,
                                  dtype=dtype) for _ in range(4))
        lens = jnp.full((bh,), t, jnp.int32)

        def dense(q, k, v):      # [2, T, D] -> heads of a [1, T, 2, D]
            return attention_reference(
                *(x.astype(jnp.float32).transpose(1, 0, 2)[None]
                  for x in (q, k, v)), causal=causal)[0].transpose(1, 0, 2)
        ref_o, vjp = jax.vjp(dense, q[:2], k[:2], v[:2])
        refs = (ref_o,) + vjp(g[:2].astype(jnp.float32))

        for bq, bk in blocks:
            if bq > t or bk > t:
                continue
            fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: pk._flash_fwd(
                q, k, v, lens, scale, causal, bq, bk, False))

            def bwd(q, k, v, o, lse, g, bq=bq, bk=bk):
                delta = jnp.sum(g.astype(jnp.float32)
                                * o.astype(jnp.float32), axis=-1)
                return pk._flash_bwd(scale, causal, bq, bk, False,
                                     (q, k, v, lens, delta, lse), g)
            line = {"kernel": "flash_sweep", "shape": [bh, t, d],
                    "causal": causal, "dtype": dtype, "block_q": bq,
                    "block_k": bk, "device": str(jax.devices()[0])}
            try:
                o, lse = fwd(q, k, v)
                line["fwd_ms"] = round(_time(fwd, q, k, v), 3)
                got = [o]
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                line["fwd_error"] = str(e).replace("\n", " ")[:200]
                o, lse = jax.jit(lambda q, k, v: pk._flash_fwd(
                    q, k, v, lens, scale, causal, 128, 128, False))(q, k, v)
                got = [None]
            for name, pick in (("dq", lambda r: r[:1]),
                               ("dkdv", lambda r: r[1:])):
                fn = jax.jit(lambda *a, pick=pick: pick(bwd(*a)))
                try:
                    got += list(fn(q, k, v, o, lse, g))
                    line[name + "_ms"] = round(
                        _time(fn, q, k, v, o, lse, g), 3)
                except Exception as e:  # noqa: BLE001
                    got += [None] * (1 if name == "dq" else 2)
                    line[name + "_error"] = str(e).replace("\n", " ")[:200]
            line["max_err"] = {
                n: None if a is None else round(float(jnp.max(jnp.abs(
                    a[:2].astype(jnp.float32) - r))), 5)
                for n, a, r in zip(("o", "dq", "dk", "dv"), got, refs)}
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    # MB_* knobs shrink the config for smoke runs (CPU interpret mode is
    # orders of magnitude slower than the real kernel)
    if os.environ.get("MB_TUNE") == "1":
        # MB_SHAPES=BHxTxDxcausal[,...], MB_BLOCKS=BQxBK[,...]
        sweep_flash_blocks(
            os.environ.get("MB_SHAPES", _SWEEP_SHAPES),
            tuple(tuple(int(x) for x in b.split("x"))
                  for b in os.environ["MB_BLOCKS"].split(","))
            if os.environ.get("MB_BLOCKS") else _SWEEP_BLOCKS,
            os.environ.get("MB_DTYPE", "bfloat16"))
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
