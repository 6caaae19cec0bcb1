"""Kernel-level microbench: pallas flash attention and fused softmax-xent
vs their dense XLA counterparts, fwd+bwd, on the chip (one process; send
it through the chip tool). One JSON line per comparison: {"kernel": ...,
"dense_ms": ..., "fused_ms": ..., "speedup": ..., "shape": ...}.
MB_TUNE=1 times the three flash kernels and the whole op over blocks
(MB_BLOCKS=512x512 for the default pair alone); MB_LN=1 compares the
layer_norm kernel at a list of tile budgets with its XLA path (device ms
from a trace).
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


_SWEEP_SHAPES = "8x2048x8x8x64x0,8x2048x8x8x64x1,4x4096x16x16x128x1"
# MXU products a [bq, bk] tile costs in the forward, dQ and dK/dV kernels:
# q k^T and p v; q k^T, dO v^T and ds k; those two scores and ds^T q, p^T
# dO. The latent form adds the rotary product to the scores of all three
# and a rotary gradient to each backward kernel.
_PRODUCTS = {False: (2, 3, 4), True: (3, 5, 6)}
_SWEEP_BLOCKS = tuple((bq, bk) for bq in (128, 256, 512, 1024)
                      for bk in (128, 256, 512, 1024))


def sweep_flash_blocks(shapes=_SWEEP_SHAPES, blocks=_SWEEP_BLOCKS,
                       dtype="bfloat16"):
    """ms a call of each of the three flash kernels alone and of
    `flash_attention` whole, forward and forward + backward, so that what
    lies around the kernels is read off one line; over block_q x block_k at
    the shapes the benchmark's cells run (BxTxHqxHkvxDxcausal, and a
    seventh number for the latent form's rotary width: 1x4096x32x32x128x1x64
    is the Xing4.0 cell's); and each result's largest error against dense
    float32 attention on the same rounded inputs (first sequence; null
    where its [H, T, T] float32 scores do not fit the chip). Beside a
    kernel's ms a call stands its ms a product (`_PRODUCTS`: the three share
    their tiles and their one `exp` an element and differ in the MXU
    products a tile costs, so a kernel that pays more for a product than
    the others is the one that is not bound by them). A pair Mosaic refuses
    is a line with its error. The kernels take the op's arrays as [B, T, H*D] (or a row a
    head: `heads_a_block`, on the line). Under jit a kernel whose result is
    dropped is dead code, so `_flash_bwd`'s dq alone times the dQ kernel;
    dK/dV reads the delta rows the dQ kernel writes, so (dk, dv) alone
    times both, and `dkdv_ms` is that less `dq_ms`."""
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel.ring_attention import attention_reference

    for spec in shapes.split(","):
        b, t, h, hkv, d, causal, *dr = (int(x) for x in spec.split("x"))
        dr = dr[0] if dr else 0
        causal, scale = bool(causal), 1.0 / float(np.sqrt(d + dr))
        rng = np.random.RandomState(0)

        def normal(heads, width):
            return jnp.asarray(rng.randn(b, t, heads, width).astype("f")
                               * 0.5, dtype=dtype)
        q, g, k, v = normal(h, d), normal(h, d), normal(hkv, d), \
            normal(hkv, d)
        rope = (normal(h, dr), normal(1, dr)) if dr else ()
        hb = pk.heads_a_block(h, hkv, d)
        lens = jnp.full((b if hb else b * h,), t, jnp.int32)
        rows = [pk._rows(x, hb) for x in (q, k, v, g)]
        flat = pk._rope_flat(rope or None)

        def dense(q, k, v, *rope):
            q, k, v, *rope = (x.astype(jnp.float32)
                              for x in (q, k, v) + rope)
            if rope:            # a head is [its own part; the shared key]
                q = jnp.concatenate([q, rope[0]], -1)
                k = jnp.concatenate([k, jnp.repeat(rope[1], h, 2)], -1)
            return attention_reference(q, k, v, causal=causal, scale=scale)
        try:
            ref_o, vjp = jax.vjp(dense, q[:1], k[:1], v[:1],
                                 *(x[:1] for x in rope))
            refs = (ref_o,) + vjp(g[:1].astype(jnp.float32))
        except Exception:  # noqa: BLE001 — [H, T, T] float32 did not fit
            refs = None

        for bq, bk in blocks:
            if bq > t or bk > t:
                continue
            fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: pk._flash_fwd(
                q, k, v, lens, d, hb or 1, scale, causal, None, bq, bk,
                False, flat))

            def bwd(q, k, v, o, lse, g, bq=bq, bk=bk):
                return pk._flash_bwd(d, hb or 1, scale, causal, None, bq,
                                     bk, False, (q, k, v, lens, o, lse), g,
                                     flat)

            def whole(q, k, v, *rope, bq=bq, bk=bk):
                return pk.flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    scale=scale, **dict(zip(("q_rope", "k_rope"), rope)))

            def whole_bwd(q, k, v, g, *rope):
                out, vjp = jax.vjp(whole, q, k, v, *rope)
                return (out,) + vjp(g)
            line = {"kernel": "flash_sweep",
                    "shape": [b, t, h, hkv, d] + ([dr] if dr else []),
                    "heads_a_block": hb or "transposed", "causal": causal,
                    "dtype": dtype, "block_q": bq, "block_k": bk,
                    "device": str(jax.devices()[0])}
            try:
                o, lse = fwd(*rows[:3])
                line["fwd_ms"] = round(_time(fwd, *rows[:3]), 3)
                for name, fn, args in (
                        ("dq_ms", lambda *a: bwd(*a)[:1], None),
                        ("dq_dkdv_ms", lambda *a: bwd(*a)[1:], None),
                        ("op_fwd_ms", whole, (q, k, v) + rope),
                        ("op_fwd_bwd_ms", whole_bwd, (q, k, v, g) + rope)):
                    args = args or (*rows[:3], o, lse, rows[3])
                    line[name] = round(_time(jax.jit(fn), *args), 3)
                line["dkdv_ms"] = round(line["dq_dkdv_ms"]
                                        - line["dq_ms"], 3)
                line["around_kernels_ms"] = round(
                    line["op_fwd_bwd_ms"] - line["fwd_ms"]
                    - line["dq_dkdv_ms"], 3)
                line["ms_a_product"] = {
                    n: round(line[n + "_ms"] / count, 4) for n, count in
                    zip(("fwd", "dq", "dkdv"), _PRODUCTS[bool(dr)])}
                got = jax.jit(whole_bwd)(q, k, v, g, *rope)
                line["max_err"] = refs and {
                    n: round(float(jnp.max(jnp.abs(
                        a[:1].astype(jnp.float32) - r))), 5)
                    for n, a, r in zip(
                        ("o", "dq", "dk", "dv", "dq_rope", "dk_rope"), got,
                        refs)}
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                line["error"] = str(e).replace("\n", " ")[-300:]
            print(json.dumps(line), flush=True)


def _device_ms(fn, *args, iters=10):
    """Device milliseconds a call of `fn`, from a jax.profiler trace of
    `iters` calls read as `python -m paddle_tpu.profiler` reads one: (self
    time of every device operation, {Pallas kernel name: its own ms})."""
    import shutil
    import tempfile
    import jax
    from paddle_tpu import profiler
    from paddle_tpu.core.utils import device_fetch_barrier
    device_fetch_barrier(fn(*args))
    trace_dir = tempfile.mkdtemp(prefix="mb_trace_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(iters):
                out = fn(*args)
            device_fetch_barrier(out)
        table = profiler.device_op_table_from(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    kernels = {}
    for row in table["rows"]:
        if row["kernel"]:
            kernels[row["kernel"]] = (kernels.get(row["kernel"], 0.0)
                                      + row["total_ms"] / iters)
    return (round(table["busy_self_ms"] / iters, 4),
            {name: round(ms, 4) for name, ms in kernels.items()})


_LN_SHAPES = "16384x512"            # both transformer cells' 32 calls a step
_LN_KIB = (128, 256, 512, 1024, 2048, 4096)


def bench_layer_norm(shapes=_LN_SHAPES, budgets_kib=_LN_KIB,
                     dtype="float32"):
    """The layer_norm kernel at each byte budget of one input tile (what
    DEFAULT_TILES["ln"] holds; rows by pallas_kernels._ln_block_rows)
    against the op's XLA path at x [N, D]: device ms a call from a traced
    run, forward alone and forward plus backward (dx, dscale, dbias under
    a random cotangent), GB/s over the bytes the pass has to move (x in
    and y out; with the backward x and dy in and dx out besides), and the
    largest difference from the XLA path over the largest value there. A
    budget Mosaic refuses is a line with its error. One line with path
    "xla" a shape comes first."""
    jax = _await()
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    def xla(x, s, b):                # the layer_norm rule's dense path
        x2 = x.astype(jnp.float32)
        mean = jnp.mean(x2, axis=1, keepdims=True)
        var = jnp.var(x2, axis=1, keepdims=True)
        y = (x2 - mean) * jax.lax.rsqrt(var + 1e-5)
        return (y * s.reshape(1, -1) + b.reshape(1, -1)).astype(x.dtype)

    for spec in shapes.split(","):
        n, d = (int(v) for v in spec.split("x"))
        rng = np.random.RandomState(0)
        x, g = (jnp.asarray(rng.randn(n, d).astype("f") * 2 + 0.5,
                            dtype=dtype) for _ in range(2))
        s = jnp.asarray(rng.rand(d).astype("f") + 0.5)
        b = jnp.asarray(rng.randn(d).astype("f"))
        nbytes = n * d * x.dtype.itemsize
        want = None
        for kib in (None,) + tuple(budgets_kib):
            line = {"kernel": "layer_norm", "shape": [n, d], "dtype": dtype,
                    "path": "xla" if kib is None else "pallas",
                    "device": str(jax.devices()[0])}
            if kib is None:
                fwd = xla
            else:
                rows = pk._ln_block_rows(n, d, x.dtype, kib * 1024)
                line.update(tile_kib=kib, block_n=rows, grid=-(-n // rows))

                def fwd(x, s, b, rows=rows):
                    return pk.layer_norm(x, s, b, block_n=rows)[0]

            def both(x, s, b, g, fwd=fwd):
                y, vjp = jax.vjp(fwd, x, s, b)
                return (y,) + vjp(g)
            try:
                f, fb = jax.jit(fwd), jax.jit(both)
                got = fb(x, s, b, g)
                line["fwd_ms"], k = _device_ms(f, x, s, b)
                line["fwd_bwd_ms"], kb = _device_ms(fb, x, s, b, g)
                if line["fwd_ms"]:      # 0 off a TPU: no device plane
                    line["fwd_gbps"] = round(
                        2 * nbytes / line["fwd_ms"] / 1e6, 1)
                    line["fwd_bwd_gbps"] = round(
                        5 * nbytes / line["fwd_bwd_ms"] / 1e6, 1)
                if kib is None:
                    want = got
                else:
                    # with no op scope around it jax names the call
                    # under vjp jvp_ptpu_layer_norm_fwd_
                    line["kernel_ms"], line["kernel_ms_under_vjp"] = (
                        round(sum(ms for name, ms in t.items()
                                  if "ptpu_layer_norm_fwd" in name), 4)
                        for t in (k, kb))
                    pairs = [(np.asarray(a, np.float64),
                              np.asarray(w, np.float64))
                             for a, w in zip(got, want)]
                    line["max_err"] = max(      # as chip_smoke.py's
                        float(np.abs(a - w).max() / (np.abs(w).max() + 1e-6))
                        for a, w in pairs)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                line["error"] = str(e).replace("\n", " ")[-300:]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    # MB_* knobs shrink the config for smoke runs (CPU interpret mode is
    # orders of magnitude slower than the real kernel)
    if os.environ.get("MB_LN") == "1":
        # MB_SHAPES=NxD[,...], MB_BLOCKS=<KiB of one float32 tile>[,...]
        bench_layer_norm(
            os.environ.get("MB_SHAPES", _LN_SHAPES),
            tuple(int(k) for k in os.environ["MB_BLOCKS"].split(","))
            if os.environ.get("MB_BLOCKS") else _LN_KIB,
            os.environ.get("MB_DTYPE", "float32"))
    elif os.environ.get("MB_TUNE") == "1":
        # MB_SHAPES=BxTxHqxHkvxDxcausal[xdr][,...], MB_BLOCKS=BQxBK[,...]
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
