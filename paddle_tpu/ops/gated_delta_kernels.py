"""The gated delta rule, chunked: a linear-attention mixer whose state is a
[d_k, d_v] matrix a head (Yang et al. 2024, arXiv:2412.06464; the mixer of
three layers in four of Qwen3-Next). A value head's recurrence over tokens,
with g_t <= 0 its log decay and beta_t in (0, 1) its write strength:

    S' = exp(g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          S_0 = 0, [d_k, d_v]
    o_t = S_t^T q_t

computed here C tokens at a time so that the work is matmuls and the
sequential depth T / C. With c the running sum of g inside a chunk, S the
state that enters it, i and j positions inside it:

    L_ij = beta_i (k_i . k_j) exp(c_i - c_j)  for j < i, else 0
    T = (I + L)^-1
    U = T (beta * V),  W = T (beta * exp(c) * K)
    V' = U - W S
    O = (exp(c) * Q) S + M V',  M_ij = (q_i . k_j) exp(c_i - c_j), j <= i
    S <- exp(c_C) S + (exp(c_C - c) * K)^T V'

What no chunk needs another for (`_prepare`: the l2 norms, the running sums
and their exponentials, L, T, U, W, M) is jax.numpy, batched over all
chunks at once and differentiated by jax; only (I + L)^-1 has a rule of its
own, and its products run on the VPU with a chunk in every lane (`_inverse`
says why). q, k and v move to [B, H, N, C, d] in the dtype they arrive in and
are converted after. The pass over chunks is the Pallas kernel
`ptpu_gated_delta_fwd`, grid (batch x value heads / block_h, T / C) with S
in VMEM scratch, and its reverse, which carries dS, `ptpu_gated_delta_bwd`.
`path="scan"` is the same chunked form with `lax.scan` over chunks, forward
and backward XLA's own: what runs where the kernels are off (the CPU by
default), and what the kernels are measured against.

Memory: the kernel path keeps, from the forward to the backward pass, the
chunk pass's operands and what `_prepare`'s transpose needs, as lax.scan's
own backward does. The backward pass runs the forward kernel once more to
write the state that enters every chunk (T / C x [d_k, d_v] a head, alive
inside the grad op only), then the reverse kernel, then the transpose
(recomputing `_prepare` instead costs 3.2 ms a layer; my chip run, PR 33).

Precision: g, beta, the running sums, every exponential, (I + L)^-1 (its
backward rule's two matmuls at precision "highest"), the state and every
accumulator are float32. A matmul takes its operands in `operand_dtype`
(bf16 under AMP, else the inputs' float32) and accumulates in float32.

In a module of its own: jax keeps source locations inside a Mosaic call's
serialized kernel, so an edit above a kernel in pallas_kernels.py re-keys
every executable that holds one (PERF.md section 6, PR 27).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["gated_delta_rule"]

_F32 = jnp.float32
_INVERSE_BASE = 16      # (I + L)^-1 of a block this size by its power series
_EPS = 1e-6             # inside the l2 norm's root


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


# ---- (I + L)^-1 of a strictly lower-triangular L ----------------------------

def _lane_mm(a, b):
    """[.., p, q, G] x [.., q, r, G] -> [.., p, r, G] on the VPU, G the lanes."""
    return jnp.sum(a[..., :, :, None, :] * b[..., None, :, :, :], axis=-3)


def _inverse(low):
    """(I + low)^-1 for low [..., n, n] strictly lower-triangular, n = 16 x
    2^m. The 16 x 16 diagonal blocks by their finite power series sum_k
    (-L)^k = (I + X)(I + X^2)(I + X^4)(I + X^8) with X = -L (L^16 = 0), then
    neighbours merged: [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1,
    D^-1]]. The series over a whole chunk would cancel catastrophically where
    consecutive keys are alike (its terms grow like binomials of n); over 16
    they stay within a few digits. The products run on the VPU, the chunks
    in the lanes: a [.., 64, 64] float32 array is padded to 128 lanes on the
    v5e, and batched matmuls cost the passes over such arrays (PERF.md, PR 36)."""
    n, m = low.shape[-1], _INVERSE_BASE
    lanes = jnp.moveaxis(low.reshape((-1, n, n)), 0, 2)         # [n, n, G]

    def blocks(s):                  # [n / s, s rows, n / s, s columns, G]
        return lanes.reshape((n // s, s, n // s, s, -1))

    x = -jnp.stack([blocks(m)[i, :, i] for i in range(n // m)])
    out = jnp.eye(m, dtype=low.dtype)[:, :, None] + x          # [n / m, m, m, G]
    for _ in range(3):
        x = _lane_mm(x, x)
        out = out + _lane_mm(out, x)
    while m < n:
        a, d = jnp.moveaxis(out.reshape((-1, 2) + out.shape[1:]), 1, 0)
        b = jnp.stack([blocks(m)[2 * p + 1, :, 2 * p]
                       for p in range(n // m // 2)])
        c = -_lane_mm(_lane_mm(d, b), a)
        out = jnp.concatenate([jnp.concatenate([a, jnp.zeros_like(a)], 2),
                               jnp.concatenate([c, d], 2)], 1)
        m *= 2
    return jnp.moveaxis(out[0], 2, 0).reshape(low.shape)


@jax.custom_vjp
def unit_lower_inverse(low):
    """T = (I + low)^-1; dL = -T^T dT T^T on the strictly lower triangle,
    so no intermediate of the blocked inverse is kept for the backward."""
    return _inverse(low)


def _unit_lower_inverse_fwd(low):
    t = _inverse(low)
    return t, t


def _unit_lower_inverse_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    return (jnp.tril(-mm(mm(tt, g), tt), -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


# ---- what no chunk needs another for ----------------------------------------

def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _EPS)


def _prepare(q, k, v, g, beta, *, chunk, dt):
    """q, k [B, T, Hk, dk], v [B, T, Hv, dv], g, beta [B, T, Hv] -> the
    chunk pass's operands, each [B x Hv, N, ...] with N = ceil(T / chunk):
    qe = exp(c) * q and kd = exp(c_C - c) * k [.., C, dk], m [.., C, C], u
    [.., C, dv], w [.., C, dk] in `dt`, and erow = exp(c_C) [.., 1, dv]
    float32 (the chunk's whole decay, one row a chunk, broadcast over dv so
    that the kernel multiplies the state by a tile row). q and k are
    l2-normalised over dk and q multiplied by dk^-0.5. Key head j serves
    value heads j x Hv / Hk .. (j + 1) x Hv / Hk - 1. Positions past T are
    padded with beta = g = 0: they write nothing and decay nothing."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep, n = hv // hk, -(-t // chunk)

    def chunks(x):                      # [B, T, H, ...] -> [B, H, N, C, ...]
        # in x's dtype: the transposes move bf16, the convert fuses after
        x = jnp.pad(x, [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)
        if x.dtype != _F32:             # or XLA hoists the convert back
            x = lax.optimization_barrier(x)
        return x.astype(_F32)

    def heads(x):                       # [B, Hk, ...] -> [B, Hv, ...]
        x = jnp.broadcast_to(x[:, :, None],
                             (b, hk, rep) + x.shape[2:])
        return x.reshape((b, hv) + x.shape[3:])

    qc, kc = _l2norm(chunks(q)) * dk ** -0.5, _l2norm(chunks(k))
    vc, gc, bc = chunks(v), chunks(g), chunks(beta)
    c = jnp.cumsum(gc, -1)                                   # [B, Hv, N, C]
    e, tail = jnp.exp(c), jnp.exp(c[..., -1:] - c)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # exp only of what is kept: above the diagonal c_i - c_j is positive
    decay = jnp.exp(jnp.where(row >= col, c[..., :, None] - c[..., None, :],
                              -jnp.inf))
    qd, kd = qc.astype(dt), kc.astype(dt)
    kk, qk = (heads(jnp.einsum("bhnid,bhnjd->bhnij", x, kd,
                               preferred_element_type=_F32)) for x in (kd, qd))
    low = jnp.where(row > col, bc[..., None] * kk * decay, 0.0)
    tm = unit_lower_inverse(low).astype(dt)
    kv_heads = heads(kc)
    u, w = (jnp.einsum("bhnij,bhnjd->bhnid", tm, x.astype(dt),
                       preferred_element_type=_F32)
            for x in (bc[..., None] * vc, (bc * e)[..., None] * kv_heads))
    erow = jnp.broadcast_to(e[..., -1:, None], (b, hv, n, 1, dv))
    out = ((heads(qc) * e[..., None]).astype(dt),
           (kv_heads * tail[..., None]).astype(dt),
           (qk * decay).astype(dt), u.astype(dt), w.astype(dt), erow)
    return tuple(x.reshape((b * hv,) + x.shape[2:]) for x in out)


# ---- the pass over chunks: lax.scan -----------------------------------------

def _chunk_pass_scan(qe, kd, m, u, w, erow):
    """o [BH, N, C, dv] of the chunk pass, the state carried by
    lax.scan: the same arithmetic, operand dtypes and accumulators as the
    kernel's body, differentiated by jax."""
    dt = qe.dtype

    def step(s, xs):
        qe, kd, m, u, w, er = xs
        sd = s.astype(dt)
        vp = u.astype(_F32) - jnp.einsum("bck,bkv->bcv", w, sd,
                                         preferred_element_type=_F32)
        vpd = vp.astype(dt)
        o = jnp.einsum("bck,bkv->bcv", qe, sd, preferred_element_type=_F32) \
            + jnp.einsum("bij,bjv->biv", m, vpd, preferred_element_type=_F32)
        s = er * s + jnp.einsum("bck,bcv->bkv", kd, vpd,
                                preferred_element_type=_F32)
        return s, o.astype(dt)

    bh, _, _, dk = qe.shape
    _, o = lax.scan(step, jnp.zeros((bh, dk, u.shape[-1]), _F32),
                    tuple(jnp.moveaxis(x, 1, 0)
                          for x in (qe, kd, m, u, w, erow)))
    return jnp.moveaxis(o, 0, 1)


# ---- the pass over chunks: Pallas -------------------------------------------

def _fwd_kernel(qe_ref, kd_ref, m_ref, u_ref, w_ref, e_ref, *rest, hb, emit):
    """One grid step: `hb` heads' chunk n. With `emit` the state that enters
    the chunk is written too (the backward pass's), and o is not."""
    out_ref, s_scr = rest
    dt = qe_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = jnp.zeros(s_scr.shape, _F32)

    for h in range(hb):
        s = s_scr[h]                                         # [dk, dv]
        sd = s.astype(dt)
        if emit:
            out_ref[h, 0] = sd
        vp = (u_ref[h, 0].astype(_F32)
              - _dot(w_ref[h, 0], sd, _NN)).astype(dt)       # [C, dv]
        if not emit:
            out_ref[h, 0] = (_dot(qe_ref[h, 0], sd, _NN)
                             + _dot(m_ref[h, 0], vp, _NN)
                             ).astype(out_ref.dtype)
        s_scr[h] = e_ref[h, 0] * s + _dot(kd_ref[h, 0], vp, _TN)


def _bwd_kernel(qe_ref, kd_ref, m_ref, u_ref, w_ref, e_ref, s_ref, do_ref,
                dqe_ref, dkd_ref, dm_ref, du_ref, dw_ref, de_ref, ds_scr, *,
                hb):
    """One grid step of the reverse pass: `hb` heads' chunk N - 1 - n, dS
    (the cotangent of the state that LEAVES the chunk) in scratch."""
    dt = qe_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[:] = jnp.zeros(ds_scr.shape, _F32)

    for h in range(hb):
        sd = s_ref[h, 0]                                     # [dk, dv], dt
        ds = ds_scr[h]                                       # float32
        dsd = ds.astype(dt)
        do = do_ref[h, 0]
        qe, kd, m, w = qe_ref[h, 0], kd_ref[h, 0], m_ref[h, 0], w_ref[h, 0]
        vp = (u_ref[h, 0].astype(_F32) - _dot(w, sd, _NN)).astype(dt)
        dvp = _dot(m, do, _TN) + _dot(kd, dsd, _NN)          # [C, dv]
        dvpd = dvp.astype(dt)
        dqe_ref[h, 0] = _dot(do, sd, _NT).astype(dqe_ref.dtype)
        dm_ref[h, 0] = _dot(do, vp, _NT).astype(dm_ref.dtype)
        dkd_ref[h, 0] = _dot(vp, dsd, _NT).astype(dkd_ref.dtype)
        du_ref[h, 0] = dvpd.astype(du_ref.dtype)
        dw_ref[h, 0] = (-_dot(dvpd, sd, _NT)).astype(dw_ref.dtype)
        de_ref[h, 0] = jnp.sum(ds * sd.astype(_F32), axis=0, keepdims=True)
        ds_scr[h] = (e_ref[h, 0] * ds + _dot(qe, do, _TN)
                     - _dot(w, dvpd, _TN))


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _specs(arrays, hb, index):
    return [_vmem((hb, 1) + a.shape[2:], index) for a in arrays]


def _block_h(bh):
    """Heads a grid step: the largest divisor of B x Hv up to the table's
    block_h."""
    most = min(bh, kernel_config.DEFAULT_TILES["gdr"]["block_h"])
    return max(d for d in range(1, most + 1) if bh % d == 0)


def _interpret():
    return kernel_config.dispatch_platform() != "tpu"


def _how(ops):
    """The static arguments of the two calls, beside `emit`: what their
    bodies would read from this module and kernel_config, resolved here (a
    trace is kept under its arguments)."""
    return dict(hb=_block_h(ops[0].shape[0]), interpret=_interpret())


# The two calls are jax.jits of their own, everything but the arrays static
# (ops/pallas_import.py has the rule): a model's layers call them at one
# shape, and a step traces each kernel's body once and not once a layer.
@kernel_entry("ptpu_gated_delta_fwd",
              static_argnames=("emit", "hb", "interpret"))
def _fwd_call(ops, *, emit, hb, interpret):
    qe, u = ops[0], ops[3]
    bh, n, chunk, dk = qe.shape
    dv = u.shape[-1]

    def index(i, j):
        return (i, j, 0, 0)

    out_shape = jax.ShapeDtypeStruct(
        (bh, n, dk, dv) if emit else (bh, n, chunk, dv), qe.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, emit=emit),
        # heads on the major axis: a block of heads walks all its chunks
        # before the next block reuses the state scratch
        grid=(bh // hb, n),
        in_specs=_specs(ops, hb, index),
        out_specs=_vmem((hb, 1) + out_shape.shape[2:], index),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        interpret=interpret,
        name="ptpu_gated_delta_fwd",
    )(*ops)


@kernel_entry("ptpu_gated_delta_bwd", static_argnames=("hb", "interpret"))
def _bwd_call(ops, states, do, *, hb, interpret):
    qe, kd, m, u, w, erow = ops
    bh, n, _, dk = qe.shape
    dv = u.shape[-1]

    def index(i, j):                    # chunks from the last to the first
        return (i, n - 1 - j, 0, 0)

    ins = ops + (states, do)
    outs = (qe, kd, m, u, w, erow)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb),
        grid=(bh // hb, n),
        in_specs=_specs(ins, hb, index),
        out_specs=_specs(outs, hb, index),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in outs],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        interpret=interpret,
        name="ptpu_gated_delta_bwd",
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_path(prepare, q, k, v, g, beta):
    ops = prepare(q, k, v, g, beta)
    return _fwd_call(ops, emit=False, **_how(ops))


def _kernel_path_fwd(prepare, q, k, v, g, beta):
    ops, prepare_vjp = jax.vjp(prepare, q, k, v, g, beta)
    return _fwd_call(ops, emit=False, **_how(ops)), (ops, prepare_vjp)


def _kernel_path_bwd(prepare, res, do):
    ops, prepare_vjp = res
    # behind one barrier with the cotangent, so that XLA does not write the
    # states as soon as the operands exist and hold them from the forward
    # pass to here (pallas_kernels._wait_for has the finding)
    do, ops = lax.optimization_barrier((do, ops))
    how = _how(ops)
    states = _fwd_call(ops, emit=True, **how)
    grads = _bwd_call(ops, states, do.astype(ops[0].dtype), **how)
    return prepare_vjp(tuple(grads))


_kernel_path.defvjp(_kernel_path_fwd, _kernel_path_bwd)


def gated_delta_rule(q, k, v, g, beta, operand_dtype=None, path="kernel",
                     chunk=None):
    """o [B, T, Hv, dv] of the gated delta rule (module docstring) for q, k
    [B, T, Hk, dk], v [B, T, Hv, dv] with Hk dividing Hv, g (the log decay,
    <= 0) and beta [B, T, Hv]. q and k are l2-normalised over dk first
    (1e-6 inside the root) and q multiplied by dk^-0.5. The result comes
    back in v's dtype.

    path "kernel": the Pallas kernels (Mosaic where the program dispatches
    to a TPU, the interpreter elsewhere); "scan": lax.scan over chunks.
    chunk (16, 32, 64 or 128) defaults to kernel_config.DEFAULT_TILES["gdr"],
    which also has the heads a grid step."""
    b, t, hk, dk = q.shape
    if k.shape != q.shape or v.shape[:2] != (b, t) or v.shape[2] % hk \
            or g.shape != v.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated_delta_rule: q and k [B, T, Hk, dk] alike, v [B, T, Hv, "
            "dv] with Hk dividing Hv, g and beta [B, T, Hv]; got q %s, k %s, "
            "v %s, g %s, beta %s" % (q.shape, k.shape, v.shape, g.shape,
                                     beta.shape))
    if path not in ("kernel", "scan"):
        raise ValueError("gated_delta_rule: path must be 'kernel' or 'scan', "
                         "got %r" % (path,))
    if chunk is None:
        chunk = kernel_config.DEFAULT_TILES["gdr"]["chunk"]
    if chunk not in (16, 32, 64, 128):
        raise ValueError("gated_delta_rule: chunk must be 16, 32, 64 or 128, "
                         "got %r" % (chunk,))
    prepare = functools.partial(
        _prepare, chunk=int(chunk),
        dt=jnp.dtype(q.dtype if operand_dtype is None else operand_dtype))
    if path == "scan":
        o = _chunk_pass_scan(*prepare(q, k, v, g, beta))
    else:
        o = _kernel_path(prepare, q, k, v, g, beta)
    hv, dv = v.shape[2], v.shape[3]
    o = o.reshape(b, hv, -1, dv)[:, :, :t]
    return jnp.moveaxis(o, 1, 2).astype(v.dtype)
