"""Kimi Delta Attention's delta rule, chunked: the gated delta rule
(gated_delta_kernels.py) with the decay a key CHANNEL's and not a head's
(Kimi Linear, arXiv:2510.26692; the mixer of five layers in six of
Ling-3.0-flash). A head's recurrence over tokens, g_t [d_k] <= 0 its log
decay a channel and beta_t in (0, 1) its write strength:

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          S_0 = 0, [d_k, d_v]
    o_t = S_t^T q_t

computed C tokens at a time. With c [C, d_k] the running sum of g inside a
chunk, S the state that enters it, i and j positions inside it:

    L_ij = beta_i sum_d k_id k_jd exp(c_id - c_jd)   for j < i, else 0
    M_ij = sum_d q_id k_jd exp(c_id - c_jd)          for j <= i, else 0
    T = (I + L)^-1
    U = T (beta * V),  W = T (beta * exp(c) * K)
    V' = U - W S
    O = (exp(c) * Q) S + M V'
    S <- Diag(exp(c_C)) S + (exp(c_C - c) * K)^T V'

the gated delta rule's own chunk pass but for the state's decay, which is a
ROW's: the kernels keep the state TRANSPOSED, S^T [d_v, d_k], so that the
decay is one tile row [1, d_k] broadcast over the sublanes, and their
products are the same six with the state's axes swapped.

With a scalar decay exp(c_i - c_j) leaves the dot product. With a channel's
it does not, and L and M are products of K * exp(c - r) against K * exp(r -
c) for some reference r [d_k]: exp(r - c) over a whole chunk of 64 would
reach e^320. So the reference is a 16-row BLOCK's: block row I (rows 16 I ..
16 I + 15) takes r_I = c at its first row. Its own factor exp(c_i - r_I) is
<= 1; the other, exp(r_I - c_j), is <= 1 for a j of an earlier block and
<= exp(15 x |g|max) inside the block, finite in float32 (and in bfloat16,
whose exponent is float32's) while a token's log decay stays above -5.9 a
channel (15 x 5.9 = 88.5 < 88.7 = ln of float32's largest): Ling's
`kda_lower_bound` of -5 is what makes the rule computable this way, and a
decay past it gives inf and then NaN, not a wrong number. Columns of later
blocks are exp(-inf) = 0. The 16 is `_inverse`'s own block.

What no chunk needs another for (`_prepare`: the l2 norms, the running sums
and their exponentials, L, M, T, U, W) is jax.numpy, batched over all chunks
at once and differentiated by jax; the decayed products L and M stand under
jax.checkpoint (their factors are [S, C, d_k] a chunk, four times K: the
backward pass makes them again from K, Q and c). (I + L)^-1 is
gated_delta_kernels' `unit_lower_inverse`. The pass over chunks is the
Pallas kernel `ptpu_kda_fwd`, grid (batch x heads / block_h, T / C) with
S^T in VMEM scratch, and its reverse, which carries dS^T, `ptpu_kda_bwd`.
`path="scan"` is the same chunked form with `lax.scan` over chunks, forward
and backward XLA's own: what runs where the kernels are off (the CPU by
default), and what the kernels are measured against.

Precision is gated_delta_kernels': g, beta, the running sums, every
exponential, (I + L)^-1, the state and every accumulator are float32; a
matmul takes its operands in `operand_dtype` (bf16 under AMP, else the
inputs' float32) and accumulates in float32. Memory is not: the backward
pass runs the forward kernel once more to write the state that enters
every chunk, as there, but `_prepare` as a whole stands under
jax.checkpoint, so what crosses from the forward to the backward pass is
the op's five inputs and the chunk pass's six operands, and the backward
pass prepares the chunks again before it transposes them. With a decay a
channel the float32 residuals of `_prepare` are [C, dk] a chunk where the
scalar rule's are [C]: six layers of Ling-3.0-flash's cell kept 0.56 GiB of
them and the compiled step read 15.43 GiB, 14.87 with the replay (AOT
compile, PR 71).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config
from .gated_delta_kernels import (_F32, _INVERSE_BASE, _NN, _NT, _TN, _dot,
                                  _l2norm, _specs, _vmem,
                                  unit_lower_inverse)
from .gated_delta_kernels import _chunk_pass_scan as _scan_pass

__all__ = ["kda_delta_rule", "SUB_BLOCK"]

SUB_BLOCK = _INVERSE_BASE       # rows that share one reference for exp(r - c)


# ---- what no chunk needs another for ----------------------------------------

@functools.partial(jax.checkpoint, static_argnums=(3,))
def _decayed_products(qc, kc, c, dt):
    """(sum_d k_id k_jd exp(c_id - c_jd), sum_d q_id k_jd exp(c_id - c_jd))
    [.., C, C] float32 for j in block row i's own block or an earlier one
    (0 in later blocks; inside the own block also above the diagonal, where
    the caller masks), from qc, kc, c [.., C, dk] float32, the matmuls'
    operands in `dt`. Module docstring: a block row's reference is c at its
    first row."""
    chunk, dk = c.shape[-2:]
    sub = min(SUB_BLOCK, chunk)
    blocks = chunk // sub
    lead = c.shape[:-2]

    def rows(x):                        # [.., C, dk] -> [.., S, sub, dk]
        return x.reshape(lead + (blocks, sub, dk))

    ref = rows(c)[..., :1, :]                               # [.., S, 1, dk]
    own = jnp.exp(rows(c) - ref)                            # <= 1
    earlier = lax.broadcasted_iota(jnp.int32, (blocks, chunk), 1) // sub \
        <= lax.broadcasted_iota(jnp.int32, (blocks, chunk), 0)
    other = jnp.exp(jnp.where(earlier[..., None],
                              ref - c[..., None, :, :], -jnp.inf))
    kb = (kc[..., None, :, :] * other).astype(dt)           # [.., S, C, dk]
    return tuple(
        jnp.einsum("...sid,...sjd->...sij", (rows(x) * own).astype(dt), kb,
                   preferred_element_type=_F32).reshape(
                       lead + (chunk, chunk))
        for x in (kc, qc))


def _prepare(q, k, v, g, beta, *, chunk, dt):
    """q, k [B, T, H, dk], v [B, T, H, dv], g [B, T, H, dk], beta [B, T, H]
    -> the chunk pass's operands, each [B x H, N, ...] with N = ceil(T /
    chunk): qe = exp(c) * q and kd = exp(c_C - c) * k [.., C, dk], m [.., C,
    C], u [.., C, dv], w [.., C, dk] in `dt`, and erow = exp(c_C) [.., 1, dk]
    float32, the chunk's whole decay a channel: one tile row of the
    transposed state. q and k are l2-normalised over dk and q multiplied by
    dk^-0.5. Positions past T are padded with beta = g = 0: they write
    nothing and decay nothing."""
    b, t, h, dk = q.shape
    n = -(-t // chunk)

    def chunks(x):                      # [B, T, H, ...] -> [B, H, N, C, ...]
        # in x's dtype: the transposes move bf16, the convert fuses after
        x = jnp.pad(x, [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)
        if x.dtype != _F32:             # or XLA hoists the convert back
            x = lax.optimization_barrier(x)
        return x.astype(_F32)

    qc, kc = _l2norm(chunks(q)) * dk ** -0.5, _l2norm(chunks(k))
    vc, gc, bc = chunks(v), chunks(g), chunks(beta)
    c = jnp.cumsum(gc, -2)                                  # [B, H, N, C, dk]
    e, tail = jnp.exp(c), jnp.exp(c[..., -1:, :] - c)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    kk, qk = _decayed_products(qc, kc, c, dt)
    low = jnp.where(row > col, bc[..., None] * kk, 0.0)
    tm = unit_lower_inverse(low).astype(dt)
    u, w = (jnp.einsum("bhnij,bhnjd->bhnid", tm, x.astype(dt),
                       preferred_element_type=_F32)
            for x in (bc[..., None] * vc, bc[..., None] * e * kc))
    out = ((qc * e).astype(dt), (kc * tail).astype(dt),
           jnp.where(row >= col, qk, 0.0).astype(dt), u.astype(dt),
           w.astype(dt), e[..., -1:, :])
    return tuple(x.reshape((b * h,) + x.shape[2:]) for x in out)


# ---- the pass over chunks: lax.scan -----------------------------------------

def _chunk_pass_scan(qe, kd, m, u, w, erow):
    """o [BH, N, C, dv] of the chunk pass, the state carried by lax.scan:
    gated_delta_kernels' own scan (the state as S [dk, dv]: a transpose
    changes no sum), given the chunk's decay as a COLUMN [dk, 1] where the
    scalar rule gives a row [1, dv]; the same arithmetic, operand dtypes and
    accumulators as the kernel's body, differentiated by jax."""
    return _scan_pass(qe, kd, m, u, w, jnp.swapaxes(erow, 2, 3))


# ---- the pass over chunks: Pallas -------------------------------------------

def _fwd_kernel(qe_ref, kd_ref, m_ref, u_ref, w_ref, e_ref, *rest, hb, emit):
    """One grid step: `hb` heads' chunk n, the state transposed, S^T [dv,
    dk]. With `emit` the state that enters the chunk is written too (the
    backward pass's), and o is not."""
    out_ref, s_scr = rest
    dt = qe_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = jnp.zeros(s_scr.shape, _F32)

    for h in range(hb):
        s = s_scr[h]                                         # [dv, dk]
        sd = s.astype(dt)
        if emit:
            out_ref[h, 0] = sd
        vp = (u_ref[h, 0].astype(_F32)
              - _dot(w_ref[h, 0], sd, _NT)).astype(dt)       # [C, dv]
        if not emit:
            out_ref[h, 0] = (_dot(qe_ref[h, 0], sd, _NT)
                             + _dot(m_ref[h, 0], vp, _NN)
                             ).astype(out_ref.dtype)
        s_scr[h] = e_ref[h, 0] * s + _dot(vp, kd_ref[h, 0], _TN)


def _bwd_kernel(qe_ref, kd_ref, m_ref, u_ref, w_ref, e_ref, s_ref, do_ref,
                dqe_ref, dkd_ref, dm_ref, du_ref, dw_ref, de_ref, ds_scr, *,
                hb):
    """One grid step of the reverse pass: `hb` heads' chunk N - 1 - n, dS^T
    (the cotangent of the state that LEAVES the chunk) in scratch."""
    dt = qe_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[:] = jnp.zeros(ds_scr.shape, _F32)

    for h in range(hb):
        sd = s_ref[h, 0]                                     # [dv, dk], dt
        ds = ds_scr[h]                                       # float32
        dsd = ds.astype(dt)
        do = do_ref[h, 0]
        qe, kd, m, w = qe_ref[h, 0], kd_ref[h, 0], m_ref[h, 0], w_ref[h, 0]
        vp = (u_ref[h, 0].astype(_F32) - _dot(w, sd, _NT)).astype(dt)
        dvp = _dot(m, do, _TN) + _dot(kd, dsd, _NT)          # [C, dv]
        dvpd = dvp.astype(dt)
        dqe_ref[h, 0] = _dot(do, sd, _NN).astype(dqe_ref.dtype)
        dm_ref[h, 0] = _dot(do, vp, _NT).astype(dm_ref.dtype)
        dkd_ref[h, 0] = _dot(vp, dsd, _NN).astype(dkd_ref.dtype)
        du_ref[h, 0] = dvpd.astype(du_ref.dtype)
        dw_ref[h, 0] = (-_dot(dvpd, sd, _NN)).astype(dw_ref.dtype)
        de_ref[h, 0] = jnp.sum(ds * sd.astype(_F32), axis=0, keepdims=True)
        ds_scr[h] = (e_ref[h, 0] * ds + _dot(do, qe, _TN)
                     - _dot(dvpd, w, _TN))


def _block_h(bh):
    """Heads a grid step: the largest divisor of B x H up to the table's
    block_h."""
    most = min(bh, kernel_config.DEFAULT_TILES["kda"]["block_h"])
    return max(d for d in range(1, most + 1) if bh % d == 0)


def _how(ops):
    """The static arguments of the two calls, beside `emit`: what their
    bodies would read from this module and kernel_config, resolved here (a
    trace is kept under its arguments)."""
    return dict(hb=_block_h(ops[0].shape[0]),
                interpret=kernel_config.dispatch_platform() != "tpu")


# The two calls are jax.jits of their own, everything but the arrays static
# (ops/pallas_import.py has the rule).
@kernel_entry("ptpu_kda_fwd", static_argnames=("emit", "hb", "interpret"))
def _fwd_call(ops, *, emit, hb, interpret):
    qe, u = ops[0], ops[3]
    bh, n, chunk, dk = qe.shape
    dv = u.shape[-1]

    def index(i, j):
        return (i, j, 0, 0)

    out_shape = jax.ShapeDtypeStruct(
        (bh, n, dv, dk) if emit else (bh, n, chunk, dv), qe.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, emit=emit),
        # heads on the major axis: a block of heads walks all its chunks
        # before the next block reuses the state scratch
        grid=(bh // hb, n),
        in_specs=_specs(ops, hb, index),
        out_specs=_vmem((hb, 1) + out_shape.shape[2:], index),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        interpret=interpret,
        name="ptpu_kda_fwd",
    )(*ops)


@kernel_entry("ptpu_kda_bwd", static_argnames=("hb", "interpret"))
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
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],
        interpret=interpret,
        name="ptpu_kda_bwd",
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


def kda_delta_rule(q, k, v, g, beta, operand_dtype=None, path="kernel",
                   chunk=None):
    """o [B, T, H, dv] of the delta rule with a decay a key channel (module
    docstring) for q, k [B, T, H, dk], v [B, T, H, dv], g [B, T, H, dk] (the
    log decay, in (-5.9, 0] a token and channel) and beta [B, T, H]. q and k
    are l2-normalised over dk first (1e-6 inside the root) and q multiplied
    by dk^-0.5. The result comes back in v's dtype.

    path "kernel": the Pallas kernels (Mosaic where the program dispatches
    to a TPU, the interpreter elsewhere); "scan": lax.scan over chunks.
    chunk (16, 32, 64 or 128) defaults to kernel_config.DEFAULT_TILES["kda"],
    which also has the heads a grid step. A T that is no multiple of the
    chunk is padded, as gated_delta_rule pads it."""
    b, t, h, dk = q.shape
    if k.shape != q.shape or v.shape[:3] != (b, t, h) or g.shape != q.shape \
            or beta.shape != (b, t, h):
        raise ValueError(
            "kda_delta_rule: q, k and g [B, T, H, dk] alike, v [B, T, H, dv], "
            "beta [B, T, H]; got q %s, k %s, v %s, g %s, beta %s"
            % (q.shape, k.shape, v.shape, g.shape, beta.shape))
    if path not in ("kernel", "scan"):
        raise ValueError("kda_delta_rule: path must be 'kernel' or 'scan', "
                         "got %r" % (path,))
    if chunk is None:
        chunk = kernel_config.DEFAULT_TILES["kda"]["chunk"]
    if chunk not in (16, 32, 64, 128):
        raise ValueError("kda_delta_rule: chunk must be 16, 32, 64 or 128, "
                         "got %r" % (chunk,))
    prepare = jax.checkpoint(functools.partial(
        _prepare, chunk=int(chunk),
        dt=jnp.dtype(q.dtype if operand_dtype is None else operand_dtype)))
    if path == "scan":
        o = _chunk_pass_scan(*prepare(q, k, v, g, beta))
    else:
        o = _kernel_path(prepare, q, k, v, g, beta)
    dv = v.shape[3]
    o = o.reshape(b, h, -1, dv)[:, :, :t]
    return jnp.moveaxis(o, 1, 2).astype(v.dtype)
