"""Manifold-constrained hyper-connections (arXiv:2512.24880 over
arXiv:2409.19606): a residual path of n streams, X [N, n*C] a token's n
vectors of C side by side, around a sub-layer F.

    x' = x / sqrt(mean(x^2) + eps)                  x = vec(X), float32
    z  = x' Phi                                     Phi [n*C, n*n + 2n]
    Ht = alpha * z + b                              alpha by group
    H_pre = sigmoid(Ht[:n])   H_post = 2 sigmoid(Ht[n:2n])
    H_res = SK(clip(Ht[2n:]))                       n x n, doubly stochastic
    h = sum_i H_pre[i] X[i]      y = F(N(h))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

SK(A): M = exp(A), then `iters` times M /= colsum(M) + eps, M /= rowsum(M)
+ eps. The stream is n times as wide as anything else a layer moves and
every one of these steps is bound by its bytes, so each pass over a stream
array is one Pallas kernel that reads and writes every element once:

  ptpu_mhc_pre_fwd   reads X; writes h and z (with the RMS scale r)
  ptpu_mhc_post_fwd  reads X, y; writes X'
  ptpu_mhc_post_bwd  reads dX', X, y; writes dX (the mixing's part), dy and
                     the row dots <dX'[i], X[j]>, <dX'[i], y>
  ptpu_mhc_pre_bwd   reads dX (that part), X, dh; writes the whole dX, with
                     the row dots <dh, X[i]> taken while the rows are in
                     VMEM
  ptpu_mhc_expand    x -> n copies (the streams' start; the readout's
                     backward)
  ptpu_mhc_reduce    sum_i X[i] (the readout; the start's backward)

What a token's 24 numbers go through between z and the coefficients
(sigmoids, clip, exp, the Sinkhorn steps) is two kernels more, bound by
nothing but the VPU: ptpu_mhc_coeffs_fwd and ptpu_mhc_coeffs_bwd, on [K, 8,
N / 8] with a coefficient of 1024 tokens one vector register, every step
elementwise; the backward replays the steps from z and differentiates
them as written. As jax.numpy on [K, N] the same arithmetic is some 3,000
small XLA fusions a step forward and backward, 67 ms of a 264 ms step on
the v5e (my chip run, PR 43); `coefficients_plain` is that, for the plain
path. dPhi = X^T (r dz) is an XLA dot. All of it float32, whatever the
stream's dtype: z = x' Phi takes x as it is stored and, where
that is bfloat16, Phi as two bfloat16 parts (Phi = hi + lo, side by side in
the 128 lanes the MXU pads 24 columns to anyway), so no bit of the float32
parameter is dropped.

`pre` passes the stream through (its third result): `post` reads that, so
the stream has one consumer and the two parts of its gradient meet inside
ptpu_mhc_pre_bwd and not in a pass of XLA's.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["pre", "post", "expand", "reduce", "coefficients",
           "coefficients_plain", "applies", "columns", "KERNELS"]

KERNELS = ("ptpu_mhc_pre_fwd", "ptpu_mhc_pre_bwd", "ptpu_mhc_post_fwd",
           "ptpu_mhc_post_bwd", "ptpu_mhc_expand", "ptpu_mhc_reduce",
           "ptpu_mhc_coeffs_fwd", "ptpu_mhc_coeffs_bwd")
_F32 = jnp.float32
LANES = 128
# a block of rows of three stream arrays, each in two buffers, and the
# float32 copies of a stream's [rows, C] the arithmetic makes: over Mosaic's
# default 16 MiB a core at n*C = 14336; the v5e's VMEM is 128 MiB
_VMEM_LIMIT = 96 << 20


def columns(n):
    """A token's coefficients: n for H_pre, n for H_post, n*n for H_res, in
    that order, H_res by rows."""
    return n * n + 2 * n


def applies(rows, n, c):
    """Do the kernels' blocks divide a stream [rows, n*c]? Whole lane tiles
    a stream, whole sublane tiles of rows, and the coefficients and the
    scale in one row of lanes."""
    return c % LANES == 0 and rows % 8 == 0 and columns(n) + 1 + n <= LANES


def _block_rows(rows):
    most = kernel_config.DEFAULT_TILES["mhc"]["block_rows"]
    return max(r for r in range(8, min(rows, most) + 1, 8) if rows % r == 0)


def _by_column(alpha, n):
    """alpha [3] (pre, post, res) -> [K], a value a column."""
    return jnp.repeat(alpha.astype(_F32), np.array([n, n, n * n]),
                      total_repeat_length=columns(n))


def sinkhorn(a, iters, eps):
    """a [n, n, N] float32 -> exp(a) after `iters` steps of: divide every
    column by its sum + eps, then every row by its sum + eps (the paper's
    T_r(T_c(.))). The sums over the n x n axes are written out as adds of
    slices: with the tokens in the lanes every step is elementwise."""
    n = a.shape[0]

    def step(m, _):
        m = m / (sum(m[i] for i in range(n)) + eps)
        m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
        return m, None

    return lax.scan(step, jnp.exp(a), None, length=iters,
                    unroll=max(1, iters))[0]


def coefficients_plain(z, alpha, bias, n, iters, eps, clamp):
    """z [N, >= K] float32, the normalised projection -> [N, 128] float32:
    columns [0, n) H_pre, [n, 2n) H_post, [2n, K) H_res by rows, zeros
    after. Under jax.checkpoint: its backward replays the Sinkhorn steps
    from z and stores none of them."""
    k = columns(n)

    @jax.checkpoint
    def run(z, alpha, bias):
        ht = _by_column(alpha, n)[:, None] * z[:, :k].T.astype(_F32) \
            + bias.astype(_F32)[:, None]                      # [K, N]
        res = sinkhorn(jnp.clip(ht[2 * n:], clamp[0], clamp[1]).reshape(
            n, n, -1), iters, eps)
        out = jnp.concatenate([jax.nn.sigmoid(ht[:n]),
                               2.0 * jax.nn.sigmoid(ht[n:2 * n]),
                               res.reshape(n * n, -1)])
        return jnp.pad(out.T, [(0, 0), (0, LANES - k)])

    return run(z, alpha, bias)


# ---------------------------------------------------------------------------
# the plain path: jax.numpy, differentiated by jax (the CPU, a mesh, shapes
# the blocks do not divide)
# ---------------------------------------------------------------------------

def _streams(x, n):
    return x.reshape(x.shape[0], n, -1)


def pre_plain(x, phi, alpha, bias, n, iters, eps, clamp):
    x32 = x.astype(_F32)
    z = jnp.dot(x32, phi.astype(_F32), precision=lax.Precision.HIGHEST) \
        * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    coef = coefficients_plain(z, alpha, bias, n, iters, eps, clamp)
    h = jnp.einsum("ti,tic->tc", coef[:, :n], _streams(x, n).astype(_F32))
    return h.astype(x.dtype), coef, x


def post_plain(x, y, coef, n):
    xs = _streams(x, n).astype(_F32)
    res = coef[:, 2 * n:columns(n)].reshape(-1, n, n)
    out = jnp.einsum("tij,tjc->tic", res, xs) \
        + coef[:, n:2 * n, None] * y.astype(_F32)[:, None]
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _call(kernel, name, rows, in_widths, out_shapes, block, interpret):
    """pallas_call over blocks of `block` rows: every operand [rows, width]
    is cut into [block, width]; an operand given as (shape,) instead of a
    width is whole in every step (a parameter)."""

    def spec(width):
        if isinstance(width, tuple):
            return pl.BlockSpec(width, lambda i: (0,) * len(width),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((block, width), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel, grid=(rows // block,),
        in_specs=[spec(w) for w in in_widths],
        out_specs=[spec(s.shape[1]) for s in out_shapes],
        out_shape=out_shapes, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel",)))


def _dot(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _lane(rows):
    return lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _h_pre(ht):
    """H_pre from Ht, [rows, 128] with a token's coefficients in the lanes
    (the first n are H_pre's): both stream kernels of `pre` make it here."""
    return _sigmoid(ht)


def _pre_fwd_kernel(x_ref, phi_ref, prm_ref, h_ref, z_ref, *, n, c, eps,
                    split):
    """z = x' Phi with r in column K, and h = sum_i sigmoid(a z_i + b) X_i.
    prm_ref rows 0 and 1: alpha and b by column."""
    rows, k = x_ref.shape[0], columns(n)
    ss = jnp.zeros((rows, 1), _F32)
    proj = jnp.zeros((rows, LANES), _F32)
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c]
        x32 = xi.astype(_F32)
        ss += jnp.sum(x32 * x32, axis=-1, keepdims=True)
        proj += _dot(xi, phi_ref[i * c:(i + 1) * c, :])
    if split:           # columns [K, 2K) hold x Phi_lo: add them to [0, K)
        proj = proj + pltpu.roll(proj, LANES - k, 1)
    r = lax.rsqrt(ss / (n * c) + eps)
    lane = _lane(rows)
    z = jnp.where(lane < k, proj * r, 0.0)
    z_ref[...] = jnp.where(lane == k, r, z)
    hp = _h_pre(z * prm_ref[0:1, :] + prm_ref[1:2, :])
    h = hp[:, 0:1] * x_ref[:, 0:c].astype(_F32)
    for i in range(1, n):
        h += hp[:, i:i + 1] * x_ref[:, i * c:(i + 1) * c].astype(_F32)
    h_ref[...] = h.astype(h_ref.dtype)


def _pre_bwd_kernel(x_ref, dxc_ref, dh_ref, z_ref, dzr_ref, prm_ref,
                    phit_ref, dx_ref, g_ref, *, n, c):
    """dX = dXc + H_pre[i] dh + r (dz Phi^T) - (r^2 <dz, z> / nC) x, with dz
    = dzr + alpha_pre dHt_pre and dHt_pre = <dh, X_i> H_pre (1 - H_pre) made
    here. g: dz in columns [0, K), dHt_pre in [K + 1, K + 1 + n)."""
    rows, k = x_ref.shape[0], columns(n)
    z = z_ref[...]
    r = z[:, k:k + 1]
    lane = _lane(rows)
    hp = _h_pre(z * prm_ref[0:1, :] + prm_ref[1:2, :])
    dh = dh_ref[...].astype(_F32)
    dhp = jnp.zeros((rows, LANES), _F32)
    for i in range(n):
        dot = jnp.sum(dh * x_ref[:, i * c:(i + 1) * c].astype(_F32),
                      axis=-1, keepdims=True)
        dhp = jnp.where(lane == i, dot, dhp)
    dht = dhp * hp * (1.0 - hp)
    dz = dzr_ref[...] + dht * prm_ref[0:1, :]
    back = r * r * jnp.sum(jnp.where(lane < k, dz * z, 0.0), axis=-1,
                           keepdims=True) / (n * c)
    dzs = (dz * r).astype(phit_ref.dtype)
    g = dz
    for i in range(n):
        sl = slice(i * c, (i + 1) * c)
        dx = dxc_ref[:, sl].astype(_F32) + hp[:, i:i + 1] * dh \
            + _dot(dzs, phit_ref[:, sl]) - back * x_ref[:, sl].astype(_F32)
        dx_ref[:, sl] = dx.astype(dx_ref.dtype)
        g = jnp.where(lane == k + 1 + i, dht[:, i:i + 1], g)
    g_ref[...] = g


def _post_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, *, n, c):
    coef = coef_ref[...]
    y = y_ref[...].astype(_F32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(_F32) for j in range(n)]
    for i in range(n):
        out = coef[:, n + i:n + i + 1] * y
        for j in range(n):
            col = 2 * n + i * n + j
            out += coef[:, col:col + 1] * xs[j]
        out_ref[:, i * c:(i + 1) * c] = out.astype(out_ref.dtype)


def _post_bwd_kernel(d_ref, x_ref, y_ref, coef_ref, dx_ref, dy_ref, dc_ref,
                     *, n, c):
    """dXc[j] = sum_i H_res[i, j] dX'[i], dy = sum_i H_post[i] dX'[i], and
    in dc the dots <dX'[i], y> (columns [n, 2n)) and <dX'[i], X[j]>."""
    rows = d_ref.shape[0]
    coef = coef_ref[...]
    lane = _lane(rows)
    y = y_ref[...].astype(_F32)
    ds = [d_ref[:, i * c:(i + 1) * c].astype(_F32) for i in range(n)]
    dc = jnp.zeros((rows, LANES), _F32)
    dy = coef[:, n:n + 1] * ds[0]
    for i in range(n):
        if i:
            dy += coef[:, n + i:n + i + 1] * ds[i]
        dc = jnp.where(lane == n + i,
                       jnp.sum(ds[i] * y, axis=-1, keepdims=True), dc)
    dy_ref[...] = dy.astype(dy_ref.dtype)
    for j in range(n):
        xj = x_ref[:, j * c:(j + 1) * c].astype(_F32)
        dx = coef[:, 2 * n + j:2 * n + j + 1] * ds[0]
        for i in range(n):
            col = 2 * n + i * n + j
            if i:
                dx += coef[:, col:col + 1] * ds[i]
            dc = jnp.where(lane == col,
                           jnp.sum(ds[i] * xj, axis=-1, keepdims=True), dc)
        dx_ref[:, j * c:(j + 1) * c] = dx.astype(dx_ref.dtype)
    dc_ref[...] = dc


def _expand_kernel(x_ref, out_ref, *, n, c):
    for i in range(n):
        out_ref[:, i * c:(i + 1) * c] = x_ref[...]


def _reduce_kernel(x_ref, out_ref, *, n, c):
    total = x_ref[:, 0:c].astype(_F32)
    for i in range(1, n):
        total += x_ref[:, i * c:(i + 1) * c].astype(_F32)
    out_ref[...] = total.astype(out_ref.dtype)


# --- a token's coefficients, the tokens in sublanes and lanes ----------------

def _tiles(a, kp):
    """[N, >= kp] -> [kp, 8, N / 8]: a coefficient's tokens as whole vector
    registers."""
    return a[:, :kp].T.reshape(kp, 8, -1)


def _untiles(t):
    """`_tiles`' inverse, to [N, 128] with zeros after the kp columns."""
    return jnp.pad(t.reshape(t.shape[0], -1).T,
                   [(0, 0), (0, LANES - t.shape[0])])


def _normalise(m, eps, rows):
    """One half of a Sinkhorn step on the n x n tiles m (by rows): every row
    (or column) divided by its sum + eps. Returns (m', the n reciprocals)."""
    n = int(len(m) ** 0.5)
    at = (lambda a, b: a * n + b) if rows else (lambda a, b: b * n + a)
    inv = [1.0 / (sum(m[at(a, b)] for b in range(n)) + eps)
           for a in range(n)]
    out = [None] * (n * n)
    for a in range(n):
        for b in range(n):
            out[at(a, b)] = m[at(a, b)] * inv[a]
    return tuple(out), inv


def _coeffs_forward(prm_ref, z_ref, out_ref, n, iters, eps, clamp,
                    keep=None):
    """out[k] of z[k], [8, L] tiles; prm_ref (SMEM) rows alpha and b by
    column. The Sinkhorn steps are one loop, traced once. `keep` (m_ref [2
    iters, n n, 8, L], inv_ref [2 iters, n, 8, L]) is given every
    half-step's result and reciprocals, for the backward kernel. Returns
    Ht's tiles."""
    ht = [z_ref[k] * prm_ref[0, k] + prm_ref[1, k] for k in range(columns(n))]
    for i in range(n):
        out_ref[i] = _sigmoid(ht[i])
        out_ref[n + i] = 2.0 * _sigmoid(ht[n + i])

    def step(it, m):
        for half, rows in enumerate((False, True)):     # columns, then rows
            m, inv = _normalise(m, eps, rows)
            if keep is not None:
                for k in range(n * n):
                    keep[0][2 * it + half, k] = m[k]
                for a in range(n):
                    keep[1][2 * it + half, a] = inv[a]
        return m

    m = lax.fori_loop(0, iters, step, tuple(
        jnp.exp(jnp.clip(ht[2 * n + k], clamp[0], clamp[1]))
        for k in range(n * n)))
    for k in range(n * n):
        out_ref[2 * n + k] = m[k]
    for k in range(columns(n), out_ref.shape[0]):
        out_ref[k] = jnp.zeros(out_ref.shape[1:], _F32)
    return ht


def _coeffs_fwd_kernel(prm_ref, z_ref, out_ref, *, n, iters, eps, clamp):
    _coeffs_forward(prm_ref, z_ref, out_ref, n, iters, eps, clamp)


def _coeffs_bwd_kernel(prm_ref, z_ref, g_ref, out_ref, coef_ref, m_ref,
                       inv_ref, *, n, iters, eps, clamp):
    """dHt[k] from g[k] = dcoef[k]: the forward replayed into scratch (m_ref,
    inv_ref; coef_ref takes its result, which nothing reads), then every
    half-step m' = m / (s + eps) backwards: dm = (dm' - sum(dm' m')) / (s +
    eps), the sum over what s was over."""
    ht = _coeffs_forward(prm_ref, z_ref, coef_ref, n, iters, eps, clamp,
                         (m_ref, inv_ref))
    for i in range(n):
        s = _sigmoid(ht[i])
        out_ref[i] = g_ref[i] * s * (1.0 - s)
        s = _sigmoid(ht[n + i])
        out_ref[n + i] = g_ref[n + i] * 2.0 * s * (1.0 - s)

    def step(back, dm):
        it = iters - 1 - back
        for half, rows in ((1, True), (0, False)):
            at = (lambda a, b: a * n + b) if rows \
                else (lambda a, b: b * n + a)
            out = [None] * (n * n)
            for a in range(n):
                dot = sum(dm[at(a, b)] * m_ref[2 * it + half, at(a, b)]
                          for b in range(n))
                for b in range(n):
                    out[at(a, b)] = (dm[at(a, b)] - dot) \
                        * inv_ref[2 * it + half, a]
            dm = tuple(out)
        return dm

    dm = lax.fori_loop(0, iters, step,
                       tuple(g_ref[2 * n + k] for k in range(n * n)))
    for k in range(n * n):
        a = ht[2 * n + k]
        inside = (a >= clamp[0]) & (a <= clamp[1])
        out_ref[2 * n + k] = jnp.where(inside, dm[k] * jnp.exp(a), 0.0)
    for k in range(columns(n), out_ref.shape[0]):
        out_ref[k] = jnp.zeros(out_ref.shape[1:], _F32)


def _coeffs_call(kernel, name, prm, tiles, n, iters, interpret):
    """A coefficient kernel over blocks of 1024 tokens (all of them where
    they are fewer or no multiple): `tiles` [kp, 8, N / 8] each. Two
    operands make it the backward kernel, with a second result and the
    scratch the replayed steps go to."""
    kp, _, lanes = tiles[0].shape
    block = LANES if lanes % LANES == 0 else lanes
    spec = pl.BlockSpec((kp, 8, block), lambda i: (0, 0, i),
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct(tiles[0].shape, _F32)
    backward = len(tiles) == 2
    return pl.pallas_call(
        kernel, grid=(lanes // block,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [spec] * len(tiles),
        out_specs=[spec] * (1 + backward),
        out_shape=[shape] * (1 + backward),
        scratch_shapes=[pltpu.VMEM((max(2 * iters, 1), n * n, 8, block), _F32),
                        pltpu.VMEM((max(2 * iters, 1), n, 8, block), _F32)]
        if backward else [],
        interpret=interpret, name=name)(prm, *tiles)[0]


def _coeffs_prm(alpha, bias, n):
    return jnp.stack([_by_column(alpha, n), bias.astype(_F32)])


def _kp(n):
    return -(-columns(n) // 8) * 8


# Both are jitted with everything but the arrays static: a model's
# sub-layers are alike, so a step traces each kernel's body once and not
# once a sub-layer (the bodies are some hundred operators on tracers, each a
# jitted call of its own under a trace: 0.5 s a sub-layer on the chip's
# host, and as much again at build time, where shape inference traces them)
@kernel_entry("ptpu_mhc_coeffs_fwd", static_argnums=(3, 4, 5, 6, 7))
def coefficients(z, alpha, bias, n, iters, eps, clamp, interpret):
    """`coefficients_plain` as ptpu_mhc_coeffs_fwd; z [N, 128]."""
    static = dict(n=n, iters=iters, eps=eps, clamp=clamp)
    return _untiles(_coeffs_call(
        functools.partial(_coeffs_fwd_kernel, **static),
        "ptpu_mhc_coeffs_fwd", _coeffs_prm(alpha, bias, n),
        [_tiles(z, _kp(n))], n, iters, interpret))


@kernel_entry("ptpu_mhc_coeffs_bwd", static_argnums=(4, 5, 6, 7, 8))
def coefficients_bwd(z, alpha, bias, dcoef, n, iters, eps, clamp, interpret):
    """(dz [N, 128], dalpha [3], dbias [K]) of `coefficients` under the
    cotangent dcoef [N, 128], by ptpu_mhc_coeffs_bwd."""
    k = columns(n)
    static = dict(n=n, iters=iters, eps=eps, clamp=clamp)
    dht = _coeffs_call(
        functools.partial(_coeffs_bwd_kernel, **static),
        "ptpu_mhc_coeffs_bwd", _coeffs_prm(alpha, bias, n),
        [_tiles(z, _kp(n)), _tiles(dcoef, _kp(n))], n, iters, interpret)
    dht = dht.reshape(dht.shape[0], -1)[:k]                     # [K, N]
    by_column = _by_column(alpha, n)
    group = np.repeat(np.arange(3), [n, n, n * n])
    dalpha = jax.ops.segment_sum(jnp.sum(dht * z[:, :k].T, 1), group, 3)
    return jnp.pad((dht * by_column[:, None]).T,
                   [(0, 0), (0, LANES - k)]), dalpha, jnp.sum(dht, 1)


# ---------------------------------------------------------------------------
# the kernels' path, each pass with its hand-written backward
# ---------------------------------------------------------------------------

def _phi_lanes(phi, dtype):
    """Phi [n*C, K] as the MXU takes it, [n*C, 128] in the stream's dtype:
    in float32 Phi and zeros; in bfloat16 its two parts hi (Phi's upper 16
    bits) and lo = Phi - hi side by side, whose products the kernel adds (x
    is bfloat16 exactly, so x hi + x lo is x Phi to 16 bits of Phi)."""
    k = phi.shape[1]
    phi = phi.astype(_F32)
    if dtype == _F32:
        return jnp.pad(phi, [(0, 0), (0, LANES - k)]), False
    # hi by a mask on the bits, not by a convert: XLA removes a float32 ->
    # bfloat16 -> float32 round trip, and phi - float32(bfloat16(phi)) is
    # then 0 on the chip (PERF.md section 6, PR 41)
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(phi, jnp.uint32) & jnp.uint32(0xFFFF0000),
        _F32)
    hi, lo = hi.astype(dtype), (phi - hi).astype(dtype)
    return jnp.pad(jnp.concatenate([hi, lo], 1),
                   [(0, 0), (0, LANES - 2 * k)]), True


def _prm(alpha, bias, n):
    """[8, 128] float32: row 0 alpha by column, row 1 the bias."""
    return jnp.pad(_coeffs_prm(alpha, bias, n),
                   [(0, 6), (0, LANES - columns(n))])


# The six stream calls, each a jax.jit of its own with everything but the
# arrays static (ops/pallas_import.py has the rule; `block`, the rows a grid
# step, is `_block_rows` of the caller): a model's sub-layers are alike, and
# a step traces each kernel's body once and not once a sub-layer.
@kernel_entry("ptpu_mhc_pre_fwd",
              static_argnames=("n", "eps", "split", "block", "interpret"))
def _pre_fwd_call(x, lanes, prm, *, n, eps, split, block, interpret):
    rows, c = x.shape[0], x.shape[1] // n
    return _call(
        functools.partial(_pre_fwd_kernel, n=n, c=c, eps=eps, split=split),
        "ptpu_mhc_pre_fwd", rows, [n * c, lanes.shape, (8, LANES)],
        [jax.ShapeDtypeStruct((rows, c), x.dtype),
         jax.ShapeDtypeStruct((rows, LANES), _F32)], block, interpret)(
             x, lanes, prm)


@kernel_entry("ptpu_mhc_pre_bwd", static_argnames=("n", "block", "interpret"))
def _pre_bwd_call(x, dxc, dh, z, dzr, prm, phit, *, n, block, interpret):
    rows, c = x.shape[0], x.shape[1] // n
    return _call(
        functools.partial(_pre_bwd_kernel, n=n, c=c), "ptpu_mhc_pre_bwd",
        rows, [n * c, n * c, c, LANES, LANES, (8, LANES), phit.shape],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((rows, LANES), _F32)], block, interpret)(
             x, dxc, dh, z, dzr, prm, phit)


@kernel_entry("ptpu_mhc_post_fwd",
              static_argnames=("n", "block", "interpret"))
def _post_fwd_call(x, y, coef, *, n, block, interpret):
    rows, c = y.shape
    return _call(
        functools.partial(_post_fwd_kernel, n=n, c=c), "ptpu_mhc_post_fwd",
        rows, [n * c, c, LANES], [jax.ShapeDtypeStruct(x.shape, x.dtype)],
        block, interpret)(x, y, coef)[0]


@kernel_entry("ptpu_mhc_post_bwd",
              static_argnames=("n", "block", "interpret"))
def _post_bwd_call(d, x, y, coef, *, n, block, interpret):
    rows, c = y.shape
    return _call(
        functools.partial(_post_bwd_kernel, n=n, c=c), "ptpu_mhc_post_bwd",
        rows, [n * c, n * c, c, LANES],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct((rows, LANES), _F32)], block, interpret)(
             d, x, y, coef)


@kernel_entry("ptpu_mhc_expand", static_argnames=("n", "block", "interpret"))
def _expand_call(x, *, n, block, interpret):
    rows, c = x.shape
    return _call(functools.partial(_expand_kernel, n=n, c=c),
                 "ptpu_mhc_expand", rows, [c],
                 [jax.ShapeDtypeStruct((rows, n * c), x.dtype)], block,
                 interpret)(x)[0]


@kernel_entry("ptpu_mhc_reduce", static_argnames=("n", "block", "interpret"))
def _reduce_call(x, *, n, block, interpret):
    rows, c = x.shape[0], x.shape[1] // n
    return _call(functools.partial(_reduce_kernel, n=n, c=c),
                 "ptpu_mhc_reduce", rows, [n * c],
                 [jax.ShapeDtypeStruct((rows, c), x.dtype)], block,
                 interpret)(x)[0]


def _how(x, n, interpret):
    """The stream calls' static arguments for a stream x [rows, ..]."""
    return dict(n=n, block=_block_rows(x.shape[0]), interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _pre(x, phi, alpha, bias, n, iters, eps, clamp, interpret):
    return _pre_fwd(x, phi, alpha, bias, n, iters, eps, clamp, interpret)[0]


def _pre_fwd(x, phi, alpha, bias, n, iters, eps, clamp, interpret):
    lanes, split = _phi_lanes(phi, x.dtype)
    h, z = _pre_fwd_call(x, lanes, _prm(alpha, bias, n), eps=eps,
                         split=split, **_how(x, n, interpret))
    coef = coefficients(z, alpha, bias, n, iters, eps, clamp, interpret)
    return (h, coef, x), (x, z, phi, alpha, bias)


def _pre_bwd(n, iters, eps, clamp, interpret, res, cts):
    x, z, phi, alpha, bias = res
    dh, dcoef, dxc = cts
    k = columns(n)
    dzr, dalpha, dbias = coefficients_bwd(z, alpha, bias, dcoef, n, iters,
                                          eps, clamp, interpret)
    phit = jnp.pad(phi.astype(_F32).T.astype(x.dtype),
                   [(0, LANES - k), (0, 0)])
    dx, g = _pre_bwd_call(x, dxc, dh, z, dzr, _prm(alpha, bias, n), phit,
                          **_how(x, n, interpret))
    dz, dht = g[:, :k], g[:, k + 1:k + 1 + n]
    r = z[:, k:k + 1]
    dphi = jnp.dot(x.T, (dz * r).astype(x.dtype),
                   preferred_element_type=_F32)
    dalpha = dalpha.at[0].add(jnp.sum(dht * z[:, :n]))
    dbias = dbias.at[:n].add(jnp.sum(dht, 0))
    return dx, dphi.astype(phi.dtype), dalpha, dbias


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _post(x, y, coef, n, interpret):
    return _post_fwd(x, y, coef, n, interpret)[0]


def _post_fwd(x, y, coef, n, interpret):
    return _post_fwd_call(x, y, coef, **_how(x, n, interpret)), (x, y, coef)


def _post_bwd(n, interpret, res, d):
    x, y, coef = res
    return tuple(_post_bwd_call(d, x, y, coef, **_how(x, n, interpret)))


_post.defvjp(_post_fwd, _post_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _expand(x, n, interpret):
    return _expand_call(x, **_how(x, n, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _reduce(x, n, interpret):
    return _reduce_call(x, **_how(x, n, interpret))


_expand.defvjp(lambda x, n, interpret: (_expand(x, n, interpret), None),
               lambda n, interpret, _, d: (_reduce(d, n, interpret),))
_reduce.defvjp(lambda x, n, interpret: (_reduce(x, n, interpret), None),
               lambda n, interpret, _, d: (_expand(d, n, interpret),))


# ---------------------------------------------------------------------------
# what the lowering rules call: [..., width] operands, either path
# ---------------------------------------------------------------------------

def _interpret():
    return kernel_config.dispatch_platform() != "tpu"


def _flat(x):
    return x.reshape(-1, x.shape[-1])


def pre(x, phi, alpha, bias, n, iters, eps, clamp, kernels):
    """(h [..., C], coef [..., 128] float32, the stream) of the stream x
    [..., n*C] read into a sub-layer. alpha [3], bias [K], phi [n*C, K]."""
    args = (_flat(x), phi, alpha, bias, n, int(iters), float(eps),
            (float(clamp[0]), float(clamp[1])))
    h, coef, stream = _pre(*args, _interpret()) if kernels \
        else pre_plain(*args)
    lead = x.shape[:-1]
    return h.reshape(lead + (-1,)), coef.reshape(lead + (LANES,)), \
        stream.reshape(x.shape)


def post(x, y, coef, n, kernels):
    """X' [..., n*C] from the stream, the sub-layer's output y [..., C] and
    `pre`'s coefficients."""
    args = (_flat(x), _flat(y), _flat(coef), n)
    out = _post(*args, _interpret()) if kernels else post_plain(*args)
    return out.reshape(x.shape)


def expand(x, n, kernels):
    """x [..., C] -> [..., n*C], n copies side by side."""
    out = _expand(_flat(x), n, _interpret()) if kernels \
        else jnp.tile(_flat(x), (1, n))
    return out.reshape(x.shape[:-1] + (-1,))


def reduce(x, n, kernels):
    """x [..., n*C] -> [..., C], the streams' sum (float32 inside)."""
    out = _reduce(_flat(x), n, _interpret()) if kernels else _streams(
        _flat(x), n).astype(_F32).sum(1).astype(x.dtype)
    return out.reshape(x.shape[:-1] + (-1,))
