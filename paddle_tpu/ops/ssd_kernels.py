"""The state-space-dual scan of a Mamba-2 mixer (Dao and Gu 2024,
arXiv:2405.21060), chunked: a state-space recurrence whose decay is ONE
scalar a head and token, so that a chunk of it is matmuls. A head h of P
channels with a state [N, P], from s = 0:

    s_t = exp(Delta_t[h] A[h]) s_(t-1) + B_t^T (Delta_t[h] x_t[h])
    y_t[h] = C_t s_t + D[h] x_t[h]

for x [B, T, H, P], Delta [B, T, H], A (negative) and D [H], and B, C [B, T,
N], which ALL heads share (one group), or [B, T, G, N]: head h reads group
h // (H / G), and a grid step takes its heads from one group (the operands
then lie a group at a time, [B, G, T', N], and a step's block is its group's
chunk: the same two kernels). Computed here Q tokens at a time, so
that the sequential depth is T / Q. With a_t = Delta_t A, c the running sum
of a inside a chunk, S the state that enters it, X = Delta * x and i, j
positions inside it:

    Y = ((C B^T) o L) X + (exp(c) * C) S,  L_ij = exp(c_i - c_j), j <= i
    S <- exp(c_Q) S + (exp(c_Q - c) * B)^T X

and y = Y + D x. It is the gated delta rule's pass over chunks
(gated_delta_kernels.py) without the delta: but C B^T is one [Q, Q] product
a chunk whatever the head, the decay is a scalar a head, and a head's
result is P = 64 columns wide. Every exponent is of a difference that is <=
0 (c falls inside a chunk): at Delta A = -6 a token exp(c_i) / exp(c_j) as
two factors overflows where exp(c_i - c_j) does not.

What no chunk needs another for (`_operands`: Delta A, its running sums in
the two orientations the kernels read them in, B and C in the operands'
dtype) is jax.numpy over all chunks at once; x stays where it lies, [B, T, H
x P], and no pass transposes it. The pass over chunks is the Pallas kernel
`ptpu_ssd_fwd`, grid (batch, H / block_h, T / Q) with the state [N, block_h
x P] float32 in VMEM scratch from chunk to chunk, and its reverse, which
carries dS, `ptpu_ssd_bwd`. A grid step takes its heads 128 lanes at a
time: 128 / P heads side by side (two at P = 64), so that every load, store
and matmul result is whole lane tiles. C S and B^T X are then ONE product
for the heads of a tile ((C S_tile) * exp(c) spread over each head's lanes);
the product under L is a head's own, [Q, Q] x [Q, 128] with the other heads'
lanes masked off afterwards: the MXU computes the tile's width for each of
its heads, which is what a result of P = 64 columns costs on the v5e's 128 x
128 array either way (half of it idles), so the kernels' share of the bf16
peak stands under 50 % by construction (PERF.md section 7).

The backward pass runs the forward kernel once more (`emit`: it also writes
the state that enters every chunk, T / Q x [N, H x P] float32, alive inside
the grad op only, and Y again, so that nothing but the op's inputs is kept
from the forward pass), then the reverse kernel, which gives dX, and dB and
dC a block of heads (summed by XLA), all of them matmuls. The decay's
gradient: a_t is in c_i for every i >= t of its chunk, and three kinds of
term carry a c. A pair (i, j < i) of one chunk carries exp(c_i - c_j): with
P_ij = L_ij (C_i . B_j) <dY_i, X_j> a head, dL/dc_i has + sum_j P_ij (the
token as a reader) and - sum_i' P_i'i (as a writer), and the reverse kernel
sums both in float32 from the one product <dY_i, X_j> it makes anyway, P once
as it lies and once transposed, so that both sums come out as rows. Every
term of what the state that ENTERED gives Y_i carries exp(c_i): + <dY_i, Y_i>
over that part of Y alone, which `emit` writes in float32. The state that
leaves carries exp(c_Q) on what entered and exp(c_Q - c_j) on what X_j wrote:
every a of the chunk gets <dS, exp(c_Q) S>, one row a chunk from the reverse
kernel, and a_t gets sum_(j < t) <dX''_j, X_j>, dX'' what reached X_j through
the state. dL/da_t is the first two summed over i >= t of the chunk plus the
last two: running sums inside each chunk over [B, T, H], jax.numpy. (Three
forms that looked simpler were wrong under bf16 operands, each on the chip
and not in the interpreter. Over the whole sequence at once, with the running
sum of all a, <dY, Y> - <dX, X> alone does it, but what the two kernels round
differently at a chunk's edge is added into every EARLIER token's gradient:
da read 30 times its size at Delta A = -6 a token. With the state's terms as
<dS, S> of the state that leaves less sum_(i >= t) <dX''_i, X_i>, a
difference of two nearly equal numbers is added to every token of the chunk:
15 % off. And with the pairs of a chunk as <dY_i, Y_i> - <dX'_i, X_i> from
two kernels' outputs, the pairs at or after t cancel only if both sides read
the same rounded X and the same rounded L o (C B^T): they did in the
interpreter, but XLA on the TPU drops a f32 -> bf16 -> f32 pair of converts
as excess precision and a model's gradient of A_log, a three-hundredth of the
sums it is the difference of, read 9-22 % off the float32 reference where
jax's own gradient of the chunked form read 0.3-4 %.)

`path="scan"` is the same chunked form in jax.numpy, the chunks' states
under `lax.scan`, differentiated by jax: what runs where the kernels are off
(the CPU by default), and what the kernels are held to.

Precision: Delta, A, the running sums, every exponential, the state and
every accumulator are float32. A matmul takes its operands in
`operand_dtype` (bf16 under AMP, else the inputs' float32) and accumulates
in float32.

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

__all__ = ["ssd_scan", "applies"]

_F32 = jnp.float32
_LANES = 128
_NEG = -1e30            # the exponent of a pair a token does not see


def applies(h, p):
    """Do the kernels' lane tiles divide x [B, T, h, p]? 128 / p whole heads
    side by side in a tile, and whole tiles of them."""
    return 0 < p <= _LANES and _LANES % p == 0 and h % (_LANES // p) == 0


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


# ---- what no chunk needs another for ----------------------------------------

def _operands(x, delta, a, b, c, *, chunk, block_h, dt):
    """x [B, T, H, P], delta [B, T, H], a [H], b, c [B, T, N] -> the
    kernels' operands: x as [B, T', H x P] and b, c [B, T', N] in `dt` (b, c
    [B, T, G, N]: [B, G, T', N], a group's tokens together);
    delta and the running sum of delta x a inside each chunk as [B, H /
    block_h, T', block_h] (a head a column) and that sum again as [B, H /
    block_h, block_h, T'] (a head a row), float32. T' is T padded to whole
    chunks with tokens that neither write (delta = x = 0) nor decay."""
    batch, t, h, p = x.shape
    n = -(-t // chunk)
    pad = [(0, 0), (0, n * chunk - t)]

    def tokens(v):
        return jnp.pad(v, pad + [(0, 0)] * (v.ndim - 2))

    delta = tokens(delta.astype(_F32))
    run = jnp.cumsum((delta * a.astype(_F32)).reshape(batch, n, chunk, h),
                     axis=2).reshape(batch, n * chunk, h)

    def columns(v):
        return jnp.moveaxis(v.reshape(batch, -1, h // block_h, block_h), 2, 1)

    def shared(v):              # a group's tokens together
        v = tokens(v).astype(dt)
        return v if v.ndim == 3 else jnp.swapaxes(v, 1, 2)

    return (tokens(x).reshape(batch, n * chunk, h * p).astype(dt),
            columns(delta), columns(run),
            jnp.swapaxes(columns(run), 2, 3), shared(b), shared(c))


# ---- the chunked form in jax.numpy ------------------------------------------

def _scan_path(x, delta, a, b, c, *, chunk, dt):
    """Y [B, T, H, P] float32 of the module docstring's chunked form, every
    chunk's products at once and the chunks' states under lax.scan: the
    kernels' arithmetic, operand dtypes and accumulators, differentiated by
    jax."""
    batch, t, h, p = x.shape
    n = -(-t // chunk)
    pad = [(0, 0), (0, n * chunk - t)]
    if b.ndim == 4:
        # G groups: the heads of a group are a batch of their own, [B x G,
        # T, H / G, P] under the group's b and c
        g = b.shape[2]

        def of(v, k):                   # group k's heads of [B, T, H, ..]
            return v.reshape((batch, t, g, h // g) + v.shape[3:])[:, :, k]

        y = jnp.stack([_scan_path(
            of(x, k), of(delta, k), a.reshape(g, -1)[k], b[:, :, k],
            c[:, :, k], chunk=chunk, dt=dt)
            for k in range(g)], axis=2)                 # [B, T, G, H / G, P]
        return y.reshape(batch, t, h, p)

    def chunks(v):
        v = jnp.pad(v, pad + [(0, 0)] * (v.ndim - 2))
        return v.reshape((batch, n, chunk) + v.shape[2:])

    delta = chunks(delta.astype(_F32))                          # [B, n, Q, H]
    run = jnp.cumsum(delta * a.astype(_F32), axis=2)
    xt = chunks(x).astype(_F32) * delta[..., None]              # X = Delta x
    xd, bd, cd = xt.astype(dt), chunks(b).astype(dt), chunks(c).astype(dt)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None]
    # exp only of what is kept: above the diagonal c_i - c_j is positive
    decay = jnp.exp(jnp.where(
        lower, run[:, :, :, None] - run[:, :, None, :], -jnp.inf))
    gram = jnp.einsum("bnis,bnjs->bnij", cd, bd, preferred_element_type=_F32)
    y = jnp.einsum("bnijh,bnjhp->bnihp",
                   (gram[..., None] * decay).astype(dt), xd,
                   preferred_element_type=_F32)
    tail = jnp.exp(run[:, :, -1:] - run)                        # [B, n, Q, H]
    wrote = jnp.einsum("bnjs,bnjhp->bnhsp", bd,
                       (xt * tail[..., None]).astype(dt),
                       preferred_element_type=_F32)

    def step(s, xs):                    # s [B, H, N, P]: what enters a chunk
        whole, wrote = xs
        return whole[..., None, None] * s + wrote, s

    _, enters = lax.scan(
        step, jnp.zeros((batch, h, b.shape[-1], p), _F32),
        (jnp.moveaxis(jnp.exp(run[:, :, -1]), 1, 0),
         jnp.moveaxis(wrote, 1, 0)))
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        "bnis,nbhsp->bnihp", cd, enters.astype(dt),
        preferred_element_type=_F32)
    return y.reshape(batch, n * chunk, h, p)[:, :t]


# ---- the pass over chunks: Pallas -------------------------------------------

def _tile_parts(k, p, group, x_ref, dt_ref, cc_ref):
    """Of lane tile k of a grid step's heads: (X = Delta x [Q, 128]
    float32, the running sum c spread over each head's lanes [Q, 128], its
    last row [1, 128], [the lanes of head j of the tile: a mask, or None
    where the tile is one head])."""
    q = x_ref.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)

    def spread(ref):            # a head a column -> a head's lanes
        out = ref[:, (k + 1) * group - 1:(k + 1) * group]
        for j in range(group - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * p,
                            ref[:, k * group + j:k * group + j + 1], out)
        return jnp.broadcast_to(out, (q, _LANES))

    run = spread(cc_ref)
    xt = x_ref[:, k * _LANES:(k + 1) * _LANES].astype(_F32) * spread(dt_ref)
    masks = [None] if group == 1 else [
        (lane >= j * p) & (lane < (j + 1) * p) for j in range(group)]
    return xt, run, run[q - 1:q, :], masks


def _lower_decay(cc_ref, cr_ref, head, transposed=False):
    """L [Q, Q] of one head: exp(c_i - c_j) where j <= i, else 0, as it lies
    (i the rows) or `transposed` (j the rows)."""
    q = cc_ref.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    column, line = cc_ref[:, head:head + 1], cr_ref[head:head + 1, :]
    if transposed:
        return jnp.exp(jnp.where(col >= row, line - column, _NEG))
    return jnp.exp(jnp.where(row >= col, column - line, _NEG))


def _fwd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, *rest, p, emit):
    """One grid step: a block of heads' chunk i. With `emit` (the backward
    pass's) the state that enters the chunk is written too, and of Y only
    what that state gives, (exp(c) * C) S."""
    if emit:
        y_ref, enter_ref, s_scr = rest
    else:
        y_ref, s_scr = rest
    dt = x_ref.dtype
    group = _LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    bm, cm = b_ref[...], c_ref[...]
    gram = _dot(cm, bm, _NT)                                 # C B^T [Q, Q]
    for k in range(x_ref.shape[1] // _LANES):
        tile = slice(k * _LANES, (k + 1) * _LANES)
        xt, run, last, masks = _tile_parts(k, p, group, x_ref, dt_ref, cc_ref)
        xd = xt.astype(dt)
        s = s_scr[:, tile]                                   # [N, 128]
        if emit:
            enter_ref[:, tile] = s
        y = _dot(cm, s.astype(dt), _NN) * jnp.exp(run)
        for j, mask in enumerate(() if emit else masks):
            decay = _lower_decay(cc_ref, cr_ref, k * group + j)
            part = _dot((gram * decay).astype(dt), xd, _NN)
            y = y + (part if mask is None else jnp.where(mask, part, 0.0))
        y_ref[:, tile] = y.astype(y_ref.dtype)
        s_scr[:, tile] = jnp.exp(last) * s + _dot(
            bm, (xt * jnp.exp(last - run)).astype(dt), _TN)


def _bwd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, enter_ref,
                dy_ref, dx_ref, dxs_ref, db_ref, dc_ref, pairs_ref, whole_ref,
                ds_scr, *, p):
    """One grid step of the reverse pass: a block of heads' chunk N - 1 - i,
    dS (the cotangent of the state that LEAVES the chunk) in scratch. Of
    the gradient of X = Delta x, dx is what the chunk's own tokens give and
    dxs what comes through the state; db and dc are this block of heads'
    part; `pairs` is what the chunk's pairs give dL/dc, a head a row (the
    module docstring's P: a token's row of it less its column); `whole` is
    dS * exp(c_Q) S of the state that ENTERED, summed over the states."""
    dt = x_ref.dtype
    group = _LANES // p
    q = x_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    bm, cm = b_ref[...], c_ref[...]
    gram, gram_t = _dot(cm, bm, _NT), _dot(bm, cm, _NT)
    dgram = jnp.zeros((q, q), _F32)
    db = jnp.zeros(db_ref.shape, _F32)
    dc = jnp.zeros(dc_ref.shape, _F32)
    for k in range(x_ref.shape[1] // _LANES):
        tile = slice(k * _LANES, (k + 1) * _LANES)
        xt, run, last, masks = _tile_parts(k, p, group, x_ref, dt_ref, cc_ref)
        xd = xt.astype(dt)
        tail = jnp.exp(last - run)
        sd = enter_ref[:, tile].astype(dt)
        ds = ds_scr[:, tile]
        dsd = ds.astype(dt)
        dy = dy_ref[:, tile]
        dye = (dy.astype(_F32) * jnp.exp(run)).astype(dt)
        dxs_ref[:, tile] = _dot(bm, dsd, _NN) * tail
        dx = jnp.zeros((q, _LANES), _F32)
        dc = dc + _dot(dye, sd, _NT)
        db = db + _dot((xt * tail).astype(dt), dsd, _NT)
        whole_ref[:, tile] = jnp.sum(
            ds * jnp.exp(last) * enter_ref[:, tile], axis=0, keepdims=True)
        for j, mask in enumerate(masks):
            head = k * group + j
            decay = _lower_decay(cc_ref, cr_ref, head)
            own = dy if mask is None else jnp.where(mask, dy,
                                                    jnp.zeros_like(dy))
            m = gram * decay
            dx = dx + _dot(m.astype(dt), own, _TN)
            dm = _dot(own, xd, _NT)                 # <dY_i, X_j> [i, j]
            dgram = dgram + dm * decay
            # a token's own pair is in both sums and stays in both
            pairs_ref[head:head + 1, :] = jnp.sum(
                _dot(xd, own, _NT) * gram_t
                * _lower_decay(cc_ref, cr_ref, head, transposed=True),
                axis=0, keepdims=True) - jnp.sum(dm * m, axis=0,
                                                 keepdims=True)
        dx_ref[:, tile] = dx
        ds_scr[:, tile] = jnp.exp(last) * ds + _dot(cm, dye, _TN)
    dgd = dgram.astype(dt)
    dc_ref[...] = dc + _dot(dgd, bm, _NN)
    db_ref[...] = db + _dot(dgd, cm, _TN)


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _interpret():
    return kernel_config.dispatch_platform() != "tpu"


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(chunk, width, block_h, n, at, blocks_a_group=None):
    """BlockSpecs of (x-like [B, T', H x P], a head a column, a head a row,
    b-like [B, T', N]) arrays, chunk `at(i)` of grid step i. With
    `blocks_a_group` (so many blocks of heads read one group of b and c) the
    b-like arrays are [B, G, T', N] and a step's block its group's."""
    return (_vmem((None, chunk, width), lambda b, h, i: (b, at(i), h)),
            _vmem((None, None, chunk, block_h),
                  lambda b, h, i: (b, h, at(i), 0)),
            _vmem((None, None, block_h, chunk),
                  lambda b, h, i: (b, h, 0, at(i))),
            _vmem((None, chunk, n), lambda b, h, i: (b, at(i), 0))
            if blocks_a_group is None else
            _vmem((None, None, chunk, n),
                  lambda b, h, i: (b, h // blocks_a_group, at(i), 0)))


def _blocks_a_group(b, blocks):
    """The blocks of heads that read one group of b [B, G, T', N], `blocks`
    of them in all; None for b [B, T', N], the one group all read."""
    return None if b.ndim == 3 else blocks // b.shape[1]


# The two calls are jax.jits of their own, everything but the arrays static
# (ops/pallas_import.py has the rule, of which these two are the model): a
# model's layers call them at one shape, the jit keeps the traced kernel
# under its arguments, and a step traces each kernel's body once and not
# once a layer (a body is a hundred equations a lane tile, unrolled; nine
# layers' three kernels were 60 % of the granite-4.0-h-micro cell's trace).
@kernel_entry("ptpu_ssd_fwd", static_argnames=("p", "chunk", "block_h", "emit",
                                               "interpret"))
def _fwd_call(ops, *, p, chunk, block_h, emit, interpret):
    x, b = ops[0], ops[4]
    batch, t, hp = x.shape
    width, n, chunks = block_h * p, b.shape[-1], t // chunk
    tokens, column, row, shared = _specs(
        chunk, width, block_h, n, lambda i: i,
        _blocks_a_group(b, hp // width))
    # the backward pass's Y in float32: its row sums are differences
    out_specs, out_shape = [tokens], [jax.ShapeDtypeStruct(
        x.shape, _F32 if emit else x.dtype)]
    if emit:
        out_specs.append(_vmem((None, None, n, width),
                               lambda b, h, i: (b, i, 0, h)))
        out_shape.append(jax.ShapeDtypeStruct((batch, chunks, n, hp), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, emit=emit),
        # chunks on the minor axis: a block of heads walks all its chunks
        # before the next block reuses the state scratch
        grid=(batch, hp // width, chunks),
        in_specs=[tokens, column, column, row, shared, shared],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_ssd_fwd",
    )(*ops)


@kernel_entry("ptpu_ssd_bwd", static_argnames=("p", "chunk", "block_h",
                                               "interpret"))
def _bwd_call(ops, enters, dy, *, p, chunk, block_h, interpret):
    x, b = ops[0], ops[4]
    batch, t, hp = x.shape
    width, n, chunks = block_h * p, b.shape[-1], t // chunk
    tokens, column, row, shared = _specs(
        chunk, width, block_h, n, lambda i: chunks - 1 - i,
        _blocks_a_group(b, hp // width))
    part = _vmem((None, None, chunk, n),
                 lambda b, h, i: (b, h, chunks - 1 - i, 0))
    part_shape = jax.ShapeDtypeStruct((batch, hp // width, t, n), _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(batch, hp // width, chunks),
        in_specs=[tokens, column, column, row, shared, shared,
                  _vmem((None, None, n, width),
                        lambda b, h, i: (b, chunks - 1 - i, 0, h)), tokens],
        out_specs=[tokens, tokens, part, part, row,
                   _vmem((None, None, 1, width),
                         lambda b, h, i: (b, chunks - 1 - i, 0, h))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(x.shape, _F32), part_shape,
                   part_shape,
                   jax.ShapeDtypeStruct((batch, hp // width, block_h, t),
                                        _F32),
                   jax.ShapeDtypeStruct((batch, chunks, 1, hp), _F32)],
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_ssd_bwd",
    )(*ops, enters, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_path(how, x, delta, a, b, c):
    return _kernel_fwd(how, x, delta, a, b, c)[0]


def _kernel_fwd(how, x, delta, a, b, c):
    chunk, block_h, dt = how
    ops = _operands(x, delta, a, b, c, chunk=chunk, block_h=block_h,
                    dt=jnp.dtype(dt))
    y, = _fwd_call(ops, p=x.shape[3], chunk=chunk, block_h=block_h,
                   emit=False, interpret=_interpret())
    return y.reshape(x.shape[0], -1, x.shape[2], x.shape[3])[:, :x.shape[1]] \
        .astype(_F32), (x, delta, a, b, c)


def _kernel_bwd(how, res, dy):
    chunk, block_h, dt = how
    x, delta, a, b, c = res
    batch, t, h, p = x.shape
    ops = _operands(x, delta, a, b, c, chunk=chunk, block_h=block_h,
                    dt=jnp.dtype(dt))
    # behind one barrier with the cotangent, so that XLA does not write the
    # states as soon as the operands exist and hold them from the forward
    # pass to here (pallas_kernels._wait_for has the finding)
    dy, ops = lax.optimization_barrier((dy, ops))
    call = dict(p=p, chunk=chunk, block_h=block_h, interpret=_interpret())
    y, enters = _fwd_call(ops, emit=True, **call)
    def as_read(v):
        """v float32 at the values a kernel's matmul reads it at. Not a
        pair of converts: XLA takes f32 -> bf16 -> f32 for excess precision
        it may keep, and drops both."""
        if ops[0].dtype == jnp.bfloat16:
            return lax.reduce_precision(v.astype(_F32), 8, 7)
        return v.astype(ops[0].dtype).astype(_F32)

    dy = as_read(dy)
    dyp = jnp.pad(dy, [(0, 0), (0, ops[0].shape[1] - t), (0, 0), (0, 0)]) \
        .astype(ops[0].dtype)
    dxt, dxs, db, dc, pairs, whole = _bwd_call(
        ops, enters, dyp.reshape(ops[0].shape), **call)

    def heads(v):               # [B, T', H x P] -> [B, T, H, P] float32
        return v.reshape(batch, -1, h, p)[:, :t].astype(_F32)

    y, dxt, dxs = heads(y), heads(dxt), heads(dxs)
    xf, delta = x.astype(_F32), delta.astype(_F32)

    def chunks(v):              # [B, T, H] -> [B, n, Q, H], zeros past T
        return jnp.pad(v, [(0, 0), (0, ops[0].shape[1] - t), (0, 0)]) \
            .reshape(batch, -1, chunk, h)

    # the sums of the module docstring: what the chunk's pairs and the
    # entered state give every c from t on, what X wrote into the state
    # before t, and the whole state's decay
    within = lax.cumsum(
        jnp.moveaxis(pairs, 3, 1).reshape(batch, -1, chunk, h)
        + chunks(jnp.sum(dy * y, -1)), axis=2, reverse=True)
    through = chunks(jnp.sum(dxs * as_read(xf * delta[..., None]), -1))
    through = lax.cumsum(through, axis=2) - through
    da = (within + through
          + whole.reshape(batch, -1, 1, h, p).sum(-1)) \
        .reshape(batch, -1, h)[:, :t]
    dxt = dxt + dxs

    def shared(part):           # a block of heads' part -> b's own shape
        if b.ndim == 3:
            return part.sum(1)[:, :t].astype(b.dtype)
        # [B, G x blocks a group, T', N] -> [B, T, G, N]
        part = part.reshape((batch, b.shape[2], -1) + part.shape[2:]).sum(2)
        return jnp.swapaxes(part, 1, 2)[:, :t].astype(b.dtype)

    return ((dxt * delta[..., None]).astype(x.dtype),
            (da * a.astype(_F32) + jnp.sum(dxt * xf, -1)).astype(delta.dtype),
            jnp.sum(da * delta, (0, 1)).astype(a.dtype),
            shared(db), shared(dc))


_kernel_path.defvjp(_kernel_fwd, _kernel_bwd)


def _block_h(h, group):
    """Heads a grid step: the largest divisor of H up to the table's
    block_h that is whole lane tiles."""
    most = min(h, kernel_config.DEFAULT_TILES["ssd"]["block_h"])
    return max(d for d in range(group, most + 1, group) if h % d == 0)


def ssd_scan(x, delta, a, b, c, d, operand_dtype=None, path="kernel",
             chunk=None):
    """y [B, T, H, P] float32 of the state-space-dual scan (module
    docstring) for x [B, T, H, P], delta [B, T, H] (> 0, after its
    softplus), a (negative) and d [H], and b, c [B, T, N], one group that
    all heads read, or [B, T, G, N], head h reading group h // (H / G).

    path "kernel": the Pallas kernels (Mosaic where the program dispatches
    to a TPU, the interpreter elsewhere; `applies` says which shapes they
    take); "scan": the same chunked form in jax.numpy, lax.scan over
    chunks. chunk defaults to kernel_config.DEFAULT_TILES["ssd"], which
    also has the heads a grid step; it is a multiple of 8 (Mosaic wants
    128s), and a T that is no multiple of it is padded with tokens that
    neither write nor decay."""
    batch, t, h, p = x.shape
    n, groups = b.shape[-1], b.shape[2] if b.ndim == 4 else 1
    if delta.shape != (batch, t, h) or a.shape != (h,) or d.shape != (h,) \
            or b.shape not in ((batch, t, n), (batch, t, groups, n)) \
            or c.shape != b.shape or h % groups:
        raise ValueError(
            "ssd_scan: x [B, T, H, P], delta [B, T, H], a and d [H], b and "
            "c [B, T, N] or [B, T, G, N] alike, G a divisor of H; got x %s, "
            "delta %s, a %s, b %s, c %s, d %s"
            % (x.shape, delta.shape, a.shape, b.shape, c.shape, d.shape))
    if path not in ("kernel", "scan"):
        raise ValueError("ssd_scan: path must be 'kernel' or 'scan', got %r"
                         % (path,))
    if chunk is None:
        chunk = kernel_config.DEFAULT_TILES["ssd"]["chunk"]
    if chunk < 8 or chunk % 8:
        raise ValueError("ssd_scan: chunk must be a multiple of 8, got %r"
                         % (chunk,))
    dt = jnp.dtype(x.dtype if operand_dtype is None else operand_dtype)
    if path == "scan":
        y = _scan_path(x, delta, a, b, c, chunk=int(chunk), dt=dt)
    else:
        if not applies(h // groups, p):
            raise ValueError(
                "ssd_scan: the kernels take heads whose width divides 128, "
                "whole lane tiles of them a group; got %d heads of %d in %d "
                "group(s)" % (h, p, groups))
        # a grid step's heads are one group's
        y = _kernel_path((int(chunk), _block_h(h // groups, _LANES // p),
                          dt.name), x, delta, a, b, c)
    return y + d.astype(_F32)[:, None] * x.astype(_F32)
