"""The selective scan of a Mamba-1 mixer (Gu and Dao 2023, arXiv:2312.00752)
as two Pallas kernels: a state-space recurrence whose decay depends on the
token, the channel AND the state, so that no chunk of it is a matmul.

    s_t[c, n] = exp(Delta_t[c] A[c, n]) s_(t-1)[c, n] + Delta_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]          s_(-1) = 0

for x, Delta [B, T, C], A [C, N], B, C [B, T, N] and D [C], all in float32.
The states of a whole sequence are [T, C, N] (2.7 GB at [8192, 5120, 16]): no
form that writes them is a path. Here a grid step is one (sequence, block of
_BLOCK_C = 1024 channels, chunk of `chunk` tokens), the chunks of a block in
order; a state n of the block is ONE vector register [8 sublanes, 128 lanes]
of channels, the N states are unrolled, and B_t[n] and C_t[n] are scalars read
from SMEM, so that nothing is broadcast across lanes. The state lives in VMEM
scratch from chunk to chunk. `ptpu_selective_scan_fwd` writes y and the state
that ENTERS every chunk (T / chunk x [N, C] float32 a sequence: 42 MB at the
shape above under chunks of 64); `ptpu_selective_scan_bwd` walks the chunks
from the last to the first, recomputes a chunk's states from the one that
entered it into VMEM scratch and runs the recurrence's transpose over them
with dL/ds in scratch. dB_t[n] and dC_t[n] are sums over channels: a token's
products are summed over the sublanes as they are made, the 128 lanes once a
chunk, and the channel blocks by XLA afterwards.

`path="xla"` is the recurrence as a plain `lax.scan` over tokens,
differentiated by jax: what runs where the kernels are off (the CPU by
default) and what the kernels are held to.

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

__all__ = ["selective_scan", "applies"]

_F32 = jnp.float32
_LANES, _SUBLANES = 128, 8
_BLOCK_C = _LANES * _SUBLANES       # channels a grid step: a register a state


def applies(c, n):
    """Do the kernels' blocks divide x [B, T, c] under n states? Whole
    registers of channels, and a token's 2 n scalars in a row of SMEM (and
    2 n + 4 registers in the 64 there are)."""
    return c % _BLOCK_C == 0 and 0 < n <= 16


def _scalars(bc_ref, t, n):
    """Token t's B and C: the 2 n scalars of row t of the chunk's SMEM block
    (a row an address and a static offset a scalar: the scalar unit has two
    slots a bundle, and index arithmetic on a packed row cost more of them
    than the loads)."""
    return ([bc_ref[t, i] for i in range(n)],
            [bc_ref[t, n + i] for i in range(n)])


def _fwd_kernel(bc_ref, x_ref, dt_ref, a_ref, d_ref, y_ref, enter_ref, s_scr,
                *, n, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    enter_ref[...] = s_scr[...]
    a = [a_ref[i] for i in range(n)]
    d = d_ref[...]

    def step(t, s):
        x, dt = x_ref[t], dt_ref[t]
        dx = dt * x
        b, c = _scalars(bc_ref, t, n)
        y = d * x
        new = []
        for i in range(n):
            si = jnp.exp(dt * a[i]) * s[i] + dx * b[i]
            y = y + si * c[i]
            new.append(si)
        y_ref[t] = y
        return tuple(new)

    s = lax.fori_loop(0, chunk, step, tuple(s_scr[i] for i in range(n)))
    for i in range(n):
        s_scr[i] = s[i]


def _bwd_kernel(bc_ref, x_ref, dt_ref, dy_ref, a_ref, d_ref, enter_ref,
                dx_ref, ddt_ref, dbc_ref, da_ref, dd_ref, g_scr, s_scr,
                red_scr, *, n, chunk):
    """The chunk N - 1 - (grid index): s_scr[t] is the state BEFORE token t
    of the chunk and s_scr[t + 1] the one after; g_scr dL/ds of the state
    that leaves the chunk; red_scr a token's 2 n sums over the sublanes."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros(g_scr.shape, _F32)
        da_ref[...] = jnp.zeros(da_ref.shape, _F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, _F32)

    a = [a_ref[i] for i in range(n)]
    d = d_ref[...]
    s_scr[0] = enter_ref[...]

    def replay(t, s):
        x, dt = x_ref[t], dt_ref[t]
        dx = dt * x
        b, _ = _scalars(bc_ref, t, n)
        new = []
        for i in range(n):
            si = jnp.exp(dt * a[i]) * s[i] + dx * b[i]
            s_scr[t + 1, i] = si
            new.append(si)
        return tuple(new)

    lax.fori_loop(0, chunk, replay, tuple(enter_ref[i] for i in range(n)))

    def step(j, carry):
        g, dd = carry
        t = chunk - 1 - j
        x, dt, dy = x_ref[t], dt_ref[t], dy_ref[t]
        dx = dt * x
        b, c = _scalars(bc_ref, t, n)
        from_b = jnp.zeros_like(x)          # sum_n B_t[n] G_t[n]
        from_decay = jnp.zeros_like(x)      # sum_n G_t a_t A s_(t-1)
        new = []
        for i in range(n):
            gi = g[i] + dy * c[i]
            red_scr[t, n + i:n + i + 1, :] = jnp.sum(
                dy * s_scr[t + 1, i], axis=0, keepdims=True)
            red_scr[t, i:i + 1, :] = jnp.sum(gi * dx, axis=0, keepdims=True)
            from_b = from_b + gi * b[i]
            h = jnp.exp(dt * a[i]) * gi
            hs = h * s_scr[t, i]
            from_decay = from_decay + hs * a[i]
            da_ref[i] = da_ref[i] + hs * dt
            new.append(h)
        dx_ref[t] = d * dy + dt * from_b
        ddt_ref[t] = from_decay + x * from_b
        return tuple(new), dd + dy * x

    g, dd = lax.fori_loop(
        0, chunk, step, (tuple(g_scr[i] for i in range(n)), dd_ref[...]))
    for i in range(n):
        g_scr[i] = g[i]
    dd_ref[...] = dd
    dbc_ref[...] = jnp.sum(red_scr[...], axis=-1)


def _interpret():
    return kernel_config.dispatch_platform() != "tpu"


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _operand_specs(n, chunk, chunk_index):
    """The specs of bc (SMEM), a token array [B, T, C / 128, 128], A [N, C /
    128, 128] and D [C / 128, 128], chunks found by `chunk_index`."""
    bc = pl.BlockSpec((None, chunk, 2 * n),
                      lambda b, c, t: (b, chunk_index(t), 0),
                      memory_space=pltpu.SMEM)
    tokens = _vmem((None, chunk, _SUBLANES, _LANES),
                   lambda b, c, t: (b, chunk_index(t), c, 0))
    a = _vmem((n, _SUBLANES, _LANES), lambda b, c, t: (0, c, 0))
    d = _vmem((_SUBLANES, _LANES), lambda b, c, t: (c, 0))
    return bc, tokens, a, d


# The two calls are jax.jits of their own, everything but the arrays static
# (ops/pallas_import.py has the rule): a model's layers call them at one
# shape, and a step traces each kernel's body once and not once a layer.
@kernel_entry("ptpu_selective_scan_fwd",
              static_argnames=("n", "chunk", "interpret"))
def _fwd_call(bc, x, dt, a, d, *, n, chunk, interpret):
    batch, t, groups, _ = x.shape
    spec_bc, tokens, spec_a, spec_d = _operand_specs(n, chunk, lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, chunk=chunk),
        grid=(batch, groups // _SUBLANES, t // chunk),
        in_specs=[spec_bc, tokens, tokens, spec_a, spec_d],
        out_specs=[tokens, _vmem((None, None, n, _SUBLANES, _LANES),
                                 lambda b, c, i: (b, i, 0, c, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (batch, t // chunk, n, groups, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, _LANES), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_selective_scan_fwd",
    )(bc, x, dt, a, d)


@kernel_entry("ptpu_selective_scan_bwd",
              static_argnames=("n", "chunk", "interpret"))
def _bwd_call(bc, x, dt, dy, a, d, enter, *, n, chunk, interpret):
    batch, t, groups, _ = x.shape
    chunks = t // chunk
    spec_bc, tokens, spec_a, spec_d = _operand_specs(
        n, chunk, lambda i: chunks - 1 - i)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, chunk=chunk),
        grid=(batch, groups // _SUBLANES, chunks),
        in_specs=[spec_bc, tokens, tokens, tokens, spec_a, spec_d,
                  _vmem((None, None, n, _SUBLANES, _LANES),
                        lambda b, c, i: (b, chunks - 1 - i, 0, c, 0))],
        out_specs=[tokens, tokens,
                   _vmem((None, None, chunk, 2 * n),
                         lambda b, c, i: (b, c, chunks - 1 - i, 0)),
                   _vmem((None, n, _SUBLANES, _LANES),
                         lambda b, c, i: (b, 0, c, 0)),
                   _vmem((None, _SUBLANES, _LANES),
                         lambda b, c, i: (b, c, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (batch, groups // _SUBLANES, t, 2 * n), _F32),
                   jax.ShapeDtypeStruct((batch, n, groups, _LANES), _F32),
                   jax.ShapeDtypeStruct((batch, groups, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, _LANES), _F32),
                        pltpu.VMEM((chunk + 1, n, _SUBLANES, _LANES), _F32),
                        pltpu.VMEM((chunk, 2 * n, _LANES), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_selective_scan_bwd",
    )(bc, x, dt, dy, a, d, enter)


def _kernel_operands(x, delta, a, b, c, d, chunk):
    """The kernels' layouts of float32 operands: T padded to whole chunks
    with tokens that neither write (Delta = x = 0) nor decay (exp(0) = 1);
    a token array as [B, T, C / 128, 128]; B and C side by side, [B, T, 2
    N]; A as [N, C / 128, 128]."""
    batch, t, ch = x.shape
    n = a.shape[1]
    pad = [(0, 0), (0, -t % chunk), (0, 0)]

    def tokens(v):
        return jnp.pad(v, pad).reshape(batch, -1, ch // _LANES, _LANES)

    return (jnp.pad(jnp.concatenate([b, c], -1), pad), tokens(x),
            tokens(delta), a.T.reshape(n, ch // _LANES, _LANES),
            d.reshape(ch // _LANES, _LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_path(chunk, x, delta, a, b, c, d):
    return _kernel_fwd(chunk, x, delta, a, b, c, d)[0]


def _kernel_fwd(chunk, x, delta, a, b, c, d):
    ops = _kernel_operands(x, delta, a, b, c, d, chunk)
    y, enter = _fwd_call(*ops, n=a.shape[1], chunk=chunk,
                         interpret=_interpret())
    return y.reshape(x.shape[0], -1, x.shape[2])[:, :x.shape[1]], (ops, enter)


def _kernel_bwd(chunk, res, dy):
    (bc, x, dt, a, d), enter = res
    batch, t, groups, _ = x.shape
    n = a.shape[0]
    t_real = dy.shape[1]
    dy = jnp.pad(dy, [(0, 0), (0, t - t_real), (0, 0)]).reshape(x.shape)
    dx, ddt, dbc, da, dd = _bwd_call(bc, x, dt, dy, a, d, enter, n=n,
                                     chunk=chunk, interpret=_interpret())
    dbc = dbc.sum(1)[:, :t_real]
    return (dx.reshape(batch, t, -1)[:, :t_real],
            ddt.reshape(batch, t, -1)[:, :t_real],
            da.sum(0).reshape(n, -1).T, dbc[..., :n], dbc[..., n:],
            dd.sum(0).reshape(-1))


_kernel_path.defvjp(_kernel_fwd, _kernel_bwd)


def _xla_path(x, delta, a, b, c, d):
    a = a.astype(_F32)

    def step(s, xs):                    # s [B, C, N]
        x, dt, b, c = xs
        s = jnp.exp(dt[..., None] * a) * s \
            + (dt * x)[..., None] * b[:, None, :]
        return s, jnp.einsum("bcn,bn->bc", s, c,
                             precision=lax.Precision.HIGHEST)

    xs = tuple(jnp.moveaxis(v.astype(_F32), 1, 0) for v in (x, delta, b, c))
    _, y = lax.scan(step, jnp.zeros(x.shape[:1] + a.shape, _F32), xs)
    return jnp.moveaxis(y, 0, 1) + d.astype(_F32) * x.astype(_F32)


def selective_scan(x, delta, a, b, c, d, path="kernel", chunk=None):
    """y [B, T, C] float32 of the selective scan (module docstring) for x,
    delta [B, T, C], a [C, N], b, c [B, T, N] and d [C]. path "kernel": the
    two Pallas kernels (Mosaic where the program dispatches to a TPU, the
    interpreter elsewhere; `applies` says which shapes they take), under
    chunks of `chunk` tokens (kernel_config.DEFAULT_TILES["scan"]); "xla":
    lax.scan over tokens."""
    batch, t, ch = x.shape
    n = a.shape[-1]
    if delta.shape != x.shape or a.shape != (ch, n) or d.shape != (ch,) \
            or b.shape != (batch, t, n) or c.shape != b.shape:
        raise ValueError(
            "selective_scan: x and delta [B, T, C] alike, a [C, N], b and c "
            "[B, T, N], d [C]; got x %s, delta %s, a %s, b %s, c %s, d %s"
            % (x.shape, delta.shape, a.shape, b.shape, c.shape, d.shape))
    if path == "xla":
        return _xla_path(x, delta, a, b, c, d)
    if path != "kernel":
        raise ValueError("selective_scan: path must be 'kernel' or 'xla', "
                         "got %r" % (path,))
    if not applies(ch, n):
        raise ValueError(
            "selective_scan: the kernels take channels in blocks of %d and "
            "at most 16 states; got %d channels, %d states"
            % (_BLOCK_C, ch, n))
    if chunk is None:
        chunk = kernel_config.DEFAULT_TILES["scan"]["chunk"]
    if chunk < _SUBLANES or chunk % _SUBLANES:
        raise ValueError("selective_scan: chunk must be a multiple of 8, got "
                         "%r" % (chunk,))
    # float32 here, outside the rule: jax carries each gradient back to its
    # operand's own dtype
    return _kernel_path(int(chunk), *(v.astype(_F32)
                                      for v in (x, delta, a, b, c, d)))
